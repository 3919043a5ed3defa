"""The traffic generator: one schedule of sizes and arrival times per
traffic file, with token ids drawn from the seed."""
import numpy as np

import loadgen


def test_every_seed_gets_the_same_schedule_and_its_own_tokens():
    t = loadgen.load_traffic("serve.chat")
    a = loadgen.requests(t, 5, 51.0, 200064)
    b = loadgen.requests(t, 2**40 + 5, 51.0, 200064)
    assert len(a) == len(b) == round(t["arrivals"]["rate_per_s"] * 51.0)
    for key in (lambda r: len(r.prompt), lambda r: r.gen,
                lambda r: r.arrival):
        assert list(map(key, a)) == list(map(key, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    again = loadgen.requests(t, 5, 51.0, 200064)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_the_schedule_follows_the_traffic_file():
    t = loadgen.load_traffic("serve.chat")
    reqs = loadgen.requests(t, 1, 51.0, 1000)
    arr = np.array([r.arrival for r in reqs])
    assert (np.diff(arr) > 0).all() and 0 < arr[0] and arr[-1] < 51.0
    p = np.array([len(r.prompt) for r in reqs])
    g = np.array([r.gen for r in reqs])
    pt, gt = t["prompt_tokens"], t["output_tokens"]
    assert pt["min"] <= p.min() and p.max() <= pt["max"]
    assert gt["min"] <= g.min() and g.max() <= gt["max"]
    assert abs(np.median(p) / pt["median"] - 1) <= 0.05
    assert abs(np.median(g) / gt["median"] - 1) <= 0.05
    assert (p + g).max() <= t["server"]["max_seq"]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)
