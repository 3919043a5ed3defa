"""The trace reduction on hand-built traces."""
import pytest

import trace_reduce as T


def test_merge_and_subtract():
    assert T.merge([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert T.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]


def test_self_times_split_enclosing_events():
    # a loop op that encloses two body ops: only the uncovered part is its own
    evs = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 3.0),
           ("dot.3", 4.0, 8.0), ("copy.4", 11.0, 12.0)]
    leaves, selfs = T.self_times(evs)
    assert sorted(n for n, _, _ in leaves) == ["copy.4", "dot.3", "fusion.2"]
    s = dict(selfs)
    assert s["while.1"] == pytest.approx(4.0)
    assert s["dot.3"] == pytest.approx(4.0)


def test_reduce_busy_idle_and_exposed_collectives():
    chip0 = [("fusion.1", 0.0, 2.0), ("all-gather-start.1", 1.5, 3.0),
             ("all-gather-done.1", 3.0, 3.5), ("dot.2", 5.0, 6.0)]
    chip1 = [("fusion.1", 0.0, 4.0), ("all-reduce.3", 3.0, 5.0)]
    red = T.reduce({0: chip0, 1: chip1}, (0.0, 10.0),
                   {0: [("jit_step", 0.0, 6.0)], 1: [("jit_step", 0.0, 5.0)]})
    assert red.chips == 2 and red.window_s == 10.0
    # chip0 busy 0-3.5 and 5-6 = 4.5; chip1 busy 0-5 = 5
    assert red.busy_s == pytest.approx(4.75)
    assert red.idle_s == pytest.approx(5.25)
    # chip0: collectives 1.5-3.5, compute 0-2 -> exposed 1.5; chip1: 4-5 -> 1
    assert red.collective_exposed_s == pytest.approx(1.25)
    assert red.collective_s == pytest.approx((2.0 + 2.0) / 2)
    assert red.idle == [(3.5, 5.0), (6.0, 10.0)]
    assert red.module_s["jit_step"] == pytest.approx(5.5)
    assert red.busy_within(2.0, 5.5) == pytest.approx(2.0)
    assert red.top_ops(1)[0][0] == "fusion.1"


def test_reduce_clips_to_the_window():
    red = T.reduce({0: [("a", -5.0, 1.0), ("b", 9.0, 20.0)]}, (0.0, 10.0))
    assert red.busy_s == pytest.approx(2.0)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        T.reduce({}, (0.0, 1.0))


def test_idle_gaps_attributed_to_the_deepest_open_span():
    spans = [("engine.run", 0.0, 10.0, 0), ("engine.data_wait", 2.0, 3.0, 1),
             ("engine.block_until_ready", 5.0, 9.0, 1)]
    idle = [(2.2, 2.8), (6.0, 7.0), (9.5, 9.9), (10.5, 11.0)]
    got = dict((n, s) for n, s in T.attribute_idle(idle, spans))
    assert got["engine.block_until_ready"] == pytest.approx(1.0)
    assert got["engine.data_wait"] == pytest.approx(0.6)
    assert got["engine.run"] == pytest.approx(0.4)
    assert got["no span"] == pytest.approx(0.5)
