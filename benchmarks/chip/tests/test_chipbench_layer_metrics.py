"""The per-layer readers of the server's and trainer's loop spans, token
times and prefill counters, on hand-built contexts: what they read, that
spans before the window are left out, and that a program without the
records reads nothing."""
import types

import numpy as np
import pytest

import layer_context
import peaks
import run
import trace_reduce
from repro.obs.metrics import MetricRegistry

#: the window is [10, 20] s; the set-up before it ran spans of its own
WINDOW = (10.0, 20.0)
BEFORE = [("serve.decode_step", 1.0, 2.0, 0), ("engine.step", 1.0, 2.0, 0),
          ("serve.admit", 0.5, 0.9, 0), ("serve.decode.launch", 1.1, 1.2, 1)]


def _read(metric, spans=(), ops=(), layer=None):
    red = trace_reduce.reduce({0: [("fusion.0", 0.0, 3.0)] + list(ops)},
                              WINDOW)
    ctx = layer_context.Context(
        cell={"chips": 1}, cfg={}, traffic={}, layer=layer or {},
        device={"kind": "TPU v5 lite"}, peak=peaks.peak("TPU v5 lite"),
        reduction=red, spans=BEFORE + list(spans), planes=[])
    return run.load_module(run.HERE / "metrics" / f"{metric}.py").read(ctx)


@pytest.mark.parametrize("metric,span", [
    ("serve_decode_device_ms.chat", "serve.decode_step"),
    ("train_step_device_ms", "engine.step")])
def test_device_time_inside_the_windows_step_spans(metric, span):
    ops = [("fusion.1", 11.0, 11.5), ("fusion.2", 13.0, 13.2),
           ("copy.3", 15.0, 16.0)]              # outside every step span
    steps = [(span, 10.9, 11.6, 0), (span, 12.9, 13.3, 0)]
    assert _read(metric, steps, ops) == pytest.approx((0.5 + 0.2) / 2 * 1e3)
    assert _read(metric, [], ops) is None


def test_loop_host_time_per_decode_step():
    loop = [("serve.admit", 10.0, 10.001, 0),
            ("serve.schedule", 10.001, 10.002, 0),
            ("serve.decode_step", 10.002, 10.030, 0),
            ("serve.decode.inputs", 10.002, 10.003, 1),
            ("serve.decode.launch", 10.003, 10.005, 1),
            ("serve.decode.sync", 10.005, 10.030, 1),
            ("serve.emit", 10.030, 10.031, 0),
            ("serve.schedule", 10.031, 10.032, 0),
            ("serve.decode_step", 10.032, 10.060, 0),
            ("serve.decode.inputs", 10.032, 10.033, 1),
            ("serve.decode.launch", 10.033, 10.034, 1),
            ("serve.decode.sync", 10.034, 10.060, 1),
            ("serve.emit", 10.060, 10.062, 0)]
    # admit 1 + schedule 2 + inputs 2 + launch 3 + emit 3 ms, 2 steps
    assert _read("serve_loop_host_ms.chat", loop) == pytest.approx(5.5)
    # a program whose step is one opaque span reads nothing
    opaque = [s for s in loop if s[3] == 0]
    assert _read("serve_loop_host_ms.chat", opaque) is None


def test_prefill_padding_share():
    reg = MetricRegistry()
    assert _read("serve_prefill_pad_pct.chat",
                 layer={"registry": reg}) is None
    reg.counter("serving.prefill_tokens").inc(1_000 + 600)
    reg.counter("serving.prefill_lane_tokens").inc(4 * 1_024 + 4 * 1_024)
    assert _read("serve_prefill_pad_pct.chat", layer={"registry": reg}) == \
        pytest.approx(100 * (1 - 1_600 / 8_192))


def test_decode_stall_p90_over_requests():
    # request rid's longest gap is 0.02 + 0.01 rid: 0.03 ... 0.12 s
    times = {rid: np.array([0.0, 0.02, 0.04 + 0.01 * rid, 0.06 + 0.01 * rid])
             for rid in range(1, 11)}
    times[0] = np.array([1.0, 9.0])            # fewer than 3 tokens: left out
    rep = types.SimpleNamespace(token_times=times)
    want = np.percentile([0.02 + 0.01 * r for r in range(1, 11)], 90) * 1e3
    assert _read("serve_decode_stall_p90_ms.chat",
                 layer={"report": rep}) == pytest.approx(want)
    # a report without token times (an older program) reads nothing
    old = types.SimpleNamespace(rids=np.arange(3))
    assert _read("serve_decode_stall_p90_ms.chat",
                 layer={"report": old}) is None
