"""Observability subsystem (repro.obs) + the Telemetry facade.

Covers: span tracer semantics (nesting, threads, zero-cost-off), the
typed metric registry and its schema-validated JSONL sink, Chrome-trace
export (spans + metrics + EventTrace tracks), the Telemetry facade's
equivalence with the registry it wraps, engine/pipeline instrumentation
end-to-end, the HE x SE report closing within the CI tolerance, the
bench env stamp + compare.py's --normalize refusal, and the validate
CLI the bench-smoke job gates on.
"""
import functools
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.workload import mlp_classify
from repro.engine import Engine
from repro.engine.timing import Telemetry, stats_of
from repro.obs import spans
from repro.obs.chrome_trace import (chrome_trace, export_chrome_trace,
                                    load_span_names)
from repro.obs.meta import env_mismatches, run_metadata
from repro.obs.metrics import (MetricRegistry, validate_jsonl,
                               validate_record)

# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_tracer_nesting_depth_and_parent():
    tr = spans.Tracer()
    with tr.span("outer", a=1):
        with tr.span("inner") as sp:
            sp.set(b=2)
    recs = tr.records()
    assert [r.name for r in recs] == ["inner", "outer"]  # commit order
    inner, outer = recs
    assert outer.depth == 0 and outer.parent is None
    assert inner.depth == 1 and inner.parent == outer.index
    assert outer.attrs == {"a": 1} and inner.attrs == {"b": 2}
    assert inner.t0 >= outer.t0 and inner.t1 <= outer.t1
    assert inner.duration_s >= 0
    assert tr.span_names() == ("inner", "outer")


def test_tracer_instant_and_threads():
    tr = spans.Tracer()

    def worker():
        with tr.span("thread-span"):
            pass

    with tr.span("main-span"):
        tr.instant("mark", bucket=3)
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    by_name = {r.name: r for r in tr.records()}
    mark = by_name["mark"]
    assert mark.t0 == mark.t1 and mark.attrs == {"bucket": 3}
    assert mark.depth == 1 and mark.parent == by_name["main-span"].index
    # the worker thread's span is depth 0 on its own stack, not nested
    # under main-span, and carries a different tid
    ts = by_name["thread-span"]
    assert ts.depth == 0 and ts.parent is None
    assert ts.tid != by_name["main-span"].tid


def test_null_tracer_is_shared_noop():
    null = spans.NullTracer()
    assert not null.enabled
    s1 = null.span("a", x=1)
    s2 = null.span("b")
    assert s1 is s2                      # one shared object, no allocation
    with s1 as sp:
        sp.set(anything=True)
    assert null.records() == ()
    assert null.instant("c") is None


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit by name."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_tracer_spans_hold_profiler_annotations(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotations)
    monkeypatch.setattr(_Annotations, "log", [])
    tr = spans.Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.instant("mark")
    assert _Annotations.log == [("enter", "outer"), ("enter", "inner"),
                                ("exit", "inner"), ("exit", "outer")]
    with pytest.raises(RuntimeError):
        with tr.span("failing"):
            raise RuntimeError("boom")
    assert _Annotations.log[-1] == ("exit", "failing")
    null = spans.NullTracer()
    with null.span("quiet"):
        pass
    null.instant("quiet")
    assert len(_Annotations.log) == 6     # the null tracer opens none


def test_named_programs_compile_under_their_name():
    f = jax.jit(spans.named(functools.partial(lambda x, k: x * k, k=2.0),
                            "scaled"))
    assert "module @jit_scaled " in f.lower(jnp.ones(3)).as_text()
    assert float(f(jnp.ones(3))[0]) == 2.0


def test_install_and_maybe_traced_restore():
    before = spans.current()
    tr = spans.Tracer()
    with spans.install(tr):
        assert spans.current() is tr
        with spans.span("via-module"):
            pass
    assert spans.current() is before
    assert tr.span_names() == ("via-module",)
    with spans.maybe_traced(False) as t:
        assert t is before               # disabled: no fresh tracer
    with spans.maybe_traced(True) as t:
        assert t.enabled and spans.current() is t
    assert spans.current() is before


# ---------------------------------------------------------------------------
# metrics registry + JSONL schema
# ---------------------------------------------------------------------------


def test_registry_kinds_and_collisions():
    reg = MetricRegistry()
    c = reg.counter("steps")
    assert c.inc() == 1 and c.inc(2) == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("k").set(4)
    assert reg.gauge("k").value == 4.0
    s = reg.series("step_s")
    s.append(0.5)
    assert s.values == [0.5] and s.steps == [0] and s.times[0] is not None
    with pytest.raises(TypeError):
        reg.series("steps")              # name bound to Counter
    with pytest.raises(TypeError):
        reg.counter("step_s")
    assert reg.names() == ("k", "step_s", "steps")
    assert reg.get("missing") is None


def test_registry_notes_dedup():
    reg = MetricRegistry()
    for _ in range(3):
        reg.note("stranded devices: g=4 uses k=1")
    reg.note("other")
    assert reg.notes == ["stranded devices: g=4 uses k=1", "other"]


def test_jsonl_roundtrip(tmp_path):
    reg = MetricRegistry()
    reg.counter("steps").inc(5)
    reg.gauge("mesh_k").set(4)
    sr = reg.series("step_s")
    for i, v in enumerate((0.5, 0.2, 0.3)):
        sr.append(v, step=i)
    reg.note("hello")
    path = tmp_path / "m.jsonl"
    n = reg.to_jsonl(path, run={"arch": "lenet", "batch": 16})
    assert n == validate_jsonl(path) == 1 + 2 + 3 + 1
    back, run = MetricRegistry.from_jsonl(path)
    assert run == {"arch": "lenet", "batch": 16}
    assert back.counter("steps").value == 5
    assert back.gauge("mesh_k").value == 4.0
    assert back.series("step_s").values == [0.5, 0.2, 0.3]
    assert back.series("step_s").times == sr.times     # stamps preserved
    assert back.notes == ["hello"]


def test_schema_validation_rejects_malformed():
    validate_record({"kind": "sample", "name": "x", "index": 0, "t": None,
                     "value": 1.5})
    for bad in (
        {"kind": "nope"},
        {"kind": "sample", "name": "x", "index": 0, "value": 1.0},  # no t
        {"kind": "sample", "name": "x", "index": 0, "t": None,
         "value": 1.0, "extra": 1},
        {"kind": "sample", "name": "", "index": 0, "t": None, "value": 1.0},
        {"kind": "sample", "name": "x", "index": -1, "t": None, "value": 1.0},
        {"kind": "counter", "name": "c", "value": -2},
        {"kind": "counter", "name": "c", "value": True},
        {"kind": "gauge", "name": "g", "value": "fast"},
        {"kind": "meta", "schema": 999, "run": {}},
        {"kind": "meta", "schema": 1, "run": {"x": [1]}},
        "not a dict",
    ):
        with pytest.raises(ValueError):
            validate_record(bad)


def test_validate_jsonl_header_first_and_empty(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"kind": "note", "msg": "no header"}) + "\n")
    with pytest.raises(ValueError, match="meta"):
        validate_jsonl(p)
    p.write_text("")
    with pytest.raises(ValueError, match="empty"):
        validate_jsonl(p)


# ---------------------------------------------------------------------------
# Telemetry facade
# ---------------------------------------------------------------------------


def test_telemetry_is_registry_facade():
    reg = MetricRegistry()
    t = Telemetry(skip=1, registry=reg)
    assert t.registry is reg
    t.record(0.5, data_s=0.1)
    t.record(0.2, data_s=0.05)
    # same lists, not copies: one stream, two views
    assert t.step_s is reg.series("step_s").values
    assert t.data_s is reg.series("data_wait_s").values
    assert reg.series("step_s").steps == [0, 1]
    t.note("x")
    t.note("x")
    assert t.notes is reg.notes and t.notes == ["x"]
    assert len(t) == 2


def test_telemetry_median_matches_stats_of():
    t = Telemetry(skip=1)
    for s in (9.0, 0.1, 0.4, 0.2, 0.3):
        t.record(s)
    steady = [0.1, 0.4, 0.2, 0.3]
    # even-length steady sample: the interpolated stats_of median, NOT
    # the old sorted[n//2] upper-median (which would be 0.3)
    assert t.median_step_s() == stats_of(steady).median_s == 0.25
    assert t.stats().min_s == 0.1
    assert t.median_step_s(window=2) == 0.25   # last two: 0.2, 0.3
    assert t.drift(window=2) == t.median_step_s(2) / t.median_step_s()
    with pytest.raises(ValueError):
        t.drift(window=0)


def test_telemetry_skip_edge_semantics():
    # skip >= len(recorded): aggregate over everything rather than nothing
    t = Telemetry(skip=5)
    t.record(0.2)
    t.record(0.4)
    assert t.median_step_s() == pytest.approx(0.3)
    assert t.mean_step_s() == pytest.approx(0.3)
    assert t.summary()["steps"] == 2
    # zero steps recorded: explicit error, not a NaN
    empty = Telemetry()
    for fn in (empty.median_step_s, empty.mean_step_s, empty.stats,
               empty.summary):
        with pytest.raises(ValueError, match="no steps"):
            fn()
    with pytest.raises(ValueError):
        Telemetry(skip=-1)
    with pytest.raises(ValueError):
        t.throughput(0)


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_spans_and_metrics(tmp_path):
    tr = spans.Tracer()
    reg = MetricRegistry()
    with tr.span("engine.run"):
        with tr.span("engine.step", step=0):
            reg.series("loss").append(1.5, step=0)
    doc = chrome_trace(tracer=tr, metrics=reg)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    cs = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert {e["name"] for e in xs} == {"engine.run", "engine.step"}
    assert all(e["pid"] == 0 and e["ts"] >= 0 for e in xs)
    assert cs and cs[0]["args"] == {"loss": 1.5}
    # shared rebased origin: the loss sample lands inside the run span
    run = next(e for e in xs if e["name"] == "engine.run")
    assert run["ts"] <= cs[0]["ts"] <= run["ts"] + run["dur"]
    path = tmp_path / "t.json"
    n = export_chrome_trace(path, tracer=tr, metrics=reg)
    assert n == len(doc["traceEvents"])
    assert load_span_names(path) == ("engine.run", "engine.step")


def test_chrome_trace_event_trace_tracks():
    from repro.exec import EventTrace
    trace = EventTrace(num_groups=2, group=[0, 1, 0], read_version=[0, 0, 1],
                       commit_time=[1.0, 1.5, 2.0])
    events = chrome_trace(event_trace=trace)["traceEvents"]
    bars = [e for e in events if e.get("ph") == "X"]
    assert len(bars) == 3
    assert all(e["pid"] == 1 for e in bars)          # separate clock pid
    assert {e["tid"] for e in bars} == {0, 1}        # one track per group
    # commit 2 read version 1 (created at commit_time[0]=1.0): bar spans
    # the read-to-commit window and its length is the visible staleness
    c2 = next(e for e in bars if e["args"]["commit"] == 2)
    assert c2["ts"] == pytest.approx(1.0 * 1e6)
    assert c2["dur"] == pytest.approx(1.0 * 1e6)
    assert c2["args"]["staleness"] == 1


# ---------------------------------------------------------------------------
# engine + pipeline instrumentation, end to end
# ---------------------------------------------------------------------------


def _run_engine(tracer, steps=6, g=2, batch=32):
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=g, lr=0.05, momentum=0.3,
                 tracer=tracer)
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), steps, batch)
    host = [jax.tree.map(lambda x: np.asarray(x[t]), batches)
            for t in range(steps)]
    eng.run(params, mom, iter(host), steps=steps)
    return eng


def test_engine_run_emits_phase_spans_and_metrics():
    tr = spans.Tracer()
    eng = _run_engine(tr)
    names = set(tr.span_names())
    expected = {"engine.run", "engine.step", "engine.data_wait",
                "engine.dispatch", "engine.prepare", "engine.launch",
                "engine.block_until_ready", "engine.build_step", "data.h2d"}
    assert expected <= names, f"missing {expected - names}"
    # the dispatch is split into the group split and the step's launch
    by_index = {r.index: r for r in tr.records()}
    for r in tr.records():
        if r.name in ("engine.prepare", "engine.launch"):
            assert by_index[r.parent].name == "engine.dispatch"
    if jax.device_count() >= 2:          # tier-1 forces the 8-device lane
        assert "exchange.bucket" in names
        buckets = [r for r in tr.records() if r.name == "exchange.bucket"]
        assert all(r.attrs["bytes"] > 0 for r in buckets)
        # annotated once per built step, not once per round: every
        # bucket index appears exactly once across the whole run
        idxs = [r.attrs["bucket"] for r in buckets]
        assert sorted(idxs) == list(range(len(idxs)))
    reg = eng.telemetry.registry
    assert len(reg.series("step_s")) == 6
    assert len(reg.series("loss")) == 6
    assert len(reg.series("h2d_s")) == 6
    assert all(v > 0 for v in reg.series("h2d_s").values)
    # per-step nesting: 6 data_wait + 6 step spans under one run span
    per = [r for r in tr.records() if r.name == "engine.data_wait"]
    assert len(per) == 6


def test_engine_step_compiles_as_train_step():
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=2, lr=0.05, momentum=0.3)
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batch = jax.tree.map(lambda x: x[0], wl.sample_batches(
        jax.random.PRNGKey(1), 1, 32))
    built = eng._built_step(eng.strategy, g=2, lr=eng.lr,
                            momentum=eng.momentum,
                            per_group_batch=eng._per_group_batch(2, 32))
    text = built.fn.lower(params, mom, built.prepare(batch)).as_text()
    assert "module @jit_train_step " in text


def test_engine_untraced_records_no_spans_and_same_metrics():
    eng = _run_engine(tracer=None)       # defaults to the null tracer
    assert not eng.tracer.enabled
    assert eng.tracer.records() == ()
    assert len(eng.telemetry) == 6       # metrics flow regardless


def test_engine_replay_staleness_series():
    from repro.exec import EventTrace
    wl = mlp_classify()
    trace = EventTrace.round_robin(num_groups=2, num_commits=6)
    tr = spans.Tracer()
    eng = Engine(wl.loss_fn, strategy="trace-replay", trace=trace,
                 lr=0.05, tracer=tr)
    params = wl.init(jax.random.PRNGKey(0))
    batches = wl.sample_batches(jax.random.PRNGKey(1), 6, wl.batch_size)
    eng.replay(params, batches)
    reg = eng.telemetry.registry
    assert reg.series("staleness").values == [float(s)
                                             for s in trace.staleness]
    assert reg.gauge("replay_max_staleness").value == trace.max_staleness
    assert reg.counter("replay_commits").value == 6
    rep = [r for r in tr.records() if r.name == "engine.replay"]
    assert len(rep) == 1 and rep[0].attrs["commits"] == 6


def test_probe_and_profile_device_emit_spans():
    from repro.cluster.devices import profile_device
    tr = spans.Tracer()
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((8,))
    with spans.install(tr):
        thr = profile_device(lambda *a: f(x), (), batch_size=8, warmup=1,
                             iters=2)
    assert thr > 0
    by_name = {r.name: r for r in tr.records()}
    assert by_name["cluster.profile_device"].attrs["examples_per_s"] == thr
    assert "timing.probe" not in by_name   # profile_device times inline


# ---------------------------------------------------------------------------
# HE x SE report
# ---------------------------------------------------------------------------


def test_hexse_report_within_ci_tolerance():
    """The acceptance loop: recompute T(g, alloc) from a run's own metric
    stream against a plan calibrated from that stream; HE must land
    within 15% of the planner's prediction (CI lane criterion)."""
    from repro.obs.report import calibrated_plan, hexse_report
    eng = _run_engine(tracer=None, steps=8, g=2, batch=32)
    plan = calibrated_plan(eng.telemetry, g=2, global_batch=32)
    rep = hexse_report(eng.telemetry, plan)
    assert rep.within(0.15), rep.render()
    assert rep.g == 2 and rep.steps == 7          # skip=1
    assert rep.he_measured_s == pytest.approx(
        eng.telemetry.median_step_s() / 2)
    assert 0.0 <= rep.data_wait_frac < 1.0
    assert "HE" in rep.render()


def test_hexse_report_roundtrips_through_jsonl(tmp_path):
    from repro.obs.report import calibrated_plan, hexse_report
    eng = _run_engine(tracer=None, steps=6)
    path = tmp_path / "m.jsonl"
    eng.telemetry.registry.to_jsonl(path, run_metadata())
    reg, run = MetricRegistry.from_jsonl(path)
    assert run["jax"] == jax.__version__
    plan = calibrated_plan(reg, g=2, global_batch=32)
    rep = hexse_report(reg, plan)
    assert rep.within(0.15)
    # windowed calibration (the online-rebalance hook) also resolves
    plan_w = calibrated_plan(reg, g=2, global_batch=32, window=3)
    assert plan_w.g == 2


def test_report_errors_on_empty_stream():
    from repro.obs.report import calibrated_plan, measured_step_stats
    reg = MetricRegistry()
    with pytest.raises(ValueError, match="step_s"):
        measured_step_stats(reg)
    with pytest.raises(ValueError, match="calibrate"):
        calibrated_plan(reg, g=2, global_batch=32)


# ---------------------------------------------------------------------------
# env stamp + compare.py refusal
# ---------------------------------------------------------------------------


def test_run_metadata_and_mismatches():
    md = run_metadata(mesh_shape=(2, 4), extra={"arch": "lenet"})
    for key in ("jax", "jaxlib", "backend", "device_count", "device_kind",
                "xla_flags", "python", "machine"):
        assert key in md
    assert md["mesh_shape"] == "2x4" and md["arch"] == "lenet"
    other = dict(md, jax="99.0", device_count=md["device_count"] + 1)
    mism = env_mismatches(md, other)
    assert len(mism) == 2 and any("jax" in m for m in mism)
    assert env_mismatches(md, dict(md)) == ()
    assert env_mismatches(None, md) == ()        # legacy baseline: no stamp
    assert env_mismatches(md, {}) == ()


def test_compare_refuses_env_mismatch(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_compare", "benchmarks/compare.py")
    cmp_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cmp_mod)

    def bench_doc(env):
        return {"bench": "x", "env": env,
                "rows": [{"g": 2, "step": {"min_us": 100.0,
                                           "median_us": 110.0,
                                           "iqr_us": 5.0, "iters": 5}}]}

    base_env = {"jax": "0.4.37", "backend": "cpu", "device_kind": "cpu",
                "device_count": 8, "xla_flags": ""}
    base, fresh = tmp_path / "base", tmp_path / "fresh"
    base.mkdir(), fresh.mkdir()
    (base / "BENCH_x.json").write_text(json.dumps(bench_doc(base_env)))
    (fresh / "BENCH_x.json").write_text(
        json.dumps(bench_doc(dict(base_env, device_count=1))))
    ok, reports, md = cmp_mod.compare_dirs(base, fresh, tol=0.15,
                                           normalize=True)
    assert not ok and "env mismatch" in reports["BENCH_x.json"]["error"]
    assert "device_count" in md
    # explicit override compares anyway
    ok, reports, _ = cmp_mod.compare_dirs(base, fresh, tol=0.15,
                                          normalize=True,
                                          allow_env_mismatch=True)
    assert ok and reports["BENCH_x.json"]["regressions"] == 0
    # without --normalize (same-machine mode) the stamp is not consulted
    ok, _, _ = cmp_mod.compare_dirs(base, fresh, tol=0.15, normalize=False)
    assert ok
    # matching envs under --normalize pass as before
    (fresh / "BENCH_x.json").write_text(json.dumps(bench_doc(base_env)))
    ok, _, _ = cmp_mod.compare_dirs(base, fresh, tol=0.15, normalize=True)
    assert ok


# ---------------------------------------------------------------------------
# validate CLI (the bench-smoke gate)
# ---------------------------------------------------------------------------


def test_validate_cli(tmp_path, capsys):
    from repro.obs import validate as V
    tr = spans.Tracer()
    reg = MetricRegistry()
    with tr.span("engine.run"):
        reg.series("step_s").append(0.1, step=0)
    mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.json"
    reg.to_jsonl(mpath, run_metadata())
    export_chrome_trace(tpath, tracer=tr, metrics=reg)
    assert V.main(["--metrics", str(mpath), "--trace", str(tpath),
                   "--expect-spans", "engine.run",
                   "--expect-series", "step_s"]) == 0
    assert V.main(["--trace", str(tpath),
                   "--expect-spans", "engine.run,engine.missing"]) == 1
    assert V.main(["--metrics", str(mpath),
                   "--expect-series", "not_there"]) == 1
    bad = tmp_path / "bad.jsonl"
    bad.write_text("{}\n")
    assert V.main(["--metrics", str(bad)]) == 1
    capsys.readouterr()
