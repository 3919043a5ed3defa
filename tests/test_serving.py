"""Serving subsystem tests.

The load-bearing contracts, in order:
- paged decode over page tables is BITWISE equal to the dense ring-buffer
  ``transformer.decode_step`` at the same batch width (logits AND cache
  content, full and sliding windows, ring wrap included);
- a slot's output is exactly independent of the other slots' contents and
  activity (what makes continuous batching safe);
- a request served through the continuous-batching loop produces the SAME
  argmax token sequence as running it alone through prefill + decode
  (token-level, not logit-level: batch *width* itself perturbs XLA matmul
  low bits, so cross-width comparisons pin tokens — see decode.py);
- the flash-attention decode hot path and the parallel prefill are
  numerically allclose to the XLA/scan references;
- a prefill call is as wide as its admissions' power-of-two lane bucket,
  serves the tokens the full slot-width call serves, its pad lanes write
  nothing, and ``warmup`` compiles every width;
- the load generator is reproducible and rid-stable across rates;
- the page allocator recycles and the serving planner's discrete-event
  model behaves monotonically.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.models import transformer as T
from repro.serving import (ContinuousServer, PageAllocator, PagedCacheSpec,
                           init_pages, paged_decode_step, poisson_trace,
                           sample_requests, static_serve_trace)


def _cfg(arch_type="dense", window=None, h=2, kv=2, hd=16, layers=2):
    moe = (MoEConfig(num_experts=4, top_k=2, d_ff_expert=32)
           if arch_type == "moe" else None)
    return ArchConfig(name=f"t-{arch_type}-kv{kv}-w{window}",
                      arch_type=arch_type, num_layers=layers,
                      d_model=h * hd, num_heads=h, num_kv_heads=kv,
                      head_dim=hd, d_ff=32, vocab_size=64, moe=moe,
                      sliding_window=window, compute_dtype="float32",
                      remat=False)


def _full_tables(spec):
    """An allocator with every slot's table fully populated."""
    alloc = PageAllocator(spec)
    for s in range(spec.num_slots):
        alloc.ensure(s, spec.seq_capacity)
    return alloc


def _gather(pages, tables, spec):
    """The dense (L, B, W, K, hd) view of the paged pool."""
    B = spec.num_slots
    return {name: np.asarray(pages[name][:, tables]).reshape(
                spec.num_layers, B, spec.seq_capacity, spec.kv_heads,
                spec.head_dim)
            for name in ("k", "v")}


# ---------------------------------------------------------------------------
# paged decode == dense ring buffer, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,steps", [(None, 12), (8, 20)])
def test_paged_decode_bitwise_matches_dense(window, steps):
    """Same batch width, same positions: logits and cache content must be
    bit-identical to ``T.decode_step`` for ``steps`` steps — with window=8
    and 20 steps the ring wraps twice."""
    cfg = _cfg(window=window)
    B = 2
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = PagedCacheSpec.for_config(cfg, num_slots=B, page_size=4,
                                     max_seq=steps if window is None else 32,
                                     window=window)
    alloc = _full_tables(spec)
    table = jnp.asarray(alloc.tables)
    pages = init_pages(spec)
    dense = T.init_cache(cfg, B, steps if window is None else 32, window)
    active = jnp.ones((B,), bool)

    dstep = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg,
                                                       window))
    pstep = jax.jit(lambda p, pg, tok, pos: paged_decode_step(
        p, pg, table, tok, pos, active, cfg, window=window))

    rng = np.random.default_rng(1)
    for t in range(steps):
        tok = jnp.asarray(rng.integers(cfg.vocab_size, size=(B, 1)),
                          jnp.int32)
        dl, dense = dstep(params, dense, tok, jnp.int32(t))
        pl, pages = pstep(params, pages, tok,
                          jnp.full((B,), t, jnp.int32))
        assert np.array_equal(np.asarray(dl), np.asarray(pl)), f"step {t}"

    view = _gather(pages, alloc.tables, spec)
    for name in ("k", "v"):
        assert np.array_equal(view[name],
                              np.asarray(dense["blocks"][name]))


def test_paged_decode_rows_are_independent():
    """Row 0's logits must not change by a single bit when row 1 flips
    between active (at a different position, different tokens) and
    inactive — the property that lets requests join/leave mid-flight."""
    cfg = _cfg()
    B = 2
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = PagedCacheSpec.for_config(cfg, num_slots=B, page_size=4,
                                     max_seq=16)
    rng = np.random.default_rng(2)
    logs = []
    for neighbor_active in (True, False):
        alloc = _full_tables(spec)
        table = jnp.asarray(alloc.tables)
        pages = init_pages(spec)
        rng0 = np.random.default_rng(3)     # row 0's stream, shared
        for t in range(8):
            toks = np.zeros((B, 1), np.int32)
            toks[0, 0] = rng0.integers(cfg.vocab_size)
            toks[1, 0] = rng.integers(cfg.vocab_size)   # differs per arm
            pos = np.array([t, 2 * t + 1], np.int32)    # differs per arm
            active = jnp.asarray([True, neighbor_active])
            logits, pages = paged_decode_step(
                params, pages, table, jnp.asarray(toks),
                jnp.asarray(pos), active, cfg, window=None)
            logs.append((neighbor_active, t, np.asarray(logits[0])))
    a = [x for act, _, x in logs if act]
    b = [x for act, _, x in logs if not act]
    for t, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(x, y), f"row-0 leak at step {t}"


def test_inactive_slots_leave_scratch_page_untouched():
    cfg = _cfg()
    spec = PagedCacheSpec.for_config(cfg, num_slots=2, page_size=4,
                                     max_seq=8)
    alloc = PageAllocator(spec)          # nothing allocated: all rows at 0
    pages = init_pages(spec)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    _, pages = paged_decode_step(
        params, pages, jnp.asarray(alloc.tables),
        jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), bool), cfg, window=None)
    assert not np.asarray(pages["k"]).any()
    assert not np.asarray(pages["v"]).any()


def test_decode_step_writes_only_active_rows_in_place():
    """One step over a pool full of prior content (three slots, the middle
    one inactive) changes the pool exactly at ``(l, pid[b], in_page[b])``
    for the active rows b and every layer l; every other element — the
    inactive row's target included — stays bitwise what it was."""
    cfg = _cfg()
    B = 3
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = PagedCacheSpec.for_config(cfg, num_slots=B, page_size=4,
                                     max_seq=16)
    alloc = _full_tables(spec)
    rng = np.random.default_rng(4)
    shape = (spec.num_layers, spec.num_pages, spec.page_size,
             spec.kv_heads, spec.head_dim)
    before = {n: rng.standard_normal(shape).astype(np.float32)
              for n in ("k", "v")}
    pos = np.array([5, 9, 14], np.int32)
    active = np.array([True, False, True])
    _, pages = paged_decode_step(
        params, {n: jnp.asarray(a) for n, a in before.items()},
        jnp.asarray(alloc.tables), jnp.asarray([[3], [7], [11]], jnp.int32),
        jnp.asarray(pos), jnp.asarray(active), cfg, window=None)
    pid = alloc.tables[np.arange(B), pos // spec.page_size]
    written = np.zeros(shape[:3], bool)               # (L, P, page)
    written[:, pid[active], (pos % spec.page_size)[active]] = True
    for n in ("k", "v"):
        after = np.asarray(pages[n])
        assert np.array_equal(after[~written], before[n][~written]), n
        assert (after[written] != before[n][written]).all(), n


# ---------------------------------------------------------------------------
# continuous batching == solo decoding, token-exact
# ---------------------------------------------------------------------------

def _solo_tokens(cfg, params, req, window, cache_len):
    """The request alone: prefill the exact-length prompt, then greedy
    decode — the reference token sequence. cache_len must equal the
    server's cache width (same-width softmax reduction trees are part of
    the bitwise contract)."""
    cache = T.init_cache(cfg, 1, cache_len, window)
    logits, cache = T.prefill(params, cache,
                              jnp.asarray(req.prompt[None, :]), cfg, window)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(req.prompt)
    for _ in range(req.gen - 1):
        logits, cache = T.decode_step(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.int32(pos), cfg, window)
        toks.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return np.array(toks, np.int32)


@pytest.mark.parametrize("arch_type,kv,window", [
    ("dense", 2, None),          # full-window MHA
    ("dense", 1, 8),             # GQA + sliding-window ring
    ("moe", 2, None),            # routed experts in the decode scan
])
def test_continuous_matches_solo(arch_type, kv, window):
    cfg = _cfg(arch_type=arch_type, kv=kv, window=window)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    trace = poisson_trace(50.0, 6, seed=3)
    reqs = sample_requests(trace, cfg, prompt_range=(4, 8),
                           gen_range=(3, 6), seed=3)
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=16,
                           window=window)
    rep = srv.run(reqs)
    assert len(rep.rids) == len(reqs)
    for r in reqs:
        want = _solo_tokens(cfg, params, r, window,
                            srv.spec.seq_capacity if window is None else 16)
        got = rep.tokens[r.rid]
        assert np.array_equal(got, want), (
            f"rid {r.rid}: continuous {got} != solo {want}")
    assert rep.total_tokens == sum(r.gen for r in reqs)
    assert (rep.queue_waits >= 0).all() and (rep.latencies > 0).all()


def test_continuous_run_is_reproducible_after_reset():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(30.0, 5, seed=1), cfg,
                           prompt_range=(4, 8), gen_range=(3, 5), seed=1)
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=16)
    rep1 = srv.run(reqs)
    srv.reset()
    rep2 = srv.run(reqs)
    for rid in rep1.tokens:
        assert np.array_equal(rep1.tokens[rid], rep2.tokens[rid])


def test_static_baseline_accounts_every_request():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(30.0, 5, seed=2), cfg,
                           prompt_range=(4, 8), gen_range=(3, 5), seed=2)
    rep = static_serve_trace(cfg, reqs, batch=2, params=params)
    assert len(rep.rids) == len(reqs)
    assert rep.total_tokens == sum(r.gen for r in reqs)
    waits = dict(zip(rep.rids, rep.queue_waits))
    for r in reqs:
        assert len(rep.tokens[r.rid]) == r.gen
        tt = rep.token_times[r.rid]
        assert len(tt) == r.gen and (np.diff(tt) > 0).all()
        assert tt[0] >= r.arrival + waits[r.rid]
    # group members share a finish time; latency is sorted by arrival wait
    assert (rep.latencies > 0).all()
    assert 0 < rep.occupancy_mean <= 1.0


# ---------------------------------------------------------------------------
# flash decode + parallel prefill hot paths
# ---------------------------------------------------------------------------

def test_pallas_decode_matches_xla():
    """q_offsets flash decode vs the masked XLA path on a primed cache."""
    cfg = _cfg(kv=1)                              # GQA through the kernel
    B = 2
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = PagedCacheSpec.for_config(cfg, num_slots=B, page_size=4,
                                     max_seq=16)
    alloc = _full_tables(spec)
    table = jnp.asarray(alloc.tables)
    pages = init_pages(spec)
    rng = np.random.default_rng(4)
    pos = None
    for t in range(6):                            # prime via the XLA path
        tok = jnp.asarray(rng.integers(cfg.vocab_size, size=(B, 1)),
                          jnp.int32)
        pos = jnp.full((B,), t, jnp.int32)
        logits, pages = paged_decode_step(
            params, pages, table, tok, pos, jnp.ones((B,), bool), cfg,
            window=None, attn_impl="xla")
    tok = jnp.asarray(rng.integers(cfg.vocab_size, size=(B, 1)), jnp.int32)
    pos = jnp.full((B,), 6, jnp.int32)
    lx, _ = paged_decode_step(params, pages, table, tok, pos,
                              jnp.ones((B,), bool), cfg, window=None,
                              attn_impl="xla")
    lp, _ = paged_decode_step(params, pages, table, tok, pos,
                              jnp.ones((B,), bool), cfg, window=None,
                              attn_impl="pallas")
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               rtol=1e-4, atol=1e-4)


def test_parallel_prefill_matches_scan_tokens():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(30.0, 4, seed=5), cfg,
                           prompt_range=(4, 8), gen_range=(3, 5), seed=5)
    tok = {}
    for mode in ("scan", "parallel"):
        srv = ContinuousServer(cfg, params, slots=2, page_size=4,
                               max_seq=16, window=None, prefill_mode=mode)
        tok[mode] = srv.run(reqs).tokens
    for rid in tok["scan"]:
        assert np.array_equal(tok["scan"][rid], tok["parallel"][rid])


# ---------------------------------------------------------------------------
# load generation
# ---------------------------------------------------------------------------

def test_poisson_trace_reproducible_and_roundtrips(tmp_path):
    a = poisson_trace(25.0, 16, seed=7)
    b = poisson_trace(25.0, 16, seed=7)
    assert np.array_equal(a.commit_time, b.commit_time)
    assert (np.diff(a.commit_time) > 0).all()
    assert np.array_equal(a.read_version, np.arange(16))  # staleness 0
    p = tmp_path / "trace.npz"
    a.save(p)
    c = type(a).load(p)
    assert np.array_equal(a.commit_time, c.commit_time)
    assert np.array_equal(a.group, c.group)
    with pytest.raises(ValueError):
        poisson_trace(0.0, 4)


def test_sample_requests_rid_stable_across_rates():
    """Request rid must be byte-identical at every offered rate — only the
    arrival times may differ (the bench replays the same work per rate)."""
    cfg = _cfg()
    r1 = sample_requests(poisson_trace(10.0, 8, seed=0), cfg, seed=9)
    r2 = sample_requests(poisson_trace(80.0, 8, seed=0), cfg, seed=9)
    for a, b in zip(r1, r2):
        assert a.rid == b.rid and a.gen == b.gen
        assert np.array_equal(a.prompt, b.prompt)
        assert a.arrival != b.arrival or a.rid == 0


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

def test_allocator_lazy_growth_recycle_and_exhaustion():
    cfg = _cfg()
    spec = PagedCacheSpec.for_config(cfg, num_slots=2, page_size=4,
                                     max_seq=16)
    al = PageAllocator(spec)
    total = spec.num_pages - 1           # scratch page 0 is never free
    assert al.pages_free == total
    al.ensure(0, 1)                      # one position -> one page
    assert al.pages_free == total - 1
    al.ensure(0, 5)                      # crosses a page boundary
    assert al.pages_free == total - 2
    al.ensure(0, 5)                      # idempotent
    assert al.pages_free == total - 2
    assert 0 not in al.tables[0, :2]     # scratch never handed out
    assert len(set(al.tables[0, :2])) == 2
    al.ensure(1, spec.seq_capacity)
    assert al.pages_free == 2
    assert al.can_fit(2 * spec.page_size)       # 2 pages still free
    assert not al.can_fit(spec.seq_capacity)    # but not 4
    al.release(0)
    assert al.pages_free == total - spec.pages_per_slot
    assert (al.tables[0] == 0).all()     # row points back at scratch
    al.release(1)
    assert al.pages_free == total
    # exhaustion guard: a drained pool must raise, not corrupt tables
    al._free.clear()
    with pytest.raises(RuntimeError):
        al.ensure(0, 1)


def test_spec_rejects_indivisible_page_size():
    cfg = _cfg()
    with pytest.raises(ValueError):
        PagedCacheSpec.for_config(cfg, num_slots=2, page_size=5, max_seq=16)


def test_request_capacity_guard():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=8)
    trace = poisson_trace(10.0, 1, seed=0)
    big = sample_requests(trace, cfg, prompt_range=(8, 8),
                          gen_range=(8, 8), seed=0)
    with pytest.raises(ValueError):
        srv.run(big)                     # 8 + 8 > capacity 8, full window


# ---------------------------------------------------------------------------
# serving planner / discrete-event sim
# ---------------------------------------------------------------------------

def _sim_kwargs(n=24, rate=20.0):
    rng = np.random.default_rng(0)
    return dict(arrivals=list(np.cumsum(rng.exponential(1 / rate, n))),
                prompt_lens=list(rng.integers(8, 33, n)),
                gen_lens=list(rng.integers(4, 33, n)))


def test_sim_decode_rate_is_monotone():
    from repro.cluster.serving import simulate_serving
    kw = _sim_kwargs()
    slow = simulate_serving(**kw, prefill_rates=[500.0],
                            decode_rates=[200.0], slots=8)
    fast = simulate_serving(**kw, prefill_rates=[500.0],
                            decode_rates=[200.0, 200.0], slots=8)
    assert fast.percentile(99) < slow.percentile(99)
    assert fast.makespan <= slow.makespan
    assert (slow.latencies > 0).all() and (slow.queue_waits >= 0).all()


def test_sim_validates_inputs():
    from repro.cluster.serving import simulate_serving
    kw = _sim_kwargs(n=4)
    with pytest.raises(ValueError):
        simulate_serving(**kw, prefill_rates=[], decode_rates=[1.0])
    with pytest.raises(ValueError):
        simulate_serving(**{**kw, "gen_lens": [0, 1, 1, 1]},
                         prefill_rates=[1.0], decode_rates=[1.0])
    with pytest.raises(ValueError):
        simulate_serving(**kw, prefill_rates=[1.0], decode_rates=[1.0],
                         slots=0)


def test_plan_serving_splits_pools_and_needs_two_devices():
    from repro.cluster.devices import DeviceSpec
    from repro.cluster.serving import plan_serving, tok_rate
    gpu = DeviceSpec(name="gpu", kind="gpu", peak_flops=4e12, mem_bw=2e11,
                     net_bw=1e10, throughput=400.0)
    cpu = DeviceSpec(name="cpu", kind="cpu", peak_flops=5e11, mem_bw=5e10,
                     net_bw=1e10, throughput=80.0)
    kw = _sim_kwargs()
    plan = plan_serving([gpu, gpu, cpu, cpu], slo_p99_s=1.0, **kw)
    assert plan.prefill_devices and plan.decode_devices
    assert len(plan.prefill_devices) + len(plan.decode_devices) == 4
    assert plan.goodput > 0
    assert "serving plan" in plan.describe()
    with pytest.raises(ValueError):
        plan_serving([gpu], slo_p99_s=1.0, **kw)
    assert tok_rate(gpu) == 400.0
    assert tok_rate(dataclasses.replace(gpu, throughput=None)) == 4e12 / 1e9


def test_serving_metrics_land_in_registry():
    from repro.obs.metrics import MetricRegistry
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reg = MetricRegistry()
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=16,
                           registry=reg)
    reqs = sample_requests(poisson_trace(30.0, 3, seed=0), cfg,
                           prompt_range=(4, 8), gen_range=(3, 4), seed=0)
    srv.run(reqs)
    for name in ("serving.queue_wait_s", "serving.prefill_s",
                 "serving.decode_s", "serving.decode_step_s",
                 "serving.latency_s", "serving.occupancy"):
        assert len(reg.series(name).values) > 0, name
    assert reg.counter("serving.requests_completed").value == 3
    assert reg.counter("serving.tokens_generated").value == \
        sum(r.gen for r in reqs)


# ---------------------------------------------------------------------------
# the server loop under spans: coverage, token times, counters, names
# ---------------------------------------------------------------------------

_LOOP_SPANS = ("serve.admit", "serve.prefill", "serve.emit",
               "serve.schedule", "serve.decode_step")


def _traced_run(prefill_mode="scan"):
    from repro.engine.timing import monotonic
    from repro.obs import spans
    from repro.obs.metrics import MetricRegistry
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(40.0, 12, seed=3), cfg,
                           prompt_range=(4, 12), gen_range=(3, 8), seed=3)
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=32,
                           prefill_mode=prefill_mode)
    srv.warmup([len(r.prompt) for r in reqs])
    reg = MetricRegistry()
    srv.reset(registry=reg)
    tracer = spans.Tracer()
    with spans.install(tracer):
        t0 = monotonic()
        srv.run(reqs)
        wall = monotonic() - t0
    return srv, reqs, reg, tracer.records(), wall


@pytest.mark.parametrize("prefill_mode", ["scan", "parallel"])
def test_server_loop_spans_cover_the_run(prefill_mode):
    srv, reqs, reg, recs, wall = _traced_run(prefill_mode)
    top = [r for r in recs if r.depth == 0]
    assert {r.name for r in top} == set(_LOOP_SPANS)
    assert sum(r.duration_s for r in top) >= 0.9 * wall
    # every decode step splits into its inputs, launch and sync
    by_index = {r.index: r for r in recs}
    steps = [r for r in recs if r.name == "serve.decode_step"]
    for name in ("serve.decode.inputs", "serve.decode.launch",
                 "serve.decode.sync"):
        kids = [r for r in recs if r.name == name]
        assert len(kids) == len(steps)
        assert all(by_index[k.parent].name == "serve.decode_step"
                   for k in kids)
    # a request's prefill span names it
    pf = [r for r in recs if r.name == "serve.prefill"]
    assert sorted(rid for r in pf for rid in r.attrs["rids"]) == \
        sorted(r.rid for r in reqs)
    # the counters: real prompt tokens, and width x bucket per call
    assert reg.counter("serving.prefill_tokens").value == \
        sum(len(r.prompt) for r in reqs)
    assert reg.counter("serving.prefill_lane_tokens").value == \
        sum(r.attrs["width"] * r.attrs["bucket"] for r in pf)
    # the per-step gauges that nothing read are gone
    assert not {"serving.batch_occupancy", "serving.pages_in_use"} & \
        set(reg.names())


def test_token_times_agree_with_the_servers_records(monkeypatch):
    """On a clock that moves one millisecond a reading, each request's
    token times hold exactly what the server's other records say: the
    first token lands one reading after admission plus the queue wait
    and the prefill call, the last at the end of its decode."""
    import repro.serving.engine as E
    tick = iter(range(1, 1 << 30))
    monkeypatch.setattr(E, "monotonic", lambda: next(tick) * 1e-3)
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(40.0, 8, seed=3), cfg,
                           prompt_range=(4, 12), gen_range=(3, 8), seed=3)
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=32)
    rep = srv.run(reqs)
    reg = srv.registry

    def by_rid(name):
        s = reg.series(name)
        return dict(zip(s.steps, s.values))
    pf, dec = by_rid("serving.prefill_s"), by_rid("serving.decode_s")
    for rid, arr, qw, gen in zip(rep.rids, rep.arrivals, rep.queue_waits,
                                 rep.gen_counts):
        tt = rep.token_times[rid]
        assert len(tt) == gen and (np.diff(tt) > 0).all()
        np.testing.assert_allclose(tt[0] - arr, qw + pf[rid] + 1e-3,
                                   rtol=0, atol=1e-9)
        np.testing.assert_allclose(tt[-1] - tt[0], dec[rid], rtol=0,
                                   atol=1e-9)


def test_server_programs_compile_under_their_names():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    srv = ContinuousServer(cfg, params, slots=2, page_size=4, max_seq=16,
                           window=None, prefill_mode="parallel")
    S = srv.spec.num_slots
    table = jnp.asarray(srv.alloc.tables)
    off, inact = jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool)
    step = srv._step_fn(None).lower(srv.params, srv.pages, table,
                                    jnp.zeros((S, 1), jnp.int32), off, inact)
    prefill = srv._prefill_fn(8).lower(srv.params, srv.pages, table,
                                       jnp.zeros((S, 8), jnp.int32), off,
                                       inact)
    assert "module @jit_serve_decode_step " in step.as_text()
    assert "module @jit_serve_prefill " in prefill.as_text()


# ---------------------------------------------------------------------------
# prefill lanes: a call is as wide as its admissions, up the ladder
# ---------------------------------------------------------------------------

def _tiny_mla_moe():
    """The benchmark's CPU stand-in for DeepSeek-V2-Lite (latent attention
    with YaRN, a leading dense layer, 4 held experts of a 16-wide router,
    a shared expert): its program config and the family's seeded
    weights."""
    import importlib.util
    import json
    import pathlib
    chip = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    spec = importlib.util.spec_from_file_location(
        "mla_moe_family", chip / "families" / "mla_moe.py")
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    cfg = json.loads((chip / "configs" / "tiny-mla-moe.json").read_text())
    return (fam.program_config(cfg),
            jax.jit(fam.weights_fn(cfg))(jax.random.PRNGKey(5)))


@pytest.mark.parametrize("arch,prefill_mode,n", [
    ("dense", "scan", 1), ("dense", "scan", 2), ("dense", "scan", 3),
    ("dense", "parallel", 1), ("dense", "parallel", 2),
    ("dense", "parallel", 3),
    ("mla-moe", "parallel", 1),
])
def test_prefill_lanes_are_the_admissions_bucket(arch, prefill_mode, n):
    """``n`` prompts admitted at once into four slots are prefilled in one
    call ``_bucket(n, 4)`` lanes wide (1, 2, 4). Per request, its token
    and (routed stacks) the call's expert pairs equal those of the same
    prompts prefilled four lanes wide, the other lanes inactive, and its
    cache rows agree to float32 rounding (a batch width re-tiles XLA's
    reductions); outside those rows no page changes: pad lanes, on
    scratch page 0, write nothing to it or to any slot's pages."""
    from repro.obs import spans
    import repro.serving.engine as E
    if arch == "dense":
        cfg = _cfg()
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        page, max_seq, lens = 4, 16, (7, 5, 8)
    else:
        cfg, params = _tiny_mla_moe()
        page, max_seq, lens = 8, 64, (21,)
    S = 4
    rng = np.random.default_rng(n)
    reqs = [E.Request(rid=i, arrival=0.0, gen=1, prompt=rng.integers(
        cfg.vocab_size, size=lens[i]).astype(np.int32)) for i in range(n)]
    srv = ContinuousServer(cfg, params, slots=S, page_size=page,
                           max_seq=max_seq, prefill_mode=prefill_mode)
    before = {k: rng.standard_normal(v.shape).astype(v.dtype)
              for k, v in jax.device_get(srv.pages).items()}

    # the four-lane call: slot s is lane s, the slots not admitted inactive
    Pb = E._bucket(max(lens[:n]), srv.spec.seq_capacity)
    alloc = PageAllocator(srv.spec)
    prompts = np.zeros((S, Pb), np.int32)
    plens = np.zeros(S, np.int32)
    for r in reqs:
        alloc.ensure(r.rid, len(r.prompt))
        prompts[r.rid, :len(r.prompt)] = r.prompt
        plens[r.rid] = len(r.prompt)
    wide, wtoks = srv._prefill_fn(Pb)(
        srv.params, jax.tree.map(jnp.asarray, before),
        jnp.asarray(alloc.tables), jnp.asarray(prompts), jnp.asarray(plens),
        jnp.asarray(plens > 0))
    wide, wtoks = jax.device_get((wide, wtoks))

    srv.pages = jax.tree.map(jnp.asarray, before)
    tracer = spans.Tracer()
    with spans.install(tracer):
        rep = srv.run(reqs)
    got = jax.device_get(srv.pages)
    pf = [r for r in tracer.records() if r.name == "serve.prefill"]
    assert [(r.attrs["lanes"], r.attrs["width"]) for r in pf] == \
        [(n, E._bucket(n, S))]
    if arch != "dense":
        wtoks, wpairs = wtoks
        assert srv.registry.series("serving.moe_prefill_pairs").values == \
            [int(wpairs)]
    for r in reqs:
        assert rep.tokens[r.rid].tolist() == \
            [int(wtoks[len(r.prompt) - 1, r.rid])], r.rid
    written = np.zeros(before[next(iter(before))].shape[:3], bool)
    for r in reqs:
        t = np.arange(len(r.prompt))
        written[:, alloc.tables[r.rid, t // page], t % page] = True
    for k in before:
        assert np.array_equal(got[k][~written], before[k][~written]), k
        np.testing.assert_allclose(
            got[k][written].astype(np.float32),
            wide[k][written].astype(np.float32), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("prefill_mode", ["scan", "parallel"])
def test_warmup_compiles_every_lane_width(prefill_mode):
    """After ``warmup``, a run whose prefill calls admit 1, 2 and 3
    prompts at once (widths 1, 2, 4) compiles nothing."""
    from repro.obs import spans
    import repro.serving.engine as E
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    arrivals = (0.0, 10.0, 10.0, 20.0, 20.0, 20.0)
    reqs = [E.Request(rid=i, arrival=a, gen=3, prompt=rng.integers(
        cfg.vocab_size, size=int(rng.integers(4, 12))).astype(np.int32))
        for i, a in enumerate(arrivals)]
    srv = ContinuousServer(cfg, params, slots=4, page_size=4, max_seq=32,
                           prefill_mode=prefill_mode)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        srv.warmup([len(r.prompt) for r in reqs])
        warm = len(compiles)
        tracer = spans.Tracer()
        with spans.install(tracer):
            rep = srv.run(reqs)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert warm > 0 and len(compiles) == warm
    assert [r.attrs["width"] for r in tracer.records()
            if r.name == "serve.prefill"] == [1, 2, 4]
    assert rep.total_tokens == 3 * len(reqs)


# ---------------------------------------------------------------------------
# bucketed gather ladder + in-kernel paged decode through the server
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 8])
def test_bucketed_gather_matches_full_tokens(window):
    """gather_mode="bucket" narrows the decode gather to the live page
    high-water bucket; tokens must equal the full-capacity bitwise arm
    (narrowing re-tiles XLA reductions — token-level, like batch width)."""
    cfg = _cfg(window=window)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(40.0, 6, seed=4), cfg,
                           prompt_range=(4, 8), gen_range=(3, 6), seed=4)
    toks = {}
    for gm in ("full", "bucket"):
        srv = ContinuousServer(cfg, params, slots=2, page_size=4,
                               max_seq=16, window=window, gather_mode=gm)
        srv.warmup([8])
        toks[gm] = srv.run(reqs).tokens
    for rid in toks["full"]:
        assert np.array_equal(toks["full"][rid], toks["bucket"][rid]), rid


def test_gather_bucket_uses_active_rows_only():
    """Retired slots keep stale positions; the ladder must size the
    gather from live rows alone (and never exceed capacity)."""
    cfg = _cfg()
    srv = ContinuousServer(cfg, slots=2, page_size=4, max_seq=16)
    pos = np.array([3, 900], np.int32)        # row 1 retired, stale pos
    act = np.array([True, False])
    assert srv._gather_bucket(pos, act) == 1
    assert srv._gather_bucket(pos, ~act) is None      # capacity-clamped
    assert srv._gather_bucket(pos, np.zeros(2, bool)) is None
    srv_full = ContinuousServer(cfg, slots=2, page_size=4, max_seq=16,
                                gather_mode="full")
    assert srv_full._gather_bucket(pos, act) is None


@pytest.mark.parametrize("arch_type,window", [("dense", None), ("moe", 8)])
def test_continuous_pallas_kernel_matches_xla_tokens(arch_type, window):
    """attn_impl="pallas" routes decode AND the scan-prefill inner step
    through the in-kernel page walk; the served token streams must match
    the XLA gather arm."""
    cfg = _cfg(arch_type=arch_type, kv=1, window=window)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = sample_requests(poisson_trace(40.0, 4, seed=6), cfg,
                           prompt_range=(4, 8), gen_range=(3, 5), seed=6)
    toks = {}
    for impl in ("xla", "pallas"):
        srv = ContinuousServer(cfg, params, slots=2, page_size=4,
                               max_seq=16, window=window, attn_impl=impl)
        toks[impl] = srv.run(reqs).tokens
    for rid in toks["xla"]:
        assert np.array_equal(toks["xla"][rid], toks["pallas"][rid]), rid


def test_pallas_gather_ring_fallback_warns_and_notes():
    """flash-over-a-copy cannot express a wrapped ring: constructing the
    server with attn_impl="pallas_gather" under a sliding window must
    warn AND pin a note in the metric registry — and re-pin it when a
    fresh registry is attached for a measured run."""
    from repro.obs.metrics import MetricRegistry
    cfg = _cfg(window=8)
    with pytest.warns(UserWarning, match="pallas_gather"):
        srv = ContinuousServer(cfg, slots=2, page_size=4, max_seq=16,
                               attn_impl="pallas_gather")
    assert any("falls back" in n for n in srv.registry.notes)
    fresh = MetricRegistry()
    srv.reset(registry=fresh)
    assert any("falls back" in n for n in fresh.notes)

    # full-window pallas_gather is the real flash arm: no warning, no note
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        srv2 = ContinuousServer(_cfg(window=None), slots=2, page_size=4,
                                max_seq=16, attn_impl="pallas_gather")
    assert srv2.registry.notes == []

    with pytest.raises(ValueError, match="attn_impl"):
        ContinuousServer(cfg, slots=2, page_size=4, max_seq=16,
                         attn_impl="nope")
    with pytest.raises(ValueError, match="gather_mode"):
        ContinuousServer(cfg, slots=2, page_size=4, max_seq=16,
                         gather_mode="nope")
