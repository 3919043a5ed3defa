"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of the
benchmarked operation on this CPU container; derived = the paper-relevant
metric).

  fig4_lowering_blocksize   paper Fig. 4  (b_p batching sweep, TPU: VMEM model)
  fig5_he_model             paper Fig. 5b (HE model vs discrete-event sim)
  fig6_implicit_momentum    paper Fig. 6  (measured vs 1-1/g)
  fig7_tradeoff             paper Fig. 7  (HE x SE x total time vs g)
  fig13_momentum_lesion     paper Fig. 13 (tuned mu vs default 0.9 at g=4)
  fig23_batch_size          paper Fig. 23 (epochs-to-converge vs batch size)
  table_optimizer_vs_bayes  paper §VI-C2  (Algorithm 1 vs GP-EI budget)
  roofline_table            EXPERIMENTS.md §Roofline (from dry-run JSONs)
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import os
import sys

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.engine import timing  # noqa: E402  (one clock repo-wide)
from repro.engine.timing import monotonic  # noqa: E402
from repro.obs.meta import run_metadata  # noqa: E402  (BENCH env stamp)


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, warmup: int = 1, iters: int = 5) -> timing.TimeStats:
    """min/median/IQR wall seconds of ``fn()`` over ``iters`` runs, after
    ``warmup`` untimed calls (absorbs jit compilation). Every BENCH_*.json
    emitter records all three (``TimeStats.row``): median alone cannot
    distinguish real effects from noise on a shared-CPU box; min is the
    noise-robust point estimate, IQR the spread certificate. Speedups are
    computed from min for that reason."""
    return timing.probe(fn, warmup=warmup, iters=iters)


def _timeit_interleaved(fns: dict, warmup: int = 1, iters: int = 9) -> dict:
    """Time several thunks round-robin: one sample of each per round, so a
    noisy scheduler window degrades every contestant equally instead of
    poisoning one contestant's whole block. The right tool whenever two
    implementations are compared head-to-head. Returns {name: TimeStats}."""
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    samples = {name: [] for name in fns}
    for _ in range(iters):
        for name, fn in fns.items():
            t0 = monotonic()
            jax.block_until_ready(fn())
            samples[name].append(monotonic() - t0)
    return {name: timing.stats_of(s) for name, s in samples.items()}


# ---------------------------------------------------------------------------

def fig4_lowering_blocksize():
    """Paper Fig. 4: GEMM speed & memory vs b_p. On TPU the tradeoff is VMEM
    footprint vs MXU tile alignment; interpret-mode wall time included for
    relative CPU sanity only."""
    from repro.kernels.lowering_conv import choose_tiles, ops as lc, vmem_bytes
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 32))
    b, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    ho, wo = (h - kh) + 1, (wd - kw) + 1           # stride 1, VALID
    for bp in (1, 2, 4, 8, 16):
        us = _timeit(lambda: lc.lowering_conv(x, w, stride=1, bp=bp, rb=7),
                     warmup=1, iters=3).median_s * 1e6
        bp_c, rb_c = choose_tiles(b, ho, bp, 7)    # tiles the kernel ran
        vm = vmem_bytes(bp=bp_c, rb=rb_c, h=h, w=wd, cin=cin, kh=kh, kw=kw,
                        cout=cout)
        gemm_m = bp_c * rb_c * wo
        aligned = "ok" if gemm_m % 128 == 0 else f"pad{128 - gemm_m % 128}"
        _row(f"fig4_bp{bp}", us,
             f"bp={bp_c};rb={rb_c};vmem_kB={vm//1024};gemm_M={gemm_m};"
             f"mxu={aligned}")


def fig5_he_model():
    from repro.core import hardware_model as hm
    from repro.core import queue_sim
    ph = hm.PhaseTimes(t_conv_compute_1=1.0, t_fc=0.08, conv_grad_bytes=0.0)
    for g in (1, 2, 4, 8, 16, 32):
        t0 = monotonic()
        sim = queue_sim.simulate(g=g, t_conv=1.0 / (32 // g), t_fc=0.08,
                                 iters=2000, exponential=False)
        us = (monotonic() - t0) * 1e6
        pred = hm.he_time_per_iteration(g, 32, ph)
        _row(f"fig5_he_g{g}", us,
             f"pred={pred:.4f};sim={sim.time_per_iteration:.4f};"
             f"err={abs(pred-sim.time_per_iteration)/pred:.1%}")


def fig6_implicit_momentum():
    from repro.core.implicit_momentum import (async_quadratic_sim,
                                              fit_ar2_momentum,
                                              implicit_momentum)
    for g in (2, 4, 8, 16):
        t0 = monotonic()
        traj = async_quadratic_sim(g=g, eta=0.2, steps=250, runs=1500)
        mu, eta_eff = fit_ar2_momentum(traj[3:])
        us = (monotonic() - t0) * 1e6
        _row(f"fig6_mom_g{g}", us,
             f"measured={mu:.3f};theory={implicit_momentum(g):.3f};"
             f"eta_eff={eta_eff:.4f}")


def _se_iters(wl, params, g, mu, eta, steps, target):
    from repro.core.async_sgd import delayed_sgd_run
    from repro.core.stat_model import iterations_to_loss
    batches = wl.sample_batches(jax.random.PRNGKey(1), steps, wl.batch_size)
    _, losses, _ = delayed_sgd_run(wl.loss_fn, params, batches,
                                   staleness=g - 1, lr=eta, momentum=mu)
    return iterations_to_loss(np.asarray(losses), target)


def fig7_tradeoff():
    """HE x SE x total-time vs number of groups, on the CNN workload.
    HE from the analytic model (TPU-style constants), SE measured by real
    delayed-SGD training on CPU; momentum tuned per g (paper protocol)."""
    from repro.core import hardware_model as hm
    from repro.core.workload import cnn_classify
    wl = cnn_classify()
    params = wl.init(jax.random.PRNGKey(0))
    ph = hm.PhaseTimes(t_conv_compute_1=1.0, t_fc=0.06, conv_grad_bytes=0.0)
    target, steps, N = 0.55, 500, 16
    base_total = None
    for g in (1, 2, 4, 8, 16):
        t0 = monotonic()
        best = (None, None)
        for mu in (0.0, 0.3, 0.6, 0.9):
            it = _se_iters(wl, params, g, mu, 0.05, steps, target)
            if it is not None and (best[0] is None or it < best[0]):
                best = (it, mu)
        us = (monotonic() - t0) * 1e6
        he = hm.he_time_per_iteration(g, N, ph)
        if best[0] is None:
            _row(f"fig7_g{g}", us, "no-convergence")
            continue
        total = he * best[0]
        if g == 1:
            base_total = total
        _row(f"fig7_g{g}", us,
             f"he={he:.4f};se_iters={best[0]};mu*={best[1]};"
             f"total={total:.2f};speedup_vs_sync="
             f"{(base_total/total if base_total else 1):.2f}")


def fig13_momentum_lesion():
    from repro.core.workload import cnn_classify
    wl = cnn_classify()
    params = wl.init(jax.random.PRNGKey(0))
    g, steps, target = 4, 500, 0.55
    for name, fixed_mu in (("default_0.9", 0.9), ("omnivore_tuned", None)):
        t0 = monotonic()
        if fixed_mu is None:
            cands = [(m, _se_iters(wl, params, g, m, 0.05, steps, target))
                     for m in (0.0, 0.3, 0.6, 0.9)]
            cands = [(m, i) for m, i in cands if i is not None]
            mu, iters = min(cands, key=lambda t: t[1])
        else:
            mu, iters = fixed_mu, _se_iters(wl, params, g, fixed_mu, 0.05,
                                            steps, target)
        us = (monotonic() - t0) * 1e6
        _row(f"fig13_{name}", us, f"mu={mu};iters={iters}")


def fig23_batch_size():
    from repro.core.async_sgd import delayed_sgd_run
    from repro.core.stat_model import iterations_to_loss
    from repro.core.workload import mlp_classify
    target = 0.35
    for b in (4, 16, 64, 256):
        wl = mlp_classify(batch_size=b)
        params = wl.init(jax.random.PRNGKey(0))
        best = None
        t0 = monotonic()
        for eta in (0.2, 0.1, 0.05, 0.02):
            batches = wl.sample_batches(jax.random.PRNGKey(1), 400, b)
            _, losses, _ = delayed_sgd_run(wl.loss_fn, params, batches,
                                           staleness=0, lr=eta, momentum=0.9)
            it = iterations_to_loss(np.asarray(losses), target)
            if it is not None and (best is None or it * b < best[0]):
                best = (it * b, eta, it)
        us = (monotonic() - t0) * 1e6
        d = (f"examples_to_target={best[0]};eta*={best[1]};iters={best[2]}"
             if best else "no-convergence")
        _row(f"fig23_b{b}", us, d)


def fig32_rnn_tradeoff():
    """Paper App. F-F: the compute-group tradeoff on RNN/LSTM models."""
    from repro.core import hardware_model as hm
    from repro.core.workload import rnn_classify
    wl = rnn_classify()
    params = wl.init(jax.random.PRNGKey(0))
    ph = hm.PhaseTimes(t_conv_compute_1=1.0, t_fc=0.08, conv_grad_bytes=0.0)
    target, steps, N = 0.30, 350, 16
    base = None
    for g in (1, 2, 4, 8):
        t0 = monotonic()
        best = (None, None)
        for mu in (0.0, 0.3, 0.6, 0.9):
            it = _se_iters(wl, params, g, mu, 0.1, steps, target)
            if it is not None and (best[0] is None or it < best[0]):
                best = (it, mu)
        us = (monotonic() - t0) * 1e6
        he = hm.he_time_per_iteration(g, N, ph)
        if best[0] is None:
            _row(f"fig32_rnn_g{g}", us, "no-convergence")
            continue
        total = he * best[0]
        if g == 1:
            base = total
        _row(f"fig32_rnn_g{g}", us,
             f"he={he:.4f};se_iters={best[0]};mu*={best[1]};"
             f"total={total:.2f};speedup_vs_sync={(base/total if base else 1):.2f}")


def fig33_schedules():
    """Paper App. F-G: Omnivore's epoch-wise re-tuning vs fixed step decay."""
    from repro.core.auto_optimizer import algorithm1
    from repro.core.async_sgd import delayed_sgd_run
    from repro.core.workload import init_state, make_runner, rnn_classify
    from repro.optim.schedules import step_decay
    wl = rnn_classify()
    runner = make_runner(wl, seed=0)
    state = init_state(wl, seed=0)

    # fixed schedule: eta drops 10x at step 150 (CaffeNet-style)
    t0 = monotonic()
    params = state[0]
    sched = step_decay(0.1, drop=10.0, every=150)
    losses = []
    for phase, steps in ((0, 150), (1, 150)):
        batches = wl.sample_batches(jax.random.PRNGKey(phase + 5), steps,
                                    wl.batch_size)
        params, l, _ = delayed_sgd_run(wl.loss_fn, params, batches,
                                       staleness=0, lr=sched(phase * 150),
                                       momentum=0.9)
        losses.append(np.asarray(l))
    us = (monotonic() - t0) * 1e6
    _row("fig33_default_schedule", us,
         f"final={np.concatenate(losses)[-20:].mean():.4f}")

    t0 = monotonic()
    res = algorithm1(runner, state, n_devices=16, epochs=1, epoch_steps=150,
                     probe_steps=30, g0=4)
    us = (monotonic() - t0) * 1e6
    _row("fig33_omnivore_retune", us,
         f"final={res.losses[-20:].mean():.4f};g={res.g};mu={res.mu};"
         f"eta={res.eta}")


def table_optimizer_vs_bayes():
    from repro.core.auto_optimizer import algorithm1
    from repro.core.bayesian import gp_ei_minimize
    from repro.core.workload import init_state, make_runner, mlp_classify
    wl = mlp_classify()
    runner = make_runner(wl, seed=0)
    state = init_state(wl, seed=0)

    t0 = monotonic()
    res = algorithm1(runner, state, n_devices=16, epochs=1, epoch_steps=150,
                     probe_steps=25, g0=8)
    us1 = (monotonic() - t0) * 1e6
    alg1_loss = float(res.losses[-20:].mean())
    _row("alg1_optimizer", us1,
         f"g={res.g};mu={res.mu};eta={res.eta};loss={alg1_loss:.4f}")

    def objective(eta, mu, g):
        _, losses = runner(state, g=g, mu=mu, eta=eta, steps=150, probe=True)
        arr = np.asarray(losses)
        arr = arr[np.isfinite(arr)]
        return float(arr[-20:].mean()) if arr.size else float("inf")

    t0 = monotonic()
    bres = gp_ei_minimize(objective, etas=(0.1, 0.01, 0.001),
                          mus=(0.0, 0.3, 0.6, 0.9), gs=(1, 2, 4, 8),
                          budget=12, seed=0)
    us2 = (monotonic() - t0) * 1e6
    _row("bayes_optimizer", us2,
         f"evals={bres.evaluations};best={bres.best_y:.4f};"
         f"wall_ratio_vs_alg1={us2/max(us1,1):.1f}x")


def bench_grouped_step():
    """Per-round grouped UPDATE application: closed-form fused single pass
    vs the literal O(g) sequential scan (gradients precomputed, so this
    isolates the optimizer hot path the fused kernel rewrites). Emits
    BENCH_grouped_step.json for cross-PR perf tracking."""
    from repro.core.async_sgd import scan_grouped_update
    from repro.kernels.fused_update.ops import fused_group_update
    from repro.optim.closed_form import grouped_coeffs, head_coeffs
    import functools

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"emb": jax.random.normal(ks[0], (2048, 256)),
              "w1": jax.random.normal(ks[1], (512, 1024)),
              "w2": jax.random.normal(ks[2], (1024, 512)),
              "head": jax.random.normal(ks[3], (512, 256))}
    mom = jax.tree.map(jnp.zeros_like, params)
    mask = {k: k == "head" for k in params}
    lr, mu, wd = 0.05, 0.9, 1e-4

    rows = []
    for g in (2, 4, 8):
        grads = jax.tree.map(
            lambda p: jax.random.normal(jax.random.PRNGKey(g), (g,) + p.shape),
            params)
        scan_fn = jax.jit(functools.partial(
            scan_grouped_update, lr=lr, momentum=mu, weight_decay=wd,
            head_mask=mask))
        fused_fn = jax.jit(functools.partial(
            fused_group_update,
            coeffs=grouped_coeffs(g, lr=lr, momentum=mu, weight_decay=wd),
            head_coeffs=head_coeffs(g, lr=lr, momentum=mu, weight_decay=wd),
            head_mask=mask))
        ts = _timeit_interleaved(
            {"scan": lambda: scan_fn(params, grads, mom),
             "fused": lambda: fused_fn(params, grads, mom)},
            warmup=2, iters=11)
        scan_t, fused_t = ts["scan"], ts["fused"]
        speedup = scan_t.min_s / fused_t.min_s
        rows.append({"g": g,
                     "scan": scan_t.row(), "fused": fused_t.row(),
                     "speedup_min": speedup})
        _row(f"grouped_step_g{g}", fused_t.median_s * 1e6,
             f"scan_us={scan_t.median_s * 1e6:.1f};speedup={speedup:.2f}x")

    out = {"bench": "grouped_step", "env": run_metadata(),
           "params": int(sum(p.size for p in jax.tree.leaves(params))),
           "lr": lr, "momentum": mu, "weight_decay": wd,
           "timeit": {"warmup": 2, "iters": 11,
                      "stat": "min+median+iqr; speedups from min"},
           "rows": rows}
    (ROOT / "BENCH_grouped_step.json").write_text(json.dumps(out, indent=2))


def bench_planner():
    """Heterogeneous planner search over a 16-device mixed cluster
    (8xGPU + 8xCPU): full (g, alloc) sweep + discrete-event validation of
    the chosen plan. Emits BENCH_planner.json; the whole search must stay
    under 5 s (it is the inner loop of cluster bring-up)."""
    from repro import cluster

    devices = cluster.parse_cluster_spec(
        "8xgpu-g2.2xlarge,8xcpu-c4.4xlarge")
    cost = cluster.WorkloadCost(flops_per_example=2e9,
                                bytes_per_example=2e8, grad_bytes=4e6)
    batch, t_fc = 64, 0.002

    plan = cluster.best_allocation(devices, global_batch=batch, t_fc=t_fc,
                                   cost=cost, mu_star_total=0.9)
    search_t = _timeit(
        lambda: cluster.best_allocation(devices, global_batch=batch,
                                        t_fc=t_fc, cost=cost,
                                        mu_star_total=0.9),
        warmup=0, iters=3)
    search_s = search_t.median_s

    sim = cluster.simulate_hetero(t_conv=plan.group_times, t_fc=t_fc,
                                  iters=3000, exponential=False)
    err = abs(sim.time_per_iteration - plan.t_iteration) / plan.t_iteration
    _row("planner_search", search_s * 1e6,
         f"g*={plan.g};t_iter={plan.t_iteration*1e3:.3f}ms;"
         f"sim_err={err:.1%};under_5s={search_s < 5.0}")

    rows = []
    for g in (1, 2, 4, 8, 16):
        p = cluster.plan_for_g(devices, g, global_batch=batch, t_fc=t_fc,
                               cost=cost, mu_star_total=0.9)
        rows.append({"g": g, "t_iteration_s": p.t_iteration,
                     "se_penalty": p.se_penalty,
                     "time_score_s": p.time_score,
                     "microbatches": list(p.allocation.microbatches)})
        _row(f"planner_g{g}", p.t_iteration * 1e6,
             f"P_SE={p.se_penalty:.2f};score={p.time_score*1e3:.3f}ms")

    out = {"bench": "planner", "env": run_metadata(),
           "cluster": "8xgpu-g2.2xlarge,8xcpu-c4.4xlarge",
           "global_batch": batch, "t_fc": t_fc,
           "search_s": search_s, "search": search_t.row(),
           "best_g": plan.g,
           "best_microbatches": list(plan.allocation.microbatches),
           "analytic_vs_sim_err": err, "rows": rows}
    (ROOT / "BENCH_planner.json").write_text(json.dumps(out, indent=2))


def _engine_probe(gs=(1, 2, 4, 8)):
    """Child-process half of ``bench_engine``: time the unified engine's
    grouped step per g at whatever device count XLA_FLAGS forced, print one
    JSON line. Run via ``python benchmarks/run.py --engine-probe``.

    With >= 8 devices the probe also runs the overlapped-exchange
    head-to-head: the bucketed SPMD step (``engine.buckets``) vs the
    legacy whole-tree-gather arm (``bucket_bytes=0``), interleaved
    round-robin at g in {2, 4}, with one row per bucket count (the
    ``bucket_bytes`` sweep covers per-leaf / packed / single-slab)."""
    from repro.core.workload import mlp_classify
    from repro.engine import Engine
    from repro.engine.buckets import assign_buckets
    from repro.engine.spmd import (DEFAULT_BUCKET_BYTES, device_batch_split,
                                   make_spmd_grouped_step)
    from repro.launch.mesh import make_group_mesh

    wl = mlp_classify(batch_size=64)
    params = wl.init(jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(params)
    batch = jax.tree.map(lambda x: x[0],
                         wl.sample_batches(jax.random.PRNGKey(1), 1, 64))
    rows = []
    for g in gs:
        eng = Engine(wl.loss_fn, strategy="grouped-fused", num_groups=g,
                     lr=0.05, momentum=0.9, donate=False)
        p, m = params, jax.tree.map(jnp.zeros_like, params)
        for _ in range(12):          # telemetry skips the compile step
            p, m, _ = eng.step(p, m, batch)
        built = next(iter(eng._steps.values()))
        nb = (len(assign_buckets(leaves, [False] * len(leaves),
                                 eng.bucket_bytes))
              if built.mode == "spmd" else 0)
        rows.append({"g": g, "mode": built.mode, "k": built.k,
                     "buckets": nb,
                     "step_us": eng.telemetry.median_step_s() * 1e6,
                     "step": eng.telemetry.stats().row()})

    overlap = []
    if jax.device_count() >= 8:
        mom = jax.tree.map(jnp.zeros_like, params)
        # bucket_bytes sweep: per-leaf buckets (1), small packed buckets
        # (600 B), one slab (default); 0 = whole-tree baseline arm
        sweep = (0, 1, 600, DEFAULT_BUCKET_BYTES)
        for g in (2, 4):
            k = 8 // g
            mesh = make_group_mesh(g, k)
            gb = jax.tree.map(
                lambda t: t.reshape((g, t.shape[0] // g) + t.shape[1:]),
                batch)
            db = device_batch_split(gb, k)
            thunks, nbuckets = {}, {}
            for bb in sweep:
                fn = jax.jit(make_spmd_grouped_step(
                    wl.loss_fn, mesh, lr=0.05, momentum=0.9,
                    bucket_bytes=bb))
                thunks[bb] = (lambda fn=fn: fn(params, mom, db))
                nbuckets[bb] = (len(assign_buckets(
                    leaves, [False] * len(leaves), bb)) if bb > 0 else 0)
            stats = _timeit_interleaved(thunks, warmup=2, iters=15)
            base = stats[0]            # whole-tree arm
            for bb in sweep:
                s = stats[bb]
                overlap.append({
                    "g": g, "k": k, "bucket_bytes": bb,
                    "buckets": nbuckets[bb],
                    "variant": "wholetree" if bb == 0 else "bucketed",
                    "step": s.row(),
                    "speedup_vs_wholetree_min": base.min_s / s.min_s})
    mp_rows = []
    if jax.device_count() >= 8:
        # model-parallel storage head-to-head: same g=2 grouped step with
        # params/momentum stored whole (mp=1) vs mp-sharded over the third
        # mesh axis (mp=2, in-step all-gather + grad slice). The delta is
        # the price of storage sharding on a model that fits either way.
        for mp in (1, 2):
            eng = Engine(wl.loss_fn, strategy="grouped-fused", num_groups=2,
                         mp=mp, lr=0.05, momentum=0.9, donate=False)
            p, m = params, jax.tree.map(jnp.zeros_like, params)
            for _ in range(12):
                p, m, _ = eng.step(p, m, batch)
            built = next(iter(eng._steps.values()))
            mp_rows.append({"g": 2, "mp": mp, "k": built.k,
                            "mode": built.mode,
                            "step_us": eng.telemetry.median_step_s() * 1e6,
                            "step": eng.telemetry.stats().row()})
    print(json.dumps({"device_count": jax.device_count(), "rows": rows,
                      "overlap": overlap, "mp": mp_rows}))


def bench_engine():
    """Unified-engine grouped step: wall time per g on 1 vs 8 forced host
    CPU devices (the SPMD ("group","data") mesh vs the single-device
    path), plus the overlapped bucketed exchange vs whole-tree gather
    head-to-head on the 8-device lane. Emits BENCH_engine.json for
    cross-PR perf tracking (gated by benchmarks/compare.py). Each device
    count needs its own XLA runtime, so the probes run as child
    processes."""
    import subprocess

    results = []
    for n in (1, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
            env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, str(ROOT / "benchmarks" / "run.py"),
             "--engine-probe"],
            env=env, capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"engine probe (devices={n}) failed:\n"
                               + proc.stderr[-2000:])
        data = json.loads(proc.stdout.strip().splitlines()[-1])
        results.append(data)
        for row in data["rows"]:
            _row(f"engine_d{data['device_count']}_g{row['g']}",
                 row["step_us"], f"mode={row['mode']};k={row['k']}")
        for row in data.get("overlap", []):
            _row(f"engine_overlap_g{row['g']}_bb{row['bucket_bytes']}",
                 row["step"]["median_us"],
                 f"buckets={row['buckets']};"
                 f"speedup_vs_wholetree="
                 f"{row['speedup_vs_wholetree_min']:.2f}x")
        for row in data.get("mp", []):
            _row(f"engine_mp{row['mp']}_g{row['g']}",
                 row["step"]["median_us"], f"k={row['k']}")

    out = {"bench": "engine", "env": run_metadata(),
           "workload": "mlp_classify(batch=64)",
           "strategy": "grouped-fused",
           "timeit": {"steps": 12, "stat": "min+median+iqr per row "
                                           "('step'); legacy step_us is "
                                           "the median", "skip": 1,
                      "overlap": "interleaved round-robin, warmup=2, "
                                 "iters=15; speedups from min"},
           "device_counts": [r["device_count"] for r in results],
           "runs": results}
    (ROOT / "BENCH_engine.json").write_text(json.dumps(out, indent=2))


def _seed_cnn_loss(params, batch, cfg):
    """The seed repo's caffenet-smoke training formulation, reconstructed:
    generic autodiff through ``lowering_conv_xla`` (pre-custom-VJP, i.e.
    ``lowered_conv_ref``) and the reduce_window max pool. This is the
    "autodiff-through-lowering_conv_xla" train step the PR replaced —
    kept here so the before/after is measured, not remembered."""
    from repro.kernels.lowering_conv.ref import lowered_conv_ref

    x = batch["images"]
    for spec, p in zip(cfg.convs, params["conv"]):
        x = jax.nn.relu(lowered_conv_ref(x, p["w"], stride=spec.stride)
                        + p["b"])
        if spec.pool > 1:
            k = spec.pool
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, k, k, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        x = x @ p["w"] + p["b"]
        if i < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
    logp = jax.nn.log_softmax(x, axis=-1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None],
                                axis=-1).mean()


def bench_cnn_throughput(archs=("lenet", "cifarnet", "caffenet"),
                         batch_sizes=(16, 64),
                         impls=("xla", "lowering", "lowering_autodiff",
                                "seed_lowering"),
                         iters: int = 15):
    """CNN images/sec per arch x conv impl x batch size, forward-only and
    full train step (paper §III: the batched-lowering GEMM conv is the
    single-node throughput contribution). Runs the smoke CNN configs
    (CPU-sized but structure-preserving: caffenet-smoke keeps the strided
    big-kernel conv1). The train step is the jitted momentum-SGD step (the
    engine's sync-update semantics; the engine's exec-mode wrappers are
    excluded so the conv path, not the batching mode, is measured).

    impls:
      xla                native conv_general_dilated
      lowering           custom-VJP batched-GEMM backward (this PR)
      lowering_autodiff  generic autodiff through the same lowering, same
                         model code otherwise (same-pool ablation)
      seed_lowering      the seed's whole formulation (autodiff lowering +
                         reduce_window pool) — the before/after headline

    Honest-measurement note (docs/lowering_conv.md): within one jitted
    step XLA CSEs the backward "re-lowering" against the forward's, so
    custom-VJP vs lowering_autodiff is ~parity on CPU; the headline
    speedup vs the seed comes from the custom backward together with the
    pool rewrite this PR ships. Emits BENCH_cnn_throughput.json; speedups
    use min (see _timeit)."""
    import dataclasses

    from repro.data.pipeline import DataConfig, SyntheticImages
    from repro.models import cnn as C
    from repro.optim.sgd import init_momentum

    def make_step(loss_fn):
        @jax.jit
        def step(p, m, bt):
            loss, g = jax.value_and_grad(loss_fn)(p, bt)
            m = jax.tree.map(lambda mm, gg: 0.9 * mm + gg, m, g)
            p = jax.tree.map(lambda pp, mm: pp - 0.05 * mm, p, m)
            return p, m, loss
        return step

    rows = []
    for arch in archs:
        base = C.get_cnn_smoke_config(arch)
        for bsz in batch_sizes:
            data = SyntheticImages(DataConfig(
                batch_size=bsz, image_size=base.image_size,
                channels=base.in_channels, num_classes=base.num_classes,
                seed=0))
            batch = jax.device_put(next(iter(data.batches(1))))
            built = {}
            for impl in impls:
                if impl == "seed_lowering":
                    cfg, lf = base, _seed_cnn_loss
                else:
                    cfg = dataclasses.replace(base, conv_impl=impl)
                    lf = C.loss_fn
                loss_fn = (lambda lf, cfg: lambda p, bt: lf(p, bt, cfg))(
                    lf, cfg)
                params = C.init_params(jax.random.PRNGKey(0), cfg)
                built[impl] = (jax.jit(loss_fn), make_step(loss_fn), params,
                               init_momentum(params))
            thunks = {}
            for impl, (fwd, step, params, mom) in built.items():
                thunks[(impl, "fwd")] = \
                    (lambda fwd, p: lambda: fwd(p, batch))(fwd, params)
                thunks[(impl, "train")] = \
                    (lambda st, p, m: lambda: st(p, m, batch))(step, params,
                                                              mom)
            stats = _timeit_interleaved(thunks, warmup=2, iters=iters)
            for impl in impls:
                fwd_t = stats[(impl, "fwd")]
                train_t = stats[(impl, "train")]
                rows.append({
                    "arch": base.name, "impl": impl, "batch": bsz,
                    "fwd": {**fwd_t.row(),
                            "images_per_s": bsz / fwd_t.min_s},
                    "train": {**train_t.row(),
                              "images_per_s": bsz / train_t.min_s},
                })
                _row(f"cnn_{base.name}_{impl}_b{bsz}",
                     train_t.median_s * 1e6,
                     f"train_img_per_s={bsz / train_t.min_s:.0f};"
                     f"fwd_img_per_s={bsz / fwd_t.min_s:.0f}")

    def _train_min(arch, impl, bsz):
        for r in rows:
            if (r["arch"], r["impl"], r["batch"]) == (arch, impl, bsz):
                return r["train"]["min_us"]
        return None

    summary = {}
    for bsz in batch_sizes:
        cust = _train_min("caffenet-smoke", "lowering", bsz)
        seed = _train_min("caffenet-smoke", "seed_lowering", bsz)
        auto = _train_min("caffenet-smoke", "lowering_autodiff", bsz)
        if cust and seed:
            summary[f"caffenet_smoke_custom_vjp_vs_seed_b{bsz}"] = \
                seed / cust
            _row(f"cnn_speedup_caffenet_b{bsz}", cust,
                 f"custom_vjp_vs_seed={seed / cust:.2f}x;"
                 f"vs_same_pool_autodiff="
                 f"{(auto / cust) if auto else float('nan'):.2f}x")
        if cust and auto:
            summary[f"caffenet_smoke_custom_vjp_vs_autodiff_b{bsz}"] = \
                auto / cust

    out = {"bench": "cnn_throughput", "env": run_metadata(),
           "configs": {a: dataclasses.asdict(C.get_cnn_smoke_config(a))
                       for a in archs},
           "impls": list(impls), "batch_sizes": list(batch_sizes),
           "timeit": {"warmup": 2, "iters": iters,
                      "stat": "min+median+iqr; images/sec and speedups "
                              "from min"},
           "rows": rows, "summary": summary}
    (ROOT / "BENCH_cnn_throughput.json").write_text(json.dumps(out, indent=2))


def roofline_table():
    d = ROOT / "experiments" / "dryrun"
    rows = sorted(d.glob("*__16x16.json"))
    for f in rows:
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            _row(f"roofline_{r['arch']}_{r['shape']}", 0.0,
                 f"status={r.get('status')}")
            continue
        rf = r["roofline"]
        useful = r.get("useful_flops_frac")
        _row(f"roofline_{r['arch']}_{r['shape']}",
             r.get("compile_s", 0) * 1e6,
             f"bottleneck={rf['bottleneck']};step_ms={rf['step_time']*1e3:.2f};"
             f"tc={rf['t_compute']*1e3:.2f};tm={rf['t_memory']*1e3:.2f};"
             f"tcoll={rf['t_collective']*1e3:.2f};"
             f"useful={round(useful, 3) if useful else None}")


BENCHES = [fig4_lowering_blocksize, fig5_he_model, fig6_implicit_momentum,
           fig7_tradeoff, fig13_momentum_lesion, fig23_batch_size,
           fig32_rnn_tradeoff, fig33_schedules,
           table_optimizer_vs_bayes, bench_grouped_step, bench_planner,
           bench_engine, bench_cnn_throughput, roofline_table]


def main() -> None:
    if "--engine-probe" in sys.argv:
        _engine_probe()
        return
    print("name,us_per_call,derived")
    for bench in BENCHES:
        t0 = monotonic()
        try:
            bench()
        except Exception as e:  # keep the harness running
            _row(bench.__name__, (monotonic() - t0) * 1e6,
                 f"ERROR={type(e).__name__}:{str(e)[:80]}")


if __name__ == "__main__":
    main()
