#!/usr/bin/env python3
"""Bring-up check on a TPU: the trainer and the server, each driven once
through its normal entry point at full width, checked against itself.

  python chip_smoke.py             one chip: the train and serve phases
  python chip_smoke.py --chips 4   four chips: the grouped SPMD phase only

Phases (all in this one process; nothing here starts a child process):

- train: CaffeNet at its published geometry (227x227x3, 1000 classes),
  batch 256, 5 rounds of ``grouped-fused`` with g=4 on one chip
  (``exec=vmap``), through ``repro.launch.train.run``. Once with
  ``--update-impl xla`` and once with ``pallas`` (the compiled fused
  update kernel) on the same seeded batches. Losses must be finite and
  the two arms' parameter updates must agree (``UPDATE_TOL``).
- serve: phi4-mini-3.8b at full width and depth with bfloat16 weights
  (fp32 weights do not fit one 16 GB chip), a ``ContinuousServer`` with
  8 slots, page 16, ``max_seq`` 512, built as ``repro.launch.serve``
  builds it. It answers 8 seeded requests (prompts of 64-256 tokens, 32
  generated tokens each) once with ``attn_impl="xla"`` and once with
  ``"pallas"`` (the paged-attention kernel); every request must finish.
  On one seeded page pool at the requests' positions, the compiled
  kernel must match the float32 attention reference (``KERNEL_TOL``),
  and the first decode step must give the same logits under both
  attention arms, within a bf16 tolerance scaled to the logits'
  magnitude and the model's depth (``LOGIT_TOL``, as the serving
  bench's ``paged_kernel_parity`` gate compares them at 2 layers).
- spmd (``--chips 4`` only): CaffeNet, batch 256, 3 rounds of the grouped
  SPMD step on the ("group", "data", "mp") mesh at (g, k, mp) = (2, 2, 1)
  and (2, 1, 2), each against ``exec_mode="reference"`` (the same (g, k)
  shard structure on one device). Checks that the batch shards and the
  mp-sharded parameters span four distinct chips and prints the largest
  parameter difference against the reference (``UPDATE_TOL``). Both
  sides run with float32 matmuls at "highest" precision: at the TPU's
  default (one bf16 pass) the two separately compiled programs round
  differently, which is noise here, not a fault of the distributed step.

Times printed are informational: compile (first step / warmup) separate
from the median of the remaining steps. The script exits non-zero, and
prints no result, unless JAX's default backend is a TPU with enough
chips. Any failed check raises. Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if SRC.is_dir() and str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

#: per leaf, max |a - b| of the final parameters of two arms that should
#: compute the same update, over max |a - p0| (the update itself): f32
#: arithmetic compiled into two programs drifts by far less; a wrong
#: kernel is off by O(1)
UPDATE_TOL = 2e-2
#: max |logits_xla - logits_pallas| over max(1, max |logits_xla|) for a
#: 2-layer model: the paged_kernel_parity bound for bf16 decode. The two
#: arms round differently (the XLA arm rounds scores and softmax weights
#: to bf16, the kernel keeps them in f32) and the difference compounds
#: through the residual stream, so the bound grows as sqrt(layers / 2)
LOGIT_TOL = 3e-2
#: max |kernel - float32 reference| over max |reference|, one layer of
#: bf16 K/V: the kernel rounds only p and its output to bf16
KERNEL_TOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def update_mismatch(a, b, p0) -> float:
    """Largest per-leaf max|a - b| / max|a - p0| over the parameter tree."""
    import jax
    import numpy as np
    worst = 0.0
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(p0)):
        x, y, z = (np.asarray(t, np.float64) for t in (x, y, z))
        step = max(float(np.abs(x - z).max()), 1e-30)
        worst = max(worst, float(np.abs(x - y).max()) / step)
    return worst


def _train_args(*, smoke: bool, batch: int, rounds: int, groups: int,
                seed: int, extra=()):
    from repro.launch import train
    return train.parse_args(
        ["--arch", "caffenet", "--batch", str(batch), "--steps", str(rounds),
         "--groups", str(groups), "--strategy", "grouped-fused",
         "--seed", str(seed), *(["--smoke"] if smoke else []), *extra])


def _init_caffenet(smoke: bool, seed: int):
    import jax
    from repro.models import cnn as C
    cfg = C.get_cnn_smoke_config("caffenet") if smoke \
        else C.get_cnn_config("caffenet")
    return cfg, C.init_params(jax.random.PRNGKey(seed), cfg)


def _step_times(engine) -> dict:
    """First step (compile included) apart from the median of the rest."""
    s = engine.telemetry.step_s
    return {"compile_step_s": s[0],
            "median_step_ms": (engine.telemetry.median_step_s() * 1e3
                               if len(s) > 1 else None)}


def _mp_sharded(x) -> bool:
    """True if array ``x`` is split over the "mp" mesh axis."""
    return any(e == "mp" or (isinstance(e, tuple) and "mp" in e)
               for e in x.sharding.spec if e is not None)


def train_phase(*, smoke: bool = False, batch: int = 256, rounds: int = 5,
                groups: int = 4, seed: int = 0) -> dict:
    """CaffeNet grouped-fused on one device, xla vs pallas fused update."""
    import numpy as np
    from repro.launch import train
    cfg, p0 = _init_caffenet(smoke, seed)
    log(f"[train] {cfg.name} {cfg.image_size}x{cfg.image_size}x"
        f"{cfg.in_channels}, {cfg.num_classes} classes, batch {batch}, "
        f"{rounds} rounds, grouped-fused g={groups}")
    out = {}
    for impl in ("xla", "pallas"):
        args = _train_args(smoke=smoke, batch=batch, rounds=rounds,
                           groups=groups, seed=seed,
                           extra=("--exec-mode", "vmap",
                                  "--update-impl", impl))
        params, losses, engine = train.run(args)
        desc = engine.describe(groups, batch // groups)
        if "exec=vmap" not in desc:
            raise RuntimeError(f"[train] expected exec=vmap, got {desc}")
        if len(losses) != rounds or not np.isfinite(losses).all():
            raise RuntimeError(f"[train] update_impl={impl}: losses {losses}")
        t = _step_times(engine)
        log(f"[train] update_impl={impl}: losses "
            + " ".join(repr(float(x)) for x in losses))
        log(f"[train] update_impl={impl}: compile+first step "
            f"{t['compile_step_s']:.2f} s; informational median step "
            f"{t['median_step_ms']} ms (excludes the compile step)")
        out[impl] = {"params": params, "losses": losses, **t}
    mism = update_mismatch(out["pallas"]["params"], out["xla"]["params"], p0)
    log(f"[train] pallas vs xla update: max per-leaf |diff| / |update| = "
        f"{mism!r} (tolerance {UPDATE_TOL})")
    if not mism <= UPDATE_TOL:
        raise RuntimeError(f"[train] pallas and xla arms disagree: {mism}")
    return {"mismatch": mism,
            **{impl: {k: v for k, v in arm.items() if k != "params"}
               for impl, arm in out.items()}}


def serve_phase(*, smoke: bool = False, slots: int = 8, page: int = 16,
                max_seq: int = 512, requests: int = 8,
                prompt_range=(64, 256), gen: int = 32,
                seed: int = 0) -> dict:
    """phi4-mini in bf16 through ContinuousServer, xla vs pallas decode."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.models import transformer as T
    from repro.serving import (ContinuousServer, PageAllocator,
                               PagedCacheSpec, init_pages, paged_decode_step,
                               poisson_trace, sample_requests)
    base = get_smoke_config("phi4-mini-3.8b") if smoke \
        else get_config("phi4-mini-3.8b")
    cfg = dataclasses.replace(base, param_dtype="bfloat16")
    log(f"[serve] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, vocab "
        f"{cfg.vocab_size}; param_dtype=bfloat16 (fp32 weights do not fit "
        "one 16 GB chip; bf16 is how the model is served)")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(T.init_params, static_argnums=1)(jax.random.PRNGKey(seed),
                                                 cfg))
    log(f"[serve] init {sum(x.size for x in jax.tree.leaves(params))} "
        f"params in {time.perf_counter() - t0:.2f} s")
    # every request arrives at once: the 8 slots fill in one admission
    trace = poisson_trace(1e6, requests, seed=seed)
    reqs = sample_requests(trace, cfg, prompt_range=prompt_range,
                           gen_range=(gen, gen), seed=seed)
    out = {}
    for impl in ("xla", "pallas"):
        srv = ContinuousServer(cfg, params, slots=slots, page_size=page,
                               max_seq=max_seq, attn_impl=impl, seed=seed)
        t0 = time.perf_counter()
        srv.warmup([len(r.prompt) for r in reqs])
        warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        rep = srv.run(reqs)
        wall = time.perf_counter() - t0
        done = int(rep.gen_counts.sum())
        if len(rep.rids) != requests or done != requests * gen:
            raise RuntimeError(f"[serve] attn_impl={impl}: finished "
                               f"{len(rep.rids)}/{requests} requests, "
                               f"{done}/{requests * gen} tokens")
        log(f"[serve] attn_impl={impl}: warmup compile {warm:.2f} s; "
            f"{len(rep.rids)}/{requests} requests finished, {done} tokens; "
            f"informational: served in {wall:.2f} s, p50 latency "
            f"{rep.percentile(50) * 1e3:.1f} ms")
        out[impl] = {"warmup_s": warm, "serve_s": wall,
                     "tokens": {int(r): rep.tokens[r] for r in rep.rids}}
        del srv
    toks = [(out["xla"]["tokens"][r], out["pallas"]["tokens"][r])
            for r in out["xla"]["tokens"]]
    same = float(np.mean([np.mean(a == b) for a, b in toks]))
    log(f"[serve] informational: greedy tokens equal across arms: "
        f"{same:.3f} (random weights leave near-ties, so not a check)")

    # one seeded page pool at the requests' positions
    # (paged_kernel_parity's comparison): the kernel against the float32
    # reference on one layer, then the first decode step under both arms
    spec = PagedCacheSpec.for_config(cfg, num_slots=slots, page_size=page,
                                     max_seq=max_seq)
    alloc = PageAllocator(spec)
    rows = reqs[:slots]
    for s, r in enumerate(rows):
        alloc.ensure(s, len(r.prompt) + 1)
    rng = np.random.default_rng(seed)
    pools = {k: jnp.asarray(rng.standard_normal(v.shape, np.float32), v.dtype)
             for k, v in jax.eval_shape(lambda: init_pages(spec)).items()}
    table = jnp.asarray(alloc.tables)
    pos = jnp.asarray([len(r.prompt) for r in rows], jnp.int32)
    tok = jnp.asarray([[int(r.prompt[-1])] for r in rows], jnp.int32)
    active = jnp.ones((len(rows),), bool)
    q = jnp.asarray(rng.standard_normal(
        (len(rows), 1, cfg.num_heads, cfg.resolved_head_dim), np.float32),
        cfg.dtype("compute"))
    kp, vp = pools["k"][0], pools["v"][0]
    up = lambda t: t.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(paged_attention_ref(up(q), up(kp), up(vp), table,
                                             pos))
    scale = float(np.abs(ref).max())
    kerr = float(np.abs(np.asarray(paged_attention(q, kp, vp, table, pos),
                                   np.float32) - ref).max()) / scale
    # the XLA arm's own bf16 numerics, for scale (informational)
    xerr = float(np.abs(np.asarray(paged_attention_ref(q, kp, vp, table, pos),
                                   np.float32) - ref).max()) / scale
    log(f"[serve] one layer vs float32 attention: max |kernel - ref| / "
        f"max |ref| = {kerr!r} (tolerance {KERNEL_TOL}); informational, "
        f"the XLA arm's bf16 einsum/softmax: {xerr!r}")
    if not kerr <= KERNEL_TOL:
        raise RuntimeError(f"[serve] paged kernel off the reference: {kerr}")
    logits = {}
    for impl in ("xla", "pallas"):
        f = jax.jit(lambda p, pg, impl=impl: paged_decode_step(
            p, pg, table, tok, pos, active, cfg, attn_impl=impl)[0])
        logits[impl] = np.asarray(f(params, pools), np.float32)
    lx, lp = logits["xla"], logits["pallas"]
    if not (np.isfinite(lx).all() and np.isfinite(lp).all()):
        raise RuntimeError("[serve] non-finite first-step logits")
    diff = float(np.abs(lx - lp).max())
    tol = (LOGIT_TOL * (cfg.num_layers / 2) ** 0.5
           * max(1.0, float(np.abs(lx).max())))
    log(f"[serve] first-step logits {lx.shape}: max |xla - pallas| = "
        f"{diff!r} (tolerance {tol!r})")
    if not diff <= tol:
        raise RuntimeError(f"[serve] logits disagree: {diff} > {tol}")
    return {"logit_diff": diff, "logit_tol": tol, "kernel_err": kerr,
            "token_agreement": same,
            **{impl: {"warmup_s": arm["warmup_s"], "serve_s": arm["serve_s"]}
               for impl, arm in out.items()}}


def spmd_phase(*, smoke: bool = False, batch: int = 256, rounds: int = 3,
               layouts=((2, 2, 1), (2, 1, 2)), seed: int = 0) -> dict:
    """Grouped SPMD step on four devices against the one-device reference."""
    import jax
    import numpy as np
    from repro.launch import train
    n = 4
    if len(jax.devices()) < n:
        raise RuntimeError(f"[spmd] needs {n} devices, have "
                           f"{len(jax.devices())}")
    _, p0 = _init_caffenet(smoke, seed)
    res = {}
    for g, k, mp in layouts:
        if g * k * mp != n:
            raise ValueError(f"layout {(g, k, mp)} does not use {n} devices")
        runs = {}
        for mode in ("spmd", "reference"):
            args = _train_args(smoke=smoke, batch=batch, rounds=rounds,
                               groups=g, seed=seed,
                               extra=("--exec-mode", mode, "--mp", str(mp)))
            with jax.default_matmul_precision("highest"):
                params, losses, engine = train.run(args, num_devices=n)
            desc = engine.describe(g, batch // g)
            if not np.isfinite(losses).all():
                raise RuntimeError(f"[spmd] {mode} {(g, k, mp)}: losses "
                                   f"{losses}")
            t = _step_times(engine)
            log(f"[spmd] {(g, k, mp)} {mode}: {desc}; losses "
                + " ".join(repr(float(x)) for x in losses)
                + f"; compile+first step {t['compile_step_s']:.2f} s; "
                f"informational median step {t['median_step_ms']} ms")
            runs[mode] = (params, losses, t)
        params = runs["spmd"][0]
        leaves = jax.tree.leaves(params)
        mesh = leaves[0].sharding.mesh
        if dict(mesh.shape) != {"group": g, "data": k, "mp": mp}:
            raise RuntimeError(f"[spmd] mesh {dict(mesh.shape)} is not "
                               f"{(g, k, mp)}")
        chips = {d.id for d in mesh.devices.flat}
        if len(chips) != n:
            raise RuntimeError(f"[spmd] mesh spans devices {chips}")
        # the step shards the batch over ("group", "data"): one shard per
        # (group, data) position, replicated over mp
        batch_chips = {d.id for d in mesh.devices[:, :, 0].flat}
        if len(batch_chips) != g * k:
            raise RuntimeError(f"[spmd] batch shards on {batch_chips}")
        if mp > 1:
            sharded = [x for x in leaves if _mp_sharded(x)]
            if not sharded:
                raise RuntimeError("[spmd] no parameter is sharded over mp")
            x = max(sharded, key=lambda t: t.size)
            holders = {s.device.id for s in x.addressable_shards}
            slices = {str(s.index) for s in x.addressable_shards}
            if len(holders) != n or len(slices) != mp:
                raise RuntimeError(f"[spmd] mp-sharded {x.shape} on "
                                   f"{holders} in {len(slices)} slices")
        mism = update_mismatch(params, runs["reference"][0], p0)
        maxdiff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                      for a, b in zip(leaves,
                                      jax.tree.leaves(runs["reference"][0])))
        log(f"[spmd] {(g, k, mp)}: batch shards on {len(batch_chips)} "
            f"devices, mesh on {len(chips)}; spmd vs reference max |diff| "
            f"= {maxdiff!r}, max per-leaf |diff| / |update| = {mism!r} "
            f"(tolerance {UPDATE_TOL})")
        if not mism <= UPDATE_TOL:
            raise RuntimeError(f"[spmd] {(g, k, mp)} disagrees with the "
                               f"reference: {mism}")
        res[(g, k, mp)] = {"max_abs_diff": maxdiff, "mismatch": mism}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve phases; 4: the grouped SPMD "
                         "phase only")
    args = ap.parse_args(argv)
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX sees "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    log(f"device: {dev}; compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        spmd_phase()
    else:
        train_phase()
        serve_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
