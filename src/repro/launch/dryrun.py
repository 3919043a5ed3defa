import os
import sys
# Forced host device pool on the CPU backend, set before jax initializes:
# 8 for the --host-smoke CI lane (matches the tier-1 test pool), 512 for
# production dry-runs. An externally provided XLA_FLAGS (test harness
# subprocess) wins. The pool is host devices by design, so the backend is
# pinned to the CPU even on a machine with an accelerator.
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count="
    + ("8" if "--host-smoke" in sys.argv[1:] else "512"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) combination with ShapeDtypeStruct
stand-ins — no allocation — and record memory analysis, cost analysis and
the collective schedule for the roofline (EXPERIMENTS.md §Dry-run/§Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
  PYTHONPATH=src python -m repro.launch.dryrun --host-smoke
Results: experiments/dryrun/<arch>__<shape>__<mesh>.json

--host-smoke is the CI regression lane for the big configs: it lowers AND
compiles the 405B-class architectures through the canonical
("group","data","mp") mesh on 8 forced host devices (no allocation —
AOT compile over ShapeDtypeStructs) and fails on HLO/memory-model
regressions: a compile error, params that stopped sharding over the mp
axis, a vanished collective schedule, or per-device argument bytes
blowing past the sharded-state memory model.
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config, list_archs  # noqa: E402
from repro.configs.base import TrainConfig                      # noqa: E402
from repro.launch import steps as ST                            # noqa: E402
from repro.launch.hlo_analysis import roofline_from_compiled  # noqa: E402
from repro.launch.mesh import make_production_mesh              # noqa: E402
from repro.launch.params_util import (active_param_count,       # noqa: E402
                                      param_bytes, param_count)
from repro.sharding import rules as SH                          # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# grad-accum (microbatching) for train_4k, tuned so remat'd activations fit
# HBM; inference shapes never accumulate.
GRAD_ACCUM = {
    "llama3-405b": 16,
    "llama-3.2-vision-90b": 16,
    "grok-1-314b": 16,
    "deepseek-coder-33b": 8,
    "qwen2-7b": 8,
    "phi4-mini-3.8b": 8,
    "qwen2-moe-a2.7b": 8,
    "mamba2-2.7b": 8,
    "recurrentgemma-2b": 8,
    "whisper-base": 8,   # 51 GiB/chip of fp32 logit temporaries at accum=1
}


def _tokens_per_step(shape) -> float:
    if shape.kind == "train":
        return shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return shape.global_batch * shape.seq_len
    return shape.global_batch  # decode: 1 token per sequence


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              tc: TrainConfig = None, verbose: bool = True,
              accum_override: int = None, seq_parallel: bool = False,
              weight_stationary: bool = False, tag: str = ""):
    """Returns a result dict (raises on lowering/compile failure)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if not ST.supports_shape(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped",
                "reason": "encdec has no 500k-token decode regime "
                          "(launch.steps.supports_shape)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    accum = GRAD_ACCUM.get(arch, 1) if shape.kind == "train" else 1
    if accum_override is not None and shape.kind == "train":
        accum = accum_override
    if tc is None:
        tc = TrainConfig(grad_accum=accum)

    pspecs = ST.params_specs(cfg)
    p_shard = SH.params_shardings(pspecs, cfg, mesh,
                                  decode_kv_hd=weight_stationary
                                  and shape.kind == "decode")
    bspecs = ST.batch_specs(cfg, shape, grad_accum=tc.grad_accum)
    b_shard = SH.batch_shardings(bspecs, mesh,
                                 batch_dim=1 if tc.grad_accum > 1 else 0)
    t0 = time.time()

    act_ctx = SH.activation_sharding(mesh, seq_parallel_attention=seq_parallel,
                                     weight_stationary=weight_stationary)
    with mesh, act_ctx:
        if shape.kind == "train":
            mspecs = jax.eval_shape(
                lambda p: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, cfg.dtype("mom")), p), pspecs)
            m_shard = SH.params_shardings(mspecs, cfg, mesh)
            step = ST.make_train_step(cfg, tc, shape, grad_shardings=p_shard)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, m_shard, b_shard),
                out_shardings=(p_shard, m_shard, SH.replicated(mesh)),
            ).lower(pspecs, mspecs, bspecs)
        elif shape.kind == "prefill":
            step = ST.make_prefill_step(cfg, shape)
            lowered = jax.jit(
                step, in_shardings=(p_shard, b_shard),
            ).lower(pspecs, bspecs)
        else:  # decode
            cspecs = ST.cache_specs_struct(cfg, shape)
            c_shard = SH.cache_shardings(cspecs, cfg, mesh,
                                         batch=shape.global_batch)
            step = ST.make_decode_step(cfg, shape)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, b_shard,
                              SH.replicated(mesh)),
                out_shardings=(SH.replicated(mesh), c_shard),
            ).lower(pspecs, cspecs, bspecs, jax.ShapeDtypeStruct((), jnp.int32))
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    n_total = param_count(pspecs)
    n_active = active_param_count(pspecs, cfg)
    pbytes = param_bytes(pspecs)
    cache_bytes = 0.0
    if shape.kind == "decode":
        cspecs2 = ST.cache_specs_struct(cfg, shape)
        cache_bytes = float(sum(
            jnp.zeros((), l.dtype).itemsize * float(jnp.prod(jnp.array(l.shape)))
            for l in jax.tree.leaves(cspecs2)))
    from repro.launch.hlo_analysis import analytic_hbm_bytes
    hbm = analytic_hbm_bytes(cfg, shape, chips, grad_accum=tc.grad_accum,
                             params_bytes_global=pbytes,
                             cache_bytes_global=cache_bytes)
    roof = roofline_from_compiled(compiled, chips, hbm_bytes=hbm)
    from repro.launch.hlo_parse import analyze_module
    stats = analyze_module(compiled.as_text())
    tokens = _tokens_per_step(shape)
    # 6ND for training (fwd+bwd), 2ND for inference (fwd only)
    model_flops = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens

    res = {
        "arch": arch, "shape": shape_name, "variant": tag or "baseline",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "status": "ok",
        "seq_parallel": seq_parallel,
        "grad_accum": tc.grad_accum,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "params_total": n_total, "params_active": n_active,
        "param_bytes_global": param_bytes(pspecs),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_per_chip_est": ma.argument_size_in_bytes
                                 + ma.temp_size_in_bytes,
        },
        "roofline": roof.as_dict(),
        "collectives": {"bytes": stats.collective_bytes,
                        "count": stats.collective_counts},
        "model_flops_global": model_flops,
        "useful_flops_frac": (model_flops / chips) / roof.flops
                             if roof.flops else None,
    }
    if verbose:
        print(f"[{res['mesh']}] {arch} x {shape_name}: "
              f"compile {res['compile_s']}s, "
              f"mem/chip {(res['memory']['peak_per_chip_est'])/2**30:.2f} GiB, "
              f"bottleneck {roof.bottleneck}, step {roof.step_time*1e3:.2f} ms")
    return res


# Big configs exercised by the CI host-smoke lane (dense 405B-class, MoE,
# SSM — one per memory-model family).
HOST_SMOKE_ARCHS = ("llama3-405b", "qwen2-moe-a2.7b", "mamba2-2.7b")


def host_smoke_one(arch: str, *, groups: int = 1, data: int = 4, mp: int = 2,
                   seq_len: int = 128, batch: int = 8, verbose: bool = True):
    """Lower + compile ``arch``'s full train step through the canonical
    ("group","data","mp") mesh on forced host devices, then check the
    memory model and HLO still behave. Returns a result dict; raises
    AssertionError / compile errors on regression.

    Checks (the "fails on HLO/memory-model regressions" contract):
      * lower + AOT compile succeed on the engine-canonical mesh;
      * when mp > 1, at least one param leaf is sharded over "mp";
      * compiled per-device argument bytes respect the sharded-state
        memory model: <= state_bytes / (data*mp) * 1.3 + 1 GiB slack
        (a replication regression inflates this by ~data*mp and trips);
      * the HLO still contains a collective schedule (sharded params on
        a multi-device mesh must communicate; zero collectives means the
        partitioner silently stopped sharding).
    """
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_smoke_mesh

    n_dev = jax.device_count()
    need = groups * data * mp
    if need > n_dev:
        raise ValueError(f"host-smoke mesh {groups}x{data}x{mp} needs {need} "
                         f"devices, have {n_dev}")
    cfg = get_config(arch)
    shape = InputShape("hostsmoke", seq_len, batch, "train")
    mesh = make_host_smoke_mesh(data=data, mp=mp, groups=groups)
    tc = TrainConfig(grad_accum=1)

    pspecs = ST.params_specs(cfg)
    p_shard = SH.params_shardings(pspecs, cfg, mesh)
    bspecs = ST.batch_specs(cfg, shape, grad_accum=1)
    b_shard = SH.batch_shardings(bspecs, mesh)
    t0 = time.time()
    with mesh, SH.activation_sharding(mesh):
        mspecs = jax.eval_shape(
            lambda p: jax.tree.map(
                lambda x: jnp.zeros(x.shape, cfg.dtype("mom")), p), pspecs)
        m_shard = SH.params_shardings(mspecs, cfg, mesh)
        step = ST.make_train_step(cfg, tc, shape, grad_shardings=p_shard)
        lowered = jax.jit(
            step,
            in_shardings=(p_shard, m_shard, b_shard),
            out_shardings=(p_shard, m_shard, SH.replicated(mesh)),
        ).lower(pspecs, mspecs, bspecs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # --- HLO sanity: the mp axis must actually shard parameter storage ---
    def _axes(spec):
        out = set()
        for entry in tuple(spec):
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if a is not None:
                    out.add(a)
        return out

    shardings = jax.tree.leaves(
        p_shard, is_leaf=lambda x: hasattr(x, "spec"))
    mp_leaves = sum(1 for s in shardings if "mp" in _axes(s.spec))
    if mp > 1:
        assert mp_leaves > 0, (
            f"{arch}: no param leaf is sharded over the 'mp' axis — the "
            "mesh/rules unification regressed (rules.default_axes)")

    # --- memory model: arguments must be state-sharded, not replicated ---
    ma = compiled.memory_analysis()
    pbytes = param_bytes(pspecs)
    import math
    mom_bytes = float(sum(
        jnp.zeros((), l.dtype).itemsize * math.prod(l.shape)
        for l in jax.tree.leaves(mspecs)))
    state_bytes = pbytes + mom_bytes
    arg_bound = state_bytes / (data * mp) * 1.3 + 2.0**30
    assert ma.argument_size_in_bytes <= arg_bound, (
        f"{arch}: per-device argument bytes "
        f"{ma.argument_size_in_bytes/2**30:.1f} GiB exceed the sharded-state "
        f"model bound {arg_bound/2**30:.1f} GiB "
        f"(state {state_bytes/2**30:.1f} GiB over data*mp={data*mp}) — "
        "parameters or momentum replicated?")

    from repro.launch.hlo_parse import analyze_module
    stats = analyze_module(compiled.as_text())
    n_coll = int(sum(stats.collective_counts.values()))
    if need > 1:
        assert n_coll > 0, (
            f"{arch}: compiled HLO has no collectives on a {need}-device "
            "mesh — partitioner silently stopped sharding")

    res = {
        "arch": arch, "shape": "hostsmoke", "status": "ok",
        "mesh": f"{groups}x{data}x{mp}", "mesh_axes": list(mesh.axis_names),
        "chips": need, "seq_len": seq_len, "global_batch": batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "params_total": param_count(pspecs),
        "state_bytes_global": state_bytes,
        "mp_sharded_param_leaves": mp_leaves,
        "param_leaves": len(shardings),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "argument_bound_bytes": arg_bound,
        },
        "collectives": {"bytes": stats.collective_bytes,
                        "count": stats.collective_counts},
    }
    if verbose:
        print(f"[host-smoke {res['mesh']}] {arch}: "
              f"lower {res['lower_s']}s, compile {res['compile_s']}s, "
              f"args/dev {ma.argument_size_in_bytes/2**30:.1f} GiB "
              f"(bound {arg_bound/2**30:.1f}), "
              f"mp-sharded leaves {mp_leaves}/{len(shardings)}, "
              f"collectives {n_coll}")
    return res


def run_host_smoke(args):
    """CLI driver for --host-smoke: run every HOST_SMOKE_ARCHS config (or
    just --arch), write JSON next to the production dry-run artifacts,
    exit non-zero on any regression."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(HOST_SMOKE_ARCHS)
    failures = []
    for arch in archs:
        tag = f"{arch}__hostsmoke__{args.smoke_g}x{args.smoke_data}x{args.smoke_mp}"
        try:
            res = host_smoke_one(arch, groups=args.smoke_g,
                                 data=args.smoke_data, mp=args.smoke_mp)
        except Exception as e:
            traceback.print_exc()
            res = {"arch": arch, "shape": "hostsmoke", "status": "FAILED",
                   "mesh": f"{args.smoke_g}x{args.smoke_data}x{args.smoke_mp}",
                   "error": str(e)[-2000:]}
            failures.append(tag)
        (OUT_DIR / f"{tag}.json").write_text(json.dumps(res, indent=2))
    if failures:
        print("HOST-SMOKE FAILURES:", failures)
        raise SystemExit(1)
    print(f"host-smoke OK ({len(archs)} configs)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="run the 2x16x16 mesh (default 16x16)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--accum", type=int, default=None,
                    help="override grad accumulation (hillclimb variant)")
    ap.add_argument("--seqpar", action="store_true",
                    help="sequence-parallel attention variant")
    ap.add_argument("--wstat", action="store_true",
                    help="weight-stationary decode variant")
    ap.add_argument("--tag", type=str, default="",
                    help="variant tag appended to the output filename")
    ap.add_argument("--host-smoke", action="store_true",
                    help="CI lane: compile the big configs on a forced "
                         "8-host-device ('group','data','mp') mesh and fail "
                         "on HLO/memory-model regressions")
    ap.add_argument("--smoke-g", type=int, default=1,
                    help="host-smoke mesh: compute groups")
    ap.add_argument("--smoke-data", type=int, default=4,
                    help="host-smoke mesh: data-parallel width")
    ap.add_argument("--smoke-mp", type=int, default=2,
                    help="host-smoke mesh: model-parallel width")
    args = ap.parse_args()

    if args.host_smoke:
        run_host_smoke(args)
        return

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = sorted(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                if args.tag:
                    tag += f"__{args.tag}"
                try:
                    res = lower_one(arch, shape, multi_pod=mp,
                                    accum_override=args.accum,
                                    seq_parallel=args.seqpar,
                                    weight_stationary=args.wstat,
                                    tag=args.tag)
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "FAILED", "error": str(e)[-2000:]}
                    failures.append(tag)
                (OUT_DIR / f"{tag}.json").write_text(json.dumps(res, indent=2))
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("all dry-runs OK")


if __name__ == "__main__":
    main()
