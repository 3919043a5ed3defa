"""End-to-end training driver — argument parsing in front of the unified
execution engine (``repro.engine``). The engine owns the loop: mesh-
sharded grouped step (real SPMD over a ("group","data") device split when
devices are available), strategy plugins, prefetch, donation, telemetry,
checkpoint hooks, trace replay.

CPU-runnable examples (reduced archs, real data pipeline, Omnivore
compute groups + strategies):

  # token LM, 4 async compute groups
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
      --steps 60 --groups 4 --momentum 0.3 --lr 0.05

  # the paper's own workload family: CNN with the merged-FC sync head
  PYTHONPATH=src python -m repro.launch.train --arch lenet --smoke \
      --steps 60 --groups 4 --momentum 0.3 --lr 0.05

  # 8 real host devices: XLA_FLAGS=--xla_force_host_platform_device_count=8

Heterogeneous planning (--cluster-spec ... --plan) picks g, the
device->group packing and throughput-proportional batch shares; trace
replay (--replay-trace trace.npz) executes along a recorded event
schedule. Both run through the same Engine.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.data.pipeline import DataConfig, SyntheticImages, SyntheticLM
from repro.engine import Engine
from repro.models import cnn as C
from repro.models import transformer as T
from repro.optim.sgd import init_momentum


def _build_workload(args):
    """(name, params, loss_fn, data_iterable, head_filter) per --arch."""
    if args.arch in C.CNN_CONFIGS:
        import dataclasses
        cfg = C.get_cnn_smoke_config(args.arch) if args.smoke \
            else C.get_cnn_config(args.arch)
        if args.conv_impl:
            cfg = dataclasses.replace(cfg, conv_impl=args.conv_impl)
        if cfg.conv_impl == "lowering_interpret":
            # probe + cache (b_p, r_b) per conv layer before the engine
            # compiles the step (paper Fig. 4 b_p sweep, automated).
            # Probe at the per-group batch — the shape the engine actually
            # traces each conv at (the cache key ignores the batch dim, so
            # per-device shards still hit)
            tiles = C.autotune_conv_tiles(cfg,
                                          max(1, args.batch // args.groups))
            print("autotuned conv tiles: " + ", ".join(
                f"layer{i}(bp={bp},rb={rb})"
                for i, (bp, rb) in sorted(tiles.items())))
        params = C.init_params(jax.random.PRNGKey(args.seed), cfg)
        data = SyntheticImages(DataConfig(
            batch_size=args.batch, image_size=cfg.image_size,
            channels=cfg.in_channels, num_classes=cfg.num_classes,
            seed=args.seed))
        return (cfg.name, params, lambda p, b: C.loss_fn(p, b, cfg),
                data.batches(args.steps), C.head_filter, cfg)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.arch_type in ("encdec", "vlm"):
        raise SystemExit("train.py drives token-LM and CNN archs; see "
                         "examples/ for the modality-stub variants")
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    data = SyntheticLM(DataConfig(batch_size=args.batch, seq_len=args.seq,
                                  vocab_size=cfg.vocab_size, seed=args.seed))
    return (cfg.name, params, lambda p, b: T.lm_loss(p, b, cfg),
            data.batches(args.steps), None, cfg)


def _plan(args, params, cfg):
    """Heterogeneous plan: g, device->group packing, batch shares."""
    from repro import cluster
    devices = cluster.parse_cluster_spec(args.cluster_spec)
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    tokens = args.seq if hasattr(cfg, "vocab_size") else 1
    # rough roofline: ~6*P FLOPs per token fwd+bwd, one param sweep of
    # memory traffic per example, fp32 gradient payload
    # fp32 params + fp32 momentum resident per model replica
    cost = cluster.WorkloadCost(flops_per_example=6.0 * n_params * tokens,
                                bytes_per_example=4.0 * n_params,
                                grad_bytes=4.0 * n_params,
                                state_bytes=8.0 * n_params)
    # merged-FC phase ~ the head matmul on the full batch on the fastest
    # device (unembed for LMs, the FC stack for CNNs)
    if hasattr(cfg, "vocab_size"):
        head_flops = 6.0 * cfg.d_model * cfg.vocab_size * args.seq
    else:
        head_flops = 6.0 * sum(int(np.prod(p["w"].shape))
                               for p in params["fc"])
    t_fc = args.batch * head_flops / max(d.peak_flops for d in devices)
    # 2-D (g, mp) search: powers of two up to the smallest group's size;
    # infeasible points (memory, group width) are skipped by the planner
    n = len(devices)
    mp_candidates = [m for m in (1, 2, 4, 8, 16) if m <= n]
    plan = cluster.best_allocation(devices, global_batch=args.batch,
                                   t_fc=t_fc, cost=cost,
                                   mp_candidates=mp_candidates)
    print(plan.describe())
    return plan


def parse_args(argv=None) -> argparse.Namespace:
    """The driver's command line, validated."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch",
                    choices=[*list_archs(), *sorted(C.CNN_CONFIGS)],
                    default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--groups", type=int, default=1,
                    help="compute groups g (paper's execution strategy)")
    ap.add_argument("--mp", type=int, default=1,
                    help="model-parallel devices per worker: shards "
                         "params/optimizer state over the mesh's 'mp' "
                         "axis (sharding.rules.engine_param_specs); the "
                         "device budget becomes groups*k*mp. --plan "
                         "overrides this with the planner's (g, mp) pick")
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--strategy",
                    choices=("sync", "grouped-fused", "grouped-scan"),
                    default="grouped-fused",
                    help="engine strategy (sync is the g=1 reduction; "
                         "--replay-trace switches to trace-replay)")
    ap.add_argument("--exec-mode",
                    choices=("auto", "spmd", "reference", "vmap"),
                    default="auto",
                    help="step placement: SPMD group mesh when devices "
                         "allow (auto), forced mesh, the bit-exact "
                         "single-device reference, or the legacy vmap path")
    ap.add_argument("--update-impl", choices=("xla", "pallas"), default="xla",
                    help="leaf kernel for the fused update (pallas runs "
                         "interpret-mode off-TPU)")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="slab size target of the SPMD step's overlapped "
                         "bucketed gradient exchange (0 = legacy "
                         "whole-tree gather; default engine.spmd."
                         "DEFAULT_BUCKET_BYTES)")
    ap.add_argument("--conv-impl",
                    choices=("xla", "lowering", "lowering_interpret",
                             "lowering_autodiff"),
                    default="",
                    help="CNN conv path (CNN archs only): lowering = "
                         "custom-VJP batched-GEMM train path (config "
                         "default), lowering_interpret = Pallas kernels "
                         "with per-layer autotuned tiles (Pallas "
                         "interpreter only; raises on TPU), "
                         "lowering_autodiff = generic-autodiff baseline, "
                         "xla = native conv")
    ap.add_argument("--replay-trace", type=str, default="",
                    help="replay a recorded event trace (.npz EventTrace): "
                         "one per-commit stale update per trace commit "
                         "instead of round-robin rounds (truncated to "
                         "--steps commits)")
    ap.add_argument("--replay-impl", choices=("scan", "python", "fused"),
                    default="scan")
    ap.add_argument("--replay-depth", type=int, default=0,
                    help="cap the replay parameter-history ring "
                         "(0 = full max-staleness depth)")
    ap.add_argument("--cluster-spec", type=str, default="",
                    help="heterogeneous cluster, e.g. "
                         "'8xgpu-g2.2xlarge,8xcpu-c4.4xlarge'")
    ap.add_argument("--plan", action="store_true",
                    help="plan g / device packing / batch shares over "
                         "--cluster-spec and train share-weighted "
                         "(overrides --groups)")
    ap.add_argument("--ckpt", type=str, default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", type=str, default="",
                    help="sink the run's metric stream (step_s, "
                         "data_wait_s, h2d_s, loss, ...) to this JSONL "
                         "file (schema: repro.obs.metrics)")
    ap.add_argument("--trace-out", type=str, default="",
                    help="export a Chrome trace-event JSON of the run's "
                         "spans + metrics to this file (open in Perfetto)")
    args = ap.parse_args(argv)
    if args.plan and not args.cluster_spec:
        ap.error("--plan requires --cluster-spec")
    if args.conv_impl and args.arch not in C.CNN_CONFIGS:
        ap.error(f"--conv-impl applies to CNN archs "
                 f"({', '.join(sorted(C.CNN_CONFIGS))}), not {args.arch}")
    if args.plan and args.replay_trace:
        ap.error("--plan and --replay-trace are mutually exclusive "
                 "(a replay executes a recorded schedule; there is "
                 "nothing for the planner to allocate)")
    return args


def main(argv=None):
    """Train per ``argv``; returns the per-round losses."""
    args = parse_args(argv)
    # install a recording span tracer for the whole run (workload build,
    # autotune probes, engine loop) iff a trace export was requested —
    # otherwise every span below stays the shared no-op
    from repro.obs import spans
    with spans.maybe_traced(bool(args.trace_out)):
        return run(args)[1]


def _export_obs(args, engine, groups: int, event_trace=None) -> None:
    """Sink the run's metric stream / Chrome trace when requested."""
    if not (args.metrics_out or args.trace_out):
        return
    from repro.obs import export_chrome_trace, run_metadata
    if args.metrics_out:
        strategy = "trace-replay" if args.replay_trace else args.strategy
        run = run_metadata(extra={"arch": args.arch, "groups": groups,
                                  "batch": args.batch, "steps": args.steps,
                                  "strategy": strategy})
        n = engine.telemetry.registry.to_jsonl(args.metrics_out, run)
        print(f"metrics -> {args.metrics_out} ({n} records)")
    if args.trace_out:
        tracer = engine.tracer if engine.tracer.enabled else None
        n = export_chrome_trace(args.trace_out, tracer=tracer,
                                metrics=engine.telemetry.registry,
                                event_trace=event_trace)
        print(f"chrome trace -> {args.trace_out} ({n} events; open at "
              "https://ui.perfetto.dev)")


def run(args, **engine_kw):
    """Build the workload and the Engine from parsed ``args`` and train.
    ``engine_kw`` reaches the grouped ``Engine`` as extra keyword
    arguments (e.g. ``num_devices`` to pin the mesh width). Returns
    ``(final_params, losses, engine)``."""
    name, params, loss_fn, data, head_filter, cfg = _build_workload(args)
    mom = init_momentum(params)

    if args.replay_trace:
        from repro.exec import EventTrace
        trace = EventTrace.load(args.replay_trace)
        engine = Engine(loss_fn, strategy="trace-replay", trace=trace,
                        lr=args.lr, momentum=args.momentum,
                        weight_decay=args.weight_decay,
                        replay_impl=args.replay_impl,
                        replay_depth=args.replay_depth or None)
        t = trace.truncate(args.steps)
        if len(t) == 0:
            raise SystemExit(f"{args.replay_trace} has no commits to replay "
                             f"(after truncation to --steps {args.steps})")
        print(f"arch={name} replaying {args.replay_trace}: {len(t)} commits, "
              f"g={trace.num_groups}, mean staleness "
              f"{float(t.staleness.mean()):.2f}, max {t.max_staleness}")
        # one microbatch per commit: the per-commit stream uses batch-size
        # microbatches, matching the per-group share of a grouped round
        params, _, losses = engine.run(params, mom, data, steps=args.steps,
                                       log_every=10)
        print(f"final loss {np.mean(losses[-5:]):.4f} "
              f"(impl={args.replay_impl})")
        _export_obs(args, engine, trace.num_groups, event_trace=t)
        return params, losses, engine

    groups, group_weights, micro_sizes, mp = args.groups, None, None, args.mp
    if args.plan:
        plan = _plan(args, params, cfg)
        groups, group_weights = plan.g, plan.weights
        micro_sizes = plan.allocation.microbatches
        mp = plan.mp
        # the plan's mp is sized for the --cluster-spec devices; when this
        # process emulates the run on a smaller local pool (the smoke
        # default: 1 host device), mp-sharded storage has no mesh to live
        # on — store unsharded and keep the rest of the plan
        if args.exec_mode == "auto" and mp > 1 \
                and jax.device_count() < groups * mp:
            print(f"plan chose mp={mp} for the cluster; local pool has "
                  f"{jax.device_count()} device(s) < g*mp={groups * mp} — "
                  "storing params unsharded here (mp=1)")
            mp = 1

    engine = Engine(loss_fn, strategy=args.strategy, num_groups=groups,
                    lr=args.lr, momentum=args.momentum,
                    weight_decay=args.weight_decay,
                    group_weights=group_weights, micro_sizes=micro_sizes,
                    head_filter=head_filter, update_impl=args.update_impl,
                    exec_mode=args.exec_mode, mp=mp,
                    **({"bucket_bytes": args.bucket_bytes}
                       if args.bucket_bytes is not None else {}),
                    checkpoint_dir=args.ckpt,
                    checkpoint_every=args.steps if args.ckpt else 0,
                    **engine_kw)
    print(f"arch={name} {engine.describe(groups, args.batch // groups)}"
          + (" (planned)" if args.plan else ""))
    params, mom, losses = engine.run(params, mom, data, steps=args.steps,
                                     log_every=10)
    print(f"final loss {np.mean(losses[-5:]):.4f}")
    summary = engine.telemetry.summary(batch_size=args.batch)
    print(f"telemetry: {summary['median_step_ms']:.1f} ms/step median, "
          f"{summary['examples_per_s']:.0f} examples/s, "
          f"{summary['data_wait_ms']:.1f} ms/step host data wait")
    _export_obs(args, engine, groups)
    if args.ckpt:
        print("checkpointed to", args.ckpt)
    return params, losses, engine


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
