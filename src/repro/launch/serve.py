"""Serving driver: static batched generation or trace-driven continuous
batching (``repro.serving``).

Two modes:

- ``--mode static`` (the original flow, kept as the baseline): one batch,
  single-pass jitted prefill filling the whole KV cache, then a per-token
  decode loop. Per-phase timings go through the ``obs`` metric registry
  on the repo's one monotonic clock.
- ``--mode continuous``: a Poisson request trace (``--rate``/``--requests``,
  or ``--arrival-trace`` to replay a saved ``EventTrace``) served by the
  ``ContinuousServer`` — slot-recycled paged KV cache, one compiled decode
  step for a changing request population, bucketed prefill — reported as
  tok/s + p50/p99 latency + goodput at ``--slo-ms``, with the static
  baseline on the same trace for comparison.

CPU-runnable:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b --smoke \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
      --mode continuous --rate 40 --requests 24 --slo-ms 500
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.engine.timing import monotonic
from repro.models import transformer as T
from repro.obs import spans
from repro.obs.metrics import MetricRegistry


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
          registry: MetricRegistry | None = None):
    """Static-batch generation: one jitted prefill call fills the whole
    KV cache, then a per-token decode loop for the generated suffix.
    Phase timings land in ``registry`` (series ``serve.prefill_s`` /
    ``serve.decode_s``). Returns (gen_tokens, prefill_seconds,
    decode_seconds)."""
    reg = registry if registry is not None else MetricRegistry()
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(cfg.vocab_size,
                                       size=(batch, prompt_len)), jnp.int32)
    total = prompt_len + gen
    cache = T.init_cache(cfg, batch, total)

    prefill = jax.jit(lambda p, c, toks: T.prefill(p, c, toks, cfg))
    decode = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg))

    t0 = monotonic()
    with spans.span("serve.prefill", batch=batch, prompt_len=prompt_len):
        logits, cache = jax.block_until_ready(prefill(params, cache, prompts))
    t_prefill = monotonic() - t0
    reg.series("serve.prefill_s").append(t_prefill)

    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    outs = [tok]
    t0 = monotonic()
    with spans.span("serve.decode", batch=batch, gen=gen):
        for t in range(prompt_len, total - 1):
            logits, cache = decode(params, cache, tok, jnp.int32(t))
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
            tok = tok.astype(jnp.int32)
            outs.append(tok)
        jax.block_until_ready(outs)
    t_decode = monotonic() - t0
    reg.series("serve.decode_s").append(t_decode)
    gen_tokens = jnp.concatenate(outs, axis=1)
    return gen_tokens, t_prefill, t_decode


def _run_continuous(cfg, args, registry: MetricRegistry):
    from repro.exec.trace import EventTrace
    from repro.serving import (ContinuousServer, poisson_trace,
                               sample_requests, static_serve_trace)
    if args.arrival_trace:
        trace = EventTrace.load(args.arrival_trace)
    else:
        trace = poisson_trace(args.rate, args.requests, seed=args.seed)
    pmax = max(args.prompt_len, 8)
    reqs = sample_requests(trace, cfg, prompt_range=(max(4, pmax // 4), pmax),
                           gen_range=(max(2, args.gen // 4), args.gen),
                           seed=args.seed)
    max_seq = -(-(pmax + args.gen) // args.page_size) * args.page_size
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    srv = ContinuousServer(cfg, params, slots=args.batch,
                           page_size=args.page_size, max_seq=max_seq,
                           attn_impl=args.attn_impl,
                           gather_mode=args.gather_mode, registry=registry,
                           seed=args.seed)
    for note in registry.notes:           # e.g. pallas_gather ring fallback
        print(f"note: {note}")
    srv.warmup([pmax])
    rep = srv.run(reqs)
    base = static_serve_trace(cfg, reqs, batch=args.batch, params=params)
    slo = args.slo_ms / 1e3
    print(f"arch={cfg.name} continuous: {len(rep.rids)} reqs "
          f"{rep.total_tokens} tok in {rep.makespan:.2f}s "
          f"({rep.throughput:.0f} tok/s) p50={rep.percentile(50) * 1e3:.0f}ms "
          f"p99={rep.percentile(99) * 1e3:.0f}ms "
          f"goodput@{args.slo_ms:.0f}ms={rep.goodput(slo):.0f} tok/s "
          f"occ={rep.occupancy_mean:.2f}/{args.batch}")
    print(f"arch={cfg.name} static    : {base.makespan:.2f}s "
          f"({base.throughput:.0f} tok/s) "
          f"p50={base.percentile(50) * 1e3:.0f}ms "
          f"p99={base.percentile(99) * 1e3:.0f}ms "
          f"goodput@{args.slo_ms:.0f}ms={base.goodput(slo):.0f} tok/s")
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="mamba2-2.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / continuous decode slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="continuous: Poisson arrival rate, req/s")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous: number of requests")
    ap.add_argument("--arrival-trace", type=str, default="",
                    help="continuous: replay a saved EventTrace .npz "
                         "instead of drawing Poisson arrivals")
    ap.add_argument("--slo-ms", type=float, default=500.0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--attn-impl",
                    choices=("xla", "pallas", "pallas_gather"),
                    default="xla",
                    help="continuous decode attention: 'pallas' = in-kernel "
                         "paged walk, 'xla' = masked bucketed gather, "
                         "'pallas_gather' = legacy flash-over-a-copy "
                         "(falls back to xla under sliding windows, loudly)")
    ap.add_argument("--gather-mode", choices=("bucket", "full"),
                    default="bucket",
                    help="xla/pallas_gather decode: narrow the dense gather "
                         "to the batch's live page bucket, or pin the "
                         "full-capacity bitwise baseline")
    ap.add_argument("--metrics-out", type=str, default="",
                    help="write the obs metric stream (JSONL) here")
    ap.add_argument("--trace-out", type=str, default="",
                    help="write a Perfetto-viewable Chrome trace here")
    args = ap.parse_args(argv)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    registry = MetricRegistry()

    with spans.maybe_traced(bool(args.trace_out)) as tracer:
        if args.mode == "continuous":
            out = _run_continuous(cfg, args, registry)
            toks = out.tokens[int(out.rids[0])]
        else:
            toks, t_prefill, t_decode = serve(cfg, batch=args.batch,
                                              prompt_len=args.prompt_len,
                                              gen=args.gen, seed=args.seed,
                                              registry=registry)
            prefill_tps = args.batch * args.prompt_len / t_prefill
            decode_steps = args.gen - 1   # first generated token: prefill
            if decode_steps > 0:
                decode_msg = (
                    f"decode {decode_steps} steps in {t_decode:.2f}s "
                    f"({args.batch * decode_steps / t_decode:.0f} tok/s)")
            else:
                decode_msg = "decode skipped (all tokens from prefill)"
            print(f"arch={cfg.name} generated {toks.shape}: "
                  f"prefill {args.prompt_len} tok in {t_prefill:.2f}s "
                  f"({prefill_tps:.0f} tok/s), " + decode_msg)

    if args.metrics_out:
        from repro.obs import run_metadata
        run = run_metadata(extra={"arch": args.arch, "mode": args.mode,
                                  "batch": args.batch, "gen": args.gen})
        n = registry.to_jsonl(args.metrics_out, run)
        print(f"metrics -> {args.metrics_out} ({n} records)")
    if args.trace_out:
        from repro.obs import export_chrome_trace
        n = export_chrome_trace(args.trace_out,
                                tracer=tracer if tracer.enabled else None,
                                metrics=registry)
        print(f"chrome trace -> {args.trace_out} ({n} events; open at "
              "https://ui.perfetto.dev)")
    assert bool(jnp.isfinite(jnp.asarray(toks, jnp.float32)).all())
    return toks


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
