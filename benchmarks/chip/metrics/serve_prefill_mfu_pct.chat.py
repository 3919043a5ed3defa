"""Prefill's share of the chip's bf16 peak: 2 FLOPs per matmul parameter
per prompt token prefilled, over the device time inside the server's
``serve.prefill`` spans."""
import families


def read(ctx):
    busy, n = ctx.span_busy("serve.prefill")
    if not n or busy <= 0:
        return None
    reqs = ctx.layer["requests"]
    done = set(int(r) for r in ctx.layer["report"].rids)
    tokens = sum(len(r.prompt) for r in reqs if r.rid in done)
    flops = families.of(ctx.cfg).forward_flops_per_token(ctx.cfg) * tokens
    return 100.0 * flops / (busy * ctx.peak.bf16_flops)
