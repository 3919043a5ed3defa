"""Decode's share of the chip's HBM bandwidth: the bytes the decode
steps must read (the family's ``decode_bytes`` of each step: every
weight but the routed experts, the routed experts given a pair,
``serving.moe_decode_experts_hit``, and the latent rows of the
positions the active slots read, ``serving.decode_live_tokens``)
over the device busy time of the same steps (``program_calls``: only the
steps the profile holds, each with the ops the trace places before its
span), at the peak bandwidth. A program without those series reads
nothing."""
import families
import program_calls


def read(ctx):
    reg = ctx.layer.get("registry")
    if reg is None:
        return None
    hit = reg.get("serving.moe_decode_experts_hit")
    live = reg.get("serving.decode_live_tokens")
    if hit is None or live is None:
        return None
    n, held = program_calls.calls(ctx, "serve.decode_step")
    if not held or n != len(hit.values):
        return None
    fam = families.of(ctx.cfg)
    need = sum(fam.decode_bytes(ctx.cfg, live.values[i], hit.values[i])
               for i, _ in held)
    device = sum(s for _, s in held)
    return 100.0 * need / (device * ctx.peak.hbm_bw)
