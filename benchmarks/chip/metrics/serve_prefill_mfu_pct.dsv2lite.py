"""Prefill's share of the chip's bf16 peak: the FLOPs the prefill calls
require (the family's ``prefill_flops`` of each call's prompts,
``serving.prefill_call_prompt_tokens``, with routed experts counted as
the (token, held expert) pairs the call computed,
``serving.moe_prefill_pairs``) over the device busy time of the same
calls (``program_calls``: only the calls the profile holds, each with
the ops the trace places before its span). A program without those
series reads nothing."""
from collections import defaultdict

import families
import program_calls


def read(ctx):
    reg = ctx.layer.get("registry")
    if reg is None:
        return None
    pairs = reg.get("serving.moe_prefill_pairs")
    lens = reg.get("serving.prefill_call_prompt_tokens")
    if pairs is None or lens is None:
        return None
    n, held = program_calls.calls(ctx, "serve.prefill")
    if not held or n != len(pairs.values):
        return None
    prompts = defaultdict(list)
    for v, call in zip(lens.values, lens.steps):
        prompts[call].append(int(v))
    fam = families.of(ctx.cfg)
    flops = sum(fam.prefill_flops(ctx.cfg, prompts[i], pairs.values[i])
                for i, _ in held)
    device = sum(s for _, s in held)
    return 100.0 * flops / (device * ctx.peak.bf16_flops)
