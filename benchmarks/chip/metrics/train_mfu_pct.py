"""Whole training step's share of the chips' bf16 peak: the model's
forward and backward FLOPs per sample, counted from the configuration's
shapes with no recompute, times samples/s over the traced window."""
import families


def read(ctx):
    rate = ctx.layer.get("samples_per_s")
    if not rate:
        return None
    flops = families.of(ctx.cfg).train_flops(ctx.cfg)
    return 100.0 * flops * rate / (ctx.chips * ctx.peak.bf16_flops)
