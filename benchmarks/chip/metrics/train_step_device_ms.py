"""Device time of a training round: the chip's busy time inside the
window's ``engine.step`` spans, over their number (the set-up rounds
before the window are left out)."""
import window_spans


def read(ctx):
    steps = window_spans.spans(ctx, "engine.step")
    if not steps:
        return None
    busy = sum(ctx.reduction.busy_within(a, b) for a, b in steps)
    return busy / len(steps) * 1e3
