"""Device time of a decode step: the chip's busy time inside the
server's ``serve.decode_step`` spans in the window, over their number."""
import window_spans


def read(ctx):
    steps = window_spans.spans(ctx, "serve.decode_step")
    if not steps:
        return None
    busy = sum(ctx.reduction.busy_within(a, b) for a, b in steps)
    return busy / len(steps) * 1e3
