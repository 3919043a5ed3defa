"""Host time of the server's loop per decode step: the window's
``serve.admit``, ``serve.schedule``, ``serve.decode.inputs``,
``serve.decode.launch`` and ``serve.emit`` spans, over the number of
``serve.decode_step`` spans. A program without the loop's spans reads
nothing."""
import window_spans

HOST = ("serve.admit", "serve.schedule", "serve.decode.inputs",
        "serve.decode.launch", "serve.emit")


def read(ctx):
    steps = window_spans.spans(ctx, "serve.decode_step")
    if not steps or not window_spans.spans(ctx, "serve.decode.launch"):
        return None
    host = sum(b - a for name in HOST
               for a, b in window_spans.spans(ctx, name))
    return host / len(steps) * 1e3
