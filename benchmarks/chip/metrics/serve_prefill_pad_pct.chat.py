"""Share of the tokens the prefill computes that are padding: 1 - the
real prompt tokens (``serving.prefill_tokens``) over slots x bucket per
call (``serving.prefill_lane_tokens``). A program without those
counters reads nothing."""


def read(ctx):
    reg = ctx.layer.get("registry")
    if reg is None:
        return None
    lanes = reg.counter("serving.prefill_lane_tokens").value
    if lanes <= 0:
        return None
    return 100.0 * (1.0 - reg.counter("serving.prefill_tokens").value / lanes)
