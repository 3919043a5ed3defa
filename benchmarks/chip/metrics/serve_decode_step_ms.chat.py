"""Mean decode step time as the server records it
(``serving.decode_step_s``)."""
import numpy as np


def read(ctx):
    s = ctx.layer["registry"].series("serving.decode_step_s").values
    if not s:
        return None
    return float(np.mean(s)) * 1e3
