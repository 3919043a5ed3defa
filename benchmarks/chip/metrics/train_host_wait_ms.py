"""Mean host wait for the next batch per step (``Engine.telemetry``
``data_wait_s``), over the window's steps."""
import numpy as np


def read(ctx):
    w = ctx.layer.get("data_wait_s")
    if not w:
        return None
    return float(np.mean(w)) * 1e3
