"""How long a running request waits for its next token at worst: per
finished request with at least 3 tokens, the longest gap between
consecutive ``ServeReport.token_times``; the 90th percentile over those
requests. A program without token times reads nothing."""
import numpy as np

import loadgen


def read(ctx):
    times = getattr(ctx.layer.get("report"), "token_times", None)
    if not times:
        return None
    stalls = [float(np.diff(t).max()) for t in times.values() if len(t) >= 3]
    if not stalls:
        return None
    return loadgen.percentile(stalls, 90) * 1e3
