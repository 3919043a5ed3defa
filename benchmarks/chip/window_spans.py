"""Host spans of a traced run that lie inside its window. The window is
what the trace reduction covers: its busy and idle intervals together.
Spans the job recorded before the window (a training cell's set-up
rounds run under the same tracer) are left out."""
from __future__ import annotations

from typing import List, Optional, Tuple


def bounds(ctx) -> Optional[Tuple[float, float]]:
    ivs = ctx.reduction.busy + ctx.reduction.idle
    if not ivs:
        return None
    return min(a for a, _ in ivs), max(b for _, b in ivs)


def spans(ctx, name: str) -> List[Tuple[float, float]]:
    """``(t0, t1)`` of each span named ``name`` that begins and ends
    inside the window, on the trace's clock."""
    w = bounds(ctx)
    if w is None:
        return []
    lo, hi = w
    return [(a, b) for n, a, b, _ in ctx.spans
            if n == name and lo <= a and b <= hi]
