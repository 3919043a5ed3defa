"""Device time of each serving program call that a traced run's profile
holds, from the host spans around the calls (``serve.decode_step``,
``serve.prefill``): the first chip's busy time from the end of the
previous such span to the end of this one. The trace places a program's
ops up to a few milliseconds before the span that launches it opens, so
busy time inside the span alone misses part of the program; the stretch
since the previous call's span holds no other program. A call whose
stretch holds no busy time lies past the end of a profile that could
not hold every event, and is left out, so a reader divides counts and
device time of the same calls."""
from __future__ import annotations

from typing import List, Tuple

import window_spans

PROGRAMS = ("serve.decode_step", "serve.prefill")


def calls(ctx, name: str) -> Tuple[int, List[Tuple[int, float]]]:
    """The number of spans named ``name`` in the window, and ``(index,
    busy seconds)`` of each whose program the profile holds, the index
    counting those spans in time order."""
    w = window_spans.bounds(ctx)
    if w is None:
        return 0, []
    prev, n, held = w[0], 0, []
    for s, a, b, _ in sorted((s for s in ctx.spans if s[0] in PROGRAMS
                              and w[0] <= s[1] and s[2] <= w[1]),
                             key=lambda s: s[1]):
        if s == name:
            busy = ctx.reduction.busy_within(prev, b)
            if busy > 0:
                held.append((n, busy))
            n += 1
        prev = b
    return n, held
