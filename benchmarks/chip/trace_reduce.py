"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read: device busy and idle time, per-op device time, collective
time with no compute beside it, time per compiled program, and idle gaps
attributed to what the host was doing.

The pure functions below work on plain lists of ``(name, t0, t1)``
intervals in seconds, so the tests can build a trace by hand;
``from_xplane`` turns the profiler's ``.xplane.pb`` file into those
lists. Host spans come from an ``obs.spans.Tracer`` the harness installs;
they are on the repo's clock (``time.perf_counter``), and a
``TraceAnnotation`` the harness opens at a known clock reading gives the
offset to the profiler's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]

#: HLO op names of collectives (async pairs included)
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all)")

#: the line of a device plane that holds one event per executed HLO op,
#: and the one that holds one event per executed program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a_list, b_list) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a_list`` not covered by the merged
    intervals ``b_list``."""
    out = []
    j = 0
    for a, b in a_list:
        cur = a
        while j < len(b_list) and b_list[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_list) and b_list[k][0] < b:
            s, e = b_list[k]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def self_times(events: Sequence[Interval]):
    """Split one line's events into leaves and self time per event: an
    event that encloses others (a loop or a call) keeps only the time no
    child covers. Returns ``(leaves, [(name, self_seconds), ...])``."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    stack: List[list] = []          # [name, t0, t1, child_cover, has_child]
    leaves: List[Interval] = []
    selfs: List[Tuple[str, float]] = []

    def close(node):
        selfs.append((node[0], max(0.0, (node[2] - node[1]) - node[3])))
        if not node[4]:
            leaves.append((node[0], node[1], node[2]))

    for name, t0, t1 in order:
        while stack and stack[-1][2] <= t0:
            close(stack.pop())
        if stack and t1 <= stack[-1][2]:
            stack[-1][3] += t1 - t0
            stack[-1][4] = True
        stack.append([name, t0, t1, 0.0, False])
    while stack:
        close(stack.pop())
    return leaves, selfs


@dataclasses.dataclass
class Reduction:
    window_s: float
    chips: int
    busy_s: float                    # mean over chips
    op_s: Dict[str, float]           # self time per op name, mean over chips
    module_s: Dict[str, float]       # time per program name, mean over chips
    module_calls: Dict[str, int]     # executions per program name, chip 0
    collective_s: float              # collective op time, mean over chips
    collective_exposed_s: float      # ... with no compute op beside it
    idle: List[Tuple[float, float]]  # idle intervals of the first chip
    busy: List[Tuple[float, float]]  # busy intervals of the first chip

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    @functools.cached_property
    def _busy_ends(self) -> List[float]:
        return [b for _, b in self.busy]

    def busy_within(self, lo: float, hi: float) -> float:
        """Seconds of the first chip's busy time inside [lo, hi]."""
        s, i = 0.0, bisect.bisect_right(self._busy_ends, lo)
        while i < len(self.busy) and self.busy[i][0] < hi:
            a, b = self.busy[i]
            s += min(b, hi) - max(a, lo)
            i += 1
        return s

    def top_ops(self, n: int = 10):
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def reduce(ops_by_chip: Dict[int, Sequence[Interval]], window: Tuple[float, float],
           modules_by_chip: Optional[Dict[int, Sequence[Interval]]] = None
           ) -> Reduction:
    """Device busy time, per-op time and exposed collective time within
    ``window``, from each chip's op events (seconds, one clock)."""
    lo, hi = window
    if not ops_by_chip:
        raise ValueError("the trace holds no device ops")
    chips = sorted(ops_by_chip)
    busy_sum = coll_sum = exposed_sum = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    first_busy: List[Tuple[float, float]] = []
    for c in chips:
        evs = [(n, max(a, lo), min(b, hi)) for n, a, b in ops_by_chip[c]
               if min(b, hi) > max(a, lo)]
        leaves, selfs = self_times(evs)
        for name, s in selfs:
            op_s[name] += s / len(chips)
        busy = merge([(a, b) for _, a, b in leaves])
        busy_sum += total(busy)
        coll = merge([(a, b) for n, a, b in leaves if COLLECTIVE.match(n)])
        comp = merge([(a, b) for n, a, b in leaves if not COLLECTIVE.match(n)])
        coll_sum += total(coll)
        exposed_sum += total(subtract(coll, comp))
        if c == chips[0]:
            first_busy = busy
    module_s: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, int] = defaultdict(int)
    for c, evs in (modules_by_chip or {}).items():
        for n, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                module_s[n] += d / len(chips)
                if c == chips[0]:
                    module_calls[n] += 1
    n = len(chips)
    return Reduction(window_s=hi - lo, chips=n, busy_s=busy_sum / n,
                     op_s=dict(op_s), module_s=dict(module_s),
                     module_calls=dict(module_calls),
                     collective_s=coll_sum / n,
                     collective_exposed_s=exposed_sum / n,
                     idle=subtract([(lo, hi)], first_busy), busy=first_busy)


def attribute_idle(idle: Sequence[Tuple[float, float]],
                   spans: Sequence[Tuple[str, float, float, int]],
                   n: int = 10) -> List[List]:
    """Idle seconds summed by the host span that was open at each gap's
    midpoint (the deepest one; ``"no span"`` where none was), longest
    first. ``spans``: ``(name, t0, t1, depth)`` on the trace's clock."""
    by_name: Dict[str, float] = defaultdict(float)
    ordered = sorted(spans, key=lambda s: s[1])
    i, open_ = 0, []                  # spans begun by the current midpoint
    for a, b in sorted(idle):
        mid = 0.5 * (a + b)
        while i < len(ordered) and ordered[i][1] <= mid:
            open_.append(ordered[i])
            i += 1
        open_ = [s for s in open_ if s[2] >= mid]
        best, depth = "no span", -1
        for name, _, _, d in open_:
            if d > depth:
                best, depth = name, d
        by_name[best] += b - a
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


# ---------------------------------------------------------------------------
# the profiler's file
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def short_name(name: str) -> str:
    """An op event's name as the trace writes it on a TPU is the whole HLO
    instruction ("%fusion.12 = bf16[4,64]{1,0} fusion(...)"): keep the
    instruction's name and its result type, without layouts. Program
    events ("jit_step(1234)") lose the hash."""
    if " = " in name:
        lhs, rhs = name.split(" = ", 1)
        rtype = re.sub(r"\{[^}]*\}", "", rhs.split(" ", 1)[0])
        if rtype.startswith("("):
            rtype = "(tuple)"
        return f"{lhs.lstrip('%')} {rtype}"[:120]
    return re.sub(r"\(\d+\)$", "", name)


def newest_xplane(profile_dir: str) -> str:
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(paths, key=os.path.getmtime)


@dataclasses.dataclass
class XTrace:
    ops: Dict[int, List[Interval]]          # chip -> op events
    modules: Dict[int, List[Interval]]      # chip -> program events
    annotations: Dict[str, List[Tuple[float, float]]]  # host TraceAnnotations
    planes: List[Tuple[str, List[str]]]     # what the file held


def from_xplane(path: str, annotations: Sequence[str] = ()) -> XTrace:
    """Device op and program events of every TPU plane, and the host
    events named in ``annotations``, in seconds on the profiler's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    found: Dict[str, List[Tuple[float, float]]] = {a: [] for a in annotations}
    planes = []
    want = set(annotations)
    for plane in data.planes:
        lines = list(plane.lines)
        planes.append((plane.name, [ln.name for ln in lines]))
        m = DEVICE_PLANE.match(plane.name)
        for ln in lines:
            if m and ln.name in (OPS_LINE, MODULES_LINE):
                dest = ops if ln.name == OPS_LINE else modules
                evs = dest.setdefault(int(m.group(1)), [])
                for e in ln.events:
                    t0 = e.start_ns * 1e-9
                    evs.append((short_name(e.name), t0,
                                t0 + e.duration_ns * 1e-9))
            elif not m and want:
                for e in ln.events:
                    if e.name in want:
                        t0 = e.start_ns * 1e-9
                        found[e.name].append((t0, t0 + e.duration_ns * 1e-9))
    return XTrace(ops=ops, modules=modules, annotations=found, planes=planes)
