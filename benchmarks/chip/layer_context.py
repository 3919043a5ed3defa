"""What a traced run hands the per-layer metric readers: the trace
reduction over the window, the host spans on the trace's clock, the
run's program-side records, the configuration's counts and the chip's
peaks."""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Tuple

import peaks
import trace_reduce
from window import MARK


@dataclasses.dataclass
class Context:
    cell: Dict
    cfg: Dict
    traffic: Dict
    layer: Dict                       # the job's program-side records
    device: Dict
    peak: peaks.Peak
    reduction: trace_reduce.Reduction
    spans: List[Tuple[str, float, float, int]]   # on the trace's clock
    planes: List

    @property
    def chips(self) -> int:
        return self.cell["chips"]

    def span_busy(self, name: str) -> Tuple[float, int]:
        """Device busy seconds (first chip) inside spans named ``name``,
        and how many there were."""
        sel = [(a, b) for n, a, b, _ in self.spans if n == name]
        return (sum(self.reduction.busy_within(a, b) for a, b in sel),
                len(sel))

    def breakdown(self) -> Dict:
        return {"device_ops": [[n, s] for n, s in self.reduction.top_ops(10)],
                "idle_gaps": trace_reduce.attribute_idle(
                    self.reduction.idle, self.spans, 10)}


def build(cell, cfg, traffic, res, win, device) -> Context:
    path = trace_reduce.newest_xplane(win.trace_dir)
    xt = trace_reduce.from_xplane(path, annotations=(MARK,))
    marks = xt.annotations.get(MARK) or []
    if not marks:
        raise RuntimeError(f"the trace holds no {MARK!r} annotation")
    lo, hi = max(marks, key=lambda ab: ab[1] - ab[0])
    offset = lo - win.t0
    ops = {c: evs for c, evs in xt.ops.items() if c < cell["chips"]}
    mods = {c: evs for c, evs in xt.modules.items() if c < cell["chips"]}
    red = trace_reduce.reduce(ops, (lo, hi), mods)
    spans = [(r.name, r.t0 + offset, r.t1 + offset, r.depth)
             for r in win.tracer.records()]
    ctx = Context(cell=cell, cfg=cfg, traffic=traffic, layer=res["layer"],
                  device=device, peak=peaks.peak(device["kind"]),
                  reduction=red, spans=spans, planes=xt.planes)
    _write_summary(win.trace_dir, ctx)
    return ctx


def _write_summary(trace_dir: str, ctx: Context) -> None:
    """A small readable digest next to the profile, for a person."""
    red = ctx.reduction
    top_mod = sorted(red.module_s.items(), key=lambda kv: -kv[1])[:20]
    with open(f"{trace_dir}/summary.json", "w") as f:
        json.dump({"planes": ctx.planes, "window_s": red.window_s,
                   "busy_s": red.busy_s, "chips": red.chips,
                   "collective_s": red.collective_s,
                   "collective_exposed_s": red.collective_exposed_s,
                   "top_ops": red.top_ops(30),
                   "modules": [[n, s, red.module_calls.get(n, 0)]
                               for n, s in top_mod],
                   "breakdown": ctx.breakdown()}, f, indent=1, default=str)
