"""The comparisons that decide ``correct``, and how they are printed.

Training (a gap of norms, taken leaf by leaf and reported for the worst
leaf): ``|norm(program leaf) - norm(reference leaf)|`` over the larger of
the reference leaf's norm and the median leaf's norm. Serving: the widest
gap, in logits, by which a served token lies below the reference's best
token at its position.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

HERE = Path(__file__).resolve().parent

#: leaves whose reference gradient norm is under this share of the median
#: leaf's move by round-off alone and are left out of the change gap
STILL_LEAF = 1e-3


def leaf_norms(tree) -> np.ndarray:
    """Norm of every leaf, in float64 on the host."""
    return np.asarray([np.linalg.norm(np.asarray(x, np.float64).ravel())
                       for x in jax.tree.leaves(tree)])


def change_norms(after, before) -> np.ndarray:
    """Norm of every leaf's change, in float64 on the host."""
    return np.asarray([
        np.linalg.norm((np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).ravel())
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))])


def leaf_gaps(prog: Sequence[float], ref: Sequence[float],
              keep: Optional[Sequence[bool]] = None) -> np.ndarray:
    """Per leaf |prog - ref| / max(ref leaf, median ref leaf); leaves not
    kept read 0."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = max(float(np.median(ref)), 1e-30)
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    if keep is not None:
        gaps = np.where(np.asarray(keep), gaps, 0.0)
    return gaps


def norm_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Optional[Sequence[bool]] = None) -> float:
    """Worst leaf of |prog - ref| / max(ref leaf, median ref leaf)."""
    return float(leaf_gaps(prog, ref, keep).max())


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    return max(abs(p - r) / max(abs(r), 1e-30) for p, r in zip(prog, ref))


def train_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """prog / ref: {"losses": [...], "grad": leaf norms of the momentum
    after round 1, "change": leaf norms of the parameter change after the
    last checked round}."""
    g = np.asarray(ref["grad"])
    keep = g >= STILL_LEAF * float(np.median(g))
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": norm_gap(prog["grad"], ref["grad"]),
            "change_gap": norm_gap(prog["change"], ref["change"], keep)}


def load_limits(cell: str) -> Dict[str, float]:
    with open(HERE / "limits" / f"{cell}.json") as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, {name: {"value", "limit"}}). A reading that is not a
    finite number fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        checks[name] = {"value": (float(v) if v is not None else None),
                        "limit": limit}
    return ok, checks


def print_checks(checks: Dict) -> None:
    """The numbers compared, each beside its limit: the last lines of
    standard error."""
    lines: List[str] = []
    for name, c in checks.items():
        lines.append(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    sys.stderr.write("\n".join(lines) + "\n")
    sys.stderr.flush()
