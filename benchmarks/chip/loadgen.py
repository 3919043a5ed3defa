"""The one traffic generator: turns a traffic file's parameters into the
requests a serving run drives.

The schedule is the traffic file's alone: the sizes and inter-arrival
gaps are the distributions' quantiles at (i + 0.5) / n, put in one order
drawn from the file's ``schedule_seed``. The run's seed draws only the
token ids (and, elsewhere, the weights). So every seed serves the same
requests at the same times, and two seeds differ by no more than two
runs of one seed do.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent


def load_traffic(name: str) -> Dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _quantiles(spec: Dict, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        v = spec["min"] + q * (spec["max"] + 1 - spec["min"])
    elif dist == "fixed":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(v), spec.get("min", 1), spec.get("max", np.inf)
                   ).astype(np.int64)


def request_count(traffic: Dict, seconds: float) -> int:
    arr = traffic["arrivals"]
    if arr["process"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    if arr["process"] == "backlog":
        return int(arr["requests"])
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def arrival_times(traffic: Dict, seconds: float, rng) -> np.ndarray:
    arr = traffic["arrivals"]
    n = request_count(traffic, seconds)
    if arr["process"] == "backlog":
        return np.zeros(n)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    t = np.cumsum(gaps)
    # n arrivals spread over the window, the last one before its end
    return t * (seconds * n / ((n + 1) * t[-1]))


def requests(traffic: Dict, seed: int, seconds: float, vocab: int) -> List:
    """The serving requests of one run, as the program's ``Request``."""
    from repro.serving import Request
    order = np.random.default_rng(int(traffic["schedule_seed"]))
    n = request_count(traffic, seconds)
    arrivals = arrival_times(traffic, seconds, order)
    plens = order.permutation(_quantiles(traffic["prompt_tokens"], n))
    gens = order.permutation(_quantiles(traffic["output_tokens"], n))
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(plens[i])).astype(np.int32)
        out.append(Request(rid=i, arrival=float(arrivals[i]), prompt=prompt,
                           gen=int(gens[i])))
    return out


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        return math.nan
    return float(np.percentile(v, q))
