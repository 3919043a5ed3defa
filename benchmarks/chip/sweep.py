#!/usr/bin/env python3
"""Find a serving cell's knee once: serve its traffic at several fixed
rates in one process and print, per rate, the tails and whether the
backlog grew (requests that arrived late in the window waited longer
than early ones, or the queue took long to drain after the last arrival).

    python3 benchmarks/chip/sweep.py --workload phi4-mini.serve.chat \
        --rates 1,1.5,2,2.5,3 --seconds 30 --seed 7

The knee is the highest rate with no growing backlog; the cell's traffic
file then fixes its rate at about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import numpy as np

    import loadgen
    import models
    import run
    import serve_job
    from repro.obs.metrics import MetricRegistry
    _, cell, _, _ = run.load_cell(args.workload)
    run.device_info(cell["chips"])
    run.enable_cache()
    cfg = models.load_config(cell["config"])
    traffic = loadgen.load_traffic(cell["traffic"])
    arch, weights, server = serve_job.build(cfg, traffic, args.seed)
    warm = False
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic["arrivals"]["rate_per_s"] = rate
        reqs = loadgen.requests(traffic, args.seed, args.seconds,
                                arch.vocab_size)
        if not warm:
            server.warmup(list(range(traffic["prompt_tokens"]["min"],
                                     traffic["prompt_tokens"]["max"] + 1)))
            warm = True
        server.reset(registry=MetricRegistry())
        rep = server.run(reqs)
        pr = serve_job.per_request(rep, server.registry)
        arr = np.asarray(rep.arrivals)
        qw = np.asarray(rep.queue_waits)
        third = args.seconds / 3
        early, late = qw[arr < third], qw[arr >= 2 * third]
        row = {"rate": rate, "requests": len(reqs), "finished": len(rep.rids),
               "ttft_p50_ms": loadgen.percentile(pr["ttft"], 50) * 1e3,
               "ttft_p90_ms": loadgen.percentile(pr["ttft"], 90) * 1e3,
               "tpot_p90_ms": loadgen.percentile(pr["tpot"], 90) * 1e3,
               "tokens_per_s": rep.total_tokens / rep.makespan,
               "offered_tokens_per_s": sum(r.gen for r in reqs) / args.seconds,
               "queue_wait_early_ms": float(early.mean() * 1e3) if len(early) else None,
               "queue_wait_late_ms": float(late.mean() * 1e3) if len(late) else None,
               "drain_s": rep.makespan - float(arr.max()),
               "occupancy_mean": rep.occupancy_mean,
               "decode_step_ms": float(np.mean(server.registry.series(
                   "serving.decode_step_s").values)) * 1e3}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
