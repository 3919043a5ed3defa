#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell,
over many seeds in one process (so set-up is paid once):

- the program's own readings, as a run compares them (the lower end);
- the control: the plain reference put in the program's place, in the
  nearest precision below the configuration's (bfloat16 for a float32
  configuration at default precision; fp8 for a bfloat16 one);
- for training, the planted faults: half of each group's rows left out
  (which is also what a data shard without the gradient exchange sees).

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--seconds 15]

One JSON line per seed on standard output. Serving seeds drive a short
window at the cell's own load (``--seconds``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def train_seeds(cell, cfg, traffic, seeds, control_seeds):
    import jax.numpy as jnp
    import numpy as np

    import check
    import train_job
    rounds = traffic["check_rounds"]
    engine = train_job._engine(cfg, traffic, cell["chips"])
    for seed in seeds:
        st = train_job.setup(cfg, traffic, seed, cell["chips"],
                             engine=engine)
        p0, pool, prog = st["p0"], st["pool"][:rounds], st["prog"]
        del st
        gc.collect()
        ref = train_job.reference_rounds(cfg, traffic, p0, pool, rounds)
        g = np.asarray(ref["grad"])
        keep = g >= check.STILL_LEAF * float(np.median(g))
        row = {"seed": seed, "program": check.train_readings(prog, ref),
               "program_losses": prog["losses"],
               "reference_losses": ref["losses"],
               "leaves": {
                   "grad_gap": check.leaf_gaps(prog["grad"], ref["grad"]).tolist(),
                   "change_gap": check.leaf_gaps(prog["change"], ref["change"],
                                                 keep).tolist(),
                   "ref_grad": ref["grad"].tolist(),
                   "ref_change": ref["change"].tolist()}}
        if seed in control_seeds:
            ctl = train_job.reference_rounds(cfg, traffic, p0, pool, rounds,
                                             dtype=jnp.bfloat16)
            row["control"] = check.train_readings(ctl, ref)
            half = train_job.reference_rounds(cfg, traffic, p0, pool, rounds,
                                              keep_rows=0.5)
            row["fault_half_batch"] = check.train_readings(half, ref)
        print(json.dumps(row), flush=True)
        del p0, pool
        gc.collect()


def serve_seeds(cell, cfg, traffic, seeds, control_seeds, seconds):
    import numpy as np

    import loadgen
    import models
    import serve_job
    from repro.obs import spans
    from repro.obs.metrics import MetricRegistry
    arch, weights, server = serve_job.build(cfg, traffic, seeds[0])
    warmed = False
    for seed in seeds:
        if seed != seeds[0]:
            del weights
            server.params = None
            gc.collect()
            weights = models.weights(cfg, models.key_from_seed(seed))
            server.params = weights
        reqs = loadgen.requests(traffic, seed, seconds, arch.vocab_size)
        if not warmed:
            server.warmup([len(r.prompt) for r in reqs])
            warmed = True
        server.reset(registry=MetricRegistry())
        tracer = spans.Tracer()
        with spans.install(tracer):
            rep = server.run(reqs)
        gap = serve_job.clock_gap(server.registry, tracer.records())
        by_rid = {r.rid: r for r in reqs}
        rids = serve_job.sample_rids(rep, seed, traffic["check"]["requests"])
        seqs = [np.concatenate([by_rid[r].prompt, rep.tokens[r]])
                .astype(np.int32) for r in rids]
        plens = [len(by_rid[r].prompt) for r in rids]
        pages = server.pages
        server.pages = None
        del pages
        gc.collect()
        g = serve_job.reference_gaps(
            cfg, weights, seqs, plens,
            quant="fp8" if seed in control_seeds else None)
        row = {"seed": seed, "program": {"logit_gap": g["logit_gap"],
                                         "clock_gap": gap},
               "tokens": g["tokens"], "requests": len(reqs),
               "finished": len(rep.rids)}
        if "control_gap" in g:
            row["control"] = {"logit_gap": g["control_gap"]}
        print(json.dumps(row), flush=True)
        server.reset()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    import run
    import loadgen
    import models
    _, cell, _, _ = run.load_cell(args.workload)
    run.device_info(cell["chips"])
    run.enable_cache()
    cfg = models.load_config(cell["config"])
    traffic = loadgen.load_traffic(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    if traffic["kind"] == "train":
        train_seeds(cell, cfg, traffic, seeds, ctl)
    else:
        serve_seeds(cell, cfg, traffic, seeds, ctl, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
