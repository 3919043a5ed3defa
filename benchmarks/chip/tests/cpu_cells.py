"""The cells' traffic and limits with CPU-sized stand-in configurations
(each configuration's ``cpu_stand_in``), for the tests that drive the
harness without a chip."""
import json
from pathlib import Path

import jax

import check
import loadgen
import models
import run

ROOT = Path(__file__).resolve().parents[3]


def cpu_traffic(traffic):
    """A serving mix cut so a CPU run holds it: shorter prompts and
    outputs and a smaller cache, the same arrival process."""
    if traffic["kind"] != "serve":
        return traffic
    t = json.loads(json.dumps(traffic))
    t["server"]["max_seq"] = 256
    t["prompt_tokens"].update(min=8, max=64, median=24)
    t["output_tokens"].update(min=4, max=32, median=12)
    t["arrivals"]["rate_per_s"] = 8.0
    return t


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cells_of_kind(kind):
    """The names of BENCHMARK.json's cells whose traffic is of ``kind``."""
    return [w["name"] for w in bench()["workloads"]
            if loadgen.load_traffic(w["traffic"])["kind"] == kind]


def stand_in(config):
    """The CPU stand-in that configuration ``config`` names."""
    return models.load_config(models.load_config(config)["cpu_stand_in"])


def drive(workload, seed=2**31 + 11, seconds=1.0):
    """One run of ``workload`` on its configuration's CPU stand-in; the
    result object."""
    b = bench()
    cell = {w["name"]: w for w in b["workloads"]}[workload]
    limits = check.load_limits(workload)
    cfg = stand_in(cell["config"])
    traffic = cpu_traffic(loadgen.load_traffic(cell["traffic"]))
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": cell["chips"]}
    e2e = [m for m in b["end_to_end"]
           if workload in m.get("workloads", [workload])]
    return run.run_cell(cell, cfg, traffic, limits, seed, seconds, False,
                        device, e2e)
