#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
at the root of the checkout and the files it names under this directory.
The run makes its inputs and weights from ``--seed``, warms every shape
it will use (set-up), measures for ``--seconds``, then checks what the
measured path produced against the plain reference. The last line of
standard output is one JSON object; the last lines of standard error are
the numbers compared, each beside its limit. Without a TPU holding as
many chips as the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# this directory's modules import by their own names; the program from src/
sys.path[:] = [str(HERE), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != HERE]

#: where a traced run's profile goes, inside the checkout (git-ignored)
TRACE_DIR = ROOT / "benchmarks" / "chip" / "out" / "trace"


def load_cell(name: str):
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return bench, cell, end_to_end, per_layer


def device_info(chips: int):
    """The device JAX reports, or exit: no TPU or too few chips."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX sees "
              f"{info['count']} {info['platform']} device(s)",
              file=sys.stderr)
        sys.exit(3)
    info["count"] = chips
    return info


def enable_cache() -> str:
    import jax
    from repro.launch import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def load_module(path: Path):
    """A module of this directory by its file (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer_metrics(per_layer, ctx) -> dict:
    out = {}
    for m in per_layer:
        v = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell: dict, cfg: dict, traffic: dict, limits: dict,
             seed: int, seconds: float, trace: bool, device: dict,
             end_to_end=(), per_layer=()) -> dict:
    """One run of ``cell``; returns the result object (the last line)."""
    import check
    from window import Window
    job = importlib.import_module(f"{traffic['kind']}_job")
    win = Window(T_START, str(TRACE_DIR) if trace else None,
                 spans=getattr(job, "SPANS", False))
    res = job.run(cell, cfg, traffic, seed, seconds, win)
    correct, checks = check.judge(res["readings"], limits)
    correct = correct and res["failed"] == 0 and win.compiles == 0
    device = dict(device)
    metrics = {}
    timing = {}
    if trace:
        import layer_context
        t = time.perf_counter()
        ctx = layer_context.build(cell, cfg, traffic, res, win, device)
        metrics = per_layer_metrics(per_layer, ctx)
        breakdown = ctx.breakdown()
        device.update(busy_s=ctx.reduction.busy_s,
                      window_s=ctx.reduction.window_s)
        timing = {"trace_stop_s": win.trace_stop_s,
                  "trace_read_s": time.perf_counter() - t}
    else:
        e2e = dict(res["e2e"], setup_s=win.setup_s)
        for m in end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = win.memory_peak_bytes
    info = dict(res.get("info", {}), setup_s=win.setup_s,
                window_s=win.seconds, compiles_in_window=win.compiles,
                e2e=res["e2e"], **timing)
    print("info " + json.dumps(info, default=float), file=sys.stderr)
    check.print_checks(checks)
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    import check
    import loadgen
    import models
    bench, cell, end_to_end, per_layer = load_cell(args.workload)
    device = device_info(cell["chips"])
    enable_cache()
    out = run_cell(cell, models.load_config(cell["config"]),
                   loadgen.load_traffic(cell["traffic"]),
                   check.load_limits(cell["name"]), args.seed, args.seconds,
                   bool(args.trace), device, end_to_end, per_layer)
    gc.collect()
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
