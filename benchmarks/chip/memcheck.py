#!/usr/bin/env python3
"""Compile a serving cell's programs for a described TPU v5e (no chip
needed) and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/memcheck.py --workload phi4-mini.serve.chat

Compiles the weight maker, the widest decode rung and the largest
prefill bucket the cell's traffic reaches, with shapes only: nothing is
allocated on the host. The weights, the page pool and the largest
program's temporaries have to fit in one chip's 16 GiB together.
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
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import families
    import loadgen
    import models
    from repro.serving import engine as E

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.load(open(HERE.parents[1] / "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = models.load_config(cell["config"])
    traffic = loadgen.load_traffic(cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
    fam = families.of(cfg)
    arch = fam.program_config(cfg)
    wshape = jax.eval_shape(fam.weights_fn(cfg), jax.random.PRNGKey(0))
    report = {}
    c = jax.jit(fam.weights_fn(cfg)).lower(key).compile()
    report["weights"] = c.memory_analysis()

    real_init = E.init_pages
    E.init_pages = lambda spec: jax.eval_shape(lambda: real_init(spec))
    srv = traffic["server"]
    server = E.ContinuousServer(arch, wshape, slots=srv["slots"],
                                page_size=srv["page_size"],
                                max_seq=srv["max_seq"],
                                prefill_mode=srv["prefill_mode"])
    E.init_pages = real_init
    S = srv["slots"]
    pages = shaped(server.pages)
    params = shaped(wshape)
    table = jax.ShapeDtypeStruct(server.alloc.tables.shape, jnp.int32,
                                 sharding=dev)
    vec = lambda dt: jax.ShapeDtypeStruct((S,), dt, sharding=dev)
    pmax = traffic["prompt_tokens"]["max"]
    Pb = E._bucket(pmax, server.spec.seq_capacity)
    f = server._prefill_fn(Pb)
    prompts = jax.ShapeDtypeStruct((S, Pb), jnp.int32, sharding=dev)
    report[f"prefill_{Pb}"] = f.lower(params, pages, table, prompts,
                                      vec(jnp.int32), vec(bool)
                                      ).compile().memory_analysis()
    live = pmax + traffic["output_tokens"]["max"]
    gp = server._gather_bucket(np.full(S, live - 1), np.ones(S, bool))
    step = server._step_fn(gp)
    tok = jax.ShapeDtypeStruct((S, 1), jnp.int32, sharding=dev)
    report[f"decode_rung_{gp}"] = step.lower(params, pages, table, tok,
                                             vec(jnp.int32), vec(bool)
                                             ).compile().memory_analysis()
    gib = 2.0 ** 30
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages))
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(wshape))
    print(f"weights {wbytes / gib:.3f} GiB, page pool {pool / gib:.3f} GiB")
    for name, m in report.items():
        print(f"{name}: args {m.argument_size_in_bytes / gib:.3f} GiB, "
              f"out {m.output_size_in_bytes / gib:.3f} GiB, temp "
              f"{m.temp_size_in_bytes / gib:.3f} GiB, alias "
              f"{m.alias_size_in_bytes / gib:.3f} GiB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
