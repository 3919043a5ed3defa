"""Integration: lower+compile train/prefill/decode for every arch family on a
small forced-device mesh (subprocess, so the 1-device default of the rest of
the test suite is untouched — the production 16x16 / 2x16x16 meshes run via
``python -m repro.launch.dryrun``)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax
import jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.configs.base import InputShape, TrainConfig
from repro.launch import steps as ST
from repro.launch.mesh import make_mesh
from repro.sharding import rules as SH

arch = sys.argv[1]
cfg = get_smoke_config(arch)
mesh = make_mesh((4, 2), ("data", "model"))
results = {}
for shape in (InputShape("train", 32, 8, "train"),
              InputShape("prefill", 64, 8, "prefill"),
              InputShape("decode", 64, 8, "decode")):
    if not ST.supports_shape(cfg, shape):
        results[shape.name] = "skipped"
        continue
    pspecs = ST.params_specs(cfg)
    p_shard = SH.params_shardings(pspecs, cfg, mesh)
    bspecs = ST.batch_specs(cfg, shape)
    b_shard = SH.batch_shardings(bspecs, mesh)
    with mesh, SH.activation_sharding(mesh):
        if shape.kind == "train":
            tc = TrainConfig(grad_accum=2)
            bspecs = ST.batch_specs(cfg, shape, grad_accum=2)
            b_shard = SH.batch_shardings(bspecs, mesh, batch_dim=1)
            mspecs = jax.eval_shape(lambda p: jax.tree.map(
                lambda x: jnp.zeros(x.shape, cfg.dtype("mom")), p), pspecs)
            m_shard = SH.params_shardings(mspecs, cfg, mesh)
            step = ST.make_train_step(cfg, tc, shape, grad_shardings=p_shard)
            c = jax.jit(step, in_shardings=(p_shard, m_shard, b_shard),
                        out_shardings=(p_shard, m_shard, None)
                        ).lower(pspecs, mspecs, bspecs).compile()
        elif shape.kind == "prefill":
            step = ST.make_prefill_step(cfg, shape)
            c = jax.jit(step, in_shardings=(p_shard, b_shard)
                        ).lower(pspecs, bspecs).compile()
        else:
            cspecs = ST.cache_specs_struct(cfg, shape)
            c_shard = SH.cache_shardings(cspecs, cfg, mesh,
                                         batch=shape.global_batch)
            step = ST.make_decode_step(cfg, shape)
            c = jax.jit(step, in_shardings=(p_shard, c_shard, b_shard, None),
                        out_shardings=(None, c_shard)
                        ).lower(pspecs, cspecs, bspecs,
                                jax.ShapeDtypeStruct((), jnp.int32)).compile()
    results[shape.name] = "ok" if c.memory_analysis() is not None else "ok"
import json
print("RESULT:" + json.dumps(results))
"""

# one representative per family keeps the suite fast; the full 10x4x2 matrix
# runs in the dry-run deliverable
FAMILIES = ["qwen2-7b", "grok-1-314b", "mamba2-2.7b", "recurrentgemma-2b",
            "whisper-base", "llama-3.2-vision-90b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_small_mesh_dryrun(arch):
    proc = subprocess.run([sys.executable, "-c", SCRIPT, arch],
                          capture_output=True, text=True, timeout=420,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    results = json.loads(line[0][len("RESULT:"):])
    for shape, status in results.items():
        assert status in ("ok", "skipped"), (shape, status)


PLAN_TO_DRYRUN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import numpy as np
from repro import cluster
from repro.core.auto_optimizer import algorithm1

# a 405B-class state does not fit one 4 GB device: the 2-D search must
# return mp > 1
devs = cluster.parse_cluster_spec("8xgpu-g2.2xlarge")
cost = cluster.WorkloadCost(flops_per_example=2e9, bytes_per_example=2e8,
                            grad_bytes=4e6, state_bytes=6e9)
plan = cluster.best_allocation(devs, global_batch=64, t_fc=0.002, cost=cost,
                               g_candidates=(1, 2), mp_candidates=(1, 2))

def runner(state, *, g, mu, eta, steps, probe):
    return state, np.linspace(1.0, 0.1 - 0.05 * mu, steps)

res = algorithm1(runner, None, n_devices=8, epochs=1, epoch_steps=10,
                 probe_steps=5, plan=plan)
assert res.mp == plan.mp and res.g == plan.g, (res.g, res.mp)

# ... and the dryrun host-smoke lane accepts the planned (g, mp) mesh for
# a 405B-class config: 8 host devices split as (g, data, mp)
from repro.launch.dryrun import host_smoke_one
data = 8 // (res.g * res.mp)
out = host_smoke_one("llama3-405b", groups=res.g, data=data, mp=res.mp,
                     verbose=False)
print("RESULT:" + json.dumps({
    "g": res.g, "mp": res.mp, "status": out["status"],
    "mp_leaves": out["mp_sharded_param_leaves"]}))
"""


def test_algorithm1_plan_accepted_by_dryrun():
    """ISSUE acceptance: algorithm1 returns a (g, mp) plan and the dryrun
    host-smoke lane lowers+compiles a 405B-class config through the
    planned ("group","data","mp") mesh."""
    proc = subprocess.run([sys.executable, "-c", PLAN_TO_DRYRUN_SCRIPT],
                          capture_output=True, text=True, timeout=420,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    res = json.loads(line[0][len("RESULT:"):])
    assert res["status"] == "ok", res
    assert res["mp"] == 2, res
    assert res["mp_leaves"] > 0, res
