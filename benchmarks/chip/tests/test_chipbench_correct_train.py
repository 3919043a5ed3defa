"""``correct`` for the training cells on the CPU stand-in: sound runs
pass, the control and each fault a training cell can have fail."""
import jax.numpy as jnp
import pytest

import check
import families
import loadgen
import models
import train_job
from cpu_cells import bench, cells_of_kind, drive, stand_in

CELLS = cells_of_kind("train")
CELL = {w["name"]: w for w in bench()["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell):
    out = drive(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_samples_per_s", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 3, 2**33 + 3])
def test_the_control_fails(cell, seed):
    """The reference in bfloat16 put in the program's place."""
    cfg = stand_in(CELL[cell]["config"])
    traffic = loadgen.load_traffic(CELL[cell]["traffic"])
    key = models.key_from_seed(seed)
    p0 = models.weights(cfg, key)
    pool = families.of(cfg).inputs(cfg, key, 3, traffic["global_batch"])
    ref = train_job.reference_rounds(cfg, traffic, p0, pool, 3)
    ctl = train_job.reference_rounds(cfg, traffic, p0, pool, 3,
                                     dtype=jnp.bfloat16)
    ok, checks = check.judge(check.train_readings(ctl, ref),
                             check.load_limits(cell))
    assert not ok, checks


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_fails(cell, monkeypatch):
    from repro.core import async_sgd
    from repro.engine import spmd
    keep = lambda params, grads, mom, **kw: (params, mom)
    monkeypatch.setattr(async_sgd, "apply_grouped_update", keep)
    monkeypatch.setattr(spmd, "apply_grouped_update", keep, raising=False)
    monkeypatch.setattr(spmd, "fused_bucket_update",
                        lambda p, g, v, **kw: (p, v), raising=False)
    out = drive(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_fails(cell, monkeypatch):
    from repro.models import cnn
    real = cnn.loss_fn

    def half(params, batch, cfg):
        n = batch["labels"].shape[0] // 2
        return real(params, {k: v[:n] for k, v in batch.items()}, cfg)

    monkeypatch.setattr(cnn, "loss_fn", half)
    out = drive(cell)
    assert not out["correct"], out["checks"]

