"""``correct`` for the serving cells on their CPU stand-ins: a sound run
passes; the fp8 control, each fault a served model can have, and a
server clock that disagrees with the harness's spans fail."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import check
import loadgen
import serve_job
from cpu_cells import bench, cells_of_kind, cpu_traffic, drive, stand_in

CELLS = cells_of_kind("serve")
CELL = {w["name"]: w for w in bench()["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell):
    out = drive(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_ttft_p90_ms", "serve_tpot_p90_ms",
                                   "serve_output_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [5, 2**31 + 5, 2**33 + 5])
def test_the_control_fails(cell, seed):
    """At the positions a run served, the token fp8 puts first lies
    further below the float32 reference's best than the limit allows."""
    cfg = stand_in(CELL[cell]["config"])
    traffic = cpu_traffic(loadgen.load_traffic(CELL[cell]["traffic"]))
    arch, weights, server = serve_job.build(cfg, traffic, seed)
    reqs = loadgen.requests(traffic, seed, 1.0, arch.vocab_size)
    rep = server.run(reqs)
    by = {r.rid: r for r in reqs}
    rids = serve_job.sample_rids(rep, seed, traffic["check"]["requests"])
    seqs = [np.concatenate([by[r].prompt, rep.tokens[r]]) for r in rids]
    g = serve_job.reference_gaps(cfg, weights, seqs,
                                 [len(by[r].prompt) for r in rids],
                                 quant="fp8")
    limit = check.load_limits(cell)["logit_gap"]
    assert g["logit_gap"] <= limit < g["control_gap"], g


def _patch_decode(monkeypatch, fn):
    from repro.serving import engine
    real = engine.paged_decode_step
    monkeypatch.setattr(engine, "paged_decode_step",
                        lambda *a, **k: fn(real, *a, **k))


@pytest.mark.parametrize("cell", CELLS)
def test_a_token_altered_where_it_is_produced_fails(cell, monkeypatch):
    def shifted(real, *a, **k):
        logits, pages = real(*a, **k)
        return jnp.roll(logits, 1, axis=-1), pages

    _patch_decode(monkeypatch, shifted)
    out = drive(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_fails(cell, monkeypatch):
    def unchanged(real, params, pages, *a, **k):
        logits, _ = real(params, pages, *a, **k)
        return logits, pages

    _patch_decode(monkeypatch, unchanged)
    out = drive(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_server_clock_that_disagrees_with_the_spans_fails(cell,
                                                              monkeypatch):
    """The server's own times (TTFT, TPOT) run at half the harness's
    clock, as when a time is taken before the call it times has synced."""
    from repro.serving import engine
    monkeypatch.setattr(engine, "monotonic",
                        lambda: 0.5 * time.perf_counter())
    out = drive(cell)
    assert not out["correct"], out["checks"]
    assert out["checks"]["clock_gap"]["value"] > 0.4
    assert out["checks"]["logit_gap"]["value"] <= 0.5
