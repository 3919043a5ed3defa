"""A model family the harness has never seen joins it as new files alone:
its module and its configuration live only in a temporary directory, and
the harness serves it, checks it against the reference and reads its
prefill MFU with the family's own count."""
import importlib
import json
import sys
import textwrap
import time

import pytest

import check
import control
import families
import layer_context
import loadgen
import models
import peaks
import run
import serve_job
import trace_reduce
from cpu_cells import bench, cpu_traffic
from families import dense_lm
from window import Window

#: the dense family under another name, with spies on what the harness
#: calls; its count is three times dense_lm's, so a reader that used
#: dense_lm's would read a third
FAMILY = textwrap.dedent('''
    from families import dense_lm
    from families.dense_lm import decode_bytes, params  # noqa: F401

    calls = {"program_config": 0, "weights_fn": 0,
             "forward_flops_per_token": 0}


    def program_config(cfg):
        calls["program_config"] += 1
        return dense_lm.program_config(cfg)


    def weights_fn(cfg):
        calls["weights_fn"] += 1
        return dense_lm.weights_fn(cfg)


    def forward_flops_per_token(cfg):
        calls["forward_flops_per_token"] += 1
        return 3.0 * dense_lm.forward_flops_per_token(cfg)
''')


@pytest.fixture
def unseen(tmp_path, monkeypatch):
    """The configuration of family ``unseen_lm``, whose module and file
    are found on the harness's search paths only through ``tmp_path``."""
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "unseen_lm.py").write_text(FAMILY)
    cfg = dict(models.load_config("tiny-lm"), name="unseen-lm",
               family="unseen_lm", cpu_stand_in="unseen-lm")
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "unseen-lm.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(families, "__path__",
                        [str(tmp_path / "families"), *families.__path__])
    monkeypatch.setattr(models, "CONFIGS", tmp_path / "configs")
    importlib.invalidate_caches()
    yield models.load_config(models.load_config("unseen-lm")["cpu_stand_in"])
    sys.modules.pop(f"{families.__name__}.unseen_lm", None)


def _serve_cell():
    """The first serving cell of BENCHMARK.json, and its limits."""
    for w in bench()["workloads"]:
        if loadgen.load_traffic(w["traffic"])["kind"] == "serve":
            return w, check.load_limits(w["name"])
    pytest.skip("BENCHMARK.json has no serving cell")


def test_an_unseen_family_is_served_checked_and_read(unseen, capsys):
    cfg = unseen
    fam = families.of(cfg)
    assert fam.__name__.endswith(".unseen_lm")
    serve, limits = _serve_cell()
    cell = dict(serve, name="unseen-lm.serve", config="unseen-lm")
    traffic = cpu_traffic(loadgen.load_traffic(cell["traffic"]))

    win = Window(time.perf_counter(), None, spans=True)
    res = serve_job.run(cell, cfg, traffic, 2**31 + 17, 1.0, win)
    ok, checks = check.judge(res["readings"], limits)
    assert ok and res["failed"] == 0 and win.compiles == 0, checks
    assert fam.calls["program_config"] == 1 and fam.calls["weights_fn"] == 1

    # the prefill MFU reader counts with the family's FLOPs: the device is
    # made busy over the whole window, so it is busy in every prefill span
    red = trace_reduce.reduce({0: [("fusion.0", win.t0, win.t1)]},
                              (win.t0, win.t1))
    spans = [(r.name, r.t0, r.t1, r.depth) for r in win.tracer.records()]
    ctx = layer_context.Context(
        cell=cell, cfg=cfg, traffic=traffic, layer=res["layer"],
        device={"kind": "TPU v5 lite"}, peak=peaks.peak("TPU v5 lite"),
        reduction=red, spans=spans, planes=[])
    mfu = run.load_module(
        run.HERE / "metrics" / "serve_prefill_mfu_pct.chat.py").read(ctx)
    done = set(int(r) for r in res["layer"]["report"].rids)
    tokens = sum(len(r.prompt) for r in res["layer"]["requests"]
                 if r.rid in done)
    busy = sum(min(b, win.t1) - max(a, win.t0) for n, a, b, _ in spans
               if n == "serve.prefill")
    assert fam.calls["forward_flops_per_token"] == 1
    assert mfu == pytest.approx(
        100 * 3 * dense_lm.forward_flops_per_token(cfg) * tokens
        / (busy * ctx.peak.bf16_flops), rel=1e-9)

    # the control's seeds: weights made anew for the second seed
    seeds = [2**31 + 18, 2**33 + 18]
    control.serve_seeds(cell, cfg, traffic, seeds, {seeds[1]}, 1.0)
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["seed"] for r in rows] == seeds
    assert all(r["program"]["logit_gap"] <= limits["logit_gap"]
               for r in rows)
    assert rows[1]["control"]["logit_gap"] > limits["logit_gap"]
    assert fam.calls["program_config"] == 2 and fam.calls["weights_fn"] == 3
