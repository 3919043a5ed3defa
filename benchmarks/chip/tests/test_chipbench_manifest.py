"""BENCHMARK.json against the files it names and the contract's rules."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_keys_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names.append(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert len(names) == len(set(names))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_resolves_to_its_files(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    for c in cfgs.values():
        path = ROOT / c["file"]
        with open(path) as f:
            data = json.load(f)
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert (HERE / "configs" / f"{data['reference']}.py").is_file()
        assert (HERE / "families" / f"{data['family']}.py").is_file()
        with open(HERE / "configs" / f"{data['cpu_stand_in']}.json") as f:
            stand_in = json.load(f)
        assert stand_in["name"] == data["cpu_stand_in"]
        assert stand_in["family"] == data["family"]
        assert stand_in["reference"] == data["reference"]
    used = set()
    for w in bench["workloads"]:
        assert w["config"] in cfgs
        used.add(w["config"])
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        with open(HERE / "limits" / f"{w['name']}.json") as f:
            assert json.load(f)["limits"]
    assert used == set(cfgs)
    for m in bench["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()


def test_every_data_file_belongs_to_an_entry(bench):
    """No traffic, limits, metric, configuration or family file beside the
    manifest that no cell, metric or configuration of it names: a
    configuration file is one of BENCHMARK.json's or the CPU stand-in of
    one, and a family module is the family of one."""
    traffic = {w["traffic"] for w in bench["workloads"]}
    cells = {w["name"] for w in bench["workloads"]}
    metrics = {m["name"] for m in bench["per_layer"]}
    assert {p.stem for p in (HERE / "traffic").glob("*.json")} == traffic
    assert {p.stem for p in (HERE / "limits").glob("*.json")} == cells
    assert {p.stem for p in (HERE / "metrics").glob("*.py")} == metrics
    cfgs = [json.loads((ROOT / c["file"]).read_text())
            for c in bench["configs"]]
    named = ({c["name"] for c in cfgs} | {c["cpu_stand_in"] for c in cfgs})
    assert {p.stem for p in (HERE / "configs").glob("*.json")} == named
    assert {p.stem for p in (HERE / "families").glob("*.py")
            if p.name != "__init__.py"} == {c["family"] for c in cfgs}


def test_each_metric_moves_a_metric_its_cells_report(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for w in cells:
        assert sum(w in ws for ws in e2e.values()) >= 2
        assert any(w in m.get("workloads", cells) for m in bench["per_layer"])
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in e2e[m["moves"]], (m["name"], w)
        layers.setdefault(m["layer"], []).append(m["name"])


def test_the_harness_refuses_to_run_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "caffenet.train.g4", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_harness_refuses_a_checkout_without_the_program(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "caffenet.train.g4", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
