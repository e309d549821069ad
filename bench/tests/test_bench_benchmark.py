"""BENCHMARK.json against the contract, and the harness finding every
cell's files by name."""
import json
import os
import re
import subprocess
import sys

import pytest

import _paths
from bench import run

with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def _reports(kind, cell):
    return {m["name"] for m in run.cell_metrics(BENCH, cell, kind)}


def test_names_and_units_use_allowed_characters():
    names = ([m["name"] for m in METRICS] + CELLS
             + [c["name"] for c in BENCH["configs"]]
             + [w["config"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)


def test_bounds_and_run_seconds():
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(cell):
    e2e = _reports("end_to_end", cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert _reports("per_layer", cell)


@pytest.mark.parametrize("cell", CELLS)
def test_moves_is_reported_by_each_cell_of_the_metric(cell):
    for name in _reports("per_layer", cell):
        m = next(x for x in BENCH["per_layer"] if x["name"] == name)
        assert m["moves"] in _reports("end_to_end", cell), (cell, name)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    _, wl, cfg, traffic, limits = run.load_cell(cell)
    assert wl["chips"] in (1, 4)
    assert len(wl["why"]) <= 200
    assert traffic["job"] in ("train", "serve")
    assert limits
    conf = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])


def test_metric_readers_declare_their_entry():
    for m in BENCH["per_layer"]:
        mod = run.metric_reader(m["name"])
        assert mod.UNIT == m["unit"], m["name"]
        assert mod.LAYER == m["layer"], m["name"]
        assert mod.MOVES == m["moves"], m["name"]
        assert mod.SOURCE == m["source"], m["name"]


def test_configs_used_and_files_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in BENCH["paths"])
        assert os.path.exists(os.path.join(_paths.ROOT, f))


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=_paths.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr
