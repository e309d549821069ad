"""The single-box training branch gives the result line that the harness
gave before it had a mesh branch (``data/train_single_box_line.json``,
recorded with that code): the same keys in the same order, the same
checks and limits, the same NLL on the same seed."""
import json
import os

import pytest

import _paths
from bench import run

with open(os.path.join(_paths.ROOT, "bench", "tests", "data",
                       "train_single_box_line.json")) as f:
    BEFORE = json.load(f)


def test_single_box_line_as_before_the_mesh_branch():
    result, checks = run.run_cell("train-nytimes", BEFORE["seed"], 1.0, False,
                                  require_chip=False,
                                  overrides=BEFORE["overrides"])
    assert list(result) == BEFORE["keys"]
    assert result["correct"] is BEFORE["correct"]
    assert result["failed"] == BEFORE["failed"]
    assert result["attempted"] > 0
    assert {k: m["unit"] for k, m in result["metrics"].items()} == \
        BEFORE["metrics"]
    assert result["metrics"]["train_nll_per_token"]["value"] == \
        pytest.approx(BEFORE["train_nll_per_token"], rel=1e-6)
    assert result["device"] == BEFORE["device"]
    assert [c.name for c in checks] == [c[0] for c in BEFORE["checks"]]
    assert [c.limit for c in checks] == [c[2] for c in BEFORE["checks"]]
    assert [c.value for c in checks] == pytest.approx(
        [c[1] for c in BEFORE["checks"]], abs=1e-5)
