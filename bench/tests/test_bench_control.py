"""The lower-precision control and the planted faults of
``control_chip.py`` at a size a test run holds: each fails a limit of the
cell, and the float32 reference put in the program's place passes them."""
import pytest

import _paths  # noqa: F401
import control_chip
from bench import run


def _fails(reading, limits):
    return any(reading[k] > v for k, v in limits.items())


@pytest.mark.parametrize("cell", ["train-nytimes", "train-webchunk-shard"])
def test_train_control_and_faults_fail(cell):
    _, _, cfg, traffic, limits = run.load_cell(cell)
    cfg.update(num_words=600, num_topics=24, num_docs=50, token_chunk=2048)
    out = control_chip.train_readings(cfg, traffic, 2**32 + 5)
    assert not _fails(out["reference_f32"], limits), out
    for name in ("control", "unchanged", "half", "altered", "dropped"):
        assert _fails(out[name], limits), (name, out[name])


@pytest.mark.parametrize("cell", ["serve-nytimes-steady",
                                  "serve-nytimes-batch"])
def test_serve_control_and_faults_fail(cell):
    _, _, cfg, traffic, limits = run.load_cell(cell)
    cfg.update(num_words=600, num_topics=24, published_num_docs=200)
    traffic.update(checked_requests=48, length_max=120)
    out = control_chip.serve_readings(cfg, traffic, 2**32 + 6)
    assert not _fails(out["reference_f32"], limits), out
    for name in ("control", "unchanged", "half"):
        assert _fails(out[name], limits), (name, out[name])
