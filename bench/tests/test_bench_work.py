"""Operations, bytes and peaks of bench/work.py, against hand-worked counts."""
import pytest

import _paths  # noqa: F401
from bench import work

V5E = "TPU v5 lite"


def test_peaks_v5e():
    p = work.peaks(V5E)
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("k,row_bytes", [(1000, 8000), (10000, 80000)])
def test_row_bytes_per_token(k, row_bytes):
    assert work.row_bytes_per_token(k) == row_bytes


@pytest.mark.parametrize("k,expected", [
    # 2K int32 rows + word, doc, old topic in + new topic out (16 B)
    # + alpha_k and N_k (2K f32) once per 256-token tile
    (1000, 8000 + 16 + 8000 / 256),
    (10000, 80000 + 16 + 80000 / 256),
])
def test_sample_bytes_per_token(k, expected):
    assert work.sample_bytes_per_token(k) == pytest.approx(expected)


@pytest.mark.parametrize("k", [1000, 10000])
def test_train_mfu_work_is_bandwidth_bound(k):
    peak = work.peaks(V5E)
    per_token = work.train_step_least_seconds_per_token(k, peak)
    assert per_token == pytest.approx(2 * k * 4 / 819e9)
    # 8 ops per topic at 197 TFLOP/s bound far lower than 8 B at 819 GB/s
    assert 8 * k / 197e12 < per_token


def test_train_kernel_least_seconds_one_chunk():
    peak = work.peaks(V5E)
    got = work.train_kernel_least_seconds(65536, 1000, peak)
    assert got == pytest.approx(65536 * (8000 + 16 + 8000 / 256) / 819e9)
    # 0.644 ms for a 65,536-token chunk at K=1,000
    assert got == pytest.approx(6.44e-4, rel=1e-3)
