"""Operations and bytes of the work the cells require, from shapes alone.

The training kernel computes, for every token and topic, the dense
collapsed-Gibbs conditional with the token left out of its counts,

    p(k) = (N_kd + alpha_k) (N_wk + beta) / (N_k + W beta),

adds Gumbel noise and keeps the running argmax. What any implementation of
that must move: the token's ``N_wk`` and ``N_kd`` rows (``2 K`` int32), its
word id, doc id and old topic (read) and new topic (written), and the two
per-topic vectors (``alpha_k``, ``N_k``; ``2 K`` f32) once per tile of
``TILE_TOKENS`` tokens. Copies that re-lay the count matrices for a kernel
are not required work. Operations: ``OPS_PER_TOPIC`` per token and topic
(2 adds, 2 multiplies, 1 divide of the conditional, its log, the noise add
and the compare). The same counts hold whatever implements the sampler, so
a share of the roofline or of the peak stays comparable across PRs.
"""
from __future__ import annotations

import json
import os
from typing import Dict

INT = 4  # bytes of an int32 count or id
F32 = 4
OPS_PER_TOPIC = 8
TILE_TOKENS = 256

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind`` (``bench/peaks.json``);
    an unknown kind is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def row_bytes_per_token(k: int) -> int:
    """Count-row bytes one token's conditional reads: ``2 K`` int32."""
    return 2 * k * INT


def sample_bytes_per_token(k: int) -> float:
    """All required bytes per token of the training sampler."""
    return (row_bytes_per_token(k) + 3 * INT + INT
            + 2 * k * F32 / TILE_TOKENS)


def sample_ops_per_token(k: int) -> int:
    return OPS_PER_TOPIC * k


def least_seconds(ops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations and bytes over peak bandwidth."""
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def train_kernel_least_seconds(tokens: float, k: int,
                               peak: Dict[str, float]) -> float:
    return least_seconds(tokens * sample_ops_per_token(k),
                         tokens * sample_bytes_per_token(k), peak)


def train_step_least_seconds_per_token(k: int, peak: Dict[str, float]) -> float:
    """Whole-step required time per token for ``train_mfu``: the dense
    conditional's count rows (``2 K`` int32) and operations at the peaks."""
    return least_seconds(sample_ops_per_token(k), row_bytes_per_token(k),
                         peak)

