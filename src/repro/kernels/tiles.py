"""Building blocks shared by the Pallas kernels and their oracles.

* ``prefix_sum`` — an inclusive scan along the lane axis as a log-step
  shift-add (Hillis-Steele). Mosaic has no ``cumsum``; the same function
  runs inside the kernels (``roll=pltpu.roll``) and in the pure-jnp
  oracles and XLA fallbacks (``roll=jnp.roll``), so every path adds the
  same pairs in the same order and agrees bit for bit on any backend.
* ``gather_rows`` — the row gather of the fused kernels. Mosaic DMAs
  only whole (8, 128) tiles out of a 2-D int32 array in HBM, so a count
  matrix goes in as its ``(rows * K / 128, 128)`` view (``row_view``)
  and the K tile is a multiple of 1024 lanes (``gather_bk``): a token's
  slice of its row is then ``bk / 128`` aligned view rows, one DMA per
  token and matrix. The row ids arrive as SMEM blocks of 1024 tokens
  (``id_block``), so SMEM size never limits how many tokens one call
  takes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# One (8, 128) int32 HBM tile: the gather kernels' K tile is a multiple of
# this, so each token's slice of a count row is whole tiles of the
# matrix's (rows * K / 128, 128) view — the only row slice a DMA out of
# (8, 128)-tiled HBM may take.
GATHER_LANES = LANES * SUBLANES
# SMEM blocks of a 1-D int32 array must match its (1024,)-tiled HBM
# layout, so per-token row ids arrive in blocks of a multiple of this.
ID_BLOCK = 1024


def prefix_sum(x: jax.Array, roll=jnp.roll) -> jax.Array:
    """Inclusive scan of ``x`` (rows, lanes) along axis 1: after the step
    with shift d, lane i holds the sum of lanes (i - 2d, i]. A lane below
    d keeps its value, so appending lanes on the right never changes the
    result of the lanes before them."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    d = 1
    while d < x.shape[1]:
        x = jnp.where(lanes >= d, x + roll(x, d, 1), x)
        d *= 2
    return x


def last_lane(x: jax.Array) -> jax.Array:
    """``x[:, -1:]`` as a masked lane sum (exact: every other term is
    +0.0), which Mosaic lowers without a lane relayout."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lanes == x.shape[1] - 1, x, 0), axis=1,
                   keepdims=True)


def gather_bk(bk: int) -> int:
    """The K tile of the gather kernels: ``bk`` rounded up to whole
    (8, 128) tiles of a count row."""
    return -(-bk // GATHER_LANES) * GATHER_LANES


def id_block(bt: int) -> int:
    """Tokens per SMEM block of row ids: a multiple of both ``bt`` and
    ``ID_BLOCK``."""
    return math.lcm(bt, ID_BLOCK)


def row_view(mat: jax.Array) -> jax.Array:
    """(R, Kp) count matrix -> its (R * Kp / 128, 128) view, in which each
    ``GATHER_LANES`` slice of a row is one aligned (8, 128) tile."""
    return mat.reshape(-1, LANES)


def gather_rows(gathers, base, kp: int, col0) -> None:
    """For each ``(ids_ref, src_view, buf, tile, sem)`` in ``gathers``:
    ``tile[r] = src[ids_ref[base + r], col0:col0 + bk]`` for every row r
    of the VMEM tile ``tile`` (bt, bk), where ``src_view`` is
    ``row_view(src)`` left in HBM and ``src`` has ``kp`` columns. Each
    token's slice lands as ``bk / 128`` rows of ``buf`` (bt * bk / 128,
    128) by one DMA; every copy starts before any is awaited, so the
    gathers of all matrices are in flight together. Strided loads then
    move each 128-lane chunk of all ``bt`` rows into ``tile``."""
    bt, bk = gathers[0][3].shape
    per_row = bk // LANES

    def copies(r):
        for ids_ref, src_view, buf, _, sem in gathers:
            start = ids_ref[base + r] * (kp // LANES) + col0 // LANES
            yield pltpu.make_async_copy(
                src_view.at[pl.ds(pl.multiple_of(start, SUBLANES), per_row)],
                buf.at[pl.ds(pl.multiple_of(r * per_row, SUBLANES), per_row)],
                sem,
            )

    def start(r, c):
        for cp in copies(r):
            cp.start()
        return c

    def wait(r, c):
        for cp in copies(r):
            cp.wait()
        return c

    jax.lax.fori_loop(0, bt, start, 0)
    jax.lax.fori_loop(0, bt, wait, 0)
    for _, _, buf, tile, _ in gathers:
        for s in range(per_row):
            tile[:, s * LANES:(s + 1) * LANES] = buf[
                pl.ds(s, bt, stride=per_row), :
            ]
