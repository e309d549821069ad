"""Scatter-free topic-count histogram — Pallas TPU kernel.

Count updates (ΔN_w|k, ΔN_k|d) are scatter-adds over (row=vertex, col=topic)
pairs; scatter lowers to serialized updates on TPU. This kernel replaces it
with the MXU-native pattern (also used for MoE dispatch): tokens arrive
sorted by row (the word-by-word order the paper already mandates for wTable
lifetime), so a tile of ``bt`` tokens touches at most ``bt`` *distinct* rows.
ops.py precomputes each token's rank among its tile's distinct rows; the
kernel one-hot-expands rank (bt × bt) and signed topic deltas (bt × bk) and
contracts them on the MXU:

    partial[r, k] = Σ_t onehot_rank[t, r] · (inc_t·[k=z_new] − inc_t·[k=z_old])

yielding (tiles, bt, K) partials whose scatter back to global rows touches
``T/bt``× fewer rows than the naive scatter (256× at defaults).

f32 accumulation is exact: per-tile partial magnitudes are ≤ bt < 2^24.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _hist_kernel(
    rank_ref,  # (bt, 1) int32 — token's row-rank within its tile
    zold_ref,  # (bt, 1) int32
    znew_ref,  # (bt, 1) int32
    inc_ref,  # (bt, 1) int32 — 1 where the token actually changed & is real
    out_ref,  # (bt, bk) int32 — per-tile partial histogram (rank-indexed)
    *,
    bt: int,
    bk: int,
):
    j = pl.program_id(1)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bt, bk), 1)
    inc = inc_ref[...].astype(jnp.float32)
    delta = (
        (cols == znew_ref[...]).astype(jnp.float32)
        - (cols == zold_ref[...]).astype(jnp.float32)
    ) * inc  # (bt, bk) signed one-hot deltas
    ranks = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    sel = (ranks == rank_ref[...]).astype(jnp.float32)  # (bt_tok, bt_rank)
    # (bt_rank, bt_tok) @ (bt_tok, bk) on the MXU
    partial = jax.lax.dot_general(
        sel, delta, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[...] = partial.astype(jnp.int32)


def tile_ranks(rows: jax.Array, bt: int) -> tuple[jax.Array, jax.Array]:
    """Precompute (rank per token, row id per (tile, rank) slot).

    ``rows`` must be sorted (tokens in word-by-word order). Pure jnp; this is
    the ops.py companion of the kernel.
    Returns rank (T,) int32 and rank_rows (T//bt, bt) int32 (sentinel -1 on
    unused slots).
    """
    t = rows.shape[0]
    assert t % bt == 0
    tiles = rows.reshape(-1, bt)
    first = jnp.concatenate([tiles[:, :1], tiles[:, :-1]], axis=1)
    is_new = tiles != first
    is_new = is_new.at[:, 0].set(False)
    rank = jnp.cumsum(is_new.astype(jnp.int32), axis=1)  # (tiles, bt)
    # rows of each rank slot: scatter row ids by rank
    n_tiles = tiles.shape[0]
    rank_rows = jnp.full((n_tiles, bt), -1, jnp.int32)
    tile_ids = jax.lax.broadcasted_iota(jnp.int32, (n_tiles, bt), 0)
    rank_rows = rank_rows.at[tile_ids, rank].set(tiles.astype(jnp.int32))
    return rank.reshape(-1).astype(jnp.int32), rank_rows


def topic_histogram_pallas(
    rows_sorted: jax.Array,  # (T,) int32 — sorted row (word/doc local) ids
    z_old: jax.Array,  # (T,) int32
    z_new: jax.Array,  # (T,) int32
    inc: jax.Array,  # (T,) int32 — 1 for changed & real tokens else 0
    num_rows: int,
    num_topics: int,
    *,
    bt: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Signed delta histogram (num_rows, num_topics) int32."""
    t = rows_sorted.shape[0]
    k = num_topics
    assert t % bt == 0 and k % bk == 0, (t, k, bt, bk)
    rank, rank_rows = tile_ranks(rows_sorted, bt)
    grid = (t // bt, k // bk)
    kernel = functools.partial(_hist_kernel, bt=bt, bk=bk)
    partials = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, k), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="topic_histogram",
    )(rank[:, None], z_old[:, None], z_new[:, None], inc[:, None])
    # combine tile partials: one scatter over (tiles * bt) rank rows —
    # T/bt x fewer scattered rows than the naive per-token scatter.
    flat_rows = rank_rows.reshape(-1)
    safe = jnp.maximum(flat_rows, 0)
    out = jnp.zeros((num_rows, k), jnp.int32)
    contrib = jnp.where(flat_rows[:, None] >= 0, partials, 0)
    return out.at[safe].add(contrib)
