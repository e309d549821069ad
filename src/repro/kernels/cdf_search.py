"""CDF inversion search — kernel suite v2, kernel (b).

``zen_cdf``'s faithful-paper path draws the term-2 word topic by
materializing a ``(W_shard, K)`` float CDF matrix (``cumsum`` of
``N_w|k · t4``) and binary-searching gathered rows through plain XLA.
This kernel fuses the whole chain — gather the token's *integer* count
rows of a token tile by DMA (``tiles.gather_rows``, as in
``fused_gather``), multiply by the broadcast per-topic term inside the
K-tile loop, and run the lower-bound search as a running-carry count —
so neither the float CDF matrix nor the gathered ``(T, K)`` rows ever
touch HBM.

Search-as-count identity: the lower-bound index of ``target`` in
``cumsum(vals)`` equals ``sum(cdf < target)``. Counting distributes over
K tiles with two ``(bt, 1)`` carries: ``acc`` (mass of all previous
tiles — the last lane of each tile's local scan — added to this tile's
local scan) and ``cnt`` (matches so far). The local scan is
``tiles.prefix_sum``.
The final ``min(cnt, k_real - 1)`` clamp covers the float edge where
``target`` exceeds the total mass (u == 1 round-off) and simultaneously
makes K-padding inert: padded columns have ``t4 == 0`` so they add no
mass, and any counts they'd contribute past ``k_real - 1`` are clamped
away. ``ref.cdf_row_search_ref`` replicates the tile-for-tile op order,
so the kernel is bit-identical to its oracle at every tile shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import (
    GATHER_LANES,
    LANES,
    gather_rows,
    id_block,
    last_lane,
    prefix_sum,
    row_view,
)


def _cdf_search_kernel(
    # inputs
    wids_ref,  # (id_block,) int32 SMEM — per-token row into the counts
    counts_view,  # row_view of the (R, K) int32 counts, left in HBM
    term_ref,  # (1, bk) f32 — per-topic multiplier tile (t4)
    tgt_ref,  # (bt, 1) f32 — per-token inversion target
    # output
    out_ref,  # (bt, 1) int32 — lower-bound index into the row CDF
    # scratch
    row_buf,  # (bt * bk / 128, 128) int32 — DMA landing rows
    row_tile,  # (bt, bk) int32 — this step's gathered count-row tiles
    sem,  # DMA semaphore of the gather
    acc_ref,  # (bt, 1) f32 — mass of all previous K tiles
    cnt_ref,  # (bt, 1) i32 — lower-bound count so far
    *,
    k_real: int,
    kp: int,
    bt: int,
    bk: int,
    ids_per_block: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    base = pl.program_id(0) % (ids_per_block // bt) * bt
    gather_rows(((wids_ref, counts_view, row_buf, row_tile, sem),),
                base, kp, j * bk)
    vals = row_tile[...].astype(jnp.float32) * term_ref[...]
    local = prefix_sum(vals, roll=pltpu.roll)
    cdf = acc_ref[...] + local
    cnt_ref[...] += jnp.sum((cdf < tgt_ref[...]).astype(jnp.int32), axis=1,
                            keepdims=True)
    acc_ref[...] += last_lane(local)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        out_ref[...] = jnp.minimum(cnt_ref[...], k_real - 1)


def cdf_row_search_pallas(
    counts: jax.Array,  # (R, K) int32 — resident count matrix
    rows: jax.Array,  # (T_ids,) int32 row ids into counts
    term: jax.Array,  # (K,) f32 — per-topic multiplier
    targets: jax.Array,  # (T,) f32 — inversion targets
    *,
    k_real: int,
    bt: int = 256,
    bk: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Lower-bound search of ``targets`` in ``cumsum(counts[rows] * term)``
    per token, fused with the row gather. T % bt == 0, K % bk == 0,
    bk % 1024 == 0 and ``rows`` padded to whole id blocks required
    (``ops.cdf_row_search`` pads); ``k_real`` is the pre-padding topic
    count used for the final clamp."""
    t, k = targets.shape[0], counts.shape[1]
    ids_per_block = id_block(bt)
    assert t % bt == 0 and k % bk == 0 and bk % GATHER_LANES == 0, \
        (t, k, bt, bk)
    assert rows.shape[0] % ids_per_block == 0, (rows.shape, ids_per_block)
    per_tile = ids_per_block // bt
    kernel = functools.partial(
        _cdf_search_kernel, k_real=k_real, kp=k, bt=bt, bk=bk,
        ids_per_block=ids_per_block,
    )
    out = pl.pallas_call(
        kernel,
        grid=(t // bt, k // bk),
        in_specs=[
            pl.BlockSpec((ids_per_block,), lambda i, j: (i // per_tile,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bt * bk // LANES, LANES), jnp.int32),
            pltpu.VMEM((bt, bk), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.int32),
        ],
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="cdf_search",
    )(
        rows.astype(jnp.int32),
        row_view(counts),
        term[None, :].astype(jnp.float32),
        targets[:, None].astype(jnp.float32),
    )
    return out[:, 0]
