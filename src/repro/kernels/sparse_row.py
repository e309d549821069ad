"""Padded-sparse row sampler — kernel suite v2, kernel (c).

The four Alg. 2 sparse backends (``zen_sparse``, ``zen_hybrid``,
``sparselda``, ``lightlda``) all end their hot loops the same way: a
token holds a compact ``(max_k,)`` row of (topic id, weight) pairs —
sentinel-masked, lane-aligned, the exact layout ``resolve_dist_row_pads``
produces — and must invert a uniform target through the row's running
sum, returning the *topic id* stored at the landing position. This
kernel is that primitive: cumsum, lower-bound count, clamp, one-hot
topic select, all on a ``(bt, J)`` tile resident in VMEM (SaberLDA's
sparsity-aware vectorized sampling, PAPERS.md).

Deliberately a whole-row kernel — grid is ``(T/bt,)`` with no J tiling.
Compact rows are short (``max_kw``/``max_kd`` ≲ a few hundred lanes) so
a row always fits; tiling J would reintroduce a cross-tile clamp hazard
(a tile-local clamp cannot know the search landed in an earlier tile),
and a 1-D grid keeps interpret mode cheap enough to dispatch in tests.
Padding is inert by construction: padded lanes carry weight 0 (no mass,
no count change below target) and sentinel topic ids that the
``min(cnt, j_real - 1)`` clamp can never select. Bit-identical to
``ref.sparse_row_sample_ref`` at every (bt, pad) shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import prefix_sum


def _sparse_row_kernel(
    vals_ref,  # (bt, J) f32 — per-lane weights, 0 on padded lanes
    topics_ref,  # (bt, J) int32 — per-lane topic ids, sentinel on padding
    tgt_ref,  # (bt, 1) f32 — per-token inversion target
    out_ref,  # (bt, 1) int32 — selected topic id
    *,
    j_real: int,
):
    vals = vals_ref[...]
    cdf = prefix_sum(vals, roll=pltpu.roll)
    cnt = jnp.sum((cdf < tgt_ref[...]).astype(jnp.int32), axis=1,
                  keepdims=True)
    pos = jnp.minimum(cnt, j_real - 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    hit = (lanes == pos).astype(jnp.int32)
    out_ref[...] = jnp.sum(topics_ref[...] * hit, axis=1, keepdims=True)


def sparse_row_sample_pallas(
    vals: jax.Array,  # (T, J) f32 — compact row weights
    topics: jax.Array,  # (T, J) int32 — compact row topic ids
    targets: jax.Array,  # (T,) f32 — inversion targets
    *,
    j_real: int,
    bt: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Per-token CDF inversion over compact sparse rows: topic id at the
    lower-bound position of ``targets`` in ``cumsum(vals, 1)``, clamped
    to ``j_real - 1``. T % bt == 0 required (``ops.sparse_row_sample``
    pads and manages the VMEM row budget)."""
    t, j = vals.shape
    assert t % bt == 0, (t, bt)
    assert topics.shape == (t, j)
    kernel = functools.partial(_sparse_row_kernel, j_real=j_real)
    out = pl.pallas_call(
        kernel,
        grid=(t // bt,),
        in_specs=[
            pl.BlockSpec((bt, j), lambda i: (i, 0)),
            pl.BlockSpec((bt, j), lambda i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        name="sparse_row",
    )(
        vals.astype(jnp.float32),
        topics.astype(jnp.int32),
        targets[:, None].astype(jnp.float32),
    )
    return out[:, 0]
