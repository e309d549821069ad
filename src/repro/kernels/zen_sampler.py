"""Fused ZenLDA Gumbel-max sampler — Pallas TPU kernel.

The TPU adaptation of the paper's sampling core (DESIGN.md §2): instead of
alias tables + per-token CDFs (random gathers, table builds), one fused pass
streams K-tiles of the three-term conditional

    p[t, k] = (α_k·β + N_w|k·α_k + N_k|d·(N_w|k+β)) / (N_k + Wβ)     (Eq. 3)

through VMEM and samples with the Gumbel-max trick:

    z_t = argmax_k ( log p[t,k] + g[t,k] ),   g ~ Gumbel(0,1)

which needs only a running (max, argmax) carry per token — no normalization,
no materialized (T, K) probability matrix in HBM, no second pass. The ¬dw
self-exclusion is applied exactly in-register (subtract the token's previous
topic from all three counts).

Gumbel noise comes from a counter-based integer hash of
(seed, token_id, topic_id) computed in-kernel on the VPU — zero HBM noise
traffic, bit-identical to the pure-jnp oracle in ``ref.py`` (the TPU-native
``pltpu.prng_*`` path is not used so that interpret-mode CPU validation is
exact).

Block layout: token tile ``bt`` (sublane-aligned, default 256) × topic tile
``bk`` (lane-aligned, default 512). Grid = (T/bt, K/bk), K innermost so the
(bt, 1) running-max scratch carries across K tiles. VMEM per step ≈
2·bt·bk·4B (count tiles) + 4·bk·4B (per-topic vectors) + noise tile
≈ 1.1 MB at defaults — comfortably under the ~16 MB/core budget, and the
MXU-free VPU pipeline is the right unit since this is elementwise math +
reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Murmur3-style finalizer constants (avalanche mixing). Plain ints: traced
# jnp constants would be captured as closure constants, which pallas rejects.
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B9


def _mix(x: jax.Array) -> jax.Array:
    x = (x ^ (x >> 16)) * jnp.asarray(_M1, jnp.uint32)
    x = (x ^ (x >> 13)) * jnp.asarray(_M2, jnp.uint32)
    return x ^ (x >> 16)


def hash_uniform(seed: jax.Array, row: jax.Array, col: jax.Array) -> jax.Array:
    """Counter-based U(0,1) from integer coordinates. Shared by kernel + ref.

    The top 23 hash bits m give ``u = (2m + 1) / 2^24``: every value is
    exact in float32 and lies in [2^-24, 1 - 2^-24], so the Gumbel noise
    ``-log(-log(u))`` is always finite, the same way on TPU and CPU. (24
    bits rounded the top value up to 1.0, an infinite Gumbel that let a
    zero-probability padded topic win the argmax.) The bits go to float
    through int32: Mosaic has no uint32 -> float32 cast.
    """
    h = _mix(
        seed.astype(jnp.uint32)
        ^ (row.astype(jnp.uint32) * jnp.asarray(_GOLD, jnp.uint32))
        ^ _mix(col.astype(jnp.uint32))
    )
    return (h >> 9).astype(jnp.int32).astype(jnp.float32) * (
        1.0 / (1 << 23)
    ) + (0.5 / (1 << 23))


def gumbel_noise(seed, row, col):
    u = hash_uniform(seed, row, col)
    return -jnp.log(-jnp.log(u))


def mix32(x: jax.Array) -> jax.Array:
    """The kernel's avalanche mixer on plain uint32 arrays (public form).

    The serving path uses it *outside* the kernel to derive per-token
    seeds from per-slot PRNG keys: the derivation is pure elementwise
    hashing of (slot key bits, token position), so it is counter-based by
    construction — prefix-stable in the bucket pad and independent of
    batch composition, unlike shaped ``jax.random`` draws under
    non-partitionable threefry.
    """
    return _mix(x.astype(jnp.uint32))


def golden_seed(key_bits_hi: jax.Array, key_bits_lo: jax.Array,
                pos: jax.Array) -> jax.Array:
    """Per-token int32 seeds from split per-slot key words + positions.

    ``seed[b, p] = mix(hi[b] ^ mix(lo[b]) ^ p * GOLDEN)`` with the high
    bit cleared (the kernels take non-negative int32 seeds). Broadcasts:
    pass ``hi``/``lo`` shaped ``(B, 1)`` and ``pos`` shaped ``(1, L)`` to
    get the ``(B, L)`` serving seed grid.
    """
    h = mix32(
        key_bits_hi.astype(jnp.uint32)
        ^ mix32(key_bits_lo)
        ^ (pos.astype(jnp.uint32) * jnp.asarray(_GOLD, jnp.uint32))
    )
    return (h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def _zen_sampler_kernel(
    # scalar prefetch
    seed_ref,
    # inputs
    nwk_ref,  # (bt, bk) int32 — gathered word-topic rows, this K tile
    nkd_ref,  # (bt, bk) int32 — gathered doc-topic rows
    zold_ref,  # (bt, 1) int32 — previous assignment (¬dw exclusion)
    alpha_ref,  # (1, bk) f32 — alpha_k
    nk_ref,  # (1, bk) f32 — N_k
    # output
    out_ref,  # (bt, 1) int32 — sampled topic
    # scratch
    m_ref,  # (bt, 1) f32 — running max of log p + g
    a_ref,  # (bt, 1) i32 — running argmax
    *,
    beta: float,
    w_beta: float,
    bt: int,
    bk: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        a_ref[...] = jnp.zeros_like(a_ref)

    # global coordinates of this tile
    rows = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bt, bk), 1)

    # exact ¬dw: subtract the token's own previous assignment
    self_hit = (cols == zold_ref[...]).astype(jnp.float32)
    nw = nwk_ref[...].astype(jnp.float32) - self_hit
    nd = nkd_ref[...].astype(jnp.float32) - self_hit
    nk = nk_ref[...] - self_hit
    alpha_k = alpha_ref[...]

    # three-term ZenLDA decomposition, fused (paper Alg. 5 FMAs)
    p = (alpha_k * beta + nw * alpha_k + nd * (nw + beta)) / (nk + w_beta)

    g = gumbel_noise(seed_ref[0], rows, cols)
    score = jnp.log(jnp.maximum(p, 1e-30)) + g

    tile_max = jnp.max(score, axis=1, keepdims=True)  # (bt, 1)
    tile_arg = jnp.argmax(score, axis=1).astype(jnp.int32)[:, None] + j * bk

    better = tile_max > m_ref[...]
    a_ref[...] = jnp.where(better, tile_arg, a_ref[...])
    m_ref[...] = jnp.where(better, tile_max, m_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        out_ref[...] = a_ref[...]


def zen_sample_pallas(
    nwk_rows: jax.Array,  # (T, K) int32
    nkd_rows: jax.Array,  # (T, K) int32
    z_old: jax.Array,  # (T,) int32
    alpha_k: jax.Array,  # (K,) f32
    n_k: jax.Array,  # (K,) f32/int32
    seed: jax.Array,  # () int32 — iteration/device-folded seed
    *,
    beta: float,
    w_beta: float,
    bt: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Sample one topic per token. T % bt == 0 and K % bk == 0 required
    (ops.py pads)."""
    t, k = nwk_rows.shape
    assert t % bt == 0 and k % bk == 0, (t, k, bt, bk)
    grid = (t // bt, k // bk)
    kernel = functools.partial(
        _zen_sampler_kernel, beta=beta, w_beta=w_beta, bt=bt, bk=bk
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, bk), lambda i, j, *_: (i, j)),
                pl.BlockSpec((bt, bk), lambda i, j, *_: (i, j)),
                pl.BlockSpec((bt, 1), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((1, bk), lambda i, j, *_: (0, j)),
                pl.BlockSpec((1, bk), lambda i, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((bt, 1), lambda i, j, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bt, 1), jnp.float32),
                pltpu.VMEM((bt, 1), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="zen_sample",
    )(
        jnp.asarray([seed], jnp.int32),
        nwk_rows,
        nkd_rows,
        z_old[:, None],
        alpha_k[None, :].astype(jnp.float32),
        n_k[None, :].astype(jnp.float32),
    )
    return out[:, 0]


def _zen_infer_kernel(
    # inputs
    nwk_ref,  # (bt, bk) int32 — frozen word-topic rows, this K tile
    nkd_ref,  # (bt, bk) int32 — gathered per-slot doc-topic rows
    zold_ref,  # (bt, 1) int32 — previous assignment (doc-side ¬t)
    seed_ref,  # (bt, 1) int32 — per-token counter-based seeds
    alpha_ref,  # (1, bk) f32 — alpha_k
    nk_ref,  # (1, bk) f32 — frozen N_k
    # output
    out_ref,  # (bt, 1) int32 — sampled topic
    # scratch
    m_ref,  # (bt, 1) f32 — running max of log p + g
    a_ref,  # (bt, 1) i32 — running argmax
    *,
    beta: float,
    w_beta: float,
    bt: int,
    bk: int,
):
    """Frozen-model serving variant of ``_zen_sampler_kernel``.

    Differences from the training kernel, both serving-exact:

    * **No word-side exclusion** — phi is frozen, the query's tokens were
      never counted in ``N_w|k``/``N_k``, so only the doc side excludes
      the token's own assignment. This removes the training path's
      pre-compensation of the gathered word rows (one (T, K) int32 add)
      *and* its N_k off-by-one denominator approximation.
    * **Per-token seeds** — noise coordinates are (seed[t], topic), with
      seed[t] derived outside from the token's *slot* key and in-doc
      position (``golden_seed``). A token's draw therefore never depends
      on the flat batch coordinates, so serving is padding-exact and
      batch-composition-independent here too (DESIGN.md §5.1/§5.2).
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        a_ref[...] = jnp.zeros_like(a_ref)

    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bt, bk), 1)

    # doc-side-only exact ¬t exclusion; word side stays frozen
    self_hit = (cols == zold_ref[...]).astype(jnp.float32)
    nw = nwk_ref[...].astype(jnp.float32)
    nd = nkd_ref[...].astype(jnp.float32) - self_hit
    alpha_k = alpha_ref[...]

    # frozen-phi conditional: (N_k|d^(¬t) + alpha_k)(N_w|k + beta)/(N_k + Wβ)
    p = (nd + alpha_k) * (nw + beta) / (nk_ref[...] + w_beta)

    g = gumbel_noise(seed_ref[...], jnp.zeros((bt, 1), jnp.uint32), cols)
    score = jnp.log(jnp.maximum(p, 1e-30)) + g

    tile_max = jnp.max(score, axis=1, keepdims=True)  # (bt, 1)
    tile_arg = jnp.argmax(score, axis=1).astype(jnp.int32)[:, None] + j * bk

    better = tile_max > m_ref[...]
    a_ref[...] = jnp.where(better, tile_arg, a_ref[...])
    m_ref[...] = jnp.where(better, tile_max, m_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        out_ref[...] = a_ref[...]


def zen_infer_sample_pallas(
    nwk_rows: jax.Array,  # (T, K) int32 frozen gathered word rows
    nkd_rows: jax.Array,  # (T, K) int32 per-slot doc rows
    z_old: jax.Array,  # (T,) int32
    seeds: jax.Array,  # (T,) int32 per-token counter-based seeds
    alpha_k: jax.Array,  # (K,) f32
    n_k: jax.Array,  # (K,) f32/int32 frozen
    *,
    beta: float,
    w_beta: float,
    bt: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Frozen-model Gumbel-max sample, one topic per token. T % bt == 0
    and K % bk == 0 required (``ops.zen_infer_sample`` pads)."""
    t, k = nwk_rows.shape
    assert t % bt == 0 and k % bk == 0, (t, k, bt, bk)
    grid = (t // bt, k // bk)
    kernel = functools.partial(
        _zen_infer_kernel, beta=beta, w_beta=w_beta, bt=bt, bk=bk
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bt, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.int32),
        ],
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="zen_infer_sample",
    )(
        nwk_rows,
        nkd_rows,
        z_old[:, None],
        seeds[:, None],
        alpha_k[None, :].astype(jnp.float32),
        n_k[None, :].astype(jnp.float32),
    )
    return out[:, 0]
