"""Fused gather+sample — kernel suite v2, kernel (a).

The first-generation sampler (``zen_sampler.py``) consumes *gathered*
``(T, K)`` word/doc count rows: the backend materializes ``n_wk[word]`` and
``n_kd[doc]`` in HBM before the kernel ever runs — at webchunk scale that is
two full token-by-topic matrices of traffic per sweep that exist only to be
streamed once. This kernel removes the materialization: the resident
``N_w|k`` / ``N_k|d`` matrices stay in HBM (``memory_space=pl.ANY``), the
per-token word/doc row ids arrive as SMEM blocks, and each grid step DMAs
the tile's ``bt`` row slices of width ``bk`` into two ``(bt, bk)`` VMEM
tiles (``tiles.gather_rows``; ``bk`` is a multiple of 1024 there) — the
gather happens in the DMA engine, never as an HBM intermediate (CuLDA_CGS's fused
gather+sample+update, rendered for the TPU memory system; see DESIGN.md
§2.3).

Grid = (T/bt, K/bk), K innermost, exactly the v1 grid: once a step's rows
are gathered it runs the v1 kernel body unchanged on the VMEM tiles, so
math, noise coordinates (global token id, topic id) and tie-break order
are the v1 kernel's by construction and the fused path is
**bit-identical** to the v1 gather-then-sample path (and to
``ref.zen_fused_sample_ref``) — dispatch choice can never change a run.

Two variants, mirroring v1:

* ``zen_fused_sample_pallas`` — training: exact ¬dw self-exclusion on all
  three counts, one scalar seed, noise rows = global token index.
* ``zen_fused_infer_sample_pallas`` — frozen-model serving: doc-side-only
  exclusion, per-token counter-based seeds (``golden_seed``), noise rows
  pinned to 0 (DESIGN.md §5.1 layout-stability contract).
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
    row_view,
)
from repro.kernels.zen_sampler import _zen_infer_kernel, _zen_sampler_kernel


def _gathered(body, n_lead: int, ids_per_block: int, kp: int):
    """Wrap a v1 kernel body: refs are ``lead..., wids, dids, nwk_view,
    nkd_view, rest..., out, bufs (2), tiles (2), sems, m_ref, a_ref``.
    Gather this step's word and doc row tiles, then run ``body(lead...,
    nw_tile, nd_tile, rest..., out, m_ref, a_ref)``."""

    def kernel(*refs, bt: int, bk: int, **params):
        lead = refs[:n_lead]
        wids, dids, nwk_view, nkd_view = refs[n_lead:n_lead + 4]
        rest = refs[n_lead + 4:-7]
        nw_buf, nd_buf, nw_tile, nd_tile, sems, m_ref, a_ref = refs[-7:]
        base = pl.program_id(0) % (ids_per_block // bt) * bt
        gather_rows(
            ((wids, nwk_view, nw_buf, nw_tile, sems.at[0]),
             (dids, nkd_view, nd_buf, nd_tile, sems.at[1])),
            base, kp, pl.program_id(1) * bk,
        )
        body(*lead, nw_tile, nd_tile, *rest, m_ref, a_ref, bt=bt, bk=bk,
             **params)

    return kernel


def _fused_call(name, body, n_lead, lead, word, doc, n_wk, n_kd, rest,
                *, beta, w_beta, bt, bk, interpret):
    """One pallas_call, named ``name``, of a gathered v1 body. ``lead`` are
    scalar-prefetch operands, ``rest`` the (bt, 1) per-token then (1, bk)
    per-topic operands of the v1 body, in its order. ``word``/``doc`` are
    padded to whole SMEM id blocks; ``rest`` holds ``t`` tokens. The count
    matrices' row views are taken under the named scope ``zen.relayout``."""
    t, k = rest[0].shape[0], n_wk.shape[1]
    ids_per_block = id_block(bt)
    assert t % bt == 0 and k % bk == 0 and bk % GATHER_LANES == 0, \
        (t, k, bt, bk)
    assert word.shape[0] % ids_per_block == 0, (word.shape, ids_per_block)
    assert n_kd.shape[1] == k, (n_wk.shape, n_kd.shape)
    per_tile = ids_per_block // bt

    def spec(x):
        if x.shape[1] == 1:  # per-token column
            return pl.BlockSpec((bt, 1), lambda i, j, *_: (i, 0))
        return pl.BlockSpec((1, bk), lambda i, j, *_: (0, j))

    ids = pl.BlockSpec((ids_per_block,), lambda i, j, *_: (i // per_tile,),
                       memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(
        _gathered(body, n_lead, ids_per_block, k),
        beta=beta, w_beta=w_beta, bt=bt, bk=bk,
    )
    with jax.named_scope("zen.relayout"):
        nwk_view, nkd_view = row_view(n_wk), row_view(n_kd)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_lead,
            grid=(t // bt, k // bk),
            in_specs=[ids, ids, hbm, hbm] + [spec(x) for x in rest],
            out_specs=pl.BlockSpec((bt, 1), lambda i, j, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bt * bk // LANES, LANES), jnp.int32),
                pltpu.VMEM((bt * bk // LANES, LANES), jnp.int32),
                pltpu.VMEM((bt, bk), jnp.int32),
                pltpu.VMEM((bt, bk), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((bt, 1), jnp.float32),
                pltpu.VMEM((bt, 1), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name=name,
    )(*lead, word.astype(jnp.int32), doc.astype(jnp.int32), nwk_view,
      nkd_view, *rest)
    return out[:, 0]


def zen_fused_sample_pallas(
    n_wk: jax.Array,  # (W, K) int32 — resident word-topic matrix
    n_kd: jax.Array,  # (D, K) int32 — resident doc-topic matrix
    word: jax.Array,  # (T,) int32 row ids into n_wk
    doc: jax.Array,  # (T,) int32 row ids into n_kd
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
    """Sample one topic per token, gathering count rows by DMA. T % bt ==
    0, K % bk == 0, bk % 1024 == 0 and word/doc padded to whole id blocks
    required (``ops.zen_fused_sample`` pads)."""
    return _fused_call(
        "zen_fused_sample", _zen_sampler_kernel, 1,
        (jnp.asarray([seed], jnp.int32),), word, doc, n_wk, n_kd,
        (z_old[:, None], alpha_k[None, :].astype(jnp.float32),
         n_k[None, :].astype(jnp.float32)),
        beta=beta, w_beta=w_beta, bt=bt, bk=bk, interpret=interpret,
    )


def zen_fused_infer_sample_pallas(
    n_wk: jax.Array,  # (W, K) int32 frozen word-topic matrix
    n_kd: jax.Array,  # (B, K) int32 per-slot doc-topic counts
    word: jax.Array,  # (T,) int32 row ids into n_wk
    slot: jax.Array,  # (T,) int32 row ids into n_kd
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
    """Frozen-model Gumbel-max sample with the row gather done by DMA.
    Same shape contract as ``zen_fused_sample_pallas``
    (``ops.zen_fused_infer_sample`` pads)."""
    return _fused_call(
        "zen_fused_infer_sample", _zen_infer_kernel, 0, (), word, slot,
        n_wk, n_kd,
        (z_old[:, None], seeds[:, None].astype(jnp.int32),
         alpha_k[None, :].astype(jnp.float32),
         n_k[None, :].astype(jnp.float32)),
        beta=beta, w_beta=w_beta, bt=bt, bk=bk, interpret=interpret,
    )
