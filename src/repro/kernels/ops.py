"""Jitted public wrappers for the Pallas kernels (padding, dtype glue).

``interpret=None`` resolves through ``default_interpret`` — the one place
that decides: compiled Mosaic kernels when JAX's devices are TPUs,
interpret mode (bit-exact validation) otherwise. Callers can force
either; compiling for a described TPU from a CPU host must pass
``interpret=False``.

The fused wrappers pad the resident count matrices to the gather tile and
take their row views under the named scope ``zen.relayout``, so that
re-layout's ops are found by name in a trace of the enclosing step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.cdf_search import cdf_row_search_pallas
from repro.kernels.fused_gather import (
    zen_fused_infer_sample_pallas,
    zen_fused_sample_pallas,
)
from repro.kernels.sparse_row import sparse_row_sample_pallas
from repro.kernels.tiles import gather_bk, id_block
from repro.kernels.topic_histogram import topic_histogram_pallas
from repro.kernels.zen_sampler import (
    zen_infer_sample_pallas,
    zen_sample_pallas,
)

# Whole-row sparse kernel VMEM budget: bt shrinks until a (bt, J) f32 tile
# plus its int32 twin fit comfortably (2 * 4B * 2^18 = 2 MiB of VMEM).
_SPARSE_ROW_BUDGET = 1 << 18


def default_interpret() -> bool:
    """Whether kernels run in interpret mode when the caller leaves it
    open: True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def _gather_tiles(t: int, bt: int, bk: int) -> tuple[int, int, int]:
    """(bt, bk, id block) of the gather kernels for ``t`` tokens: bt a
    multiple of 8 no larger than needed, bk whole (8, 128) row tiles."""
    bt_eff = min(bt, -(-max(t, 8) // 8) * 8)
    return bt_eff, gather_bk(bk), id_block(bt_eff)


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("beta", "w_beta", "bt", "bk", "interpret"),
)
def zen_sample(
    nwk_rows: jax.Array,
    nkd_rows: jax.Array,
    z_old: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    seed: jax.Array,
    *,
    beta: float,
    w_beta: float,
    bt: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused three-term CGS sample per token (see zen_sampler.py).

    Pads T to bt and K to bk; K padding gets p=0 rows (alpha_k=0, counts 0)
    so padded topics can never win the argmax.
    """
    if interpret is None:
        interpret = default_interpret()
    t, k = nwk_rows.shape
    bt_eff = min(bt, max(8, t))
    nwk_p = _pad_to(_pad_to(nwk_rows, 0, bt_eff), 1, bk)
    nkd_p = _pad_to(_pad_to(nkd_rows, 0, bt_eff), 1, bk)
    z_p = _pad_to(z_old, 0, bt_eff)
    # padded topics: alpha_k = 0 and n_k large => p == 0 there
    a_p = _pad_to(alpha_k.astype(jnp.float32), 0, bk, value=0.0)
    nk_p = _pad_to(n_k.astype(jnp.float32), 0, bk, value=1e9)
    out = zen_sample_pallas(
        nwk_p, nkd_p, z_p, a_p, nk_p, seed,
        beta=beta, w_beta=w_beta, bt=bt_eff, bk=bk, interpret=interpret,
    )
    return out[:t]


@functools.partial(
    jax.jit,
    static_argnames=("beta", "w_beta", "bt", "bk", "interpret"),
)
def zen_infer_sample(
    nwk_rows: jax.Array,
    nkd_rows: jax.Array,
    z_old: jax.Array,
    seeds: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    *,
    beta: float,
    w_beta: float,
    bt: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Frozen-model serving sample (see ``_zen_infer_kernel``): doc-side
    exclusion only, per-token counter-based seeds.

    Pads T to bt (inert seed-0 tokens, sliced off) and K to bk; K padding
    gets alpha_k = 0 and zero doc counts, so p == 0 there and a padded
    topic can never win the argmax.
    """
    if interpret is None:
        interpret = default_interpret()
    t, k = nwk_rows.shape
    bt_eff = min(bt, max(8, t))
    nwk_p = _pad_to(_pad_to(nwk_rows, 0, bt_eff), 1, bk)
    nkd_p = _pad_to(_pad_to(nkd_rows, 0, bt_eff), 1, bk)
    z_p = _pad_to(z_old, 0, bt_eff)
    s_p = _pad_to(seeds, 0, bt_eff)
    a_p = _pad_to(alpha_k.astype(jnp.float32), 0, bk, value=0.0)
    nk_p = _pad_to(n_k.astype(jnp.float32), 0, bk, value=1e9)
    out = zen_infer_sample_pallas(
        nwk_p, nkd_p, z_p, s_p, a_p, nk_p,
        beta=beta, w_beta=w_beta, bt=bt_eff, bk=bk, interpret=interpret,
    )
    return out[:t]


@functools.partial(
    jax.jit,
    static_argnames=("beta", "w_beta", "bt", "bk", "interpret"),
)
def zen_fused_sample(
    n_wk: jax.Array,
    n_kd: jax.Array,
    word: jax.Array,
    doc: jax.Array,
    z_old: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    seed: jax.Array,
    *,
    beta: float,
    w_beta: float,
    bt: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused gather+sample (see fused_gather.py): ``zen_sample`` without
    the ``(T, K)`` gathered-row HBM intermediate — the count rows are
    DMA'd per token straight out of the resident matrices. Bit-identical
    to ``zen_sample(n_wk[word], n_kd[doc], ...)`` for real tokens, at any
    tiling.

    Pads T to bt (row-0 tokens, sliced off) and K to the gather tile
    (``bk`` rounded up to a multiple of 1024) on the resident matrices;
    K padding gets alpha_k = 0 / counts 0 / n_k = 1e9 so p == 0 there and
    a padded topic can never win the argmax.
    """
    if interpret is None:
        interpret = default_interpret()
    t = word.shape[0]
    bt_eff, bk, ids = _gather_tiles(t, bt, bk)
    with jax.named_scope("zen.relayout"):
        nwk_p = _pad_to(n_wk.astype(jnp.int32), 1, bk)
        nkd_p = _pad_to(n_kd.astype(jnp.int32), 1, bk)
    w_p = _pad_to(word, 0, ids)
    d_p = _pad_to(doc, 0, ids)
    z_p = _pad_to(z_old, 0, bt_eff)
    a_p = _pad_to(alpha_k.astype(jnp.float32), 0, bk, value=0.0)
    nk_p = _pad_to(n_k.astype(jnp.float32), 0, bk, value=1e9)
    out = zen_fused_sample_pallas(
        nwk_p, nkd_p, w_p, d_p, z_p, a_p, nk_p, seed,
        beta=beta, w_beta=w_beta, bt=bt_eff, bk=bk, interpret=interpret,
    )
    return out[:t]


@functools.partial(
    jax.jit,
    static_argnames=("beta", "w_beta", "bt", "bk", "interpret"),
)
def zen_fused_infer_sample(
    n_wk: jax.Array,
    n_kd: jax.Array,
    word: jax.Array,
    slot: jax.Array,
    z_old: jax.Array,
    seeds: jax.Array,
    alpha_k: jax.Array,
    n_k: jax.Array,
    *,
    beta: float,
    w_beta: float,
    bt: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused gather + frozen-model serving sample: ``zen_infer_sample``
    without the gathered-row intermediates. Bit-identical to
    ``zen_infer_sample(n_wk[word], n_kd[slot], ...)`` for real tokens.

    Padding contract matches ``zen_fused_sample``: T pads to bt with
    row-0/seed-0 tokens (sliced off), K pads to the gather tile with
    alpha_k = 0 / counts 0 / n_k = 1e9.
    """
    if interpret is None:
        interpret = default_interpret()
    t = word.shape[0]
    bt_eff, bk, ids = _gather_tiles(t, bt, bk)
    with jax.named_scope("zen.relayout"):
        nwk_p = _pad_to(n_wk.astype(jnp.int32), 1, bk)
        nkd_p = _pad_to(n_kd.astype(jnp.int32), 1, bk)
    w_p = _pad_to(word, 0, ids)
    s_p = _pad_to(slot, 0, ids)
    z_p = _pad_to(z_old, 0, bt_eff)
    seeds_p = _pad_to(seeds, 0, bt_eff)
    a_p = _pad_to(alpha_k.astype(jnp.float32), 0, bk, value=0.0)
    nk_p = _pad_to(n_k.astype(jnp.float32), 0, bk, value=1e9)
    out = zen_fused_infer_sample_pallas(
        nwk_p, nkd_p, w_p, s_p, z_p, seeds_p, a_p, nk_p,
        beta=beta, w_beta=w_beta, bt=bt_eff, bk=bk, interpret=interpret,
    )
    return out[:t]


@functools.partial(
    jax.jit,
    static_argnames=("bt", "bk", "interpret"),
)
def cdf_row_search(
    counts: jax.Array,
    rows: jax.Array,
    term: jax.Array,
    targets: jax.Array,
    *,
    bt: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused gather + CDF lower-bound search (see cdf_search.py): the
    index of ``targets[t]`` in ``cumsum(counts[rows[t]] * term)``, clamped
    to K-1, without materializing the float CDF matrix or the gathered
    rows. Bit-identical to ``ref.cdf_row_search_ref`` at the same bk (both
    walk K in tiles of ``bk`` rounded up to a multiple of 1024).

    Pads T to bt (row-0 tokens, sliced off) and K to the gather tile with
    term = 0, so padded topics add no mass; the in-kernel clamp keeps any
    counts past K-1 from escaping.
    """
    if interpret is None:
        interpret = default_interpret()
    t = rows.shape[0]
    k = counts.shape[1]
    bt_eff, bk, ids = _gather_tiles(t, bt, bk)
    counts_p = _pad_to(counts.astype(jnp.int32), 1, bk)
    rows_p = _pad_to(rows, 0, ids)
    term_p = _pad_to(term.astype(jnp.float32), 0, bk, value=0.0)
    tgt_p = _pad_to(targets.astype(jnp.float32), 0, bt_eff)
    out = cdf_row_search_pallas(
        counts_p, rows_p, term_p, tgt_p,
        k_real=k, bt=bt_eff, bk=bk, interpret=interpret,
    )
    return out[:t]


@functools.partial(
    jax.jit,
    static_argnames=("bt", "bs", "interpret"),
)
def sparse_row_sample(
    vals: jax.Array,
    topics: jax.Array,
    targets: jax.Array,
    *,
    bt: int = 256,
    bs: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Whole-row sparse CDF inversion (see sparse_row.py): the topic id at
    the lower-bound position of ``targets[t]`` in ``cumsum(vals[t])``,
    clamped to the last real lane. Bit-identical to
    ``ref.sparse_row_sample_ref``.

    Pads the lane dim to a multiple of bs with weight-0 lanes (inert: they
    add no mass and the clamp can never land on them) and T to the
    effective bt; bt halves while a (bt, J) tile would overflow the VMEM
    row budget.
    """
    if interpret is None:
        interpret = default_interpret()
    t, j = vals.shape
    vals_p = _pad_to(vals.astype(jnp.float32), 1, bs)
    topics_p = _pad_to(topics.astype(jnp.int32), 1, bs)
    jp = vals_p.shape[1]
    bt_eff = min(bt, max(8, t))
    while bt_eff > 8 and bt_eff * jp > _SPARSE_ROW_BUDGET:
        bt_eff = max(8, bt_eff // 2)
    vals_p = _pad_to(vals_p, 0, bt_eff)
    topics_p = _pad_to(topics_p, 0, bt_eff)
    tgt_p = _pad_to(targets.astype(jnp.float32), 0, bt_eff)
    out = sparse_row_sample_pallas(
        vals_p, topics_p, tgt_p,
        j_real=j, bt=bt_eff, interpret=interpret,
    )
    return out[:t]


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "num_topics", "bt", "bk", "interpret"),
)
def topic_histogram(
    rows_sorted: jax.Array,
    z_old: jax.Array,
    z_new: jax.Array,
    inc: jax.Array,
    num_rows: int,
    num_topics: int,
    *,
    bt: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Signed delta histogram (num_rows, num_topics); see topic_histogram.py.

    Padding tokens get inc=0 (inert) and row = last row (stays sorted).
    """
    if interpret is None:
        interpret = default_interpret()
    t = rows_sorted.shape[0]
    bt_eff = min(bt, max(8, t))
    last_row = rows_sorted[-1]
    rows_p = _pad_to(rows_sorted, 0, bt_eff)
    pad = rows_p.shape[0] - t
    if pad:
        rows_p = rows_p.at[t:].set(last_row)
    z_old_p = _pad_to(z_old, 0, bt_eff)
    z_new_p = _pad_to(z_new, 0, bt_eff)
    inc_p = _pad_to(inc, 0, bt_eff)  # zero => inert
    k_pad = (-num_topics) % bk
    out = topic_histogram_pallas(
        rows_p, z_old_p, z_new_p, inc_p, num_rows, num_topics + k_pad,
        bt=bt_eff, bk=bk, interpret=interpret,
    )
    return out[:, :num_topics]
