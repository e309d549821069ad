"""``zen_cdf`` — the TPU-native faithful ZenLDA backend (moved here from
``core.distributed``).

Per-iteration precomputed CDFs replace alias tables (log K binary-search
gathers beat alias-table random gathers on TPU), the fresh dSparse term runs
over top-``max_kd`` sparse doc rows (O(K_d) gathers per token, the paper's
complexity), and staleness in gDense/wSparse is remedied by the paper's
resampling trick (§3.1).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.algorithms.base import CellBackend, SamplerKnobs, kernel_dispatch
from repro.algorithms.registry import register
from repro.algorithms.zen_dense import _searchsorted_rows
from repro.core.decompositions import precompute_zen_terms

# sparse doc-row width when the config leaves max_kd = 0 (auto): inside
# shard_map the width must be static, so auto means this default.
DEFAULT_MAX_KD = 64


def _bsearch_gather(
    mat: jax.Array,  # (R, K) row-wise ascending CDFs
    rows: jax.Array,  # (T,) row id per query
    targets: jax.Array,  # (T,)
) -> jax.Array:
    """True O(log K) lower-bound per query: one scalar gather per halving
    step, never materializing (T, K). This is the TPU rendering of the
    paper's BSearch samplers (Table 1)."""
    k = mat.shape[1]
    pos = jnp.zeros(rows.shape, jnp.int32)
    step = 1 << (k - 1).bit_length()
    while step > 0:
        cand = pos + step
        safe = jnp.minimum(cand - 1, k - 1)
        vals = mat[rows, safe]
        take = (cand <= k) & (vals < targets)
        pos = jnp.where(take, cand, pos)
        step //= 2
    return jnp.minimum(pos, k - 1)


def _bsearch_shared(cdf: jax.Array, targets: jax.Array) -> jax.Array:
    """Lower-bound of each target in one shared ascending CDF (K,)."""
    return jnp.minimum(
        jnp.searchsorted(cdf, targets).astype(jnp.int32), cdf.shape[0] - 1
    )


def zen_cdf_cell(
    key, word_l, doc_l, z_old, mask, n_wk_l, n_kd_l, n_k, hyper,
    num_words: int, max_kd: int,
    use_kernel: bool = False, bt: int = 256, bk: int = 512,
):
    """TPU-native faithful ZenLDA: precomputed CDFs + sparse doc rows.

    Work per token: O(log K) (terms 1-2) + O(max_kd) (term 3); per-iteration
    precompute: two passes over the local N_w|k block.

    ``use_kernel`` routes the term-2 draw through the fused CDF-search
    kernel (``kernels.cdf_search``): the ``(Ws, K)`` float ``w_cdf`` matrix
    becomes a matvec for the branch masses and the per-token search fuses
    gather + term-multiply + lower-bound inside the kernel. Same
    lower-bound semantics, different float summation order than the
    whole-row cumsum — distribution-equal, not bit-equal, to the XLA path
    (zen_cdf's cross-path contract is statistical; see DESIGN.md §2.3).
    """
    k = hyper.num_topics
    terms = precompute_zen_terms(n_k, hyper, num_words)

    # --- per-iteration precompute (the "build tables" stage, Alg. 2 l.5-13)
    g_cdf = jnp.cumsum(terms.g_dense)  # (K,)
    m1 = g_cdf[-1]
    if use_kernel:
        # branch masses only — no (Ws, K) float CDF matrix in HBM
        n_wk_i = n_wk_l.astype(jnp.int32)
        m2_all = n_wk_l.astype(jnp.float32) @ terms.t4  # (Ws,)
        w_cdf = None
    else:
        w_vals = n_wk_l.astype(jnp.float32) * terms.t4[None, :]  # (Ws, K)
        w_cdf = jnp.cumsum(w_vals, axis=-1)
        m2_all = w_cdf[:, -1]  # (Ws,)
    # sparse doc rows: top-max_kd topics by count. approx_max_k lowers to
    # the TPU PartialReduce unit (one pass over the block); exact top_k
    # lowers to a full row sort (§Perf iteration l2)
    kd_cnt, kd_idx = jax.lax.approx_max_k(
        n_kd_l.astype(jnp.float32), min(max_kd, k), recall_target=0.95
    )
    kd_cnt = kd_cnt.astype(jnp.int32)

    # --- per-token terms
    rows_idx = kd_idx[doc_l]  # (T, max_kd)
    rows_cnt = kd_cnt[doc_l]
    nwk_at = n_wk_l[word_l[:, None], rows_idx]  # (T, max_kd) gathers
    d_vals = (
        rows_cnt.astype(jnp.float32)
        * (nwk_at.astype(jnp.float32) + hyper.beta)
        * terms.t1[rows_idx]
    )
    d_vals = jnp.where(rows_cnt > 0, d_vals, 0.0)
    d_cdf = jnp.cumsum(d_vals, axis=-1)
    m3 = d_cdf[:, -1]
    m2 = m2_all[word_l]

    def draw(key):
        ku, kr = jax.random.split(key)
        u = jax.random.uniform(ku, word_l.shape) * (m1 + m2 + m3)
        # term 1: shared global CDF (replaces gTable) — O(log K)
        z_g = _bsearch_shared(g_cdf, u)
        # term 2: per-word CDF row (replaces wTable) — O(log K) scalar
        # gathers per token; the dense form gathered (T, K) rows (31 GB at
        # webchunk scale — §Perf iteration l1)
        t2_target = jnp.maximum(u - m1, 0.0)
        if use_kernel:
            from repro.kernels.ops import cdf_row_search

            z_w = cdf_row_search(
                n_wk_i, word_l, terms.t4, t2_target, bt=bt, bk=bk
            )
        else:
            z_w = _bsearch_gather(w_cdf, word_l, t2_target)
        # term 3: doc sparse row CDF (paper's dSparse + BSearch) — rows are
        # only max_kd wide, dense compare is the cheaper form here
        t3_target = jnp.maximum(u - m1 - m2, 0.0)
        pos = _searchsorted_rows(d_cdf, t3_target)
        z_d = jnp.take_along_axis(rows_idx, pos[:, None], -1)[:, 0]
        branch = jnp.where(u < m1, 0, jnp.where(u < m1 + m2, 1, 2))
        z = jnp.where(branch == 0, z_g, jnp.where(branch == 1, z_w, z_d))
        return jnp.minimum(z, k - 1).astype(jnp.int32), branch

    key_a, key_b, key_r = jax.random.split(key, 3)
    z1, branch = draw(key_a)
    z2, _ = draw(key_b)

    # resampling remedy (§3.1) for the staleness of terms 2 and 3
    nw_prev = jnp.maximum(
        n_wk_l[word_l, z_old].astype(jnp.float32), 1.0
    )
    nd_prev = jnp.maximum(
        n_kd_l[doc_l, z_old].astype(jnp.float32), 1.0
    )
    p_w = 1.0 / nw_prev
    p_d = jnp.clip(1.0 / nd_prev + (nd_prev + nw_prev - 1.0) / (nd_prev * nw_prev), 0.0, 1.0)
    remedy_p = jnp.where(branch == 1, p_w, jnp.where(branch == 2, p_d, 0.0))
    u_r = jax.random.uniform(key_r, z1.shape)
    return jnp.where((z1 == z_old) & (u_r < remedy_p), z2, z1)


class FrozenCdfTables(NamedTuple):
    """Sampling-ready frozen model: the per-word prior-term CDFs.

    Because the model never moves while serving, the per-iteration "build
    tables" stage of training (Alg. 2 l.5-13) collapses to a one-time
    precompute: ``a_cdf[w]`` is the cumulative of the doc-independent term
    alpha_k * (N_w|k + beta) * t1, so branch-1 draws are O(log K) scalar
    gathers per token for the engine's whole lifetime.
    """

    a_cdf: jax.Array  # (W, K) f32 row-wise CDF of the prior term
    a_mass: jax.Array  # (W,) f32 row masses
    t1: jax.Array  # (K,) f32 1 / (N_k + W*beta)
    alpha_k: jax.Array  # (K,) f32


def zen_cdf_infer_sweep(
    keys, words, mask, z_old, n_kd, n_wk, n_k, hyper,
    max_kd: int, tables: FrozenCdfTables,
):
    """Frozen-model sweep via the two-branch CDF decomposition.

    With phi frozen the Eq. 3 conditional splits into a doc-independent
    prior term (precomputed per-word CDFs, branch 1) and the sparse doc
    term over the slot's at-most-L live topics (branch 2):

        p(k) = [alpha_k + N_k|d^(-t)] * (N_w|k + beta) * t1

    Randomness is drawn per slot (``keys[b]`` -> one uniform per token
    position), so slots are independent and draws are prefix-stable in the
    bucket pad.
    """
    b, l = words.shape
    k = hyper.num_topics
    kd = min(max_kd, k)

    # sparse doc rows: exact top-kd per slot (serving docs hold <= L live
    # topics; exact top_k keeps the engine's oracle comparison clean)
    kd_cnt, kd_idx = jax.lax.top_k(n_kd, kd)  # (B, kd)

    slot = jax.lax.broadcasted_iota(jnp.int32, (b, l), 0).reshape(-1)
    w = words.reshape(-1)
    z = z_old.reshape(-1)
    live = mask.reshape(-1)

    rows_idx = kd_idx[slot]  # (BL, kd)
    rows_cnt = kd_cnt[slot]
    # exact doc-side ¬t exclusion: drop the token's own current assignment
    self_hit = (rows_idx == z[:, None]) & live[:, None]
    rows_cnt = rows_cnt - self_hit.astype(rows_cnt.dtype)
    nwk_at = n_wk[w[:, None], rows_idx].astype(jnp.float32)
    d_vals = (
        rows_cnt.astype(jnp.float32)
        * (nwk_at + hyper.beta)
        * tables.t1[rows_idx]
    )
    d_vals = jnp.where(rows_cnt > 0, d_vals, 0.0)
    d_cdf = jnp.cumsum(d_vals, axis=-1)
    m_d = d_cdf[:, -1]
    m_a = tables.a_mass[w]

    # one uniform per token, drawn from the token's *slot* key
    u01 = jax.vmap(lambda kk: jax.random.uniform(kk, (l,)))(keys).reshape(-1)
    u = u01 * (m_a + m_d)
    z_a = _bsearch_gather(tables.a_cdf, w, jnp.minimum(u, m_a))
    pos = _searchsorted_rows(d_cdf, jnp.maximum(u - m_a, 0.0))
    z_d = jnp.take_along_axis(rows_idx, pos[:, None], -1)[:, 0]
    z_new = jnp.where(u < m_a, z_a, z_d)
    return jnp.minimum(z_new, k - 1).astype(jnp.int32).reshape(b, l)


@register("zen_cdf")
class ZenCdf(CellBackend):
    """Precomputed-CDF ZenLDA; works single-box (one cell) and sharded."""

    native_infer = True
    # the frozen tables' word-indexed leaves: under sharded serving the
    # per-word CDF rows live with the word shard; t1/alpha_k replicate
    infer_aux_word_fields = ("a_cdf", "a_mass")

    def resolve_cell_knobs(self, knobs: SamplerKnobs, hyper):
        return dataclasses.replace(
            knobs,
            max_kd=min(knobs.max_kd or DEFAULT_MAX_KD, hyper.num_topics),
        )

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        return zen_cdf_cell(
            key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
            num_words, knobs.max_kd or DEFAULT_MAX_KD,
            use_kernel=kernel_dispatch(knobs.kernels),
            bt=knobs.bt, bk=knobs.bk,
        )

    def prepare_infer(self, n_wk, n_k, hyper, knobs: SamplerKnobs,
                      num_words_total=None):
        # sharded builds pass the true W: n_wk is then one shard's row
        # block, and the t1 denominator must still be N_k + W*beta
        w_total = (n_wk.shape[0] if num_words_total is None
                   else num_words_total)
        alpha_k = hyper.alpha_k(n_k)
        t1 = 1.0 / (n_k.astype(jnp.float32) + w_total * hyper.beta)
        a_vals = (n_wk.astype(jnp.float32) + hyper.beta) * (alpha_k * t1)
        a_cdf = jnp.cumsum(a_vals, axis=-1)
        return FrozenCdfTables(
            a_cdf=a_cdf, a_mass=a_cdf[:, -1], t1=t1, alpha_k=alpha_k
        )

    def infer_sweep(
        self, keys, words, mask, z_old, n_kd, n_wk, n_k, hyper,
        knobs: SamplerKnobs, aux=None, num_words_total=None,
    ):
        if aux is None:
            aux = self.prepare_infer(n_wk, n_k, hyper, knobs,
                                     num_words_total=num_words_total)
        return zen_cdf_infer_sweep(
            keys, words, mask, z_old, n_kd, n_wk, n_k, hyper,
            knobs.max_kd or DEFAULT_MAX_KD, aux,
        )
