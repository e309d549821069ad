"""Faithful ZenLDA sampler on padded-sparse topic rows (paper Alg. 2).

This is the paper's algorithm with its CPU sparse structures adapted to
fixed shapes (DESIGN.md §2): doc-topic and word-topic rows are stored as
``(idx, cnt)`` pairs padded to a static max-nnz, so K_d / K_w cost shows up
as the padded row width — work per token is O(max_kd) (resp. O(max_kw) for
the hybrid's alternate branch), not O(K).

Per iteration (Alg. 2 structure):
  lines 3-6   gDense = alpha_k*beta/(N_k+W*beta)        -> gTable (alias)
  lines 7-11  wSparse[w] = N_w|k*alpha_k/(N_k+W*beta)   -> wTable (alias, per
              word, over the padded slots)               [stale, remedied]
  lines 12-16 dSparse = N_k|d*(N_w|k+beta)/(N_k+W*beta) -> CDF + binary
              search over the doc's padded slots         [fresh per (d,w)]
  line 18     two-level sample: pick the term by mass, then within the term
  remedy      if the draw equals the previous topic, resample once with the
              paper's per-term probability (§3.1).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.alias import AliasTable, build_alias, sample_alias
from repro.core.decompositions import ZenTerms, precompute_zen_terms
from repro.core.types import CGSState, Corpus, LDAHyperParams
from repro.kernels.ref import sparse_row_sample_ref


class SparseRows(NamedTuple):
    """Padded-sparse rows of a count matrix: row r = {(idx[r,j], cnt[r,j])}.

    ``idx`` is sorted ascending per row; empty slots hold idx == K (sentinel)
    and cnt == 0, so searchsorted lookups miss them naturally.
    """

    idx: jax.Array  # (R, max_nnz) int32
    cnt: jax.Array  # (R, max_nnz) int32
    num_topics: int

    @property
    def nnz(self) -> jax.Array:  # (R,)
        return jnp.sum(self.cnt > 0, axis=-1)


def sparsify_rows(dense: jax.Array, max_nnz: int) -> SparseRows:
    """Dense (R, K) -> padded-sparse. Rows with more than ``max_nnz``
    nonzeros would be truncated — callers assert via ``max_row_nnz``."""
    k = dense.shape[-1]
    # sort key: zeros last, then by topic id -> sorted nonzero prefix
    key = jnp.where(dense > 0, jnp.arange(k, dtype=jnp.int32)[None, :], k)
    order = jnp.argsort(key, axis=-1)[:, :max_nnz]
    idx = jnp.take_along_axis(key, order, axis=-1).astype(jnp.int32)
    cnt = jnp.take_along_axis(dense, order, axis=-1).astype(jnp.int32)
    cnt = jnp.where(idx < k, cnt, 0)
    return SparseRows(idx=idx, cnt=cnt, num_topics=k)


def max_row_nnz(dense: jax.Array) -> jax.Array:
    return jnp.max(jnp.sum(dense > 0, axis=-1))


def shard_row_capacity(dense_block: jax.Array, multiple: int = 8) -> int:
    """Padded-row capacity for one shard's count block (host-side).

    The capacity is computed from the rows the shard will actually
    sparsify — a lane-friendly round-up of the block's max row nnz, capped
    at K (a row can never hold more than K live topics, so any larger pad
    is pure waste). On a sharded global-view array the reduction runs
    shard-locally and only the scalar max crosses devices, so no shard ever
    gathers another shard's block.
    """
    k = dense_block.shape[-1]
    m = int(jax.device_get(max_row_nnz(dense_block)))
    m = max(multiple, ((m + multiple - 1) // multiple) * multiple)
    return min(m, k)


def densify_rows(rows: SparseRows) -> jax.Array:
    r = rows.idx.shape[0]
    out = jnp.zeros((r, rows.num_topics + 1), jnp.int32)
    out = out.at[jnp.arange(r)[:, None], rows.idx].add(rows.cnt)
    return out[:, : rows.num_topics]


def lookup_rows(rows: SparseRows, row_ids: jax.Array, topics: jax.Array) -> jax.Array:
    """cnt[row_ids, topics] via per-row binary search. Shapes broadcast:
    row_ids (T,), topics (T, J) -> (T, J)."""
    idx = rows.idx[row_ids]  # (T, max_nnz)
    cnt = rows.cnt[row_ids]
    pos = jax.vmap(jnp.searchsorted)(idx, topics)  # (T, J)
    pos = jnp.minimum(pos, idx.shape[-1] - 1)
    hit = jnp.take_along_axis(idx, pos, axis=-1) == topics
    val = jnp.take_along_axis(cnt, pos, axis=-1)
    return jnp.where(hit, val, 0)


class ZenTables(NamedTuple):
    """Per-iteration sampling state (the 'ship model state' payload)."""

    terms: ZenTerms
    g_table: AliasTable  # over K
    w_prob: jax.Array  # (W, max_kw) alias prob over padded slots
    w_alias: jax.Array  # (W, max_kw) alias target (slot index)
    w_mass: jax.Array  # (W,) total wSparse mass per word
    wk_rows: SparseRows
    kd_rows: SparseRows


def build_tables(
    n_wk: jax.Array,
    n_kd: jax.Array,
    n_k: jax.Array,
    hyper: LDAHyperParams,
    num_words: int,
    max_kw: int,
    max_kd: int,
) -> ZenTables:
    terms = precompute_zen_terms(n_k, hyper, num_words)
    g_table = build_alias(terms.g_dense)
    wk_rows = sparsify_rows(n_wk, max_kw)
    kd_rows = sparsify_rows(n_kd, max_kd)
    # wSparse over padded slots: cnt * t4[idx]; empty slots -> 0 mass.
    t4 = jnp.concatenate([terms.t4, jnp.zeros((1,), jnp.float32)])
    w_vals = wk_rows.cnt.astype(jnp.float32) * t4[wk_rows.idx]
    w_table = jax.vmap(build_alias)(w_vals)
    return ZenTables(
        terms=terms,
        g_table=g_table,
        w_prob=w_table.prob,
        w_alias=w_table.alias,
        w_mass=jnp.sum(w_vals, axis=-1),
        wk_rows=wk_rows,
        kd_rows=kd_rows,
    )


def _d_sparse(
    tables: ZenTables, word: jax.Array, doc: jax.Array, beta: float
) -> Tuple[jax.Array, jax.Array]:
    """dSparse values over the doc's padded slots. Returns (vals (T, max_kd),
    topics (T, max_kd))."""
    kd_idx = tables.kd_rows.idx[doc]  # (T, max_kd)
    kd_cnt = tables.kd_rows.cnt[doc]
    n_wk_at = lookup_rows(tables.wk_rows, word, kd_idx)  # (T, max_kd)
    t1 = jnp.concatenate([tables.terms.t1, jnp.zeros((1,), jnp.float32)])
    vals = (
        kd_cnt.astype(jnp.float32)
        * (n_wk_at.astype(jnp.float32) + beta)
        * t1[kd_idx]
    )
    vals = jnp.where(kd_cnt > 0, vals, 0.0)
    return vals, kd_idx


def zen_sample_tokens(
    key: jax.Array,
    tables: ZenTables,
    word: jax.Array,  # (T,)
    doc: jax.Array,  # (T,)
    prev_topic: jax.Array,  # (T,) z from last iteration (for the remedy)
    hyper: LDAHyperParams,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """Sample new topics for T tokens — the faithful two-level ZenLDA draw.

    ``use_kernel`` routes the term-3 dSparse inversion through the
    padded-sparse Pallas kernel (``kernels.sparse_row``); the XLA path is
    the kernel's bit-exact oracle, so dispatch is bit-identical."""

    def draw(key):
        k_u, k_g1, k_g2, k_w1, k_w2, k_d = jax.random.split(key, 6)
        d_vals, d_topics = _d_sparse(tables, word, doc, hyper.beta)
        m3 = jnp.sum(d_vals, axis=-1)
        m1 = tables.terms.g_mass
        m2 = tables.w_mass[word]
        total = m1 + m2 + m3
        u = jax.random.uniform(k_u, word.shape) * total

        # term 1: global alias table
        z_g = sample_alias(
            tables.g_table,
            jax.random.uniform(k_g1, word.shape),
            jax.random.uniform(k_g2, word.shape),
        )
        # term 2: per-word alias over padded slots -> topic id
        w_tab = AliasTable(prob=tables.w_prob[word], alias=tables.w_alias[word])
        slots = jnp.arange(tables.w_prob.shape[-1])
        u1 = jax.random.uniform(k_w1, word.shape)
        u2 = jax.random.uniform(k_w2, word.shape)
        nbins = tables.w_prob.shape[-1]
        bins = jnp.minimum((u1 * nbins).astype(jnp.int32), nbins - 1)
        keep = u2 < jnp.take_along_axis(w_tab.prob, bins[:, None], axis=-1)[:, 0]
        slot = jnp.where(
            keep, bins, jnp.take_along_axis(w_tab.alias, bins[:, None], axis=-1)[:, 0]
        )
        z_w = jnp.take_along_axis(
            tables.wk_rows.idx[word], slot[:, None], axis=-1
        )[:, 0]
        # term 3: CDF binary search over the doc's padded slots
        target = jnp.maximum(u - (m1 + m2), 0.0)
        if use_kernel:
            from repro.kernels.ops import sparse_row_sample

            z_d = sparse_row_sample(d_vals, d_topics, target, bt=bt, bs=bs)
        else:
            z_d = sparse_row_sample_ref(d_vals, d_topics, target)

        branch = jnp.where(u < m1, 0, jnp.where(u < m1 + m2, 1, 2))
        z = jnp.where(branch == 0, z_g, jnp.where(branch == 1, z_w, z_d))
        # guard: sentinel K can only appear from fully-padded rows
        z = jnp.minimum(z, hyper.num_topics - 1).astype(jnp.int32)
        return z, branch

    key_a, key_b, key_r = jax.random.split(key, 3)
    z1, branch1 = draw(key_a)
    z2, _ = draw(key_b)

    # Resampling remedy (§3.1): the stale tables did not exclude the token's
    # own previous assignment. If the draw equals prev_topic, redraw once
    # with the per-term probability.
    n_wk_prev = lookup_rows(tables.wk_rows, word, prev_topic[:, None])[:, 0]
    n_kd_prev = lookup_rows(tables.kd_rows, doc, prev_topic[:, None])[:, 0]
    nw = jnp.maximum(n_wk_prev.astype(jnp.float32), 1.0)
    nd = jnp.maximum(n_kd_prev.astype(jnp.float32), 1.0)
    p_w = 1.0 / nw  # wSparse remedy
    p_d = jnp.clip(1.0 / nd + (nd + nw - 1.0) / (nd * nw), 0.0, 1.0)  # dSparse
    remedy_p = jnp.where(branch1 == 1, p_w, jnp.where(branch1 == 2, p_d, 0.0))
    u_r = jax.random.uniform(key_r, z1.shape)
    take_second = (z1 == prev_topic) & (u_r < remedy_p)
    return jnp.where(take_second, z2, z1).astype(jnp.int32)


def zen_sparse_cell(
    key: jax.Array,
    word: jax.Array,  # (T,) shard-local word ids
    doc: jax.Array,  # (T,) shard-local doc ids
    z_old: jax.Array,  # (T,)
    n_wk: jax.Array,  # (Ws, K) local word-topic block
    n_kd: jax.Array,  # (Ds, K) local doc-topic block
    n_k: jax.Array,  # (K,) replicated
    hyper: LDAHyperParams,
    num_words: int,  # global (padded) vocabulary — the W in W*beta
    max_kw: int,
    max_kd: int,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """One faithful ZenLDA pass over a cell's tokens (stale counts) -> (T,).

    Everything is shard-relative: ids index the local count blocks, the
    padded-sparse tables are built from the local blocks only (widths are
    the *per-shard* capacities, see ``shard_row_capacity``), and only the
    replicated ``n_k``/``num_words`` carry global scale. The single-box
    sweep is this with the whole corpus as one cell.
    """
    tables = build_tables(n_wk, n_kd, n_k, hyper, num_words, max_kw, max_kd)
    return zen_sample_tokens(
        key, tables, word, doc, z_old, hyper,
        use_kernel=use_kernel, bt=bt, bs=bs,
    )


def zen_sparse_sweep(
    state: CGSState,
    corpus: Corpus,
    hyper: LDAHyperParams,
    max_kw: int,
    max_kd: int,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """One faithful ZenLDA sweep over all tokens (stale counts). -> (E,)."""
    key = jax.random.fold_in(state.rng, state.iteration)
    return zen_sparse_cell(
        key, corpus.word, corpus.doc, state.topic,
        state.n_wk, state.n_kd, state.n_k, hyper, corpus.num_words,
        max_kw, max_kd, use_kernel=use_kernel, bt=bt, bs=bs,
    )
