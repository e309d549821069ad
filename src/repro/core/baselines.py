"""Baseline CGS algorithms implemented in the same framework (paper §7.2).

The paper's generality claim is that switching the CGS algorithm is "a few
lines of code change" on the shared substrate: both baselines below consume
the same counts/corpus state and return new per-token topics, so the
iteration driver, distribution, exclusion, metrics, etc. are shared.

* SparseLDA (Yao et al.) — s/r/q three-bucket decomposition with linear
  search; fresh counts (exact ¬dw on the gathered values).
* LightLDA (Yuan et al.) — cycle Metropolis-Hastings alternating the word
  proposal (N_wk+β)/(N_k+Wβ) (alias, stale) and the doc proposal N_kd+α
  (O(1) via a random token of the same doc — the paper's lookup-table trick).

Both use iteration-start (stale) counts, matching how the paper runs them
distributed ("the only difference is the algorithm").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.alias import AliasTable, build_alias, sample_alias
from repro.core.decompositions import precompute_zen_terms
from repro.core.types import CGSState, Corpus, LDAHyperParams
from repro.kernels.ref import sparse_row_sample_ref
from repro.core.zen_sparse import SparseRows, lookup_rows, sparsify_rows


# ---------------------------------------------------------------------------
# SparseLDA
# ---------------------------------------------------------------------------

def sparselda_cell(
    key: jax.Array,
    word: jax.Array,  # (T,) shard-local word ids
    doc: jax.Array,  # (T,) shard-local doc ids
    z_old: jax.Array,  # (T,)
    n_wk: jax.Array,  # (Ws, K) local block
    n_kd: jax.Array,  # (Ds, K) local block
    n_k: jax.Array,  # (K,) replicated
    hyper: LDAHyperParams,
    num_words: int,  # global (padded) vocabulary — the W in W*beta
    max_kw: int,
    max_kd: int,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """One SparseLDA pass over a cell's tokens (stale counts, exact
    self-exclusion on the gathered values) -> (T,). Shard-relative: the
    padded s/r/q rows are sparsified from the local count blocks only.

    ``use_kernel`` routes the r/q bucket inversions through the
    padded-sparse Pallas kernel (``kernels.sparse_row``), whose op
    sequence matches the XLA form below exactly — dispatch is
    bit-identical. The shared dense s bucket stays on XLA (one (K,) CDF
    for the whole sweep; nothing to fuse)."""
    terms = precompute_zen_terms(n_k, hyper, num_words)
    kd_rows = sparsify_rows(n_kd, max_kd)
    wk_rows = sparsify_rows(n_wk, max_kw)
    w, d, z = word, doc, z_old
    k = hyper.num_topics
    beta = hyper.beta

    t1 = jnp.concatenate([terms.t1, jnp.zeros((1,), jnp.float32)])
    t5 = jnp.concatenate([terms.t5, jnp.zeros((1,), jnp.float32)])
    t4 = jnp.concatenate([terms.t4, jnp.zeros((1,), jnp.float32)])
    alpha_pad = jnp.concatenate([terms.alpha_k, jnp.zeros((1,), jnp.float32)])

    # --- bucket s: alpha_k*beta*t1, dense over K (shared by all tokens) ---
    s_vals = terms.g_dense  # (K,)
    s_mass = jnp.sum(s_vals)

    # --- bucket r: N_kd*beta*t1 over the doc's padded slots (self-excl) ---
    kd_idx = kd_rows.idx[d]  # (T, max_kd)
    kd_cnt = kd_rows.cnt[d]
    self_kd = (kd_idx == z[:, None]).astype(jnp.int32)
    kd_cnt_x = kd_cnt - self_kd
    r_vals = kd_cnt_x.astype(jnp.float32) * t5[kd_idx]
    r_mass = jnp.sum(r_vals, axis=-1)

    # --- bucket q: N_wk*(N_kd+alpha_k)*t1 over the word's padded slots ---
    wk_idx = wk_rows.idx[w]  # (T, max_kw)
    wk_cnt = wk_rows.cnt[w]
    self_wk = (wk_idx == z[:, None]).astype(jnp.int32)
    wk_cnt_x = wk_cnt - self_wk
    n_kd_at = lookup_rows(kd_rows, d, wk_idx)
    n_kd_at = n_kd_at - (wk_idx == z[:, None]).astype(jnp.int32)
    q_coef = n_kd_at.astype(jnp.float32) * t1[wk_idx] + t4[wk_idx]
    q_vals = wk_cnt_x.astype(jnp.float32) * q_coef
    q_mass = jnp.sum(q_vals, axis=-1)

    total = s_mass + r_mass + q_mass
    k_u, k_s = jax.random.split(key)
    u = jax.random.uniform(k_u, w.shape) * total

    # LSearch within each bucket (vectorized as CDF + count; complexity
    # modeled as O(K)/O(K_d)/O(K_w) per Table 1).
    s_cdf = jnp.cumsum(s_vals)
    z_s = jnp.minimum(jnp.sum(s_cdf[None, :] < u[:, None], axis=-1), k - 1)

    r_target = jnp.maximum(u - s_mass, 0.0)
    q_target = jnp.maximum(u - s_mass - r_mass, 0.0)
    if use_kernel:
        from repro.kernels.ops import sparse_row_sample

        z_r = sparse_row_sample(r_vals, kd_idx, r_target, bt=bt, bs=bs)
        z_q = sparse_row_sample(q_vals, wk_idx, q_target, bt=bt, bs=bs)
    else:
        z_r = sparse_row_sample_ref(r_vals, kd_idx, r_target)
        z_q = sparse_row_sample_ref(q_vals, wk_idx, q_target)

    z_new = jnp.where(
        u < s_mass, z_s, jnp.where(u < s_mass + r_mass, z_r, z_q)
    )
    return jnp.minimum(z_new, k - 1).astype(jnp.int32)


def sparselda_sweep(
    state: CGSState,
    corpus: Corpus,
    hyper: LDAHyperParams,
    max_kw: int,
    max_kd: int,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """One SparseLDA sweep (stale counts, exact self-exclusion). -> (E,)."""
    key = jax.random.fold_in(state.rng, state.iteration)
    return sparselda_cell(
        key, corpus.word, corpus.doc, state.topic,
        state.n_wk, state.n_kd, state.n_k, hyper, corpus.num_words,
        max_kw, max_kd, use_kernel=use_kernel, bt=bt, bs=bs,
    )


# ---------------------------------------------------------------------------
# LightLDA
# ---------------------------------------------------------------------------

class DocIndex(NamedTuple):
    """CSR doc->token index for the O(1) doc proposal (LightLDA's lookup
    table: 'stores the corresponding topic for its word occurrences')."""

    token_of: jax.Array  # (E,) token ids sorted by doc
    offsets: jax.Array  # (D+1,) start of each doc's slice in token_of
    lengths: jax.Array  # (D,)


def build_doc_index(corpus: Corpus) -> DocIndex:
    return build_cell_doc_index(
        corpus.doc, jnp.ones(corpus.doc.shape, bool), corpus.num_docs
    )


def build_cell_doc_index(
    doc: jax.Array, mask: jax.Array, num_docs: int
) -> DocIndex:
    """Trace-compatible ``DocIndex`` over one cell's (possibly padded)
    tokens: masked-out tokens sort to the end behind a sentinel doc id and
    contribute no length, so a doc's slice holds only its live local
    tokens. With an all-true mask this reproduces ``build_doc_index``."""
    sort_key = jnp.where(mask, doc, num_docs)
    order = jnp.argsort(sort_key, stable=True).astype(jnp.int32)
    lengths = (
        jnp.zeros((num_docs,), jnp.int32).at[doc].add(mask.astype(jnp.int32))
    )
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(lengths).astype(jnp.int32)]
    )
    return DocIndex(token_of=order, offsets=offsets, lengths=lengths)


def _true_prob(
    n_wk_m, n_kd_m, n_k_v, w, d, z_self, ks, hyper: LDAHyperParams,
    num_words: int,
):
    """Exact Eq. 3 p(k) at candidate topics ks (T,) with ¬dw exclusion."""
    self_hit = (ks == z_self).astype(jnp.float32)
    n_wk = n_wk_m[w, ks].astype(jnp.float32) - self_hit
    n_kd = n_kd_m[d, ks].astype(jnp.float32) - self_hit
    n_k = n_k_v[ks].astype(jnp.float32) - self_hit
    alpha_k = hyper.alpha_k(n_k_v)[ks]
    return (
        (n_wk + hyper.beta) / (n_k + num_words * hyper.beta) * (n_kd + alpha_k)
    )


def lightlda_cell(
    key: jax.Array,
    word: jax.Array,  # (T,) shard-local word ids
    doc: jax.Array,  # (T,) shard-local doc ids
    z_old: jax.Array,  # (T,)
    mask: jax.Array,  # (T,) bool — False on cell padding
    n_wk: jax.Array,  # (Ws, K) local block
    n_kd: jax.Array,  # (Ds, K) local block
    n_k: jax.Array,  # (K,) replicated
    hyper: LDAHyperParams,
    num_words: int,  # global (padded) vocabulary — the W in W*beta
    doc_index: DocIndex,  # over THIS cell's tokens (shard-local doc ids)
    max_kw: int,
    num_mh: int = 8,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """One LightLDA pass over a cell's tokens: ``num_mh`` cycle-MH steps
    per token -> (T,).

    ``use_kernel`` replaces the word proposal's sparse-branch *alias*
    draw with CDF inversion through the padded-sparse Pallas kernel
    (``kernels.sparse_row``) over the same ``N_wk * t1`` density — and
    skips building the per-word alias tables entirely. The proposal
    distribution is unchanged (alias and CDF inversion sample the same
    pmf), so ``word_q`` still describes what was proposed and the MH
    chain stays valid; draws differ bitwise (different uniforms-to-topic
    mapping), matching the backend's statistical cross-path contract.

    Shard-relative: the word-proposal alias rows come from the local
    ``n_wk`` block, and the O(1) doc proposal draws from the doc's tokens
    *within this cell* (its word-shard slice). The proposal's MH density
    must describe what was actually proposed, so ``doc_q`` is evaluated on
    the cell-local doc-topic histogram of ``z_old`` — NOT the synced
    ``n_kd`` block, which counts tokens on other word shards the proposal
    can never draw. Acceptance targets the true conditional from the
    synced blocks, so the chain is a valid MH sampler of Eq. 3 with a
    locality-restricted proposal. Single-box (one cell, all tokens live)
    the histogram equals ``n_kd`` exactly and draws are unchanged.
    """
    k = hyper.num_topics
    beta = hyper.beta
    w, d = word, doc
    terms = precompute_zen_terms(n_k, hyper, num_words)
    alpha_bar = jnp.mean(terms.alpha_k)  # doc proposal uses symmetric alpha
    # the density the doc proposal actually samples from: this cell's live
    # (doc, topic) histogram (== n_kd when the cell is the whole corpus)
    n_kd_cell = (
        jnp.zeros(n_kd.shape, jnp.int32)
        .at[doc, z_old].add(mask.astype(jnp.int32))
    )

    # word proposal = mixture of sparse part N_wk*t1 (per-word alias) and
    # dense part beta*t1 (one global alias shared by every word).
    wk_rows = sparsify_rows(n_wk, max_kw)
    t1 = jnp.concatenate([terms.t1, jnp.zeros((1,), jnp.float32)])
    w_vals = wk_rows.cnt.astype(jnp.float32) * t1[wk_rows.idx]
    # kernel path draws the sparse branch by CDF inversion instead — the
    # per-word alias build (a vmapped O(max_kw) fixpoint per word) is the
    # single biggest table-build cost and is skipped entirely
    w_alias = None if use_kernel else jax.vmap(build_alias)(w_vals)
    w_sparse_mass = jnp.sum(w_vals, axis=-1)  # (W,)
    dense_tab = build_alias(terms.t5)
    dense_mass = jnp.sum(terms.t5)

    n_d = doc_index.lengths.astype(jnp.float32)

    def word_proposal(key, w_ids):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        m_s = w_sparse_mass[w_ids]
        pick_sparse = jax.random.uniform(k1, w_ids.shape) * (m_s + dense_mass) < m_s
        nbins = wk_rows.idx.shape[-1]
        u1 = jax.random.uniform(k2, w_ids.shape)
        u2 = jax.random.uniform(k3, w_ids.shape)
        if use_kernel:
            from repro.kernels.ops import sparse_row_sample

            z_sparse = sparse_row_sample(
                w_vals[w_ids], wk_rows.idx[w_ids], u1 * m_s, bt=bt, bs=bs
            )
        else:
            bins = jnp.minimum((u1 * nbins).astype(jnp.int32), nbins - 1)
            probs = jnp.take_along_axis(w_alias.prob[w_ids], bins[:, None], -1)[:, 0]
            aliases = jnp.take_along_axis(w_alias.alias[w_ids], bins[:, None], -1)[:, 0]
            slot = jnp.where(u2 < probs, bins, aliases)
            z_sparse = jnp.take_along_axis(
                wk_rows.idx[w_ids], slot[:, None], -1
            )[:, 0]
        z_dense = sample_alias(
            dense_tab, jax.random.uniform(k4, w_ids.shape),
            jax.random.uniform(jax.random.fold_in(k4, 1), w_ids.shape),
        )
        z = jnp.where(pick_sparse, z_sparse, z_dense)
        return jnp.minimum(z, k - 1).astype(jnp.int32)

    def word_q(w_ids, ks, z_self):
        """q_w(k) ∝ (N_wk + beta) * t1[k], with self-exclusion skipped —
        LightLDA proposals are stale by construction."""
        return (n_wk[w_ids, ks].astype(jnp.float32) + beta) * terms.t1[ks]

    def doc_proposal(key, d_ids):
        k1, k2, k3 = jax.random.split(key, 3)
        mass_doc = n_d[d_ids]
        pick_doc = (
            jax.random.uniform(k1, d_ids.shape) * (mass_doc + k * alpha_bar)
            < mass_doc
        )
        # O(1): topic of a uniformly random token of the same doc
        u = jax.random.uniform(k2, d_ids.shape)
        tok = doc_index.offsets[d_ids] + jnp.minimum(
            (u * jnp.maximum(mass_doc, 1.0)).astype(jnp.int32),
            jnp.maximum(doc_index.lengths[d_ids] - 1, 0),
        )
        z_doc = z_old[doc_index.token_of[tok]]
        z_unif = jax.random.randint(k3, d_ids.shape, 0, k, dtype=jnp.int32)
        return jnp.where(pick_doc, z_doc, z_unif)

    def doc_q(d_ids, ks):
        return n_kd_cell[d_ids, ks].astype(jnp.float32) + alpha_bar

    z0 = z_old

    def mh_step(i, carry):
        z_cur, key = carry
        key, k_prop, k_acc = jax.random.split(key, 3)
        use_word = (i % 2) == 0  # cycle proposal: word, doc, word, doc ...

        z_w = word_proposal(k_prop, w)
        z_d = doc_proposal(k_prop, d)
        z_new = jnp.where(use_word, z_w, z_d)

        p_new = _true_prob(n_wk, n_kd, n_k, w, d, z0, z_new, hyper, num_words)
        p_old = _true_prob(n_wk, n_kd, n_k, w, d, z0, z_cur, hyper, num_words)
        q_new = jnp.where(use_word, word_q(w, z_new, z0), doc_q(d, z_new))
        q_old = jnp.where(use_word, word_q(w, z_cur, z0), doc_q(d, z_cur))
        ratio = (p_new * q_old) / jnp.maximum(p_old * q_new, 1e-30)
        accept = jax.random.uniform(k_acc, z_cur.shape) < jnp.minimum(ratio, 1.0)
        return jnp.where(accept, z_new, z_cur), key

    z, _ = jax.lax.fori_loop(0, num_mh, mh_step, (z0, key))
    return z.astype(jnp.int32)


def lightlda_sweep(
    state: CGSState,
    corpus: Corpus,
    hyper: LDAHyperParams,
    doc_index: DocIndex,
    max_kw: int,
    num_mh: int = 8,
    use_kernel: bool = False,
    bt: int = 256,
    bs: int = 128,
) -> jax.Array:
    """One LightLDA sweep: ``num_mh`` cycle-MH steps per token. -> (E,)."""
    key = jax.random.fold_in(state.rng, state.iteration)
    mask = jnp.ones(corpus.word.shape, bool)
    return lightlda_cell(
        key, corpus.word, corpus.doc, state.topic, mask,
        state.n_wk, state.n_kd, state.n_k, hyper, corpus.num_words,
        doc_index, max_kw, num_mh=num_mh,
        use_kernel=use_kernel, bt=bt, bs=bs,
    )
