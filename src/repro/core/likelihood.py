"""Model-quality metrics: log-likelihood (total/word/doc split) and perplexity.

Two likelihoods are implemented:

* ``predictive_llh`` — the formula the paper states it uses (footnote 6):
      llh = sum_tokens log sum_k [(N_k|d + α_k)/(N_d + Kα̂)] ·
                               [(N_w|k + β)/(N_k + Wβ)]
  used for the Fig. 3/4 comparisons and for perplexity.

* ``joint_llh`` — the standard collapsed joint p(w, z) split into its word
  part and doc part (paper Fig. 7 plots "word log-likelihood" and "doc
  log-likelihood" separately).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln

from repro.core.types import CGSState, Corpus, LDAHyperParams
from repro.core import counts as counts_lib


class LLH(NamedTuple):
    total: jax.Array
    word: jax.Array
    doc: jax.Array


def predictive_llh(
    state: CGSState, corpus: Corpus, hyper: LDAHyperParams,
    token_chunk: int | None = None,
) -> jax.Array:
    """Paper footnote-6 log-likelihood (a token-level predictive score)."""
    alpha_k = hyper.alpha_k(state.n_k)
    alpha_sum = jnp.sum(alpha_k)
    n_d = counts_lib.doc_lengths(corpus.doc, corpus.num_docs).astype(jnp.float32)
    w_beta = corpus.num_words * hyper.beta
    phi_denom = state.n_k.astype(jnp.float32) + w_beta  # (K,)

    def chunk(args):
        w, d = args
        theta = (state.n_kd[d].astype(jnp.float32) + alpha_k[None, :]) / (
            n_d[d][:, None] + alpha_sum
        )
        phi = (state.n_wk[w].astype(jnp.float32) + hyper.beta) / phi_denom[None, :]
        return jnp.log(jnp.maximum(jnp.sum(theta * phi, axis=-1), 1e-30))

    e = corpus.word.shape[0]
    if token_chunk is None or token_chunk >= e:
        return jnp.sum(chunk((corpus.word, corpus.doc)))
    n_chunks = -(-e // token_chunk)
    pad = n_chunks * token_chunk - e
    vals = jax.lax.map(
        chunk,
        tuple(jnp.pad(a, (0, pad)).reshape(n_chunks, -1)
              for a in (corpus.word, corpus.doc)),
    )
    if pad:  # the padded tail is id-0 tokens: drop their terms
        vals = vals.reshape(-1)[:e]
    return jnp.sum(vals)


def perplexity(
    state: CGSState, corpus: Corpus, hyper: LDAHyperParams,
    token_chunk: int | None = None,
) -> jax.Array:
    llh = predictive_llh(state, corpus, hyper, token_chunk=token_chunk)
    return jnp.exp(-llh / corpus.num_tokens)


def joint_llh(state: CGSState, corpus: Corpus, hyper: LDAHyperParams) -> LLH:
    """Collapsed joint log p(w, z | α, β) = word part + doc part."""
    k = hyper.num_topics
    w = corpus.num_words
    d = corpus.num_docs
    beta = hyper.beta
    alpha_k = hyper.alpha_k(state.n_k)
    alpha_sum = jnp.sum(alpha_k)
    n_d = counts_lib.doc_lengths(corpus.doc, corpus.num_docs).astype(jnp.float32)

    # word part: prod_k [Γ(Wβ)/Γ(N_k+Wβ)] * prod_w Γ(N_wk+β)/Γ(β)
    word_part = (
        k * gammaln(w * beta)
        - jnp.sum(gammaln(state.n_k.astype(jnp.float32) + w * beta))
        + jnp.sum(gammaln(state.n_wk.astype(jnp.float32) + beta))
        - k * w * gammaln(beta)
    )
    # doc part: prod_d [Γ(Σα)/Γ(N_d+Σα)] * prod_k Γ(N_kd+α_k)/Γ(α_k)
    doc_part = (
        d * gammaln(alpha_sum)
        - jnp.sum(gammaln(n_d + alpha_sum))
        + jnp.sum(gammaln(state.n_kd.astype(jnp.float32) + alpha_k[None, :]))
        - d * jnp.sum(gammaln(alpha_k))
    )
    return LLH(total=word_part + doc_part, word=word_part, doc=doc_part)
