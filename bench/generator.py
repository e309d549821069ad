"""The one LDA corpus generator of the benchmark, driven by data.

Every workload draws its documents here, from a configuration file
(``bench/configs/<name>.json``: vocabulary, topics, documents, average
length) and a traffic file (``bench/traffic/<name>.json``: length clip,
document topic count, request counts and arrivals). Nothing is read from
disk or the network; the seed decides everything.

The model behind the words keeps the Zipf marginal of real vocabularies
exactly and gives the topics structure to recover:

* word ``w`` of Zipf rank ``r`` has marginal ``q_w ~ r^-s``;
* each word has a home topic ``h(w)``; ``p(k | w) = rho [h(w) = k] +
  (1 - rho) / K``, so topic ``k`` has mass ``pi_k = rho Q_k + (1-rho)/K``
  (``Q_k`` the marginal mass of its home words) and word distribution
  ``phi_k(w) = q_w p(k | w) / pi_k``;
* a document mixes ``doc_topics`` topics drawn from ``pi`` with Dirichlet
  weights, so the corpus-wide topic mixture is ``pi`` and the word marginal
  stays ``q``.

A word is drawn from ``phi_k`` as a two-part mixture: from the home words
of ``k`` (inverse CDF over their ``q``) with probability ``rho Q_k /
pi_k``, else from ``q`` itself. Everything is one jitted call on the
device: no ``(K, W)`` matrix is ever formed for the corpus.

The multiset of document lengths (lognormal quantiles, clipped) and of
request gaps (exponential quantiles) is the same for every seed; the seed
only permutes them, so every seed does the same amount of work and
compiles the same shapes.
"""
from __future__ import annotations

import functools
import math
import statistics
from typing import Dict, NamedTuple

import numpy as np

# Zipf exponent of the vocabulary and home-topic share of a word's topic
# distribution. The exponent is the one the repository's synthetic corpora
# use; rho = 0.5 leaves half of each word's mass spread over all topics.
ZIPF_S = 1.2
RHO = 0.5
# A fixed key for the word-id permutation: ids are not in frequency order
# (as in a real vocabulary file), and the order is the same for every seed.
ID_PERM_KEY = 20151102


class Model(NamedTuple):
    """Device arrays of the generating model (all from the seed)."""

    q: object  # (W,) f32 word marginal, indexed by word id
    home: object  # (W,) i32 home topic of each word id
    pi: object  # (K,) f32 corpus topic mixture


def length_multiset(n: int, mean: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    """``n`` lengths at the lognormal quantiles ``(i + 0.5) / n``, with the
    given mean before clipping, clipped to ``[lo, hi]``, ascending."""
    nd = statistics.NormalDist()
    mu = math.log(mean) - sigma * sigma / 2
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(np.exp(mu + sigma * z)), lo, hi).astype(np.int64)


def gap_multiset(n: int, rate: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (seconds) at the quantiles
    ``(i + 0.5) / n`` of a Poisson process of ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def slice_ranks(cfg: Dict) -> np.ndarray:
    """Zipf ranks (1-based, in the full published vocabulary) of the words
    this chip holds: every ``vocab_stride``-th rank, ``num_words`` of them."""
    stride = int(cfg.get("vocab_stride", 1))
    return np.arange(cfg["num_words"], dtype=np.float64) * stride + 1


def slice_token_share(cfg: Dict) -> float:
    """Share of the full vocabulary's Zipf mass that the slice holds."""
    stride = int(cfg.get("vocab_stride", 1))
    full = np.arange(1, cfg["num_words"] * stride + 1, dtype=np.float64)
    return float(np.sum(slice_ranks(cfg) ** -ZIPF_S) / np.sum(full ** -ZIPF_S))


def doc_length_mean(cfg: Dict) -> float:
    """Mean tokens of a document on this chip: the published average times
    the slice's token share (1 for an unsliced vocabulary)."""
    if int(cfg.get("vocab_stride", 1)) == 1:
        return float(cfg["avg_doc_len"])
    return float(cfg["avg_doc_len"]) * slice_token_share(cfg)


def _word_marginal(cfg: Dict) -> np.ndarray:
    """(W,) Zipf marginal indexed by word id (ids permuted by a fixed key)."""
    q = slice_ranks(cfg) ** -ZIPF_S
    q /= q.sum()
    order = np.random.default_rng(ID_PERM_KEY).permutation(q.shape[0])
    out = np.empty_like(q)
    out[order] = q  # rank i gets id order[i]
    return out.astype(np.float32)


def make_model(key, cfg: Dict) -> Model:
    import jax
    import jax.numpy as jnp

    k = int(cfg["num_topics"])
    q = jnp.asarray(_word_marginal(cfg))
    home = jax.random.randint(key, q.shape, 0, k, dtype=jnp.int32)
    mass = jnp.zeros((k,), jnp.float32).at[home].add(q)
    pi = RHO * mass + (1.0 - RHO) / k
    return Model(q=q, home=home, pi=pi / jnp.sum(pi))


@functools.partial(__import__("jax").jit,
                   static_argnames=("num_topics", "doc_topics", "total"))
def _draw_tokens(key, q, home, pi, lengths, *, num_topics: int,
                 doc_topics: int, total: int):
    """(word, doc) ids of ``total`` tokens for documents of ``lengths``."""
    import jax
    import jax.numpy as jnp

    k = num_topics
    d = lengths.shape[0]
    kt, kw, ku, kb, kh = jax.random.split(key, 5)
    # documents: doc_topics topics each, drawn from pi, Dirichlet(1) weights
    cum_pi = jnp.cumsum(pi)
    u = jax.random.uniform(kt, (d, doc_topics), jnp.float32)
    topics = jnp.minimum(jnp.searchsorted(cum_pi, u * cum_pi[-1]), k - 1)
    weights = jax.random.gamma(kw, jnp.ones((d, doc_topics), jnp.float32))
    cum_w = jnp.cumsum(weights, axis=1)
    doc = jnp.repeat(jnp.arange(d, dtype=jnp.int32), lengths,
                     total_repeat_length=total)
    # token topic: one of its document's topics, by weight
    ut = jax.random.uniform(ku, (total,), jnp.float32)
    slot = jnp.sum(cum_w[doc] < (ut * cum_w[doc, -1])[:, None], axis=1)
    z = topics[doc, jnp.minimum(slot, doc_topics - 1)]
    # home words grouped by topic, for the home part of the mixture
    order = jnp.argsort(home)
    cum_home = jnp.cumsum(q[order])
    starts = jnp.searchsorted(home[order], jnp.arange(k + 1, dtype=jnp.int32))
    lo = jnp.where(starts[:-1] > 0, cum_home[starts[:-1] - 1], 0.0)
    hi = jnp.where(starts[1:] > 0, cum_home[starts[1:] - 1], 0.0)
    mass = hi - lo
    share = RHO * mass / pi  # probability of a home draw, per topic
    ub, uh = jax.random.uniform(kb, (2, total), jnp.float32)
    from_home = (ub < share[z]) & (mass[z] > 0)
    hidx = jnp.searchsorted(cum_home, lo[z] + uh * mass[z], side="right")
    hidx = jnp.clip(hidx, starts[z], jnp.maximum(starts[z + 1] - 1, starts[z]))
    home_word = order[jnp.minimum(hidx, q.shape[0] - 1)]
    cum_q = jnp.cumsum(q)
    bg = jax.random.uniform(kh, (total,), jnp.float32) * cum_q[-1]
    bg_word = jnp.minimum(jnp.searchsorted(cum_q, bg, side="right"),
                          q.shape[0] - 1)
    word = jnp.where(from_home, home_word, bg_word).astype(jnp.int32)
    return word, doc


def corpus(seed: int, cfg: Dict, num_docs: int, lengths_sorted: np.ndarray,
           doc_topics: int):
    """(word, doc, lengths, model) for ``num_docs`` documents whose lengths
    are ``lengths_sorted`` in a seeded order. Arrays stay on the device."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    k_model, k_perm, k_tok = jax.random.split(key, 3)
    model = make_model(k_model, cfg)
    perm = np.asarray(jax.random.permutation(k_perm, num_docs))
    lengths = lengths_sorted[perm]
    total = int(lengths.sum())
    word, doc = _draw_tokens(
        k_tok, model.q, model.home, model.pi,
        jnp.asarray(lengths, jnp.int32), num_topics=int(cfg["num_topics"]),
        doc_topics=doc_topics, total=total,
    )
    return word, doc, lengths, model


@functools.partial(__import__("jax").jit,
                   static_argnames=("num_topics", "total_tokens"))
def ground_truth_counts(key, q, home, *, num_topics: int, total_tokens: int):
    """(N_wk, N_k) that a corpus of ``total_tokens`` tokens drawn from the
    model holds at its generating assignment: the expected counts
    ``total_tokens * q_w * p(k | w)``, stochastically rounded. This is the
    frozen model that serving cells serve, drawn directly (no training)."""
    import jax
    import jax.numpy as jnp

    k = num_topics
    spread = total_tokens * q[:, None] * ((1.0 - RHO) / k)
    onehot = jax.nn.one_hot(home, k, dtype=jnp.float32)
    mean = spread + onehot * (RHO * total_tokens * q)[:, None]
    u = jax.random.uniform(key, mean.shape, jnp.float32)
    n_wk = jnp.floor(mean + u).astype(jnp.int32)
    return n_wk, jnp.sum(n_wk, axis=0)
