"""Core dataclasses for ZenLDA: hyper-parameters, corpus, and sampler state.

The CGS Markov state is exactly ``(topic assignments, rng)`` — all count
matrices are derived — which is what makes checkpointing and elastic
re-sharding cheap (see ``repro.train.checkpoint``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LDAHyperParams:
    """Hyper-parameters of the (asymmetric-prior) LDA model, paper Eq. 3."""

    num_topics: int
    alpha: float = 0.01
    beta: float = 0.01
    # Wallach-style asymmetric document-topic prior strength (paper's alpha').
    alpha_prime: float = 1.0
    # Use the asymmetric alpha_k = K*alpha*(N_k + alpha'/K)/(N + alpha')
    # approximation.  If False, alpha_k == alpha (symmetric).
    asymmetric_alpha: bool = True

    def alpha_k(self, n_k: jax.Array) -> jax.Array:
        """Per-topic alpha_k from the asymmetric prior (paper Alg. 5, t2/t4)."""
        if not self.asymmetric_alpha:
            return jnp.full(self.num_topics, self.alpha, dtype=jnp.float32)
        n_k = n_k.astype(jnp.float32)
        n_total = jnp.sum(n_k)
        k = float(self.num_topics)
        return (k * self.alpha) * (n_k + self.alpha_prime / k) / (
            n_total + self.alpha_prime
        )


@dataclasses.dataclass(frozen=True)
class Corpus:
    """A token-level (edge list) corpus.

    One row per token occurrence; this is the flattened form of the paper's
    bipartite graph where an edge (w, d) carries an *array* of topic slots
    (one per occurrence).
    """

    word: jax.Array  # (E,) int32 word id per token
    doc: jax.Array  # (E,) int32 doc id per token
    num_words: int  # W
    num_docs: int  # D

    @property
    def num_tokens(self) -> int:
        return int(self.word.shape[0])

    def validate(self) -> None:
        assert self.word.shape == self.doc.shape
        assert self.word.dtype == jnp.int32 and self.doc.dtype == jnp.int32


@dataclasses.dataclass
class CGSState:
    """Full sampler state: assignments + derived counts + RNG.

    ``topic`` is the per-token topic assignment z_dw (edge attribute).
    ``prev_topic`` is the assignment from the previous iteration — needed by
    delta aggregation (paper §5.2: "requires to store the old topic sampled
    last time ... doubles the attribute size in edge").
    ``stale_iters``/``same_count`` drive "converged" token exclusion (§5.1):
    i = iterations not processed, t = times processed with unchanged topic.
    """

    topic: jax.Array  # (E,) int32
    prev_topic: jax.Array  # (E,) int32
    n_wk: jax.Array  # (W, K) int32
    n_kd: jax.Array  # (D, K) int32
    n_k: jax.Array  # (K,) int32
    rng: jax.Array
    iteration: int = 0
    stale_iters: Optional[jax.Array] = None  # (E,) int32, token-exclusion "i"
    same_count: Optional[jax.Array] = None  # (E,) int32, token-exclusion "t"

    def check_invariants(self, corpus: Corpus) -> None:
        """Count-conservation invariants (used by property tests)."""
        import numpy as np

        n_wk = np.asarray(self.n_wk)
        n_kd = np.asarray(self.n_kd)
        n_k = np.asarray(self.n_k)
        assert n_wk.sum() == corpus.num_tokens
        assert n_kd.sum() == corpus.num_tokens
        assert n_k.sum() == corpus.num_tokens
        np.testing.assert_array_equal(n_wk.sum(axis=0), n_k)
        np.testing.assert_array_equal(n_kd.sum(axis=0), n_k)
        assert (n_wk >= 0).all() and (n_kd >= 0).all() and (n_k >= 0).all()


# a pytree, so a whole training step can be one jitted program
jax.tree_util.register_dataclass(
    CGSState,
    data_fields=[f.name for f in dataclasses.fields(CGSState)],
    meta_fields=[],
)
