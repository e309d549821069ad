"""Dense (T, K) three-term backends: ``zen`` (+ ``zen_dense`` alias) and
``std``.

The zen cell sweep is the distributed runtime's hillclimb baseline (moved
here from ``core.distributed``): per-token dense probabilities with exact
¬dw self-exclusion, sampled by Gumbel-max or inverse CDF. Simple;
memory-bound at large K (the gathered rows dominate HBM traffic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.algorithms.base import (
    CellBackend,
    SamplerBackend,
    SamplerKnobs,
    chunked_token_map,
)
from repro.algorithms.registry import register
from repro.core.sampler import cgs_sweep_stale


def _searchsorted_rows(cdf: jax.Array, targets: jax.Array) -> jax.Array:
    """Row-wise lower bound: cdf (T, N) ascending, targets (T,) -> (T,)."""
    return jnp.minimum(
        jnp.sum(cdf < targets[:, None], axis=-1), cdf.shape[-1] - 1
    ).astype(jnp.int32)


def zen_dense_cell(
    key, word_l, doc_l, z_old, mask, n_wk_l, n_kd_l, n_k, hyper,
    num_words: int, method: str, token_chunk: int,
):
    """Dense per-token (T, K) three-term probabilities; exact ¬dw."""
    k = hyper.num_topics

    def chunk(args):
        w, d, z, subkey = args
        onehot = jax.nn.one_hot(z, k, dtype=jnp.int32)
        nw = (n_wk_l[w] - onehot).astype(jnp.float32)
        nd = (n_kd_l[d] - onehot).astype(jnp.float32)
        nk = (n_k[None, :] - onehot).astype(jnp.float32)
        alpha_k = hyper.alpha_k(n_k)[None, :]
        w_beta = num_words * hyper.beta
        t1 = 1.0 / (nk + w_beta)
        p = (alpha_k * hyper.beta + nw * alpha_k + nd * (nw + hyper.beta)) * t1
        if method == "gumbel":
            g = jax.random.gumbel(subkey, p.shape, dtype=jnp.float32)
            return jnp.argmax(jnp.log(jnp.maximum(p, 1e-30)) + g, -1).astype(jnp.int32)
        cdf = jnp.cumsum(p, axis=-1)
        u = jax.random.uniform(subkey, (p.shape[0], 1)) * cdf[:, -1:]
        return _searchsorted_rows(cdf, u[:, 0])

    return chunked_token_map(chunk, key, (word_l, doc_l, z_old), token_chunk)


@register("zen", "zen_dense")
class ZenDense(CellBackend):
    """ZenLDA three-term decomposition over dense rows (paper Eq. 3)."""

    decomposition = "zen"

    def sweep(self, state, corpus, hyper, knobs: SamplerKnobs, aux=None):
        # single-box path keeps the oracle sweep (identical math; preserves
        # the reference RNG stream used by the statistical tests)
        return cgs_sweep_stale(
            state, corpus, hyper, method=knobs.sampling_method,
            decomposition=self.decomposition,
            token_chunk=knobs.chunk_or_none(),
        )

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        return zen_dense_cell(
            key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
            num_words, knobs.sampling_method, knobs.token_chunk,
        )


@register("std")
class StdDense(SamplerBackend):
    """Textbook (non-decomposed) Eq. 3 conditional — dense, single-box."""

    def sweep(self, state, corpus, hyper, knobs: SamplerKnobs, aux=None):
        return cgs_sweep_stale(
            state, corpus, hyper, method=knobs.sampling_method,
            decomposition="std", token_chunk=knobs.chunk_or_none(),
        )
