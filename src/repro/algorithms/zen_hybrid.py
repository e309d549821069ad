"""``zen_hybrid`` — ZenLDAHybrid (paper §3.1): per-token pick the
decomposition whose fresh term ranges over the sparser row.

Realized as two-group dispatch over the *registry's own* ``zen_sparse``
(fresh term over K_d) and ``sparselda`` (fresh term over K_w) backends, so
measured work tracks min(K_d, K_w) and the hybrid automatically follows any
improvement to either constituent backend.

The switch is evaluated on the rows each constituent will *actually
sample*: the raw row nnz is clamped to the padded capacity the constituent
sparsifies at (``max_kd`` for the doc side, ``max_kw`` for the word side),
and — under ``shard_map`` — the nnz comes from the shard-local count
blocks, not any global density. A doc row with 100 live topics truncated
to a 16-wide pad costs 16, not 100, and the route must price it that way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.algorithms.base import CellBackend, SamplerKnobs
from repro.algorithms.registry import get, register


def hybrid_route_doc_side(
    n_wk: jax.Array,  # (Ws, K) the block the word side will sparsify
    n_kd: jax.Array,  # (Ds, K) the block the doc side will sparsify
    word: jax.Array,  # (T,)
    doc: jax.Array,  # (T,)
    max_kw: int,
    max_kd: int,
) -> jax.Array:
    """True where the doc-side decomposition (zen_sparse) samples the
    narrower *effective* row — nnz clamped to the constituent's padded
    capacity, computed on the exact count blocks the constituents get."""
    kd_eff = jnp.minimum(jnp.sum(n_kd > 0, axis=-1), max_kd)[doc]
    kw_eff = jnp.minimum(jnp.sum(n_wk > 0, axis=-1), max_kw)[word]
    return kd_eff <= kw_eff


@register("zen_hybrid")
class ZenHybrid(CellBackend):
    """Route each token to the sparser of the two decompositions."""

    needs_row_pads = True

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        knobs = self.resolve_cell_knobs(knobs, hyper)
        use_zen = hybrid_route_doc_side(
            n_wk, n_kd, word, doc, knobs.max_kw, knobs.max_kd
        )
        z_zen = get("zen_sparse").cell_sweep(
            key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
            num_words, knobs,
        )
        z_alt = get("sparselda").cell_sweep(
            key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
            num_words, knobs,
        )
        return jnp.where(use_zen, z_zen, z_alt)
