"""``lightlda`` — LightLDA (Yuan et al.) cycle Metropolis-Hastings on the
shared substrate (paper §7.2). ``prepare`` builds the CSR doc->token index
that realizes the O(1) doc proposal.

Mesh-capable: ``cell_sweep`` rebuilds the doc->token index *inside* the
cell (an O(T log T) sort per iteration over the cell's tokens, masked
padding excluded), so the doc proposal draws from the doc's tokens within
this word shard, and its MH density is evaluated on the same cell-local
histogram (see ``lightlda_cell``) — a locality-restricted proposal with a
matching density, targeting the true conditional from the synced blocks.
The single-box sweep keeps the once-per-run prepared index.
"""
from __future__ import annotations

from repro.algorithms.base import CellBackend, SamplerKnobs, kernel_dispatch
from repro.algorithms.registry import register
from repro.core.baselines import (
    build_cell_doc_index,
    build_doc_index,
    lightlda_cell,
    lightlda_sweep,
)


@register("lightlda")
class LightLDA(CellBackend):
    """Alternating word/doc proposals, ``num_mh`` MH steps per token."""

    needs_doc_index = True
    needs_row_pads = True

    def prepare(self, corpus, hyper, knobs: SamplerKnobs):
        return build_doc_index(corpus)

    def sweep(self, state, corpus, hyper, knobs: SamplerKnobs, aux=None):
        # single-box keeps the prepared corpus-level index (static across
        # iterations; the cell path re-sorts per sweep because shard_map
        # hands it only the cell's token arrays)
        assert aux is not None, "lightlda needs prepare()'s doc index"
        return lightlda_sweep(
            state, corpus, hyper, aux, knobs.max_kw, num_mh=knobs.num_mh,
            use_kernel=kernel_dispatch(knobs.kernels),
            bt=knobs.bt, bs=knobs.bs,
        )

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        knobs = self.resolve_cell_knobs(knobs, hyper)
        doc_index = build_cell_doc_index(doc, mask, n_kd.shape[0])
        return lightlda_cell(
            key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
            num_words, doc_index, knobs.max_kw, num_mh=knobs.num_mh,
            use_kernel=kernel_dispatch(knobs.kernels),
            bt=knobs.bt, bs=knobs.bs,
        )
