"""``zen_sparse`` — the faithful padded-sparse ZenLDA sampler (paper Alg. 2)
behind the backend interface. The heavy lifting stays in
``core.zen_sparse``; this wrapper only adapts the contract.

Mesh-capable: the sampler is a ``CellBackend``, so the same padded-row
machinery runs per (word-shard x doc-shard) cell under ``shard_map`` —
tables are built from the *local* count blocks with shard-relative padded
capacities, and the single-box sweep is the whole corpus as one cell.
"""
from __future__ import annotations

from repro.algorithms.base import CellBackend, SamplerKnobs, kernel_dispatch
from repro.algorithms.registry import register
from repro.core.zen_sparse import zen_sparse_cell


@register("zen_sparse")
class ZenSparse(CellBackend):
    """Alias tables + padded-sparse rows; work/token tracks O(K_d)."""

    needs_row_pads = True

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        knobs = self.resolve_cell_knobs(knobs, hyper)
        return zen_sparse_cell(
            key, word, doc, z_old, n_wk, n_kd, n_k, hyper, num_words,
            knobs.max_kw, knobs.max_kd,
            use_kernel=kernel_dispatch(knobs.kernels),
            bt=knobs.bt, bs=knobs.bs,
        )
