"""``sparselda`` — SparseLDA (Yao et al.) on the shared substrate (paper
§7.2): s/r/q three-bucket decomposition with linear search, fresh counts.

Mesh-capable: a ``CellBackend`` whose s/r/q rows are sparsified from the
shard-local count blocks, so the same pass runs per mesh cell under
``shard_map`` and over the whole corpus single-box.
"""
from __future__ import annotations

from repro.algorithms.base import CellBackend, SamplerKnobs, kernel_dispatch
from repro.algorithms.registry import register
from repro.core.baselines import sparselda_cell


@register("sparselda")
class SparseLDA(CellBackend):
    """s/r/q bucket sampler; work/token tracks O(K_d + K_w)."""

    needs_row_pads = True

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        knobs = self.resolve_cell_knobs(knobs, hyper)
        return sparselda_cell(
            key, word, doc, z_old, n_wk, n_kd, n_k, hyper, num_words,
            knobs.max_kw, knobs.max_kd,
            use_kernel=kernel_dispatch(knobs.kernels),
            bt=knobs.bt, bs=knobs.bs,
        )
