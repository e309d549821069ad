"""Pallas TPU kernels for the paper's compute hot spots.

Kernel suite v1 (PR 1):

* ``zen_sampler``     — fused three-term CGS probability + Gumbel-max topic
  sampling, streaming K tiles through VMEM (the paper's sampling inner loop).
* ``topic_histogram`` — scatter-free signed count-delta histogram via
  rank-one-hot MXU contraction (the paper's count-update step).

Kernel suite v2 (PR 6) — in-register gathers, no HBM intermediates:

* ``fused_gather``    — gather+sample fusion: per-token count-row slices are
  DMA'd straight out of the resident matrices into VMEM tiles
  (``tiles.gather_rows``), eliminating the ``(T, K)`` gathered-row
  materialization (training + frozen-model serving variants; CuLDA_CGS's
  fusion on TPU).
* ``cdf_search``      — zen_cdf's term-2 lower-bound search fused with the
  row gather and term multiply as a running-carry count over K tiles.
* ``sparse_row``      — whole-row CDF inversion over the Alg. 2 compact
  ``(T, max_k)`` sentinel-masked rows (SaberLDA-style vectorized sparsity).

Each kernel ships ``ref.py`` pure-jnp oracles (bit-exact, tile-accurate
where the carry order matters) and jitted padding wrappers in ``ops.py``;
``tiles.py`` holds the pieces kernels and oracles share. Validation runs in
interpret mode on CPU, Mosaic on TPUs (``ops.default_interpret`` decides).
Backend dispatch is policy-gated by ``SamplerKnobs.kernels``
(see ``repro.algorithms.base.kernel_dispatch``).
"""
from repro.kernels.ops import (  # noqa: F401
    cdf_row_search,
    sparse_row_sample,
    topic_histogram,
    zen_fused_infer_sample,
    zen_fused_sample,
    zen_infer_sample,
    zen_sample,
)
