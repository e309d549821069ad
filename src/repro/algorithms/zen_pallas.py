"""``zen_pallas`` — the fused Gumbel-max Pallas kernel as a first-class
backend (headline hot path; ``zen_dense_kernel`` kept as the legacy alias).

One fused VMEM pass streams K-tiles of the three-term conditional and keeps
only a running (max, argmax) carry per token: no normalization, no
materialized (T, K) probability matrix in HBM, no second pass (see
``kernels/zen_sampler.py`` and DESIGN.md §2). On CPU the same kernel runs
in interpret mode, bit-identical to the ``kernels/ref.py`` oracle, so the
backend is selectable everywhere: kernel on TPU, interpreted ref on CPU.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.algorithms.base import (
    CellBackend,
    SamplerKnobs,
    chunked_token_map,
    kernel_dispatch,
)
from repro.algorithms.registry import register


class FrozenPallasModel(NamedTuple):
    """One-time ``prepare_infer`` precompute for the serving kernel: the
    per-topic vectors the frozen-model variant streams as (1, bk) tiles.
    Tiny, but hoisting them out of the sweep keeps every ``infer_sweep``
    dispatch free of the alpha_k derivation and float casts."""

    alpha_k: jax.Array  # (K,) f32
    n_k_f: jax.Array  # (K,) f32 frozen topic totals


@register("zen_pallas", "zen_dense_kernel")
class ZenPallas(CellBackend):
    """Fused three-term Gumbel-max sampler (Pallas TPU kernel)."""

    native_infer = True

    def prepare_infer(self, n_wk, n_k, hyper, knobs: SamplerKnobs,
                      num_words_total=None):
        """Freeze the per-topic serving vectors (see
        :class:`FrozenPallasModel`). The count rows themselves stay in
        the engine's ``FrozenLDAModel`` — the kernel gathers them
        per-sweep, uncompensated (the frozen-model kernel variant needs
        no word-side one-hot add)."""
        return FrozenPallasModel(
            alpha_k=hyper.alpha_k(n_k),
            n_k_f=n_k.astype(jnp.float32),
        )

    def infer_sweep(
        self, keys, words, mask, z_old, n_kd, n_wk, n_k, hyper,
        knobs: SamplerKnobs, aux=None, num_words_total=None,
    ):
        """Frozen-model serving through the dedicated kernel variant
        (``kernels.zen_sampler._zen_infer_kernel``).

        Unlike the training kernel (which applies ¬dw exclusion to all
        three counts in-register, and previously forced this path to
        pre-compensate the gathered word rows with a (T, K) one-hot add
        plus an N_k off-by-one approximation), the frozen variant
        excludes on the **doc side only** — exactly the frozen-phi
        conditional, no compensation rows, no denominator skew.

        Randomness: per-token seeds are hashed from the token's *slot*
        key and in-doc position (``kernels.zen_sampler.golden_seed``), so
        a request's draws depend only on its own key and tokens — the
        same padding-exactness / batch-composition-independence contract
        as the default derivation, just under the kernel's counter-based
        hash instead of threefry (so it is not draw-for-draw comparable
        with ``cgs_infer``, but it IS bit-stable across batch layouts;
        ``tests/test_latency_serving.py`` pins both properties).
        """
        from repro.kernels.ops import zen_fused_infer_sample, zen_infer_sample

        if aux is None:
            aux = self.prepare_infer(n_wk, n_k, hyper, knobs)
        b, l = words.shape
        slot = jax.lax.broadcasted_iota(jnp.int32, (b, l), 0).reshape(-1)
        w = words.reshape(-1)
        z = z_old.reshape(-1)

        from repro.kernels.zen_sampler import golden_seed

        bits = jax.random.key_data(keys).astype(jnp.uint32)  # (B, 2)
        pos = jax.lax.broadcasted_iota(jnp.uint32, (1, l), 1)
        seeds = golden_seed(
            bits[:, :1], bits[:, 1:], pos
        ).reshape(-1)  # (B*L,) int32, counter-based in (slot key, pos)

        # w_beta stays a static python float (jit static arg), so it is
        # derived from shapes/hyper here, never threaded through the aux;
        # sharded dispatch passes the true W (n_wk is then a row block)
        w_total = (n_wk.shape[0] if num_words_total is None
                   else num_words_total)
        if kernel_dispatch(knobs.kernels):
            # fused gather+sample: scalar-prefetched word/slot ids, count
            # rows tiled from the resident matrices — no (B*L, K) gathered
            # intermediates. Bit-identical to the legacy path below.
            out = zen_fused_infer_sample(
                n_wk.astype(jnp.int32), n_kd.astype(jnp.int32), w, slot, z,
                seeds, aux.alpha_k, aux.n_k_f,
                beta=hyper.beta, w_beta=w_total * hyper.beta,
                bt=knobs.bt, bk=knobs.bk,
            )
        else:
            out = zen_infer_sample(
                n_wk[w].astype(jnp.int32), n_kd[slot].astype(jnp.int32), z,
                seeds, aux.alpha_k, aux.n_k_f,
                beta=hyper.beta, w_beta=w_total * hyper.beta,
                bt=knobs.bt, bk=knobs.bk,
            )
        return out.reshape(b, l)

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words, knobs: SamplerKnobs,
    ):
        # lazy: keep pallas out of the import path of everything that
        # never selects this backend
        from repro.kernels.ops import zen_fused_sample, zen_sample

        # scalar prep + count-matrix dtype casts hoisted out of the chunk
        # fn (the FrozenPallasModel pattern for the training path): a
        # token_chunk run re-enters chunk() per chunk, but alpha_k / n_k_f
        # / the int32 casts depend only on sweep-start state. The kernel
        # tiles assume 4-byte count rows (the distributed path may hold
        # N_kd in int16), so the casts happen exactly once per sweep here.
        alpha_k = hyper.alpha_k(n_k)
        n_k_f = n_k.astype(jnp.float32)
        w_beta = num_words * hyper.beta
        n_wk_i = n_wk.astype(jnp.int32)
        n_kd_i = n_kd.astype(jnp.int32)
        use_kernel = kernel_dispatch(knobs.kernels)

        def chunk(args):
            w, d, z, subkey = args
            seed = jax.random.randint(
                subkey, (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
            )
            if use_kernel:
                # fused gather+sample: no (chunk, K) gathered rows in HBM;
                # bit-identical to the legacy gather-then-sample path
                return zen_fused_sample(
                    n_wk_i, n_kd_i, w, d, z, alpha_k, n_k_f, seed,
                    beta=hyper.beta, w_beta=w_beta,
                    bt=knobs.bt, bk=knobs.bk,
                )
            return zen_sample(
                n_wk_i[w], n_kd_i[d], z, alpha_k, n_k_f, seed,
                beta=hyper.beta, w_beta=w_beta, bt=knobs.bt, bk=knobs.bk,
            )

        # chunking bounds the per-chunk workspace (and, on the legacy
        # path, the gathered (chunk, K) row tiles in HBM)
        return chunked_token_map(
            chunk, key, (word, doc, z_old), knobs.token_chunk
        )
