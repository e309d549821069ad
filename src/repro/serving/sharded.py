"""Sharded model-parallel serving: the frozen model over a device mesh.

Training already shards ``N_w|k`` by word rows (``core.graph`` grid
partition); this module gives the *serving* half the same layout
(DESIGN.md §5.4). A :class:`ShardedFrozenLDAModel` lays the frozen count
rows over the mesh's ``model`` axis — LPT-balanced by row token mass,
relabeled contiguous per shard exactly like ``grid_partition`` relabels
word columns — and :func:`make_sharded_sweep_fn` turns any registered
backend's ``infer_sweep`` into a ``shard_map`` dispatch over that layout.

Correctness rests on one property of the ``infer_sweep`` contract
(``algorithms/base.py``): every per-slot key is consumed at the full
(B, L) layout and every draw is per-token, so a shard that computes the
whole batch but keeps only the tokens whose word rows it owns draws
**bit-identically** to the single-host sweep. Each shard therefore:

1. maps global (relabeled) word ids to shard-local rows and masks down to
   its owned tokens;
2. runs the backend's unmodified ``infer_sweep`` on its ``(W/m, K)`` row
   block with ``num_words_total`` carrying the true W (the ``W * beta``
   denominator must not see the block shape);
3. ``psum``\\ s the owned assignments over the ``model`` axis — every real
   token is owned by exactly one shard, so the sum *is* the combined
   sweep.

Backend tables built by ``prepare_infer`` follow the same split: leaves
the backend declares in ``infer_aux_word_fields`` (word-indexed, dim 0 =
word rows — e.g. ``zen_cdf``'s per-word CDFs) are built per-shard from the
local row block; everything else (topic-indexed vectors) replicates.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import LDAHyperParams
from repro.utils import compat


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedFrozenLDAModel:
    """A :class:`~repro.serving.lda_engine.FrozenLDAModel` laid out over a
    mesh: word rows LPT-balanced over the ``model`` axis, padded to equal
    per-shard blocks, topic totals replicated.

    Duck-types the frozen model everywhere the engine reads it
    (``num_words``/``num_topics``/``hyper``/``phi()``), but its ``n_wk``
    holds the *relabeled padded* ``(words_per_shard * m, K)`` layout — the
    engine relabels request token ids through :meth:`relabel` at slot
    placement, so only the sharded decode path ever sees shard-space ids.

    ``eq=False``: slots compare by identity (the engine pins slots with
    ``is``), never by array contents.
    """

    n_wk: jax.Array  # (W_pad, K) int32, sharded P("model", None)
    n_k: jax.Array  # (K,) int32, replicated
    hyper: LDAHyperParams
    mesh: Mesh
    word_perm: np.ndarray  # (W,) original row id -> padded shard-space row
    words_per_shard: int
    num_words_unsharded: int  # the true W

    @property
    def num_words(self) -> int:
        """The *original* vocabulary size W — token-id validation and
        ``phi()`` speak original ids, never the padded shard space."""
        return self.num_words_unsharded

    @property
    def num_topics(self) -> int:
        return int(self.n_wk.shape[1])

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape["model"])

    def relabel(self, words: np.ndarray) -> np.ndarray:
        """Original token ids -> shard-space rows (host-side, at slot
        placement). Ids must already be filtered to ``[0, W)``."""
        return self.word_perm[np.asarray(words, np.int64)].astype(np.int32)

    def phi(self) -> jax.Array:
        """Smoothed topic-word distributions in *original* word order,
        (W, K) — gathers the shards, inverts the relabeling."""
        padded = np.asarray(self.n_wk, np.float32)
        n_wk = padded[self.word_perm]  # (W, K) original order
        w_beta = self.num_words * self.hyper.beta
        return jnp.asarray(
            (n_wk + self.hyper.beta)
            / (np.asarray(self.n_k, np.float32) + w_beta)[None, :]
        )

    @classmethod
    def shard(cls, model, mesh: Mesh) -> "ShardedFrozenLDAModel":
        """Lay a frozen model out over ``mesh``'s ``model`` axis.

        Rows are LPT-assigned by token mass (hot words spread first — the
        ``grid_partition`` balance heuristic applied to serving), then
        relabeled contiguous per shard and zero-padded to the max bin
        size so every device holds one equal ``(words_per_shard, K)``
        block.
        """
        from repro.sharding.partition import shard_rows_balanced

        n_wk = np.asarray(model.n_wk)
        w, k = n_wk.shape
        m = int(mesh.shape["model"])
        perm, per = shard_rows_balanced(n_wk.sum(axis=1), m)
        padded = np.zeros((per * m, k), n_wk.dtype)
        padded[perm] = n_wk
        return cls(
            n_wk=jax.device_put(
                jnp.asarray(padded, jnp.int32),
                NamedSharding(mesh, P("model", None)),
            ),
            n_k=jax.device_put(
                jnp.asarray(model.n_k, jnp.int32), NamedSharding(mesh, P())
            ),
            hyper=model.hyper,
            mesh=mesh,
            word_perm=perm,
            words_per_shard=per,
            num_words_unsharded=w,
        )


def layout_key(model) -> Optional[Tuple[int, int, int]]:
    """The static layout a sharded jitted program closes over — two model
    slots may share jit caches only when these match (plain frozen models
    close over hyper alone and return None)."""
    if isinstance(model, ShardedFrozenLDAModel):
        return (model.words_per_shard, model.num_words_unsharded,
                model.num_shards)
    return None


def _aux_specs(backend, aux) -> Any:
    """PartitionSpec tree for a backend's ``prepare_infer`` aux: leaves
    named in ``infer_aux_word_fields`` shard their dim 0 over ``model``,
    everything else replicates."""
    word_fields = frozenset(getattr(backend, "infer_aux_word_fields", ()))
    fields = getattr(type(aux), "_fields", None)
    if fields is None:  # not a NamedTuple: nothing is word-indexed
        return jax.tree_util.tree_map(lambda _: P(), aux)
    return type(aux)(*(
        P("model", *([None] * (jnp.ndim(leaf) - 1)))
        if name in word_fields else P()
        for name, leaf in zip(fields, aux)
    ))


def sharded_prepare_infer(backend, smodel: ShardedFrozenLDAModel, knobs):
    """Build the backend's frozen serving tables per word shard.

    Each shard runs the unmodified ``prepare_infer`` on its own
    ``(words_per_shard, K)`` row block with ``num_words_total`` = the true
    W, so word-indexed tables (``infer_aux_word_fields``) come out sharded
    row-for-row with the counts and topic-indexed ones replicated —
    bit-identical rows to a single-host build, since every table row is a
    function of its own count row plus replicated vectors.
    """
    mesh, hyper = smodel.mesh, smodel.hyper
    w_total = smodel.num_words

    def build(n_wk_blk, n_k):
        return backend.prepare_infer(
            n_wk_blk, n_k, hyper, knobs, num_words_total=w_total
        )

    probe = jax.eval_shape(
        build,
        jax.ShapeDtypeStruct(
            (smodel.words_per_shard, smodel.num_topics), smodel.n_wk.dtype
        ),
        jax.ShapeDtypeStruct(smodel.n_k.shape, smodel.n_k.dtype),
    )
    if probe is None:
        return None
    specs = _aux_specs(backend, probe)
    fn = jax.jit(compat.shard_map(
        build, mesh, in_specs=(P("model", None), P()), out_specs=specs,
    ))
    return fn(smodel.n_wk, smodel.n_k)


def make_sharded_sweep_fn(backend, knobs, smodel: ShardedFrozenLDAModel,
                          aux):
    """The sharded analogue of the engine's jitted per-bucket sweep.

    Same call signature as the single-host program —
    ``fn(key_bits, words, mask, z, n_kd, n_wk, n_k, aux)`` with ``words``
    already in shard space (``ShardedFrozenLDAModel.relabel``) — so the
    engine's stepping loop is layout-blind. Inside the ``shard_map``
    every device computes the full (B, L) batch against its own row
    block, keeps the tokens it owns, and ``psum``\\ s assignments; keys
    arrive and cross the shard boundary as raw uint32 bits (extended key
    dtypes and ``shard_map`` disagree across jax versions)."""
    mesh, hyper = smodel.mesh, smodel.hyper
    wps, w_total = smodel.words_per_shard, smodel.num_words
    k = smodel.num_topics
    aux_spec = P() if aux is None else _aux_specs(backend, aux)

    def local(key_bits, words, mask, z, n_kd, n_wk_blk, n_k, aux_l):
        keys = jax.random.wrap_key_data(key_bits)
        col = jax.lax.axis_index("model")
        wl = words - (col * wps).astype(words.dtype)
        owned = mask & (wl >= 0) & (wl < wps)
        wl = jnp.clip(wl, 0, wps - 1)
        z_prop = backend.infer_sweep(
            keys, wl, owned, z, n_kd, n_wk_blk, n_k, hyper, knobs,
            aux_l, num_words_total=w_total,
        )
        # every live token is owned by exactly one shard: sum = combine
        return jax.lax.psum(
            jnp.where(owned, z_prop.astype(jnp.int32), 0), "model"
        )

    sharded = compat.shard_map(
        local, mesh,
        in_specs=(P(), P(), P(), P(), P(), P("model", None), P(), aux_spec),
        out_specs=P(),
    )

    def fn(key_bits, words, mask, z, n_kd, n_wk, n_k, aux_a):
        z_sum = sharded(key_bits, words, mask, z, n_kd, n_wk, n_k, aux_a)
        z_new = jnp.where(mask, z_sum, z)
        onehot = (
            jax.nn.one_hot(z_new, k, dtype=jnp.int32) * mask[..., None]
        )
        return z_new, jnp.sum(onehot, axis=1)

    return jax.jit(fn)
