"""Distributed ZenLDA iteration on a TPU mesh (paper Fig. 2 workflow).

One iteration, under ``shard_map`` on a ``(pod?, data, model)`` mesh:

  step 1  N_k is replicated (the "driver broadcast" is free in SPMD)
  step 2  model state is already resident: N_w|k sharded over `model`
          (replicated over data axes), N_k|d sharded over data axes
          (replicated over `model`) — the master->mirror ship becomes the
          sharding layout itself
  step 3  every device samples its token cell with iteration-start counts
          ("unsynchronized model", §4.1)
  step 4  mirror->master aggregation = psum of *delta* counts (§5.2 delta
          aggregation): ΔN_k|d over `model`, ΔN_w|k over data axes —
          optionally width-compressed (int16/int8), the TPU realization of
          "only the topic of changed tokens is transferred"
  step 5  ΔN_k aggregated from the word side only (as the paper does —
          docs outnumber words 100+x)

Sampling algorithms are resolved through the ``repro.algorithms`` registry
(DESIGN.md §4): any backend with ``supports_shard_map`` plugs into step 3 —
the dense paths (``zen_dense``, ``zen_cdf``, ``zen_pallas``) and the
padded-sparse ones (``zen_sparse``, ``zen_hybrid``, ``sparselda``,
``lightlda``), whose Alg. 2 row machinery runs cell-locally on the shard
blocks. The single-box trainer resolves the *same* entries.

The step makes no dense-backend assumptions: each backend declares its
static per-cell workspace through ``resolve_cell_knobs`` (padded row
widths, tiles), and data-driven widths are filled from the *sharded*
counts by ``resolve_dist_row_pads`` before the step is built — capacities
are per-shard row maxima (clamped to K), never a gather of the global
matrices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import algorithms
from repro.algorithms import SamplerKnobs
from repro.core.graph import GridPartition
from repro.core.types import LDAHyperParams
from repro.utils import compat


@dataclasses.dataclass(frozen=True)
class DistConfig:
    algorithm: str = "zen_cdf"  # any registered backend w/ supports_shard_map
    sampling_method: str = "gumbel"  # zen_dense: gumbel | cdf
    # padded-sparse row widths (doc / word side). 0 = auto: fill from the
    # sharded counts via ``resolve_dist_row_pads``, else the backend's
    # static default via ``resolve_cell_knobs`` (shard_map workspaces need
    # concrete widths at trace time).
    max_kd: int = 0
    max_kw: int = 0
    num_mh: int = 8  # lightlda cycle-MH steps per token
    delta_dtype: str = "int32"  # int32 | int16 | int8 (psum payload width)
    rebuild_every: int = 0  # exact count rebuild period (0 = never)
    exclusion_start: int = 0  # 0 = disabled; else iteration to enable at
    # 0 = whole cell at once (zen_dense / zen_pallas memory knob); nonzero
    # values must divide the padded per-cell token count
    token_chunk: int = 0
    # doc-topic state width: counts are bounded by doc length, so int16
    # halves every N_kd pass (top-k extraction, delta apply, llh reads) —
    # §Perf iteration l4. Requires max doc length < 32768.
    kd_dtype: str = "int32"  # int32 | int16
    bt: int = 256  # zen_pallas token tile
    bk: int = 512  # zen_pallas topic tile
    bs: int = 128  # sparse-row lane tile (kernel suite v2, kernel (c))
    kernels: str = "auto"  # Pallas kernel dispatch: auto | on | off

    def knobs(self) -> SamplerKnobs:
        """The shared backend knob dataclass (the single ``knobs_from``
        derivation — same one ``RunConfig``/``TrainConfig`` use)."""
        return algorithms.knobs_from(self)


class DistLDAState(NamedTuple):
    """Global-view sharded state (a pytree; see ``state_shardings``)."""

    topic: jax.Array  # (cells, e_cell) int32
    prev_topic: jax.Array  # (cells, e_cell) int32
    n_wk: jax.Array  # (W_pad, K) int32
    n_kd: jax.Array  # (D_pad, K) int32
    n_k: jax.Array  # (K,) int32
    stale_iters: jax.Array  # (cells, e_cell) int32
    same_count: jax.Array  # (cells, e_cell) int32
    iteration: jax.Array  # () int32
    rng: jax.Array  # key


class DistLDAData(NamedTuple):
    """Static (per-run) sharded token data."""

    word: jax.Array  # (cells, e_cell) int32 — global relabeled ids
    doc: jax.Array  # (cells, e_cell) int32
    mask: jax.Array  # (cells, e_cell) bool


def _axes(mesh: Mesh) -> Tuple[Tuple[str, ...], str]:
    names = mesh.axis_names
    model = "model"
    data_axes = tuple(n for n in names if n != model)
    return data_axes, model


def state_shardings(mesh: Mesh) -> Tuple[DistLDAState, DistLDAData]:
    """NamedShardings for state/data pytrees (also the dry-run in_shardings)."""
    data_axes, model = _axes(mesh)
    cellspec = P(data_axes + (model,), None)
    tok = NamedSharding(mesh, cellspec)
    return (
        DistLDAState(
            topic=tok, prev_topic=tok,
            n_wk=NamedSharding(mesh, P(model, None)),
            n_kd=NamedSharding(mesh, P(data_axes, None)),
            n_k=NamedSharding(mesh, P()),
            stale_iters=tok, same_count=tok,
            iteration=NamedSharding(mesh, P()),
            rng=NamedSharding(mesh, P()),
        ),
        DistLDAData(word=tok, doc=tok, mask=tok),
    )


def _specs(mesh: Mesh) -> Tuple[DistLDAState, DistLDAData]:
    data_axes, model = _axes(mesh)
    cellspec = P(data_axes + (model,), None)
    return (
        DistLDAState(
            topic=cellspec, prev_topic=cellspec,
            n_wk=P(model, None), n_kd=P(data_axes, None), n_k=P(),
            stale_iters=cellspec, same_count=cellspec,
            iteration=P(), rng=P(),
        ),
        DistLDAData(word=cellspec, doc=cellspec, mask=cellspec),
    )


# ---------------------------------------------------------------------------
# The distributed step
# ---------------------------------------------------------------------------

def resolve_dist_row_pads(state: DistLDAState, cfg: DistConfig) -> DistConfig:
    """Fill auto (0) padded-row widths from the *sharded* counts.

    Capacity is the per-shard row maximum: the nnz reduction runs
    shard-locally under the arrays' sharding (no shard gathers another's
    block) and only two scalars reach the host. SPMD compiles one program
    for all shards, so the static width is the max over the per-shard
    maxima — lane-rounded and clamped to K (``shard_row_capacity``), which
    keeps a hot word's global density from exploding every cell's pad.

    The width is frozen into the compiled step, but rows keep moving: a
    row that later grows past the capacity is *truncated by the sparse
    tables* (its overflow topics become unproposable that iteration — a
    sampling-quality bias, never a count-corruption, since the driver
    merges deltas against the dense state). One lane multiple of headroom
    is added against that drift; random init starts rows near their
    occupancy ceiling, so growth past init+headroom is rare. The full
    answer is periodic re-resolution: ``TrainSession``'s "repad" schedule
    action re-runs this on the ``rebuild_every`` cadence against the
    current counts and rebuilds the jitted step when the widths changed.

    Host-side, once per (re)build — not callable inside jit/shard_map.
    """
    backend = algorithms.get(cfg.algorithm)
    if not backend.needs_row_pads or (cfg.max_kw and cfg.max_kd):
        return cfg
    from repro.core.zen_sparse import shard_row_capacity

    k = state.n_wk.shape[-1]
    return dataclasses.replace(
        cfg,
        max_kw=cfg.max_kw or min(shard_row_capacity(state.n_wk) + 8, k),
        max_kd=cfg.max_kd or min(shard_row_capacity(state.n_kd) + 8, k),
    )


def _compress_psum(delta: jax.Array, axes, dtype: str) -> jax.Array:
    """Width-compressed collective (§5.2 delta aggregation, TPU realization).

    int16/int8 halve/quarter the all-reduce payload. Saturating cast; any
    clipped residue is corrected by the periodic exact rebuild
    (``rebuild_every``) — same staleness-tolerance argument as the paper's.
    """
    if dtype == "int32":
        return jax.lax.psum(delta, axes)
    info = jnp.iinfo(jnp.int16 if dtype == "int16" else jnp.int8)
    small = jnp.clip(delta, info.min, info.max).astype(dtype)
    return jax.lax.psum(small, axes).astype(jnp.int32)


def make_dist_step(
    mesh: Mesh,
    hyper: LDAHyperParams,
    cfg: DistConfig,
    words_per_shard: int,
    docs_per_shard: int,
    num_words: int,
):
    """Build the jitted distributed iteration fn: (state, data) -> state.

    ``num_words`` is the corpus's W, the smoothing mass's count (the grid
    pads the vocabulary to ``words_per_shard`` a column). Its ops carry
    the named scopes ``zen.sweep`` (with ``zen.relayout`` inside the
    kernel wrappers), ``zen.delta_counts``, ``zen.exchange`` (the delta
    ``psum``s) and ``zen.update`` in their HLO metadata."""
    data_axes, model = _axes(mesh)
    state_spec, data_spec = _specs(mesh)
    k = hyper.num_topics
    backend = algorithms.get(cfg.algorithm)
    if not backend.supports_shard_map:
        raise ValueError(
            f"backend {cfg.algorithm!r} does not support shard_map cells; "
            f"mesh-capable backends: "
            f"{', '.join(n for n in algorithms.registered() if algorithms.get(n).supports_shard_map)}"
        )
    # the backend declares its static per-cell workspace (padded row
    # widths, tiles): every auto knob must be concrete before tracing
    knobs = backend.resolve_cell_knobs(cfg.knobs(), hyper)

    def local_step(state: DistLDAState, data: DistLDAData) -> DistLDAState:
        # local views --------------------------------------------------
        word = data.word.reshape(-1)
        doc = data.doc.reshape(-1)
        mask = data.mask.reshape(-1)
        z_old = state.topic.reshape(-1)
        stale_i = state.stale_iters.reshape(-1)
        same_t = state.same_count.reshape(-1)
        n_wk_l = state.n_wk  # (Ws, K) local block
        n_kd_l = state.n_kd  # (Ds, K)
        n_k = state.n_k

        col = jax.lax.axis_index(model)
        row = jax.lax.axis_index(data_axes[0])
        for ax in data_axes[1:]:
            row = row * mesh.shape[ax] + jax.lax.axis_index(ax)
        word_l = word - col * words_per_shard
        doc_l = doc - row * docs_per_shard

        dev = row * mesh.shape[model] + col
        key = jax.random.fold_in(state.rng, state.iteration)
        key = jax.random.fold_in(key, dev)
        k_sample, k_excl = jax.random.split(key)

        # converged-token exclusion (§5.1) ------------------------------
        if cfg.exclusion_start > 0:
            prob = jnp.clip(
                jnp.exp2(stale_i.astype(jnp.float32) - same_t.astype(jnp.float32)),
                0.0, 1.0,
            )
            u = jax.random.uniform(k_excl, z_old.shape)
            active = (u < prob) | (state.iteration < cfg.exclusion_start)
        else:
            active = jnp.ones_like(mask)
        active = active & mask

        # step 3: sample on stale counts — one registry-resolved call
        # (zen_dense / zen_cdf / zen_pallas / any future cell backend)
        with jax.named_scope("zen.sweep"):
            z_prop = backend.cell_sweep(
                k_sample, word_l, doc_l, z_old, mask, n_wk_l, n_kd_l, n_k,
                hyper, num_words, knobs,
            )
        z_new = jnp.where(active, z_prop, z_old)

        # step 4: delta aggregation (§5.2) -------------------------------
        # the delta buffers are built directly in the compressed dtype:
        # per-iteration per-(vertex, topic) changes are bounded by the
        # vertex's local token count, so int16 is exact for docs and safe
        # for all but ultra-hot words (periodic rebuild corrects any
        # saturation — §Perf iteration l3)
        ddt = jnp.int32 if cfg.delta_dtype == "int32" else jnp.dtype(cfg.delta_dtype)
        changed = (z_new != z_old) & mask
        with jax.named_scope("zen.delta_counts"):
            inc = changed.astype(ddt)
            d_wk = (
                jnp.zeros(n_wk_l.shape, ddt)
                .at[word_l, z_new].add(inc)
                .at[word_l, z_old].add(-inc)
            )
            d_kd = (
                jnp.zeros(n_kd_l.shape, ddt)
                .at[doc_l, z_new].add(inc)
                .at[doc_l, z_old].add(-inc)
            )
        with jax.named_scope("zen.exchange"):
            d_wk = jax.lax.psum(d_wk, data_axes).astype(jnp.int32)
            d_kd = jax.lax.psum(d_kd, (model,)).astype(jnp.int32)
            # step 5: N_k from the word side only (paper Fig. 2 step 5)
            d_k = jax.lax.psum(jnp.sum(d_wk, axis=0), model)

        with jax.named_scope("zen.update"):
            # exclusion stats update
            new_i = jnp.where(active, 0, stale_i + 1)
            new_t = jnp.where(
                active, jnp.where(changed, 0, same_t + 1), same_t
            )
            shp = state.topic.shape
            new_n_kd = (n_kd_l.astype(jnp.int32) + d_kd).astype(n_kd_l.dtype)
            return DistLDAState(
                topic=z_new.reshape(shp),
                prev_topic=z_old.reshape(shp),
                n_wk=n_wk_l + d_wk,
                n_kd=new_n_kd,
                n_k=n_k + d_k,
                stale_iters=new_i.reshape(shp),
                same_count=new_t.reshape(shp),
                iteration=state.iteration + 1,
                rng=state.rng,
            )

    step = compat.shard_map(
        local_step, mesh, (state_spec, data_spec), state_spec,
    )
    return jax.jit(step, donate_argnums=(0,))


def make_rebuild_counts(
    mesh: Mesh,
    hyper: LDAHyperParams,
    words_per_shard: int,
    docs_per_shard: int,
):
    """Exact count rebuild from assignments (elastic restore / drift fix)."""
    data_axes, model = _axes(mesh)
    state_spec, data_spec = _specs(mesh)
    k = hyper.num_topics

    def local(state: DistLDAState, data: DistLDAData) -> DistLDAState:
        word = data.word.reshape(-1)
        doc = data.doc.reshape(-1)
        mask = data.mask.reshape(-1)
        z = state.topic.reshape(-1)
        col = jax.lax.axis_index(model)
        row = jax.lax.axis_index(data_axes[0])
        for ax in data_axes[1:]:
            row = row * mesh.shape[ax] + jax.lax.axis_index(ax)
        word_l = word - col * words_per_shard
        doc_l = doc - row * docs_per_shard
        ones = mask.astype(jnp.int32)
        n_wk = jnp.zeros_like(state.n_wk).at[word_l, z].add(ones)
        n_kd = jnp.zeros(state.n_kd.shape, jnp.int32).at[doc_l, z].add(ones)
        n_wk = jax.lax.psum(n_wk, data_axes)
        n_kd = jax.lax.psum(n_kd, (model,)).astype(state.n_kd.dtype)
        n_k = jax.lax.psum(jnp.sum(n_wk, axis=0), model)
        return state._replace(n_wk=n_wk, n_kd=n_kd, n_k=n_k)

    fn = compat.shard_map(
        local, mesh, (state_spec, data_spec), state_spec,
    )
    return jax.jit(fn, donate_argnums=(0,))


def make_dist_llh(
    mesh: Mesh, hyper: LDAHyperParams, words_per_shard: int,
    docs_per_shard: int, num_words: int,
):
    """Distributed predictive log-likelihood (paper footnote 6), with the
    corpus's W (``num_words``) in the smoothing mass."""
    data_axes, model = _axes(mesh)
    all_axes = data_axes + (model,)
    state_spec, data_spec = _specs(mesh)

    def local(state: DistLDAState, data: DistLDAData) -> jax.Array:
        word = data.word.reshape(-1)
        doc = data.doc.reshape(-1)
        mask = data.mask.reshape(-1)
        col = jax.lax.axis_index(model)
        row = jax.lax.axis_index(data_axes[0])
        for ax in data_axes[1:]:
            row = row * mesh.shape[ax] + jax.lax.axis_index(ax)
        word_l = word - col * words_per_shard
        doc_l = doc - row * docs_per_shard
        alpha_k = hyper.alpha_k(state.n_k)
        alpha_sum = jnp.sum(alpha_k)
        n_d = jnp.sum(state.n_kd, axis=-1).astype(jnp.float32)  # (Ds,)
        w_beta = num_words * hyper.beta
        theta = (state.n_kd[doc_l].astype(jnp.float32) + alpha_k[None, :]) / (
            n_d[doc_l][:, None] + alpha_sum
        )
        phi = (state.n_wk[word_l].astype(jnp.float32) + hyper.beta) / (
            state.n_k.astype(jnp.float32)[None, :] + w_beta
        )
        token_llh = jnp.log(jnp.maximum(jnp.sum(theta * phi, -1), 1e-30))
        local_sum = jnp.sum(jnp.where(mask, token_llh, 0.0))
        return jax.lax.psum(local_sum, all_axes)

    fn = compat.shard_map(
        local, mesh, (state_spec, data_spec), P(),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

def init_dist_state(
    rng: jax.Array,
    mesh: Mesh,
    grid: GridPartition,
    hyper: LDAHyperParams,
    init_topics: Optional[np.ndarray] = None,
    kd_dtype=jnp.int32,
) -> Tuple[DistLDAState, DistLDAData]:
    """Build the sharded state and data from a host GridPartition. Each
    array goes to its shards in one transfer, and the initial topics are
    drawn on the devices straight into their sharding: the values of an
    eager ``randint(rng, (cells, e_cell), 0, K)``."""
    state_sh, data_sh = state_shardings(mesh)
    cells, e_cell = grid.word.shape
    k = hyper.num_topics
    data = jax.device_put(DistLDAData(grid.word, grid.doc, grid.mask), data_sh)
    if init_topics is not None:
        init_topics = jax.device_put(np.asarray(init_topics, np.int32),
                                     state_sh.topic)

    @functools.partial(jax.jit, out_shardings=state_sh)
    def fresh(rng, topic):
        if topic is None:
            topic = jax.random.randint(rng, (cells, e_cell), 0, k,
                                       dtype=jnp.int32)
        tok = jnp.zeros((cells, e_cell), jnp.int32)
        # each output is a buffer of its own (the step donates the state,
        # and the runtime rejects donating one buffer twice)
        return DistLDAState(
            topic=topic, prev_topic=topic,
            n_wk=jnp.zeros((grid.num_words_padded, k), jnp.int32),
            n_kd=jnp.zeros((grid.num_docs_padded, k), kd_dtype),
            n_k=jnp.zeros((k,), jnp.int32),
            stale_iters=tok, same_count=tok,
            iteration=jnp.int32(0), rng=rng,
        )

    state = fresh(rng, init_topics)
    rebuild = make_rebuild_counts(
        mesh, hyper, grid.words_per_shard, grid.docs_per_shard
    )
    state = rebuild(state, data)
    return state, data
