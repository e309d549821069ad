"""SamplerBackend protocol + the one shared knob dataclass (DESIGN.md §4).

Every CGS sampling algorithm in the repo — single-box, distributed, and the
fused Pallas kernel — implements the same contract over the shared
counts/corpus substrate:

* ``prepare(corpus, hyper, knobs) -> aux`` — optional per-run precompute
  (e.g. LightLDA's CSR doc->token index). Called once by the driver; the
  result is passed back into every ``sweep``.
* ``sweep(state, corpus, hyper, knobs, aux) -> new_topics (E,)`` — one full
  pass over all tokens against iteration-start (stale) counts. The driver
  owns masking (token exclusion), the delta merge, and the state update, so
  a backend is *only* the per-token draw.
* ``cell_sweep(key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
  num_words, knobs) -> new_topics (T,)`` — the per-device form used
  inside ``shard_map`` by the distributed runtime: all ids are local to the
  device's (word-shard x doc-shard) cell and the count blocks are the local
  shards, while ``num_words`` is the corpus's W (the smoothing mass counts
  it, not the padded vocabulary). Only backends with
  ``supports_shard_map`` implement it.
* ``prepare_infer(n_wk, n_k, hyper, knobs) -> frozen aux`` /
  ``infer_sweep(keys, words, mask, z_old, n_kd, n_wk, n_k, hyper, knobs,
  aux) -> new_topics (B, L)`` — the *serving* form (frozen-model
  inference, paper §4.3): the trained ``N_w|k``/``N_k`` are held fixed and
  only the per-slot doc-topic counts move. The base class provides a
  default derivation that every backend inherits (the dense frozen-phi
  sweep, sweep-equivalent math with the word side frozen), so all
  registered backends serve for free; ``zen_cdf`` (one-time frozen
  per-word CDFs) and ``zen_pallas`` (a dedicated frozen-model kernel
  variant with per-slot seeds) override it natively and set
  ``native_infer``.

Capability flags let drivers adapt instead of hard-coding per-name logic:

* ``supports_shard_map`` — has a ``cell_sweep`` the mesh path can call
  (``make_dist_step`` rejects backends without it).
* ``needs_row_pads``     — the trainer resolves ``max_kw``/``max_kd`` (>0)
  before ``sweep`` (padded-sparse row widths; 0 = "auto from the counts").
* ``needs_doc_index``    — declares the aux contract: ``prepare`` returns a
  doc->token index that ``sweep`` requires (drivers call ``prepare``
  unconditionally; the flag tells them the aux is a corpus-sized structure
  worth budgeting for, not a behavior switch).

Backends also *declare their cell workspace shapes*: the distributed step
calls ``resolve_cell_knobs(knobs, hyper)`` once at trace time, and the
backend fills every knob that sizes a static per-cell workspace (padded
row widths, tile sizes). Inside ``shard_map`` nothing can be data-derived,
so 0/auto knobs must become concrete static widths here; drivers then
treat the returned knobs as the backend's actual workspace commitment
(benchmarks and launch scripts report them). Data-driven widths come from
the *shards* instead: ``repro.core.distributed.resolve_dist_row_pads``
fills 0 knobs from the sharded counts before the step is built.

``CellBackend`` derives the single-box ``sweep`` from ``cell_sweep`` by
treating the whole corpus as one cell — this is what makes the distributed
algorithms (``zen_cdf``, ``zen_dense``, ``zen_pallas``) selectable from the
single-box trainer with zero extra code.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

# Kernel dispatch policy values for SamplerKnobs.kernels (see
# ``kernel_dispatch``): "auto" = Pallas kernels on TPU, legacy XLA
# elsewhere; "on"/"off" force either path (interpret-mode kernels on CPU
# are bit-exact but walk the grid step by step — fine for tests, far too
# slow for CPU *training*, hence a policy knob instead of a backend flag).
VALID_KERNEL_MODES = ("auto", "on", "off")

# TPU f32 tiling floors: Pallas blocks need >= 8 sublanes and lane-dim
# multiples of 128; violations surface as opaque Mosaic lowering errors
# deep inside jit, so SamplerKnobs rejects them at construction instead.
_MIN_BT = 8
_LANE = 128


@dataclasses.dataclass(frozen=True)
class SamplerKnobs:
    """Algorithm knobs shared by every backend and both drivers.

    This unifies what used to be divergent fields on ``TrainConfig``
    (``token_chunk: Optional[int]``) and ``DistConfig``
    (``token_chunk: int = 0``): 0 always means "disabled / auto".

    Tile knobs are validated at construction (``__post_init__`` fires for
    ``knobs_from``, direct construction, and ``dataclasses.replace`` alike)
    so a bad ``bt``/``bk``/``bs`` fails with a clear ``ValueError`` at
    config time, not as a Pallas lowering error mid-trace.
    """

    sampling_method: str = "cdf"  # dense paths: cdf | gumbel
    max_kw: int = 0  # padded-sparse word-row width (0 = auto)
    max_kd: int = 0  # padded-sparse doc-row width (0 = auto)
    num_mh: int = 8  # LightLDA cycle-MH steps
    token_chunk: int = 0  # bound peak memory by chunking tokens (0 = off)
    bt: int = 256  # Pallas token-tile (zen_pallas + kernel suite v2)
    bk: int = 512  # Pallas topic-tile (gather kernels: up to 1024-multiple)
    bs: int = 128  # sparse-row lane-alignment tile (kernel (c))
    kernels: str = "auto"  # kernel dispatch policy: auto | on | off

    def __post_init__(self):
        if self.bt < _MIN_BT:
            raise ValueError(
                f"SamplerKnobs.bt={self.bt}: Pallas token tiles need at "
                f"least {_MIN_BT} rows (TPU f32 sublane minimum)"
            )
        for name, v in (("bk", self.bk), ("bs", self.bs)):
            if v < _LANE or v % _LANE:
                raise ValueError(
                    f"SamplerKnobs.{name}={v}: topic/lane tiles must be "
                    f"positive multiples of the {_LANE}-wide TPU lane dim"
                )
        if self.kernels not in VALID_KERNEL_MODES:
            raise ValueError(
                f"SamplerKnobs.kernels={self.kernels!r}: expected one of "
                f"{VALID_KERNEL_MODES}"
            )

    def chunk_or_none(self) -> Optional[int]:
        return self.token_chunk or None


def kernel_dispatch(mode: str) -> bool:
    """Resolve a ``SamplerKnobs.kernels`` policy to "use Pallas kernels?".

    ``auto`` dispatches kernels when the default backend is a TPU and the
    legacy XLA paths elsewhere (interpret-mode grids are too slow for CPU
    training); ``on``/``off`` force either path. The ``REPRO_KERNELS``
    environment variable overrides the knob when set (read at call time,
    not import time) — this is how the parity tests force kernel dispatch
    through the unchanged mesh harness.
    """
    mode = os.environ.get("REPRO_KERNELS", mode)
    if mode not in VALID_KERNEL_MODES:
        raise ValueError(
            f"kernel mode {mode!r}: expected one of {VALID_KERNEL_MODES}"
        )
    if mode == "auto":
        from repro.kernels.ops import default_interpret

        return not default_interpret()
    return mode == "on"


_KNOB_FIELDS = tuple(f.name for f in dataclasses.fields(SamplerKnobs))


def knobs_from(cfg) -> SamplerKnobs:
    """THE SamplerKnobs derivation — every driver config builds its knobs
    here (``RunConfig``, and the deprecated ``TrainConfig``/``DistConfig``
    shims), so a new knob is one field on ``SamplerKnobs`` plus one field
    on ``RunConfig``, never a per-config copy."""
    return SamplerKnobs(**{f: getattr(cfg, f) for f in _KNOB_FIELDS})


class SamplerBackend:
    """Base class: capability flags + the sweep contract."""

    name: str = "?"
    supports_shard_map: bool = False
    needs_doc_index: bool = False
    needs_row_pads: bool = False

    def prepare(self, corpus, hyper, knobs: SamplerKnobs) -> Any:
        """Per-run precompute; returns the aux object threaded into sweep."""
        return None

    def sweep(
        self, state, corpus, hyper, knobs: SamplerKnobs, aux: Any = None
    ) -> jax.Array:
        raise NotImplementedError(
            f"backend {self.name!r} has no single-box sweep"
        )

    def cell_sweep(
        self, key, word, doc, z_old, mask, n_wk, n_kd, n_k, hyper,
        num_words: int, knobs: SamplerKnobs,
    ) -> jax.Array:
        raise NotImplementedError(
            f"backend {self.name!r} does not support shard_map cells"
        )

    def resolve_cell_knobs(
        self, knobs: SamplerKnobs, hyper
    ) -> SamplerKnobs:
        """Declare the static per-cell workspace the backend will use.

        Called once by ``make_dist_step`` before tracing: every knob that
        sizes a ``cell_sweep`` workspace (padded row widths, tiles) must
        come back concrete — 0/auto values replaced by the backend's
        static defaults, capacities clamped to K. The default declares no
        workspace (dense backends size everything from the shard blocks
        themselves)."""
        return knobs

    # -- frozen-model serving (repro.serving.lda_engine) -------------------
    native_infer: bool = False
    # names of ``prepare_infer`` aux leaves indexed by word rows along dim
    # 0 (NamedTuple field names). The sharded serving path
    # (``repro.serving.sharded``) uses this declaration to lay the frozen
    # tables out over the mesh's model axis — word-indexed tables shard
    # with the count rows, everything else replicates. Backends whose aux
    # is None or purely topic-indexed leave it empty.
    infer_aux_word_fields: tuple = ()

    def prepare_infer(
        self, n_wk, n_k, hyper, knobs: SamplerKnobs,
        num_words_total: Optional[int] = None,
    ) -> Any:
        """Freeze the trained model into a sampling-ready aux object.

        Called once when a serving engine is built; the result is passed
        back into every ``infer_sweep``. The default needs no tables.

        ``num_words_total`` is the true (unsharded) vocabulary size W for
        any table whose math involves ``W * beta`` — the mesh-capable
        path mirroring ``cell_sweep``'s ``num_words``: under sharded
        serving ``n_wk`` is one shard's padded row block, so its leading
        dim is *not* W. None (single-host) means ``n_wk.shape[0]``."""
        return None

    def infer_sweep(
        self, keys, words, mask, z_old, n_kd, n_wk, n_k, hyper,
        knobs: SamplerKnobs, aux: Any = None,
        num_words_total: Optional[int] = None,
    ) -> jax.Array:
        """One frozen-model CGS sweep over a padded slot batch.

        ``keys`` (B,) per-slot PRNG keys; ``words``/``mask``/``z_old``
        (B, L) padded token rows; ``n_kd`` (B, K) per-slot doc-topic
        counts; ``n_wk``/``n_k`` the frozen trained model. Returns new
        topics (B, L) (padded positions produce garbage the engine masks).

        ``num_words_total`` mirrors ``cell_sweep``'s ``num_words``:
        inside a sharded dispatch ``n_wk`` is the device's word-row block
        and ``words`` are shard-local row ids with ``mask`` true only on
        tokens the shard owns, so the ``W * beta`` denominator must come
        from this argument, never from ``n_wk.shape[0]``. Single-host
        callers omit it. Because per-slot keys are consumed at the full
        (B, L) layout and draws are per-token, a shard that computes the
        whole batch but keeps only its owned tokens draws bit-identically
        to the single-host sweep — the property the sharded serve parity
        test pins (``tests/test_sharded_serving.py``).

        Contract of the *default derivation* (the engine's tests rely on
        it): slot b consumes randomness only from ``keys[b]``, so results
        are independent of batch composition; draws are prefix-stable in
        L (threefry counters are per-token), so growing the bucket pad
        never changes a real token's sample; and it is draw-for-draw
        compatible with the single-doc oracle
        ``repro.core.inference.cgs_infer`` (same conditional, same cdf
        inversion, same key schedule), which the serving tests verify
        bit-exactly. Overrides must keep slot chains *statistically*
        independent AND layout-stable: ``zen_cdf`` inherits both from
        per-slot threefry keys; ``zen_pallas`` gets layout-stability
        from per-token counter-based seeds hashed out of the slot key +
        in-doc position (so it is bit-stable across batch layouts, but
        under its own hash noise — statistically, not bitwise,
        comparable to the oracle; see its docstring).
        """
        return _dense_infer_sweep(
            keys, words, mask, z_old, n_kd, n_wk, n_k, hyper,
            knobs.sampling_method, num_words_total=num_words_total,
        )

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        flags = [
            f for f in ("supports_shard_map", "needs_doc_index",
                        "needs_row_pads", "native_infer")
            if getattr(self, f)
        ]
        return f"<{type(self).__name__} {self.name!r} {' '.join(flags)}>"


def _dense_infer_sweep(
    keys, words, mask, z_old, n_kd, n_wk, n_k, hyper, method: str,
    num_words_total: Optional[int] = None,
) -> jax.Array:
    """Default frozen-model sweep: dense phi rows, doc-side-only exclusion.

    Draw-for-draw identical to one ``cgs_infer`` sweep per slot (cdf
    method): same conditional, same cumsum inversion, and per-slot keys so
    slots are independent. Keep the op sequence in lockstep with
    ``repro.core.inference.cgs_infer`` — tests enforce bit-equality.
    """
    k = hyper.num_topics
    w_total = (n_wk.shape[0] if num_words_total is None
               else num_words_total)
    alpha_k = hyper.alpha_k(n_k)
    denom = n_k.astype(jnp.float32) + w_total * hyper.beta

    def slot(key, w_row, m_row, z_row, nkd_row):
        phi = (n_wk[w_row].astype(jnp.float32) + hyper.beta) / denom[None, :]
        onehot = jax.nn.one_hot(z_row, k, dtype=jnp.int32) * m_row[:, None]
        nkd_excl = (nkd_row[None, :] - onehot).astype(jnp.float32)
        probs = phi * (nkd_excl + alpha_k)
        if method == "gumbel":
            g = jax.random.gumbel(key, probs.shape, dtype=jnp.float32)
            return jnp.argmax(
                jnp.log(jnp.maximum(probs, 1e-30)) + g, -1
            ).astype(jnp.int32)
        cdf = jnp.cumsum(probs, axis=-1)
        u = jax.random.uniform(key, (probs.shape[0], 1))
        return jnp.minimum(
            jnp.sum(cdf < u * cdf[:, -1:], axis=-1), k - 1
        ).astype(jnp.int32)

    return jax.vmap(slot)(keys, words, mask.astype(jnp.int32), z_old, n_kd)


class CellBackend(SamplerBackend):
    """Single-box sweep derived from the per-device cell sweep: the whole
    corpus is one cell, every id is already local, every token is live."""

    supports_shard_map = True

    def resolve_cell_knobs(self, knobs: SamplerKnobs, hyper) -> SamplerKnobs:
        """Padded-row backends (``needs_row_pads``) share one workspace
        declaration: auto widths become the static defaults, clamped to K
        (``fill_cell_row_pads``). Idempotent, so cell sweeps may re-apply
        it defensively for direct callers that skipped resolution."""
        if self.needs_row_pads:
            return fill_cell_row_pads(knobs, hyper.num_topics)
        return knobs

    def sweep(self, state, corpus, hyper, knobs, aux=None):
        key = jax.random.fold_in(state.rng, state.iteration)
        mask = jnp.ones(corpus.word.shape, bool)
        return self.cell_sweep(
            key, corpus.word, corpus.doc, state.topic, mask,
            state.n_wk, state.n_kd, state.n_k, hyper, corpus.num_words,
            knobs,
        )


def chunked_token_map(chunk_fn, key, arrays, token_chunk: int) -> jax.Array:
    """Apply ``chunk_fn((arr0, arr1, ..., subkey)) -> (chunk,)`` over token
    chunks (bounds peak memory; 0/oversized chunk = one whole-sweep call).

    Every ``(E,)`` array in ``arrays`` is zero-padded to a whole number of
    chunks and reshaped to ``(n, token_chunk)``; the padded tail (id-0
    tokens) is sliced off the result."""
    e = arrays[0].shape[0]
    if not token_chunk or token_chunk >= e:
        return chunk_fn(tuple(arrays) + (key,))
    n = -(-e // token_chunk)
    pad = n * token_chunk - e
    keys = jax.random.split(key, n)
    out = jax.lax.map(
        chunk_fn,
        tuple(jnp.pad(a, (0, pad)).reshape(n, -1) for a in arrays) + (keys,),
    )
    return out.reshape(-1)[:e]


def auto_pad(n: jax.Array, multiple: int = 8) -> int:
    """Round a (traced-free) max-nnz up to a lane-friendly multiple."""
    m = int(jax.device_get(n))
    return max(multiple, ((m + multiple - 1) // multiple) * multiple)


def resolve_row_pads(state, knobs: SamplerKnobs) -> SamplerKnobs:
    """Fill max_kw/max_kd = 0 from the current counts (host-side; not for
    use inside jit/shard_map — the distributed path resolves widths via
    ``resolve_dist_row_pads`` / ``resolve_cell_knobs`` instead)."""
    if knobs.max_kw and knobs.max_kd:
        return knobs
    from repro.core.zen_sparse import max_row_nnz

    max_kw = knobs.max_kw or auto_pad(max_row_nnz(state.n_wk))
    max_kd = knobs.max_kd or auto_pad(max_row_nnz(state.n_kd))
    return dataclasses.replace(knobs, max_kw=max_kw, max_kd=max_kd)


# static fallback row widths for padded-sparse cell sweeps when nothing
# data-driven was resolved: shard_map workspaces need concrete shapes, and
# these match the paper's observed row-sparsity regime (K_d smaller than
# K_w; both clamped to K so small-topic runs never over-pad)
DEFAULT_CELL_MAX_KW = 128
DEFAULT_CELL_MAX_KD = 64


def fill_cell_row_pads(
    knobs: SamplerKnobs,
    num_topics: int,
    default_kw: int = DEFAULT_CELL_MAX_KW,
    default_kd: int = DEFAULT_CELL_MAX_KD,
) -> SamplerKnobs:
    """Make the padded-row widths concrete for a cell workspace: 0/auto
    becomes the static default clamped to K (a row never holds more than K
    live topics — wider pads are pure waste, the 'padding explodes'
    failure mode). Explicit nonzero widths are honored untouched so
    resolved single-box pads keep their exact (lane-rounded) shapes."""
    return dataclasses.replace(
        knobs,
        max_kw=knobs.max_kw or min(default_kw, num_topics),
        max_kd=knobs.max_kd or min(default_kd, num_topics),
    )
