"""Batched topic-inference serving engine over a frozen trained model.

This is the deployment half of the paper's system (§4.3 "Model
inference"): training produces ``N_w|k``/``N_k``; downstream traffic is
unseen documents whose topic mixture theta must be inferred at high
throughput — or, for millisecond SLAs, at low latency. The engine:

* freezes the trained counts into a :class:`FrozenLDAModel` (plus any
  backend-specific sampling tables via ``SamplerBackend.prepare_infer`` —
  e.g. ``zen_cdf`` builds its per-word CDFs once, for the engine's whole
  lifetime);
* packs incoming documents into **length-bucketed padded batches** — one
  slot array per bucket width, so every jitted sweep sees a fixed shape
  and XLA compiles each bucket exactly once;
* decodes through one of two execution plans (DESIGN.md §5.1):

  - ``mode="throughput"`` (default) — continuously-admitting
    multi-document CGS sweeps through the ``repro.algorithms`` registry's
    ``infer_sweep`` capability: one sweep per step, finished slots are
    refilled from the queue every step (continuous batching applied to
    Gibbs chains);
  - ``mode="latency"`` — the RT-LDA fast path: each admission tick runs a
    **single fused** deterministic decode per non-empty bucket
    (``repro.core.inference.rtlda_assign`` vmapped over slots — argmax
    sweeps, no burn-in chains, no thinning, no RNG), so every admitted
    request completes in that same tick. One dispatch per decode instead
    of ``num_sweeps`` chained dispatches.

* fronts both plans with an **async ticket API** — :meth:`LDAEngine.submit_async`
  returns a ticket immediately, :meth:`LDAEngine.poll` reports the ticket
  lifecycle (``queued -> admitted -> done``), and :meth:`LDAEngine.result`
  blocks (with optional timeout) and reaps. Requests arriving between
  ticks coalesce into the next tick's batch instead of blocking the
  caller; an optional background ticker (:meth:`LDAEngine.start`) drives
  admission at a fixed ``tick_period``;

* supports **hot model reload** (DESIGN.md §7): :meth:`LDAEngine.reload`
  atomically swaps in a new :class:`FrozenLDAModel` between admission
  ticks. Versioned model slots make the swap safe under load — every
  request is stamped with the version it decodes under
  (``InferRequest.model_version``), a bucket's in-flight slots always
  finish on the model they were admitted under (the bucket pins its
  model slot until it drains), and a request admitted after the swap
  decodes under the new model. :meth:`LDAEngine.watch_checkpoint_dir`
  turns this into a live train→serve pipeline: poll a model checkpoint
  directory and reload every new step the trainer commits.

Statistical contract (throughput mode): each request's chain consumes
randomness only from its own key, with the same schedule as the
single-doc oracle ``repro.core.inference.cgs_infer`` (z0 from
``randint(key)``, sweep j from ``split(key)[j]``). For the default
(dense) backend with cdf sampling this makes a served document's theta
*bit-identical* to ``cgs_infer(key, ...)`` regardless of bucket padding
or batch composition — the property ``tests/test_lda_engine.py`` pins
down. Latency mode is fully deterministic: the same document always
yields bit-identical topic assignments for every bucketing, batch
composition, submission order, and engine seed — engine-to-engine thetas
are therefore bit-equal too, and they match the single-doc
``rtlda_infer`` oracle to float tolerance (the engine's theta arithmetic
is numpy, the oracle's is XLA; the count inputs are integer-identical)
(``tests/test_latency_serving.py``).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import algorithms
from repro.algorithms import SamplerKnobs
from repro.core.inference import rtlda_assign
from repro.core.types import LDAHyperParams
# canonical home of the percentile math is the observability layer; the
# import keeps the historical ``repro.serving.latency_percentile`` working
from repro.observe.metrics import latency_percentile  # noqa: F401
from repro.observe.metrics import span
from repro.serving.sharded import (
    ShardedFrozenLDAModel,
    layout_key,
    make_sharded_sweep_fn,
    sharded_prepare_infer,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class FrozenLDAModel:
    """A trained LDA model frozen for serving.

    Attributes:
        n_wk: ``(W, K)`` int32 word-topic counts from training.
        n_k: ``(K,)`` int32 per-topic totals (``n_wk.sum(0)``).
        hyper: the :class:`~repro.core.types.LDAHyperParams` the model was
            trained with (``num_topics``, alpha, beta).

    The counts never change while serving; backends may precompute
    sampling tables from them once (``SamplerBackend.prepare_infer``).
    Build one with :meth:`from_state` (from a live trainer state) or
    :meth:`from_checkpoint` (from the artifact ``launch/train.py
    --checkpoint-dir`` writes).
    """

    n_wk: jax.Array  # (W, K) int32 word-topic counts
    n_k: jax.Array  # (K,) int32 topic totals
    hyper: LDAHyperParams

    @property
    def num_words(self) -> int:
        """Vocabulary size W (token ids outside ``[0, W)`` are unknown)."""
        return int(self.n_wk.shape[0])

    @property
    def num_topics(self) -> int:
        """Topic count K — the length of every served theta."""
        return int(self.n_wk.shape[1])

    def phi(self) -> jax.Array:
        """Smoothed topic-word distributions, (W, K) column-normalized."""
        w_beta = self.num_words * self.hyper.beta
        return (self.n_wk.astype(jnp.float32) + self.hyper.beta) / (
            self.n_k.astype(jnp.float32) + w_beta
        )[None, :]

    @classmethod
    def from_state(cls, state, hyper: LDAHyperParams) -> "FrozenLDAModel":
        """Freeze a trainer ``CGSState`` (single-box or gathered).

        Args:
            state: any object with ``n_wk``/``n_k`` count arrays (a
                ``CGSState`` or the session's gathered model arrays).
            hyper: the hyper-parameters used in training.
        """
        return cls(
            n_wk=jnp.asarray(state.n_wk, jnp.int32),
            n_k=jnp.asarray(state.n_k, jnp.int32),
            hyper=hyper,
        )

    @classmethod
    def from_checkpoint(cls, directory: str) -> "FrozenLDAModel":
        """Load the newest committed model checkpoint (see
        ``repro.train.checkpoint.save_lda_model``)."""
        from repro.train.checkpoint import load_lda_model

        n_wk, n_k, hyper, _meta, _step = load_lda_model(directory)
        return cls(
            n_wk=jnp.asarray(n_wk, jnp.int32),
            n_k=jnp.asarray(n_k, jnp.int32),
            hyper=hyper,
        )


@dataclasses.dataclass(frozen=True)
class LDAServeConfig:
    """Engine knobs.

    Execution plan: ``mode="throughput"`` (default) runs chain-based CGS
    sweeps through the registry backend ``algorithm``; ``mode="latency"``
    runs the deterministic RT-LDA fast path (``rtlda_sweeps`` fused argmax
    passes, one dispatch per bucket per tick, no RNG — per-request
    ``key``/``num_sweeps``/``burn_in``/``thin`` are ignored).

    Chain estimator (throughput mode): ``burn_in < 0`` (default)
    reproduces the oracle estimator — theta from the final sweep's
    doc-topic counts. ``burn_in >= 0`` switches to the posterior-mean
    estimator: counts are sampled every ``thin`` sweeps after the first
    ``burn_in`` and theta is their average — better quality per sweep, no
    longer bit-comparable to ``cgs_infer``.

    SLA knobs (DESIGN.md §5.1): ``tick_period`` is the background
    ticker's admission cadence in seconds (:meth:`LDAEngine.start`; 0
    picks a 1 ms default); ``max_slot_wait`` bounds queueing at a
    saturated bucket — a request that has waited that many ticks for its
    preferred (smallest-fit) bucket may spill into any wider bucket with
    a free slot (0 = strict smallest-fit forever).

    Sharded serving (DESIGN.md §5.4): ``mesh_shape`` = ``(1, m)`` lays
    the frozen model's word rows over an ``m``-way ``model`` axis
    (:class:`~repro.serving.sharded.ShardedFrozenLDAModel`) and runs
    every bucket sweep as a ``shard_map`` dispatch. The data dim must be
    1 — replica parallelism comes from ``serving.router.LDARouter``, not
    a data axis — and latency mode (RT-LDA) does not shard. ``None``
    (default) serves single-host.
    """

    buckets: Tuple[int, ...] = (32, 64, 128, 256)
    max_batch: int = 32  # slots per bucket
    num_sweeps: int = 10
    burn_in: int = -1  # < 0 => final-sweep theta (oracle-compatible)
    thin: int = 1
    algorithm: str = "zen"  # any algorithms.registered() name
    sampling_method: str = "cdf"  # cdf | gumbel (dense default path)
    max_kd: int = 0  # zen_cdf doc-row width (0 = backend default)
    mode: str = "throughput"  # throughput | latency (RT-LDA fast path)
    rtlda_sweeps: int = 2  # latency mode: fused deterministic passes
    tick_period: float = 0.0  # background ticker cadence, s (0 = 1 ms)
    max_slot_wait: int = 0  # ticks before bucket spill (0 = never spill)
    kernels: str = "auto"  # Pallas kernel dispatch: auto | on | off
    mesh_shape: Optional[Tuple[int, int]] = None  # (1, m) word shards
    # -- observability + autopilot (DESIGN.md §8): all inert by default ----
    metrics_out: Optional[str] = None  # telemetry JSONL path (None = off)
    autopilot: bool = False  # derive tick_period/max_slot_wait/buckets
    autopilot_window: int = 0  # arrivals per decision window (0 = 64)

    def knobs(self) -> SamplerKnobs:
        return SamplerKnobs(
            sampling_method=self.sampling_method, max_kd=self.max_kd,
            kernels=self.kernels,
        )

    # -- serialization (mirrors RunConfig: a serving setup is a file) ------
    def to_json(self, indent: Optional[int] = 2) -> str:
        d = dataclasses.asdict(self)
        d["buckets"] = list(d["buckets"])
        if d["mesh_shape"] is not None:
            d["mesh_shape"] = list(d["mesh_shape"])
        return json.dumps(d, indent=indent)

    @classmethod
    def from_json(cls, text: str) -> "LDAServeConfig":
        d = json.loads(text)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(
                f"unknown LDAServeConfig fields: {', '.join(unknown)}"
            )
        if d.get("buckets") is not None:
            d["buckets"] = tuple(int(x) for x in d["buckets"])
        if d.get("mesh_shape") is not None:
            d["mesh_shape"] = tuple(int(x) for x in d["mesh_shape"])
        return cls(**d)


@dataclasses.dataclass
class InferRequest:
    """One in-flight (or finished) serving request.

    ``theta`` is the (K,) doc-topic distribution once ``done``; ``z`` is
    the final per-token assignment (latency mode only). ``t_submit`` /
    ``t_done`` are ``time.monotonic`` stamps for latency accounting.
    ``model_version`` is the version tag of the model the request decoded
    under (stamped at admission — or at submit for instantly-completed
    requests; ``-1`` until then), the diagnostic that makes hot reloads
    auditable per request.
    """

    uid: int
    words: np.ndarray  # filtered (and possibly truncated) token ids
    key: Optional[jax.Array]  # whole-chain PRNG key (throughput mode)
    num_sweeps: int
    burn_in: int
    thin: int
    orig_len: int = 0
    truncated: bool = False
    dropped_unknown: int = 0
    theta: Optional[np.ndarray] = None
    done: bool = False
    # lifecycle / SLA bookkeeping
    admitted: bool = False
    ticks_waited: int = 0
    model_version: int = -1
    t_submit: float = 0.0
    t_done: float = 0.0
    # in-flight bookkeeping
    sweeps_done: int = 0
    theta_sum: Optional[np.ndarray] = None
    theta_samples: int = 0
    z: Optional[np.ndarray] = None  # final assignments (latency mode)


@dataclasses.dataclass
class _ModelSlot:
    """One servable model version: the frozen counts plus everything the
    decode paths derive from them (backend tables, the asymmetric-prior
    alpha_k, and the per-bucket jitted programs). ``reload`` builds a new
    slot and swaps the engine's current pointer; buckets still decoding
    pin the slot they were admitted under, so an old version stays alive
    exactly as long as its in-flight requests."""

    model: FrozenLDAModel
    aux: Any
    alpha_k: np.ndarray
    version: int
    # jit caches keyed by bucket length; shared between slots whose hyper
    # is equal (the closures capture only hyper + engine knobs — the
    # counts are traced arguments, so XLA handles shape changes itself)
    sweep_fns: Dict[int, Any]
    rtlda_fns: Dict[int, Any]


class _Bucket:
    """One fixed-shape slot batch: all device state for bucket width L.

    ``slot_model`` pins the model version the bucket's current occupants
    decode under: it is (re)tagged to the engine's current slot whenever
    a request is placed into an *empty* bucket, and never changes while
    any slot is active — the invariant that lets ``reload`` swap the
    engine's model without touching in-flight chains."""

    def __init__(self, length: int, slots: int, num_topics: int):
        self.length = length
        self.words = jnp.zeros((slots, length), jnp.int32)
        self.mask = jnp.zeros((slots, length), bool)
        self.z = jnp.zeros((slots, length), jnp.int32)
        self.n_kd = jnp.zeros((slots, num_topics), jnp.int32)
        self.active: List[Optional[InferRequest]] = [None] * slots
        # per slot: the chain's per-sweep keys as host bits, shape
        # (num_sweeps, *key_data_shape) uint32 (None when the slot is empty)
        self.sweep_keys: List[Optional[np.ndarray]] = [None] * slots
        self.slot_model: Optional[_ModelSlot] = None

    def free_slot(self) -> Optional[int]:
        for s, r in enumerate(self.active):
            if r is None:
                return s
        return None

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self.active)


class CheckpointWatcher:
    """Poll a model-checkpoint directory and push every new committed
    step through ``reload_fn`` — the consuming half of the live
    train→serve pipeline, shared by :class:`LDAEngine` and
    ``serving.router.LDARouter``.

    Failure policy (the old inline watcher swallowed *every* OSError/
    ValueError/KeyError forever, so a corrupt checkpoint looked exactly
    like an empty directory): a load failure is **benign** only while
    nothing is committed yet (``FileNotFoundError`` with no committed
    step dirs — the trainer simply hasn't written one). Anything else —
    a committed step that fails to load (truncated leaf, bad manifest),
    or repeated errors with committed steps present — is a real failure:
    it is retried up to ``max_failures`` consecutive times with a logged
    warning each, then the watcher gives up. The last error is surfaced
    on :attr:`error` and returned by :meth:`stop` (and by the owners'
    ``stop_watching()`` / ``watch_error``); a successful load clears it
    and resets the retry budget.
    """

    def __init__(
        self,
        reload_fn: Callable[["FrozenLDAModel"], Any],
        directory: str,
        period: float = 1.0,
        initial_step: Optional[int] = None,
        max_failures: int = 8,
    ):
        self.reload_fn = reload_fn
        self.directory = directory
        self.period = period
        self.max_failures = max_failures
        self.error: Optional[Exception] = None
        self.failures = 0  # consecutive
        self.last_step = initial_step
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="lda-ckpt-watcher", daemon=True
        )

    def start(self) -> "CheckpointWatcher":
        self._thread.start()
        return self

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self) -> Optional[Exception]:
        """Stop polling; returns the last load error (None = healthy)."""
        self._stop.set()
        self._thread.join()
        return self.error

    def _loop(self) -> None:
        from repro.train.checkpoint import committed_steps, load_lda_model

        while not self._stop.is_set():
            try:
                n_wk, n_k, hyper, _meta, step = load_lda_model(
                    self.directory
                )
            except (OSError, ValueError, KeyError) as exc:
                if (isinstance(exc, FileNotFoundError)
                        and not committed_steps(self.directory)):
                    # benign: nothing committed yet — keep waiting, and
                    # don't let an empty dir burn the retry budget
                    self.failures = 0
                else:
                    self.failures += 1
                    self.error = exc
                    logger.warning(
                        "checkpoint watch of %r: load failed (%d/%d): %s",
                        self.directory, self.failures, self.max_failures,
                        exc,
                    )
                    if self.failures >= self.max_failures:
                        logger.warning(
                            "checkpoint watch of %r: giving up after %d "
                            "consecutive failures",
                            self.directory, self.failures,
                        )
                        return
                self._stop.wait(self.period)
                continue
            self.failures = 0
            self.error = None
            if self.last_step is None or step > self.last_step:
                self.reload_fn(FrozenLDAModel(
                    n_wk=jnp.asarray(n_wk, jnp.int32),
                    n_k=jnp.asarray(n_k, jnp.int32),
                    hyper=hyper,
                ))
                self.last_step = step
            self._stop.wait(self.period)


class LDAEngine:
    """Continuously-admitting batched frozen-model inference.

    Two call styles front the same bucketed packer:

    * **Blocking batch** — :meth:`infer_batch` submits many documents,
      drains the engine, and returns the (N, K) thetas in order.
    * **Async tickets** — :meth:`submit_async` returns a ticket
      immediately; :meth:`poll` reports ``queued``/``admitted``/``done``;
      :meth:`result` blocks (with optional timeout), returns theta, and
      reaps the ticket. Drive ticks either inline (``result`` steps the
      engine itself when no ticker runs) or via the background ticker
      (:meth:`start`/:meth:`stop`).

    All public methods are thread-safe (one engine-wide lock).
    """

    def __init__(self, model: FrozenLDAModel, cfg: LDAServeConfig,
                 seed: int = 0):
        if not cfg.buckets:
            raise ValueError("need at least one bucket length")
        if cfg.mode not in ("throughput", "latency"):
            raise ValueError(f"unknown serve mode {cfg.mode!r}")
        self.cfg = cfg
        self.backend = algorithms.get(cfg.algorithm)
        self._knobs = cfg.knobs()
        self._mesh = None
        if cfg.mesh_shape is not None:
            if cfg.mode == "latency":
                raise ValueError(
                    "latency mode (RT-LDA) does not shard: drop "
                    "mesh_shape or serve mode='throughput'"
                )
            if len(cfg.mesh_shape) != 2 or cfg.mesh_shape[0] != 1:
                raise ValueError(
                    f"serving mesh_shape must be (1, m) — word rows shard "
                    f"over the model axis, replicas come from the router "
                    f"— got {cfg.mesh_shape!r}"
                )
            from repro.utils import compat

            self._mesh = compat.make_mesh(
                tuple(cfg.mesh_shape), ("data", "model")
            )
        self._current = self._build_slot(model, version=0)
        self._buckets = {
            length: _Bucket(length, cfg.max_batch, model.num_topics)
            for length in sorted(cfg.buckets)
        }
        self._base_key = jax.random.key(seed)
        # the key bits every sweep hands to a slot without a live chain
        dummy_key = jax.random.key(0)
        self._key_impl = jax.random.key_impl(dummy_key)
        self._dummy_bits = np.asarray(jax.random.key_data(dummy_key))
        self.queue: List[InferRequest] = []
        self._instant: List[InferRequest] = []  # empty docs: done at submit
        self._uid = 0
        self.docs_done = 0
        self.sweeps_run = 0  # jitted bucket sweeps/decodes executed
        self.reloads = 0
        self.spills = 0  # SLA bucket spills (max_slot_wait admissions)
        self.ticks = 0  # admission ticks run (``step`` calls)
        # bucket packing, summed over every bucket sweep: the real tokens
        # of the active slots, and the tokens the kernel swept (every
        # slot of the bucket, empty ones included)
        self.tokens_swept = 0
        self.slot_tokens_swept = 0
        # runtime SLA knobs: seeded from cfg, retuned in place by the
        # autopilot — cfg itself stays frozen (it is the *requested*
        # setup; these are the *current* values, see the properties below)
        self._tick_period = cfg.tick_period or 0.001
        self._max_slot_wait = cfg.max_slot_wait
        self._pending_buckets: Optional[Tuple[int, ...]] = None
        # observability + autopilot (DESIGN.md §8): built ONLY when
        # enabled — off means no telemetry objects exist and every tick
        # runs the exact pre-observability code path
        self._telemetry = None
        self._autopilot = None
        if cfg.metrics_out or cfg.autopilot:
            from repro.observe import JsonlSink, MetricsRegistry, ServeTelemetry

            sink = JsonlSink(cfg.metrics_out) if cfg.metrics_out else None
            arrivals = cfg.autopilot_window or 64
            self._telemetry = ServeTelemetry(
                MetricsRegistry(sink),
                window_ticks=max(8, 4 * arrivals),
                window_arrivals=arrivals,
            )
        if cfg.autopilot:
            from repro.autotune import ServeAutopilot

            self._autopilot = ServeAutopilot()
        # async front
        self._tickets: Dict[int, InferRequest] = {}
        self._cv = threading.Condition(threading.RLock())
        self._ticker: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        # checkpoint watcher (watch_checkpoint_dir)
        self._watcher: Optional[CheckpointWatcher] = None

    # -- the current model slot --------------------------------------------
    @property
    def model(self) -> FrozenLDAModel:
        """The model new admissions decode under (the *current* slot —
        in-flight buckets may still be finishing an older version)."""
        return self._current.model

    @property
    def model_version(self) -> int:
        """Version tag of the current model slot (0 at construction,
        bumped by every :meth:`reload`)."""
        return self._current.version

    @property
    def _alpha_k(self) -> np.ndarray:
        return self._current.alpha_k

    def _build_slot(self, model: FrozenLDAModel, version: int,
                    share_from: Optional[_ModelSlot] = None) -> _ModelSlot:
        if self._mesh is not None and not isinstance(
            model, ShardedFrozenLDAModel
        ):
            model = ShardedFrozenLDAModel.shard(model, self._mesh)
        # latency mode never runs backend sweeps — skip table builds
        # (zen_cdf's prepare_infer materializes a (W, K) CDF)
        if self.cfg.mode == "latency":
            aux = None
        elif isinstance(model, ShardedFrozenLDAModel):
            aux = sharded_prepare_infer(self.backend, model, self._knobs)
        else:
            aux = self.backend.prepare_infer(
                model.n_wk, model.n_k, model.hyper, self._knobs
            )
        # the jitted per-bucket programs close over hyper only (counts
        # and tables are traced arguments) — same hyper, same programs.
        # Sharded programs additionally close over the static row layout
        # (words_per_shard / W / shard count), so the caches only carry
        # across reloads that keep it.
        share = (
            share_from is not None
            and share_from.model.hyper == model.hyper
            and layout_key(share_from.model) == layout_key(model)
        )
        return _ModelSlot(
            model=model,
            aux=aux,
            alpha_k=np.asarray(model.hyper.alpha_k(model.n_k), np.float32),
            version=version,
            sweep_fns=share_from.sweep_fns if share else {},
            rtlda_fns=share_from.rtlda_fns if share else {},
        )

    def reload(self, model: FrozenLDAModel,
               version: Optional[int] = None) -> int:
        """Atomically swap in a new model between admission ticks.

        The swap only moves the engine's *current* slot pointer: requests
        admitted from now on decode under ``model``; every in-flight
        request keeps decoding under the slot its bucket pinned at
        admission and completes on that model (its
        ``InferRequest.model_version`` says which). Nothing is dropped,
        nothing re-decodes, and a bucket starts serving the new version
        as soon as it drains.

        Args:
            model: the new frozen model. Vocabulary/topic-count changes
                are allowed (buckets re-shape their count state when they
                re-tag); hyper changes rebuild the jit caches.
            version: explicit version tag (must be greater than the
                current one); default is ``current + 1``.

        Returns:
            The new version tag.
        """
        with self._cv:
            new_version = (self._current.version + 1 if version is None
                           else int(version))
            if new_version <= self._current.version:
                raise ValueError(
                    f"model version must increase: {new_version} <= "
                    f"{self._current.version}"
                )
            self._current = self._build_slot(
                model, new_version, share_from=self._current
            )
            self.reloads += 1
            return new_version

    def watch_checkpoint_dir(
        self,
        directory: str,
        period: float = 1.0,
        initial_step: Optional[int] = None,
        max_failures: int = 8,
    ) -> None:
        """Poll a model-checkpoint directory and reload every new step.

        The consuming half of the live pipeline (``launch/train.py
        --stream`` writes steps, this follows them): a
        :class:`CheckpointWatcher` daemon checks ``directory`` every
        ``period`` seconds for a committed ``save_lda_model`` checkpoint
        with a step newer than the last one seen and
        hot-:meth:`reload`\\ s it. An empty directory is quietly
        retried; a committed checkpoint that fails to load (truncated
        leaf, torn manifest) is retried ``max_failures`` times with
        logged warnings and then surfaced on :attr:`watch_error` (see
        :class:`CheckpointWatcher` for the policy). Idempotent while a
        watcher runs; stop with :meth:`stop_watching`.

        Args:
            directory: the ``checkpoint_dir`` a trainer writes model
                checkpoints into.
            period: poll cadence in seconds.
            initial_step: treat this step as already served (pass the
                step the engine's construction model came from to avoid
                one redundant reload); default reloads the first
                checkpoint the watcher sees.
            max_failures: consecutive real load failures before the
                watcher gives up.
        """
        with self._cv:
            if self._watcher is not None and self._watcher.is_alive():
                return
            self._watcher = CheckpointWatcher(
                self.reload, directory, period=period,
                initial_step=initial_step, max_failures=max_failures,
            ).start()

    @property
    def watch_error(self) -> Optional[Exception]:
        """Last checkpoint-watcher load error (None = healthy / no
        watcher). Non-None with a dead watcher means it gave up — the
        engine keeps serving its current model, but the pipeline needs
        an operator."""
        watcher = self._watcher
        return None if watcher is None else watcher.error

    def stop_watching(self) -> Optional[Exception]:
        """Stop the checkpoint watcher (no-op if none is running). The
        currently-loaded model keeps serving. Returns the watcher's last
        load error, None when it was healthy (or never ran)."""
        watcher = self._watcher
        if watcher is None:
            return None
        err = watcher.stop()
        self._watcher = None
        return err

    # -- request intake ----------------------------------------------------
    def submit(
        self,
        words,
        key: Optional[jax.Array] = None,
        num_sweeps: Optional[int] = None,
        burn_in: Optional[int] = None,
        thin: Optional[int] = None,
    ) -> int:
        """Queue one document for inference; returns its uid.

        Args:
            words: 1-D array-like of int token ids (any shape is
                flattened). Unknown ids (outside ``[0, W)``) are dropped;
                documents longer than the widest bucket are truncated to
                it; a document that ends up empty completes immediately
                with the normalized prior theta.
            key: whole-chain PRNG key for this request (throughput mode;
                default derives one from the engine seed + uid). Ignored
                in latency mode — RT-LDA decoding is deterministic.
            num_sweeps: CGS sweeps for this request's chain (default
                ``cfg.num_sweeps``; ``<= 0`` completes from the initial
                assignment). Ignored in latency mode, which always runs
                ``cfg.rtlda_sweeps`` fused argmax passes.
            burn_in / thin: per-request estimator knobs (see
                :class:`LDAServeConfig`). Ignored in latency mode.

        Returns:
            The request uid. The finished request (theta, diagnostics,
            timestamps) comes back from :meth:`step` /
            :meth:`run_until_done` — *to whoever called them*, so plain
            ``submit`` is for caller-driven engines only: with the
            background ticker running (:meth:`start`), the ticker's own
            steps collect (and discard) finished non-ticketed requests.
            Use :meth:`submit_async` + :meth:`result` whenever a ticker
            may be driving.
        """
        with self._cv:
            return self._submit(words, key, num_sweeps, burn_in, thin).uid

    def submit_async(
        self,
        words,
        key: Optional[jax.Array] = None,
        num_sweeps: Optional[int] = None,
        burn_in: Optional[int] = None,
        thin: Optional[int] = None,
    ) -> int:
        """Queue one document and return a pollable ticket immediately.

        Same arguments and admission behavior as :meth:`submit`; the
        request additionally registers in the ticket table, so its
        lifecycle is observable with :meth:`poll` and its theta
        retrievable (exactly once) with :meth:`result`. The caller never
        blocks: the request coalesces into the next admission tick's
        batch — whoever drives ticks (the background ticker started with
        :meth:`start`, another thread calling :meth:`step`, or this
        caller's own later :meth:`result`).

        Returns:
            The ticket (an int uid) to pass to :meth:`poll` /
            :meth:`result`.
        """
        with self._cv:
            req = self._submit(words, key, num_sweeps, burn_in, thin)
            self._tickets[req.uid] = req
            return req.uid

    def _submit(self, words, key, num_sweeps, burn_in, thin) -> InferRequest:
        self._uid += 1
        raw = np.asarray(words, np.int32).ravel()
        known = raw[(raw >= 0) & (raw < self.model.num_words)]
        max_len = max(self._buckets)
        latency = self.cfg.mode == "latency"
        req = InferRequest(
            uid=self._uid,
            words=known[:max_len],
            # latency mode is deterministic — never pay the fold_in
            key=None if latency else (
                key if key is not None
                else jax.random.fold_in(self._base_key, self._uid)
            ),
            num_sweeps=self.cfg.rtlda_sweeps if latency
            else (self.cfg.num_sweeps if num_sweeps is None else num_sweeps),
            burn_in=-1 if latency
            else (self.cfg.burn_in if burn_in is None else burn_in),
            thin=1 if latency
            else max(1, self.cfg.thin if thin is None else thin),
            orig_len=int(raw.shape[0]),
            truncated=known.shape[0] > max_len,
            dropped_unknown=int(raw.shape[0] - known.shape[0]),
            t_submit=time.monotonic(),
        )
        if req.words.shape[0] == 0:
            # nothing observed: theta is the normalized prior
            req.model_version = self._current.version
            req.theta = self._alpha_k / self._alpha_k.sum()
            self._complete(req)
            self._instant.append(req)
        elif not latency and req.num_sweeps <= 0:
            # zero sweeps: theta straight from the z0 assignment, matching
            # the oracle's empty scan (never occupies a slot)
            req.model_version = self._current.version
            z0 = np.asarray(jax.random.randint(
                req.key, (req.words.shape[0],), 0, self.model.num_topics,
                dtype=jnp.int32,
            ))
            n_kd0 = np.bincount(
                z0, minlength=self.model.num_topics
            ).astype(np.int32)
            req.theta = self._theta(req, n_kd0, self._alpha_k)
            self._complete(req)
            self._instant.append(req)
        else:
            self.queue.append(req)
        if self._telemetry is not None:
            self._telemetry.record_submit(req.t_submit,
                                          int(req.words.shape[0]))
        return req

    def _complete(self, req: InferRequest) -> None:
        req.done = True
        req.t_done = time.monotonic()
        self.docs_done += 1

    # -- the async ticket lifecycle ----------------------------------------
    def poll(self, ticket: int) -> str:
        """Report a ticket's lifecycle state without blocking.

        Returns ``"queued"`` (waiting for a bucket slot), ``"admitted"``
        (packed into a slot batch / decoding), or ``"done"`` (theta
        ready — collect it with :meth:`result`). Raises ``KeyError`` for
        a ticket that was never issued by :meth:`submit_async` or was
        already reaped by :meth:`result`.
        """
        with self._cv:
            req = self._tickets.get(ticket)
            if req is None:
                raise KeyError(f"unknown or reaped ticket {ticket}")
            if req.done:
                return "done"
            return "admitted" if req.admitted else "queued"

    def result(self, ticket: int, timeout: Optional[float] = None
               ) -> np.ndarray:
        """Block until a ticket's theta is ready; return it and reap.

        If a background ticker is running (:meth:`start`), this waits on
        it; otherwise the caller drives admission ticks itself, so
        progress never depends on another thread. ``timeout`` is in
        seconds (``None`` = wait forever; ``0`` = must already be done).

        Returns:
            theta — the (K,) float32 doc-topic distribution.

        Raises:
            KeyError: unknown or already-reaped ticket.
            TimeoutError: theta not ready within ``timeout`` seconds.

        The ticket is consumed: a second ``result`` (or ``poll``) for it
        raises ``KeyError``. Keep the uid-indexed thetas yourself if you
        need them twice. A ``TimeoutError`` does NOT consume the ticket —
        retry ``result`` later, or :meth:`cancel` it if you are
        abandoning the request (otherwise its entry stays claimable, and
        accumulating abandoned tickets is a leak in a long-running
        server).
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            req = self._tickets.get(ticket)
            if req is None:
                raise KeyError(f"unknown or reaped ticket {ticket}")
            while not req.done:
                if deadline is not None and time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"ticket {ticket} not done within {timeout}s"
                    )
                if self._ticker is not None and self._ticker.is_alive():
                    # bounded wait so a ticker stopped mid-flight hands
                    # driving back to this caller instead of stranding it
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    self._cv.wait(0.05 if remaining is None
                                  else min(remaining, 0.05))
                else:
                    self.step()
            del self._tickets[ticket]
            return req.theta

    def cancel(self, ticket: int) -> bool:
        """Abandon a ticket: drop it from the ticket table and from
        wherever its request lives — the admission queue (it will never
        decode) or, if it was already admitted, its bucket slot (the
        slot is evacuated immediately).

        Evacuating admitted requests matters beyond freeing a slot one
        tick earlier: a bucket pins the model version its occupants were
        admitted under, so a cancelled-but-still-decoding request used
        to be a *zombie* — under an engine driven by cancel-then-reload
        traffic it could keep its bucket on the old model arbitrarily
        long, blocking admission there (``_admittable`` refuses
        cross-version co-residency) while nobody was waiting for its
        theta. Cancel and the stepping loop hold the same engine lock,
        so the slot arrays are never mutated mid-sweep; a sweep already
        dispatched just computes one masked-out garbage row.

        Call this for every ticket you stop waiting on (e.g. after a
        :meth:`result` timeout you don't intend to retry), or abandoned
        entries accumulate for the engine's lifetime.

        Returns:
            True if the ticket existed (now reaped), False if it was
            unknown or already reaped — cancel never raises, so timeout
            cleanup paths can call it unconditionally.
        """
        with self._cv:
            req = self._tickets.pop(ticket, None)
            if req is None:
                return False
            if req.done:
                return True
            if req.admitted:
                for bucket in self._buckets.values():
                    for slot, r in enumerate(bucket.active):
                        if r is req:
                            bucket.active[slot] = None
                            bucket.sweep_keys[slot] = None
                            bucket.mask = bucket.mask.at[slot].set(False)
                            return True
            else:
                self.queue = [r for r in self.queue if r.uid != ticket]
            return True

    def request(self, ticket: int) -> InferRequest:
        """The live :class:`InferRequest` behind an un-reaped ticket
        (diagnostics: timestamps, truncation, sweep counts). Raises
        ``KeyError`` after :meth:`result` reaped it."""
        with self._cv:
            req = self._tickets.get(ticket)
            if req is None:
                raise KeyError(f"unknown or reaped ticket {ticket}")
            return req

    # -- background ticker -------------------------------------------------
    def start(self, tick_period: Optional[float] = None) -> None:
        """Start the background admission ticker.

        Every ``tick_period`` seconds (default ``cfg.tick_period``, or
        1 ms when that is 0) the ticker runs one :meth:`step` if any work
        is pending, so async submitters coalesce into batches without any
        caller driving the engine. Idempotent while running. While a
        ticker drives, retrieve results through tickets
        (:meth:`submit_async` + :meth:`result`): finished requests from
        plain :meth:`submit` are returned only to whichever caller's
        ``step`` finished them — here, the ticker, which discards them.
        """
        with self._cv:
            if self._ticker is not None and self._ticker.is_alive():
                return
            if tick_period is not None:
                self._tick_period = tick_period
            self._stop_evt = threading.Event()

            def loop():
                # the period is re-read every iteration: the autopilot
                # retunes ``self._tick_period`` in place and the ticker
                # follows from the next wait on — no restart needed
                while not self._stop_evt.is_set():
                    with self._cv:
                        if self._pending():
                            self.step()
                    self._stop_evt.wait(self._tick_period)

            self._ticker = threading.Thread(
                target=loop, name="lda-engine-ticker", daemon=True
            )
            self._ticker.start()

    def stop(self) -> None:
        """Stop the background ticker (no-op if it is not running).
        In-flight requests stay queued/admitted and finish under whoever
        drives ticks next."""
        ticker = self._ticker
        if ticker is None:
            return
        self._stop_evt.set()
        ticker.join()
        self._ticker = None

    def _pending(self) -> bool:
        return bool(
            self.queue or self._instant
            or any(b.num_active for b in self._buckets.values())
        )

    @property
    def load(self) -> int:
        """Queued + in-flight request count — the admission-pressure
        signal ``serving.router.LDARouter`` balances replicas on."""
        with self._cv:
            return len(self.queue) + sum(
                b.num_active for b in self._buckets.values()
            )

    def warm(self) -> None:
        """Compile every bucket's decode program before traffic arrives:
        one minimal document per bucket width through the normal path,
        so first-request latency never pays a jit trace."""
        self.infer_batch(
            [np.zeros(bl, np.int32) for bl in self.bucket_widths]
        )

    # -- runtime SLA knobs (autopilot-visible; DESIGN.md §8.4) --------------
    @property
    def tick_period(self) -> float:
        """The CURRENT ticker cadence (cfg seed, autopilot-retuned)."""
        return self._tick_period

    @property
    def max_slot_wait(self) -> int:
        """The CURRENT bucket-spill SLA knob (cfg seed, autopilot-retuned)."""
        return self._max_slot_wait

    @property
    def bucket_widths(self) -> Tuple[int, ...]:
        """The CURRENT bucket lengths, ascending."""
        return tuple(sorted(self._buckets))

    def _apply_pending_buckets(self) -> None:
        """Swap in an autopilot-proposed bucket grid, but only once every
        bucket has drained — the same discipline as a hot model reload:
        in-flight slot state is never reshaped under a running decode.
        Queued requests survive the swap (their words re-bucket at the
        next admission; over-long ones truncate to the new widest)."""
        if self._pending_buckets is None:
            return
        if any(b.num_active for b in self._buckets.values()):
            return
        widths = self._pending_buckets
        self._pending_buckets = None
        k = self._current.model.num_topics
        self._buckets = {
            length: _Bucket(length, self.cfg.max_batch, k)
            for length in sorted(widths)
        }
        max_len = max(self._buckets)
        for req in self.queue:
            if req.words.shape[0] > max_len:
                req.words = req.words[:max_len]
                req.truncated = True

    def _observe_tick(self, finished: List[InferRequest]) -> None:
        """Measure this tick; when it closes a telemetry window, let the
        autopilot derive new SLA knobs from the window's summary and
        apply them (period/spill immediately — the next tick reads them;
        buckets deferred to a full drain). Called under the engine lock
        from :meth:`step`."""
        summary = self._telemetry.record_tick(
            queue_depth=len(self.queue),
            occupancy=sum(b.num_active for b in self._buckets.values()),
            finished=finished,
            spills_total=self.spills,
            tick_period=self._tick_period,
            max_slot_wait=self._max_slot_wait,
            bucket_widths=self.bucket_widths,
            model_version=self._current.version,
            tokens_swept=self.tokens_swept,
            slot_tokens_swept=self.slot_tokens_swept,
        )
        if summary is None or self._autopilot is None:
            return
        decision = self._autopilot.decide(
            summary,
            tick_period=self._tick_period,
            max_slot_wait=self._max_slot_wait,
            buckets=self.bucket_widths,
        )
        if decision is None:
            return
        applied = False
        if decision.tick_period is not None:
            self._tick_period = float(decision.tick_period)
            applied = True
        if decision.max_slot_wait is not None:
            self._max_slot_wait = int(decision.max_slot_wait)
            applied = True
        if (decision.buckets is not None
                and tuple(sorted(decision.buckets)) != self.bucket_widths):
            self._pending_buckets = tuple(sorted(decision.buckets))
            applied = True
        rec = decision.to_record()
        rec["applied"] = applied
        self._telemetry.emit_decision(rec)

    # -- admission ---------------------------------------------------------
    def _bucket_for(self, length: int) -> _Bucket:
        for bl in sorted(self._buckets):
            if length <= bl:
                return self._buckets[bl]
        return self._buckets[max(self._buckets)]

    def _admittable(self, bucket: _Bucket) -> Optional[int]:
        """A free slot in ``bucket`` a request may take *now*, or None.

        A drained bucket is always admittable (it re-tags to the current
        model slot at placement); an occupied bucket only admits
        co-residents of the same model version — a request must never
        join a batch that decodes under a model it wasn't admitted for.
        After a reload, occupied buckets therefore finish their old-
        version occupants first and flip to the new model when empty.
        """
        if bucket.num_active and bucket.slot_model is not self._current:
            return None
        return bucket.free_slot()

    def _admit(self) -> None:
        still_queued = []
        for req in self.queue:
            bucket = self._bucket_for(req.words.shape[0])
            slot = self._admittable(bucket)
            if slot is None and self._max_slot_wait > 0 \
                    and req.ticks_waited >= self._max_slot_wait:
                # SLA spill: the preferred bucket has been saturated for
                # max_slot_wait ticks — take any wider free slot instead
                for bl in sorted(self._buckets):
                    wider = self._buckets[bl]
                    if bl <= bucket.length or bl < req.words.shape[0]:
                        continue
                    s = self._admittable(wider)
                    if s is not None:
                        bucket, slot = wider, s
                        self.spills += 1
                        break
            if slot is None:
                req.ticks_waited += 1
                still_queued.append(req)
                continue
            self._place(req, bucket, slot)
        self.queue = still_queued

    def _place(self, req: InferRequest, bucket: _Bucket, slot: int) -> None:
        if bucket.num_active == 0:
            # empty bucket: (re)pin to the current model version; if K
            # changed across a reload, re-shape the doc-topic state
            bucket.slot_model = self._current
            k_now = self._current.model.num_topics
            if bucket.n_kd.shape[1] != k_now:
                bucket.n_kd = jnp.zeros(
                    (bucket.n_kd.shape[0], k_now), jnp.int32
                )
        l, k = bucket.length, bucket.slot_model.model.num_topics
        n = req.words.shape[0]
        words = np.zeros(l, np.int32)
        placed_model = bucket.slot_model.model
        if isinstance(placed_model, ShardedFrozenLDAModel):
            # shard-space row ids, mapped at *placement* (not submit):
            # req.words keep original ids, so a request admitted after a
            # reload relabels through the new model's permutation
            words[:n] = placed_model.relabel(req.words)
        else:
            words[:n] = req.words
        mask = np.zeros(l, bool)
        mask[:n] = True
        bucket.words = bucket.words.at[slot].set(jnp.asarray(words))
        bucket.mask = bucket.mask.at[slot].set(jnp.asarray(mask))
        bucket.active[slot] = req
        req.admitted = True
        req.model_version = bucket.slot_model.version
        if self.cfg.mode == "latency":
            # RT-LDA needs no chain state: z/n_kd are produced whole by
            # the fused decode, nothing to initialize per slot
            bucket.sweep_keys[slot] = None
            return
        # same schedule as cgs_infer: z0 from the request key itself, sweep
        # j from split(key)[j]; randint/uniform draws are prefix-stable in
        # the padded length, so the bucket width never changes the chain.
        # One device->host copy brings z0 and the sweep keys' host bits.
        z0 = jax.random.randint(req.key, (l,), 0, k, dtype=jnp.int32)
        z0_np, bucket.sweep_keys[slot] = jax.device_get((
            z0, jax.random.key_data(jax.random.split(req.key, req.num_sweeps))
        ))
        n_kd = np.bincount(z0_np[:n], minlength=k).astype(np.int32)
        bucket.z = bucket.z.at[slot].set(z0)
        bucket.n_kd = bucket.n_kd.at[slot].set(jnp.asarray(n_kd))

    # -- the jitted per-bucket programs -------------------------------------
    def _sweep_fn(self, slot_model: _ModelSlot, length: int):
        """Throughput mode: one chain CGS sweep over a bucket's slots.
        Cached on the model slot (shared across reloads with equal
        hyper — the counts are traced arguments). Sharded slots get the
        ``shard_map`` program instead — same signature, so the stepping
        loop is layout-blind."""
        if length not in slot_model.sweep_fns:
            if isinstance(slot_model.model, ShardedFrozenLDAModel):
                slot_model.sweep_fns[length] = make_sharded_sweep_fn(
                    self.backend, self._knobs, slot_model.model,
                    slot_model.aux,
                )
                return slot_model.sweep_fns[length]
            backend, knobs, impl = self.backend, self._knobs, self._key_impl
            hyper = slot_model.model.hyper

            def fn(key_bits, words, mask, z, n_kd, n_wk, n_k, aux):
                keys = jax.random.wrap_key_data(key_bits, impl=impl)
                z_new = backend.infer_sweep(
                    keys, words, mask, z, n_kd, n_wk, n_k, hyper, knobs, aux
                )
                z_new = jnp.where(mask, z_new, z)
                onehot = (
                    jax.nn.one_hot(z_new, hyper.num_topics, dtype=jnp.int32)
                    * mask[..., None]
                )
                return z_new, jnp.sum(onehot, axis=1)

            slot_model.sweep_fns[length] = jax.jit(fn)
        return slot_model.sweep_fns[length]

    def _rtlda_fn(self, slot_model: _ModelSlot, length: int):
        """Latency mode: the whole RT-LDA decode for one bucket, fused
        into a single dispatch (init + ``rtlda_sweeps`` argmax passes)."""
        if length not in slot_model.rtlda_fns:
            hyper = slot_model.model.hyper
            sweeps = self.cfg.rtlda_sweeps

            def fn(words, mask, n_wk, n_k):
                return jax.vmap(
                    lambda w, m: rtlda_assign(n_wk, n_k, w, m, hyper, sweeps)
                )(words, mask)

            slot_model.rtlda_fns[length] = jax.jit(fn)
        return slot_model.rtlda_fns[length]

    # -- stepping ----------------------------------------------------------
    def step(self) -> List[InferRequest]:
        """Run one admission tick; return the requests it finished.

        Throughput mode: admit into free slots, run one chain sweep per
        non-empty bucket, finish ripe chains. Latency mode: admit, run
        one fused RT-LDA decode per non-empty bucket — every admitted
        request finishes in the same tick.

        The tick is the trace span ``zen.engine.tick``; its children are
        ``zen.engine.admit`` and, per bucket, ``zen.engine.keys``,
        ``zen.engine.sweep`` and ``zen.engine.finish``.
        """
        with self._cv, span("engine.tick"):
            self.ticks += 1
            self._apply_pending_buckets()
            finished = (self._latency_step() if self.cfg.mode == "latency"
                        else self._throughput_step())
            if self._telemetry is not None:
                self._observe_tick(finished)
            if finished and self._tickets:
                self._cv.notify_all()
            return finished

    def _count_sweep(self, bucket: _Bucket) -> None:
        self.sweeps_run += 1
        self.tokens_swept += sum(r.words.shape[0] for r in bucket.active
                                 if r is not None)
        self.slot_tokens_swept += bucket.length * len(bucket.active)

    def _latency_step(self) -> List[InferRequest]:
        with span("engine.admit"):
            self._admit()
        finished, self._instant = self._instant, []
        for bucket in self._buckets.values():
            if bucket.num_active == 0:
                continue
            sm = bucket.slot_model  # pinned: in-flight = admitted model
            with span("engine.sweep"):
                z, n_kd = self._rtlda_fn(sm, bucket.length)(
                    bucket.words, bucket.mask, sm.model.n_wk, sm.model.n_k
                )
            self._count_sweep(bucket)
            with span("engine.finish"):
                z_host, n_kd_host = np.asarray(z), np.asarray(n_kd)
                for slot, req in enumerate(bucket.active):
                    if req is None:
                        continue
                    req.sweeps_done = req.num_sweeps
                    req.z = z_host[slot, : req.words.shape[0]].copy()
                    self._finish(req, bucket, slot, n_kd_host[slot],
                                 clear_mask=False)
                    finished.append(req)
                bucket.mask = jnp.zeros_like(bucket.mask)  # one bulk clear
        return finished

    def _throughput_step(self) -> List[InferRequest]:
        with span("engine.admit"):
            self._admit()
        finished, self._instant = self._instant, []
        for bucket in self._buckets.values():
            if bucket.num_active == 0:
                continue
            with span("engine.keys"):
                keys = self._sweep_key_bits(bucket)
            sm = bucket.slot_model  # pinned: in-flight = admitted model
            with span("engine.sweep"):
                bucket.z, bucket.n_kd = self._sweep_fn(sm, bucket.length)(
                    keys, bucket.words, bucket.mask, bucket.z, bucket.n_kd,
                    sm.model.n_wk, sm.model.n_k, sm.aux,
                )
            self._count_sweep(bucket)
            with span("engine.finish"):
                finished.extend(self._finish_ripe(bucket, sm))
        return finished

    def _sweep_key_bits(self, bucket: _Bucket) -> np.ndarray:
        """The bucket sweep's keys as one host array of key bits, shape
        (slots, *key_data_shape) uint32: row ``sweeps_done`` of each live
        chain's table, the dummy bits for every other slot."""
        bits = np.repeat(self._dummy_bits[None], len(bucket.active), axis=0)
        for s, req in enumerate(bucket.active):
            table = bucket.sweep_keys[s]
            if (req is not None and table is not None
                    and req.sweeps_done < req.num_sweeps):
                bits[s] = table[req.sweeps_done]
        return bits

    def _finish_ripe(self, bucket: _Bucket,
                     sm: _ModelSlot) -> List[InferRequest]:
        """After a throughput sweep: take the posterior-mean samples due
        and finish the chains that ran all their sweeps."""
        finished = []
        n_kd_host = None
        for slot, req in enumerate(bucket.active):
            if req is None:
                continue
            req.sweeps_done += 1
            want_sample = (
                req.burn_in >= 0
                and req.sweeps_done > req.burn_in
                and (req.sweeps_done - req.burn_in) % req.thin == 0
            )
            ripe = req.sweeps_done >= req.num_sweeps
            if want_sample or ripe:
                if n_kd_host is None:
                    n_kd_host = np.asarray(bucket.n_kd)
                if want_sample:
                    if req.theta_sum is None:
                        req.theta_sum = np.zeros(
                            sm.model.num_topics, np.float32
                        )
                    req.theta_sum += self._theta(req, n_kd_host[slot],
                                                 sm.alpha_k)
                    req.theta_samples += 1
            if ripe:
                self._finish(req, bucket, slot,
                             None if n_kd_host is None
                             else n_kd_host[slot])
                finished.append(req)
        return finished

    def _theta(self, req: InferRequest, n_kd_row: np.ndarray,
               alpha_k: np.ndarray) -> np.ndarray:
        l = req.words.shape[0]
        return (n_kd_row.astype(np.float32) + alpha_k) / (
            l + alpha_k.sum()
        )

    def _finish(self, req: InferRequest, bucket: _Bucket, slot: int,
                n_kd_row: Optional[np.ndarray],
                clear_mask: bool = True) -> None:
        if req.theta_samples:
            req.theta = req.theta_sum / req.theta_samples
        else:
            if n_kd_row is None:  # num_sweeps == 0: counts from z0
                n_kd_row = np.asarray(bucket.n_kd[slot])
            # prior smoothing from the model the request decoded under
            req.theta = self._theta(req, n_kd_row, bucket.slot_model.alpha_k)
        bucket.active[slot] = None
        bucket.sweep_keys[slot] = None
        if clear_mask:
            bucket.mask = bucket.mask.at[slot].set(False)
        self._complete(req)

    def run_until_done(self, max_steps: int = 100_000) -> List[InferRequest]:
        """Drive ticks until the queue and every bucket drain; return all
        requests finished along the way (instant completions included)."""
        with self._cv:
            done: List[InferRequest] = list(self._instant)
            self._instant = []
            for _ in range(max_steps):
                done.extend(self.step())
                if not self.queue and all(
                    b.num_active == 0 for b in self._buckets.values()
                ):
                    break
            return done

    def infer_batch(self, docs: Sequence, **submit_kw) -> np.ndarray:
        """Submit many documents, drain the engine, return their thetas.

        Args:
            docs: sequence of 1-D int token-id arrays (one per document).
            **submit_kw: forwarded to :meth:`submit` for every document
                (``key``/``num_sweeps``/``burn_in``/``thin``).

        Returns:
            ``(N, K)`` float32 thetas in submission order. Shape
            convention: N = ``len(docs)``, K = ``model.num_topics``; row
            n sums to 1 and is the inferred topic mixture of ``docs[n]``.

        This is the blocking convenience front; it shares admission,
        bucketing, and decoding with the async path, so the returned
        thetas are identical to what :meth:`submit_async` +
        :meth:`result` would produce for the same inputs.
        """
        with self._cv:
            uids = [self.submit(d, **submit_kw) for d in docs]
            by_uid = {r.uid: r for r in self.run_until_done()}
            missing = [u for u in uids if u not in by_uid]
            if missing:
                raise RuntimeError(f"engine did not finish requests {missing}")
            return np.stack([by_uid[u].theta for u in uids])


# -- held-out evaluation ---------------------------------------------------
def doc_completion_perplexity(
    engine: LDAEngine, docs: Sequence[np.ndarray]
) -> float:
    """Doc-completion held-out perplexity (Wallach et al.'s estimator).

    Each document is split alternately into an observed half (theta is
    inferred on it through the engine) and a held-out half, scored as
    ``p(w | theta, phi)``. Lower is better; this is the serving-quality
    number ``launch/serve_lda.py --eval`` reports.
    """
    observed, heldout = [], []
    for d in docs:
        d = np.asarray(d, np.int32)
        observed.append(d[0::2])
        heldout.append(d[1::2])
    thetas = engine.infer_batch(observed)  # (N, K)
    phi = np.asarray(engine.model.phi(), np.float32)  # (W, K)
    total_ll, total_tokens = 0.0, 0
    for theta, held in zip(thetas, heldout):
        held = held[(held >= 0) & (held < engine.model.num_words)]
        if held.shape[0] == 0:
            continue
        p = phi[held] @ theta  # (n,)
        total_ll += float(np.sum(np.log(np.maximum(p, 1e-30))))
        total_tokens += int(held.shape[0])
    if total_tokens == 0:
        return float("nan")
    return float(np.exp(-total_ll / total_tokens))


def docs_from_corpus(corpus) -> List[np.ndarray]:
    """Split an edge-list ``Corpus`` into per-document token arrays."""
    words = np.asarray(corpus.word)
    docs = np.asarray(corpus.doc)
    order = np.argsort(docs, kind="stable")
    words, docs = words[order], docs[order]
    bounds = np.searchsorted(docs, np.arange(corpus.num_docs + 1))
    return [words[bounds[d]:bounds[d + 1]] for d in range(corpus.num_docs)]
