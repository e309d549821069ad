"""Unified training sessions: one schedule-driven driver for single-box
and mesh LDA training (DESIGN.md §6).

The paper's workflow is one loop with periodic structural events — model
sync, exact count rebuild (Fig. 2), "converged" token exclusion (§5.1),
duplicate-topic merging (§4.3), capacity-sensitive sparse tables (§4-5).
This module drives that loop through exactly one API:

* ``RunConfig`` — the declarative run description (supersedes the old
  ``TrainConfig`` + ``DistConfig`` + ``LoopConfig`` triple): algorithm +
  sampler knobs (one ``SamplerKnobs`` derivation via
  ``algorithms.knobs_from``), initialization, the execution plan
  (``mesh_shape=None`` = single-box, ``(rows, cols)`` = SPMD mesh), and
  every event cadence. ``to_json``/``from_json`` round-trip, so a run is a
  file (``launch/train.py --config run.json``).

* ``TrainSession`` — resolves the backend once, selects an execution plan
  — single-box as a whole-corpus one-cell plan, mesh via
  ``grid_partition`` + ``make_dist_step`` — and exposes the same
  ``init() / step() / run() / metrics() / save_model()`` surface for both.
  Events are first-class ``Schedule`` actions (``repro.train.schedule``):
  llh/perplexity eval (with ``target_perplexity`` early stop derived from
  the *already computed* llh — no second likelihood pass), model and
  elastic training checkpoints, exclusion enablement at
  ``exclusion_start``, exact count rebuild, duplicate-topic merge, and
  periodic row-capacity re-resolution: on the ``rebuild_every`` cadence
  the padded-sparse widths are re-resolved against the *current* counts
  (``resolve_dist_row_pads``) and the jitted step is rebuilt when they
  changed, so rows that outgrow their init-frozen capacity stop being
  truncated and sharpened rows shed oversized pads.

The deprecated ``repro.core.LDATrainer`` / ``TrainConfig`` are thin shims
delegating here; new code should construct sessions directly.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import signal
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import algorithms
from repro.algorithms import SamplerKnobs, knobs_from
from repro.core import counts as counts_lib
from repro.core import init as init_lib
from repro.core.exclusion import (
    ExclusionConfig,
    active_mask,
    update_exclusion_stats,
)
from repro.core.hyper import duplicate_topic_map, merge_topics
from repro.core.likelihood import joint_llh, predictive_llh
from repro.core.types import CGSState, Corpus, LDAHyperParams
from repro.observe.metrics import span
from repro.train.schedule import ActionContext, Schedule, ScheduledAction


# ---------------------------------------------------------------------------
# RunConfig
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Declarative description of one training run (both plans).

    ``mesh_shape=None`` selects the single-box plan; ``(rows, cols)``
    selects the SPMD mesh plan (data x model axes). Cadences count
    post-step iterations (the first step is iteration 1); 0 disables.
    ``num_iterations`` is the *absolute* target iteration, so resuming a
    checkpointed run needs no arithmetic.

    Field reference (grouped as below; see DESIGN.md §6.1 for rationale):

    * ``algorithm`` — any ``algorithms.registered()`` backend name.
    * ``sampling_method`` — dense-path inversion, ``"cdf"``/``"gumbel"``;
      ``None`` = the plan default (cdf single-box, gumbel mesh).
    * ``max_kw``/``max_kd`` — padded-sparse row widths (topics per
      word/doc row); 0 = resolve from the counts (or static cell
      defaults on the mesh).
    * ``num_mh`` — LightLDA cycle-MH proposals per token.
    * ``token_chunk`` — bound peak memory by sweeping tokens in chunks
      of this size; 0 = whole sweep at once.
    * ``bt``/``bk``/``bs`` — Pallas kernel tiles: token rows, topic
      lanes, and the sparse-row lane-alignment tile (kernel suite v2).
    * ``kernels`` — Pallas kernel dispatch policy, ``"auto"`` (kernels
      on TPU, legacy XLA elsewhere) / ``"on"`` / ``"off"``.
    * ``init``/``sparse_init_degree`` — topic init strategy (paper §5.1).
    * ``mesh_shape``/``delta_dtype``/``kd_dtype`` — execution plan and
      mesh payload widths.
    * ``num_iterations`` — absolute target iteration for :meth:`TrainSession.run`.
    * ``eval_every``/``target_perplexity`` — eval cadence and the
      early-stop threshold checked on those evals.
    * ``exclusion_start``/``exclusion_min_prob`` — "converged" token
      exclusion (paper §5.1): enable iteration and resample floor.
    * ``rebuild_every`` — exact count rebuild + row re-pad cadence.
    * ``merge_every``/``merge_threshold`` — duplicate-topic merge
      (paper §4.3) cadence and L1 closeness threshold.
    * ``checkpoint_dir``/``checkpoint_every`` — serving model
      checkpoints (``launch/serve_lda.py`` loads these); 0 = final only.
    * ``train_checkpoint_dir``/``train_checkpoint_every`` — elastic
      training checkpoints (assignments; ``run()`` auto-resumes).
    * ``window_docs``/``window_sweeps``/``decay``/``stream_source`` —
      windowed online training (``repro.train.online.StreamingSession``,
      DESIGN.md §7): docs per window, CGS sweeps per window visit, the
      forgetting factor applied to the global counts at each window
      transition, and the ``CorpusSource`` spec string
      (``replay`` | ``libsvm:<path>`` | ``drift[:<seed>]``). In
      streaming mode the cadences count *windows*, not iterations, and
      ``num_iterations`` bounds the absolute window cursor (0 = run to
      source exhaustion). Batch ``TrainSession`` ignores these fields.
    * ``metrics_out``/``metrics_every`` — per-iteration telemetry JSONL
      via ``repro.observe`` (path, record cadence). ``autopilot``/
      ``autopilot_every`` — ``repro.autotune`` backend + row-capacity
      re-pick from the measured counts on a cadence (DESIGN.md §8).
      All four are inert by default: with ``metrics_out=None`` and
      ``autopilot=False`` no telemetry is built and the schedule is
      bit-identical to a pre-observability session (pinned by test).
    * ``quality_every`` + ``quality_*`` — model-quality evaluation
      (``repro.eval``, DESIGN.md §9): UMass/NPMI topic coherence over
      the top ``quality_top_n`` words per topic and (when
      ``quality_l2r_docs > 0``) Wallach left-to-right held-out
      log-likelihood, contributed to the iteration metrics as
      ``coherence_umass``/``coherence_npmi``/``l2r_llh``/
      ``l2r_per_token``. 0 disables (no evaluator is built).
    * ``hyper_every``/``hyper_alpha``/``hyper_beta_anneal``/
      ``hyper_beta_floor`` — Alg. 5 hyper-parameter optimization as a
      schedule action: a Minka fixed-point step on the scalar alpha
      concentration and geometric beta annealing toward a floor, fired
      on the cadence; compiled steps rebuild when hypers change.
      ``hyper_every=0`` disables and is pinned bit-identical to a
      no-hyper run (same contract as the autopilot).
    """

    # -- algorithm + sampler knobs (one SamplerKnobs derivation) ----------
    algorithm: str = "zen"  # any algorithms.registered() name
    # dense-path inversion method: cdf | gumbel. None = the plan default
    # (cdf single-box, gumbel on the mesh — the historical defaults of
    # TrainConfig and DistConfig, kept so neither path silently changes
    # samplers); TrainSession resolves it at construction.
    sampling_method: Optional[str] = None
    max_kw: int = 0  # padded-sparse word-row width (0 = auto from counts)
    max_kd: int = 0  # padded-sparse doc-row width (0 = auto)
    num_mh: int = 8  # LightLDA cycle-MH steps (paper uses 8)
    token_chunk: int = 0  # 0 = whole sweep at once (memory knob)
    bt: int = 256  # Pallas token tile
    bk: int = 512  # Pallas topic tile
    bs: int = 128  # sparse-row lane tile (kernel suite v2)
    kernels: str = "auto"  # Pallas kernel dispatch: auto | on | off
    # -- initialization ---------------------------------------------------
    init: str = "random"  # random | sparse_word | sparse_doc
    sparse_init_degree: float = 0.1
    # -- execution plan ---------------------------------------------------
    mesh_shape: Optional[Tuple[int, int]] = None  # None = single-box
    delta_dtype: str = "int32"  # mesh psum payload: int32 | int16 | int8
    kd_dtype: str = "int32"  # mesh doc-topic state width: int32 | int16
    # -- run length + schedule cadences -----------------------------------
    num_iterations: int = 100
    eval_every: int = 0  # llh/perplexity eval cadence
    target_perplexity: Optional[float] = None  # early stop on eval ticks
    exclusion_start: int = 0  # 0 = disabled; else iteration to enable at
    exclusion_min_prob: float = 0.0  # floor on the resample probability
    rebuild_every: int = 0  # exact count rebuild + row re-pad cadence
    merge_every: int = 0  # duplicate-topic merge cadence (paper §4.3)
    merge_threshold: float = 0.05  # L1 distance below which topics merge
    checkpoint_dir: Optional[str] = None  # model ckpts (serving artifact)
    checkpoint_every: int = 0  # 0 = final only (when checkpoint_dir set)
    train_checkpoint_dir: Optional[str] = None  # elastic training ckpts
    train_checkpoint_every: int = 0
    # -- streaming (repro.train.online.StreamingSession; DESIGN.md §7) ----
    window_docs: int = 0  # docs per stream window (0 = batch training)
    window_sweeps: int = 1  # CGS sweeps per window visit
    decay: float = 0.0  # online forgetting: counts *= (1-decay) per window
    stream_source: Optional[str] = None  # replay | libsvm:<path> | drift[:<seed>]
    # -- observability + autopilot (DESIGN.md §8) --------------------------
    metrics_out: Optional[str] = None  # telemetry JSONL path (None = off)
    metrics_every: int = 1  # telemetry record cadence (iterations)
    autopilot: bool = False  # measured backend/capacity re-pick when True
    autopilot_every: int = 0  # decision cadence (0 = rebuild_every, else 10)
    # -- model-quality evaluation (repro.eval, DESIGN.md §9) ----------------
    quality_every: int = 0  # coherence (+ left-to-right) cadence (0 = off)
    quality_top_n: int = 10  # top words per topic for coherence
    quality_npmi_window: int = 10  # NPMI sliding-window size (0 = UMass only)
    quality_l2r_docs: int = 0  # left-to-right held-out docs (0 = skip l2r)
    quality_l2r_particles: int = 20  # particles per left-to-right doc
    # -- Alg. 5 hyper-parameter optimization (DESIGN.md §9.3) ---------------
    hyper_every: int = 0  # Minka alpha + beta anneal cadence (0 = off)
    hyper_alpha: bool = True  # run the Minka fixed-point alpha step
    hyper_beta_anneal: float = 1.0  # beta *= this per firing (1.0 = off)
    hyper_beta_floor: float = 1e-4  # annealing floor for beta

    def knobs(self) -> SamplerKnobs:
        return knobs_from(self)

    def exclusion(self) -> ExclusionConfig:
        return ExclusionConfig(
            enabled=self.exclusion_start > 0,
            start_iteration=self.exclusion_start,
            min_sample_prob=self.exclusion_min_prob,
        )

    # -- serialization ----------------------------------------------------
    def to_json(self, indent: Optional[int] = 2) -> str:
        d = dataclasses.asdict(self)
        if d["mesh_shape"] is not None:
            d["mesh_shape"] = list(d["mesh_shape"])
        return json.dumps(d, indent=indent)

    @classmethod
    def from_json(cls, text: str) -> "RunConfig":
        d = json.loads(text)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown RunConfig fields: {', '.join(unknown)}")
        if d.get("mesh_shape") is not None:
            d["mesh_shape"] = tuple(int(x) for x in d["mesh_shape"])
        return cls(**d)


# ---------------------------------------------------------------------------
# Execution plans
# ---------------------------------------------------------------------------

class ExecutionPlan:
    """What a ``TrainSession`` needs from a substrate: init/step/metrics
    plus the structural-event hooks the schedule fires. Both plans keep
    the paper's contract — the backend is only the per-token draw; the
    plan owns masking, the delta merge, and the state update."""

    backend: algorithms.SamplerBackend

    def init(self, rng: jax.Array, init_topics=None):
        raise NotImplementedError

    def step(self, state):
        raise NotImplementedError

    def llh(self, state) -> float:
        raise NotImplementedError

    def change_rate(self, state) -> float:
        raise NotImplementedError

    @property
    def num_tokens(self) -> int:
        raise NotImplementedError

    # -- structural events -------------------------------------------------
    def enable_exclusion(self) -> None:
        raise NotImplementedError

    def rebuild(self, state):
        """Exact count rebuild from the assignments (drift fix)."""
        raise NotImplementedError

    def repad(self, state) -> bool:
        """Re-resolve padded-row capacities against the current counts;
        rebuild the step when they changed. Returns True on a rebuild."""
        return False

    @property
    def row_pads(self) -> Tuple[int, int]:
        """(max_kw, max_kd) currently in effect (0 = per-sweep auto)."""
        raise NotImplementedError

    def apply_row_pads(self, max_kw: int, max_kd: int) -> bool:
        """Set explicit padded-row capacities (autopilot actuation).
        Returns True when the widths changed (and any compiled step was
        rebuilt); same re-jit move as :meth:`repad` with the targets
        decided by policy instead of re-resolved from the counts."""
        raise NotImplementedError

    def set_backend(self, name: str, state) -> bool:
        """Swap the sampler backend in place (autopilot actuation).
        Returns True when the backend actually changed. The swap reuses
        the repad machinery: rebuild whatever the old backend prepared
        (aux tables, compiled step) under the new registry entry."""
        raise NotImplementedError

    def merge(self, state, topic_map):
        """Apply a duplicate-topic map (remap assignments, merge counts)."""
        raise NotImplementedError

    def set_hyper(self, hyper: LDAHyperParams) -> None:
        """Swap the model hyper-parameters in place (the Alg. 5 "hyper"
        action). Anything compiled against the old values — backend aux
        tables, the mesh plan's jitted step/llh/rebuild — is rebuilt."""
        raise NotImplementedError

    def host_n_wk(self, state) -> np.ndarray:
        """N_w|k in original word ids (host) — merge detection, save_model."""
        raise NotImplementedError

    # -- checkpoint surfaces -----------------------------------------------
    def model_arrays(self, state) -> Tuple[np.ndarray, np.ndarray]:
        """(n_wk, n_k) in original word ids — the serving artifact."""
        raise NotImplementedError

    def checkpoint_tree(self, state) -> Dict[str, Any]:
        """Elastic training checkpoint: assignments only (counts rebuild)."""
        raise NotImplementedError

    def restore(self, state, tree):
        raise NotImplementedError


class SingleBoxPlan(ExecutionPlan):
    """The whole corpus as one cell: the paper's driver program on one
    device. Numerics are kept in lockstep with the historical
    ``LDATrainer`` (same key schedule, same delta merge) — the session
    bit-equality tests pin this."""

    def __init__(self, corpus: Corpus, hyper: LDAHyperParams, cfg: RunConfig):
        self.corpus = corpus
        self.hyper = hyper
        self.cfg = cfg
        self.backend = algorithms.get(cfg.algorithm)
        self._knobs = cfg.knobs()
        self._aux = self.backend.prepare(corpus, hyper, self._knobs)
        # single-box warmup is handled in-trace by ``active_mask`` (the
        # historical behavior — keeps direct ``step()`` loops exact), so
        # the schedule's "exclusion_on" firing is a recorded no-op here;
        # on the mesh plan it swaps the compiled step for real
        self._excl = cfg.exclusion()
        # compiled steps (``compiled_step``); the backend, hyper and
        # exclusion config are baked in, so changing any of them clears it
        self._compiled: Dict[Any, Any] = {}
        self.compiles = 0  # programs compiled by ``compiled_step``

    # -- lifecycle ---------------------------------------------------------
    def init(self, rng: jax.Array, init_topics=None) -> CGSState:
        c, h, cfg = self.corpus, self.hyper, self.cfg
        if init_topics is not None:
            topic = jnp.asarray(init_topics, jnp.int32).reshape(-1)
            n_wk, n_kd, n_k = counts_lib.build_counts(
                c.word, c.doc, topic, c.num_words, c.num_docs, h.num_topics
            )
            zeros = jnp.zeros((c.num_tokens,), jnp.int32)
            return CGSState(
                topic=topic, prev_topic=topic, n_wk=n_wk, n_kd=n_kd,
                n_k=n_k, rng=rng, iteration=0,
                stale_iters=zeros, same_count=zeros,
            )
        if cfg.init == "random":
            return init_lib.random_init(rng, c, h)
        if cfg.init == "sparse_word":
            return init_lib.sparse_word_init(rng, c, h, cfg.sparse_init_degree)
        if cfg.init == "sparse_doc":
            return init_lib.sparse_doc_init(rng, c, h, cfg.sparse_init_degree)
        raise ValueError(cfg.init)

    def _sweep_knobs(self, state: CGSState) -> SamplerKnobs:
        if self.backend.needs_row_pads:
            # host-side auto pads from the current counts (0 = auto):
            # single-box re-resolves every sweep, so row growth never
            # truncates here (the mesh plan re-pads on the rebuild cadence)
            return algorithms.resolve_row_pads(state, self._knobs)
        return self._knobs

    def sweep(self, state: CGSState) -> jax.Array:
        return self.backend.sweep(state, self.corpus, self.hyper,
                                  self._sweep_knobs(state), self._aux)

    def step(self, state: CGSState) -> CGSState:
        with span("train.step"):
            compiled, args = self.compiled_step(state)
            return compiled(*args)

    def compiled_step(self, state: CGSState):
        """The step as one compiled program, and its arguments: ``(exe,
        args)`` with ``exe(*args)`` the next state. Compiled once per
        sampler knobs and argument shapes (padded-sparse backends resolve
        their row widths from ``state`` first); ``exe`` exposes
        ``memory_analysis()`` and ``as_text()``. A compile is the trace
        span ``zen.train.compile`` and counts in ``compiles``."""
        knobs = self._sweep_knobs(state)
        state = dataclasses.replace(
            state, iteration=jnp.asarray(state.iteration, jnp.int32)
        )
        args = (state, self.corpus.word, self.corpus.doc, self._aux)
        sig = (knobs, jax.tree.structure(args),
               tuple((x.shape, x.dtype) for x in jax.tree.leaves(args)))
        exe = self._compiled.get(sig)
        if exe is None:
            if len(self._compiled) >= 8:  # padded-sparse widths drift
                self._compiled.pop(next(iter(self._compiled)))
            with span("train.compile"):
                exe = jax.jit(functools.partial(self._step, knobs)).lower(
                    *args).compile()
            self.compiles += 1
            self._compiled[sig] = exe
        return exe, args

    def _step(self, knobs, state: CGSState, word, doc, aux) -> CGSState:
        """One sweep and count update. Its ops carry the named scopes
        ``zen.sweep`` (with ``zen.relayout`` inside the kernel wrappers),
        ``zen.delta_counts`` and ``zen.update`` in their HLO metadata."""
        c = dataclasses.replace(self.corpus, word=word, doc=doc)
        h = self.hyper
        key = jax.random.fold_in(state.rng, 2**20 + state.iteration)
        mask = active_mask(state, self._excl, key)
        with jax.named_scope("zen.sweep"):
            z_new_all = self.backend.sweep(state, c, h, knobs, aux)
        z_new = jnp.where(mask, z_new_all, state.topic)
        with jax.named_scope("zen.delta_counts"):
            d_wk, d_kd, d_k = counts_lib.delta_counts(
                c.word, c.doc, state.topic, z_new, c.num_words, c.num_docs,
                h.num_topics,
            )
        with jax.named_scope("zen.update"):
            i_new, t_new = update_exclusion_stats(state, z_new, mask)
            return CGSState(
                topic=z_new,
                prev_topic=state.topic,
                n_wk=state.n_wk + d_wk,
                n_kd=state.n_kd + d_kd,
                n_k=state.n_k + d_k,
                rng=state.rng,
                iteration=state.iteration + 1,
                stale_iters=i_new,
                same_count=t_new,
            )

    # -- metrics -----------------------------------------------------------
    def llh(self, state: CGSState) -> float:
        return float(predictive_llh(state, self.corpus, self.hyper,
                                    token_chunk=self._knobs.chunk_or_none()))

    def llh_split(self, state: CGSState):
        return joint_llh(state, self.corpus, self.hyper)

    def change_rate(self, state: CGSState) -> float:
        return float(jnp.mean(
            (state.topic != state.prev_topic).astype(jnp.float32)
        ))

    @property
    def num_tokens(self) -> int:
        return self.corpus.num_tokens

    # -- structural events -------------------------------------------------
    def enable_exclusion(self) -> None:
        self._excl = self.cfg.exclusion()  # idempotent (in-trace warmup)
        self._compiled.clear()

    def rebuild(self, state: CGSState) -> CGSState:
        c, h = self.corpus, self.hyper
        n_wk, n_kd, n_k = counts_lib.build_counts(
            c.word, c.doc, state.topic, c.num_words, c.num_docs, h.num_topics
        )
        return dataclasses.replace(state, n_wk=n_wk, n_kd=n_kd, n_k=n_k)

    @property
    def row_pads(self) -> Tuple[int, int]:
        return (self._knobs.max_kw, self._knobs.max_kd)

    def apply_row_pads(self, max_kw: int, max_kd: int) -> bool:
        if (self._knobs.max_kw, self._knobs.max_kd) == (max_kw, max_kd):
            return False
        # explicit widths stick: ``resolve_row_pads`` honors nonzero
        # values, so the per-sweep auto-resolution stops overriding them
        self._knobs = dataclasses.replace(
            self._knobs, max_kw=int(max_kw), max_kd=int(max_kd)
        )
        return True

    def set_backend(self, name: str, state: CGSState) -> bool:
        if name == self.backend.name:
            return False
        self.backend = algorithms.get(name)
        self._aux = self.backend.prepare(self.corpus, self.hyper,
                                         self._knobs)
        self._compiled.clear()
        return True

    def set_hyper(self, hyper: LDAHyperParams) -> None:
        self.hyper = hyper
        # aux tables may encode beta/alpha (alias tables, frozen CDFs)
        self._aux = self.backend.prepare(self.corpus, hyper, self._knobs)
        self._compiled.clear()

    def merge(self, state: CGSState, topic_map) -> CGSState:
        tm = jnp.asarray(topic_map, jnp.int32)
        new_topic, n_wk, n_kd, n_k = merge_topics(
            state.topic, state.n_wk, state.n_kd, state.n_k, tm
        )
        return dataclasses.replace(
            state, topic=new_topic,
            prev_topic=tm[state.prev_topic].astype(jnp.int32),
            n_wk=n_wk, n_kd=n_kd, n_k=n_k,
        )

    def host_n_wk(self, state: CGSState) -> np.ndarray:
        return np.asarray(jax.device_get(state.n_wk))

    # -- checkpoint surfaces -----------------------------------------------
    def model_arrays(self, state: CGSState):
        return (np.asarray(jax.device_get(state.n_wk)),
                np.asarray(jax.device_get(state.n_k)))

    def checkpoint_tree(self, state: CGSState) -> Dict[str, Any]:
        return {"topic": state.topic, "iteration": jnp.asarray(state.iteration)}

    def restore(self, state: CGSState, tree) -> CGSState:
        restored = dataclasses.replace(
            state,
            topic=jnp.asarray(tree["topic"], jnp.int32),
            prev_topic=jnp.asarray(tree["topic"], jnp.int32),
            iteration=int(tree["iteration"]),
            stale_iters=jnp.zeros_like(state.topic),
            same_count=jnp.zeros_like(state.topic),
        )
        return self.rebuild(restored)


class MeshPlan(ExecutionPlan):
    """SPMD mesh execution: ``grid_partition`` lays the corpus out on a
    (data x model) grid, ``make_dist_step`` builds the shard_map iteration
    (paper Fig. 2), and structural events that change the compiled step's
    static workspace — exclusion enablement, row-capacity re-resolution —
    rebuild the jitted step in place. The partition and ``init`` are the
    host spans ``zen.mesh.partition`` and ``zen.mesh.init``."""

    def __init__(self, corpus: Corpus, hyper: LDAHyperParams, cfg: RunConfig,
                 mesh=None):
        from repro.core.distributed import DistConfig
        from repro.core.graph import grid_partition
        from repro.launch.mesh import make_mesh

        self.corpus = corpus
        self.hyper = hyper
        self.cfg = cfg
        self.backend = algorithms.get(cfg.algorithm)
        if not self.backend.supports_shard_map:
            raise ValueError(
                f"backend {cfg.algorithm!r} does not support shard_map "
                f"cells; mesh-capable backends: "
                f"{', '.join(n for n in algorithms.registered() if algorithms.get(n).supports_shard_map)}"
            )
        rows, cols = cfg.mesh_shape
        self.mesh = mesh if mesh is not None else make_mesh(
            (rows, cols), ("data", "model")
        )
        with span("mesh.partition"):
            self.grid = grid_partition(corpus, rows, cols)
        # the user's explicit widths; 0 stays "auto" across re-resolutions
        self._user_kw, self._user_kd = cfg.max_kw, cfg.max_kd
        self.dcfg = DistConfig(
            algorithm=cfg.algorithm,
            sampling_method=cfg.sampling_method,
            max_kd=cfg.max_kd, max_kw=cfg.max_kw, num_mh=cfg.num_mh,
            delta_dtype=cfg.delta_dtype,
            rebuild_every=cfg.rebuild_every,
            exclusion_start=0,  # enabled by the schedule action
            token_chunk=cfg.token_chunk, kd_dtype=cfg.kd_dtype,
            bt=cfg.bt, bk=cfg.bk, bs=cfg.bs, kernels=cfg.kernels,
        )
        self._step_fn = None
        self._compiled: Dict[Any, Any] = {}  # ``compiled_step``'s programs
        self.compiles = 0  # programs compiled by ``compiled_step``
        self._data = None
        self._llh_fn = None
        self._rebuild_fn = None
        self._kd_dtype = jnp.int16 if cfg.kd_dtype == "int16" else jnp.int32

    # -- lifecycle ---------------------------------------------------------
    def init(self, rng: jax.Array, init_topics=None):
        from repro.core.distributed import (
            init_dist_state,
            make_dist_llh,
            make_rebuild_counts,
            resolve_dist_row_pads,
        )

        with span("mesh.init"):
            state, data = init_dist_state(
                rng, self.mesh, self.grid, self.hyper,
                init_topics=init_topics, kd_dtype=self._kd_dtype,
            )
            self._data = data
            # shard-relative padded-row capacities from the *init* counts;
            # the repad action re-resolves them on the rebuild cadence
            self.dcfg = resolve_dist_row_pads(state, self.dcfg)
            self._llh_fn = make_dist_llh(
                self.mesh, self.hyper, self.grid.words_per_shard,
                self.grid.docs_per_shard, self.grid.num_words,
            )
            self._rebuild_fn = make_rebuild_counts(
                self.mesh, self.hyper, self.grid.words_per_shard,
                self.grid.docs_per_shard,
            )
            self._build_step()
        return state

    def _build_step(self) -> None:
        from repro.core.distributed import make_dist_step

        self._step_fn = make_dist_step(
            self.mesh, self.hyper, self.dcfg, self.grid.words_per_shard,
            self.grid.docs_per_shard, self.grid.num_words,
        )
        self._compiled.clear()

    def step(self, state):
        with span("train.step"):
            exe, args = self.compiled_step(state)
            return exe(*args)

    def compiled_step(self, state):
        """The step as one compiled program, and its arguments: ``(exe,
        args)`` with ``exe(*args)`` the next state (it donates ``state``).
        Compiled once per argument shapes, dtypes and shardings, until a
        structural event rebuilds the step; ``exe`` exposes
        ``memory_analysis()`` and ``as_text()``. A compile is the trace
        span ``zen.train.compile`` and counts in ``compiles``."""
        args = (state, self._data)
        sig = (jax.tree.structure(args),
               tuple((x.shape, x.dtype, x.sharding)
                     for x in jax.tree.leaves(args)))
        exe = self._compiled.get(sig)
        if exe is None:
            with span("train.compile"):
                exe = self._step_fn.lower(*args).compile()
            self.compiles += 1
            self._compiled[sig] = exe
        return exe, args

    # -- metrics -----------------------------------------------------------
    def llh(self, state) -> float:
        return float(self._llh_fn(state, self._data))

    def change_rate(self, state) -> float:
        changed = (state.topic != state.prev_topic) & jnp.asarray(
            self.grid.mask
        )
        return float(jnp.sum(changed) / self.num_tokens)

    @property
    def num_tokens(self) -> int:
        return int(self.grid.mask.sum())

    # -- structural events -------------------------------------------------
    def enable_exclusion(self) -> None:
        if self.dcfg.exclusion_start == self.cfg.exclusion_start:
            return
        self.dcfg = dataclasses.replace(
            self.dcfg, exclusion_start=self.cfg.exclusion_start
        )
        self._build_step()

    def rebuild(self, state):
        return self._rebuild_fn(state, self._data)

    def repad(self, state) -> bool:
        """The PR-3 follow-up: re-resolve shard row capacities against the
        CURRENT counts and re-jit when the padded widths changed. Widths
        are frozen into the compiled step, so without this a row that
        grows past its init capacity is truncated by the sparse tables
        (sampling-quality bias) and a row that sharpens leaves its pad
        oversized; re-resolving fixes both directions."""
        from repro.core.distributed import resolve_dist_row_pads

        if not self.backend.needs_row_pads or (self._user_kw and self._user_kd):
            return False
        probe = dataclasses.replace(
            self.dcfg, max_kw=self._user_kw, max_kd=self._user_kd
        )
        probe = resolve_dist_row_pads(state, probe)
        if (probe.max_kw, probe.max_kd) == (self.dcfg.max_kw, self.dcfg.max_kd):
            return False
        self.dcfg = probe
        self._build_step()
        return True

    @property
    def row_pads(self) -> Tuple[int, int]:
        return (self.dcfg.max_kw, self.dcfg.max_kd)

    def apply_row_pads(self, max_kw: int, max_kd: int) -> bool:
        if (self.dcfg.max_kw, self.dcfg.max_kd) == (max_kw, max_kd):
            return False
        self.dcfg = dataclasses.replace(
            self.dcfg, max_kw=int(max_kw), max_kd=int(max_kd)
        )
        self._build_step()
        return True

    def set_backend(self, name: str, state) -> bool:
        if name == self.dcfg.algorithm:
            return False
        backend = algorithms.get(name)
        if not backend.supports_shard_map:
            raise ValueError(
                f"backend {name!r} does not support shard_map cells; "
                f"cannot swap onto a mesh plan"
            )
        self.backend = backend
        self.dcfg = dataclasses.replace(self.dcfg, algorithm=name)
        if backend.needs_row_pads and not (self.dcfg.max_kw
                                           and self.dcfg.max_kd):
            # coming from a padless backend: resolve capacities against
            # the CURRENT counts before the new step compiles
            from repro.core.distributed import resolve_dist_row_pads

            self.dcfg = resolve_dist_row_pads(state, self.dcfg)
        self._build_step()
        return True

    def set_hyper(self, hyper: LDAHyperParams) -> None:
        from repro.core.distributed import make_dist_llh, make_rebuild_counts

        self.hyper = hyper
        if self._data is None:
            return  # pre-init: init() builds everything against self.hyper
        # the compiled step, llh, and rebuild all close over hyper
        self._llh_fn = make_dist_llh(
            self.mesh, hyper, self.grid.words_per_shard,
            self.grid.docs_per_shard, self.grid.num_words,
        )
        self._rebuild_fn = make_rebuild_counts(
            self.mesh, hyper, self.grid.words_per_shard,
            self.grid.docs_per_shard,
        )
        self._build_step()

    def merge(self, state, topic_map):
        tm = jnp.asarray(topic_map, jnp.int32)
        state = state._replace(
            topic=tm[state.topic],
            prev_topic=tm[state.prev_topic],
        )
        # counts follow the assignments exactly (reuses the rebuild step)
        return self.rebuild(state)

    def host_n_wk(self, state) -> np.ndarray:
        return np.asarray(jax.device_get(state.n_wk))[self.grid.word_perm]

    # -- checkpoint surfaces -----------------------------------------------
    def model_arrays(self, state):
        n_wk = self.host_n_wk(state)
        n_k = np.asarray(jax.device_get(state.n_k))
        return n_wk, n_k

    def checkpoint_tree(self, state) -> Dict[str, Any]:
        return {"topic": state.topic, "iteration": state.iteration}

    def restore(self, state, tree):
        state = state._replace(
            topic=jax.device_put(tree["topic"], state.topic.sharding),
            iteration=jnp.asarray(tree["iteration"]),
        )
        return self.rebuild(state)


# ---------------------------------------------------------------------------
# TrainSession
# ---------------------------------------------------------------------------

class TrainSession:
    """One training run behind one interface, whichever substrate executes
    it. Resolves the backend once, selects the execution plan from
    ``cfg.mesh_shape``, and fires the event schedule after every step."""

    def __init__(self, corpus: Corpus, hyper: LDAHyperParams, cfg: RunConfig,
                 mesh=None, plan: Optional[ExecutionPlan] = None):
        if cfg.sampling_method is None:
            cfg = dataclasses.replace(
                cfg,
                sampling_method="cdf" if cfg.mesh_shape is None else "gumbel",
            )
        self.corpus = corpus
        self.hyper = hyper
        self.cfg = cfg
        self.backend = algorithms.get(cfg.algorithm)  # one resolution
        if plan is not None:
            # an already-prepared plan (see ``with_run_params``); the
            # caller guarantees it was built from the same non-run fields
            self.plan = plan
        elif cfg.mesh_shape is None:
            self.plan = SingleBoxPlan(corpus, hyper, cfg)
        else:
            self.plan = MeshPlan(corpus, hyper, cfg, mesh=mesh)
        # observability + autopilot (DESIGN.md §8) — built ONLY when
        # enabled: with metrics_out=None and autopilot=False nothing here
        # exists and the schedule below is exactly the pre-PR one
        self.telemetry = None
        self._autopilot_policy = None
        self._metrics_sink = None
        if cfg.metrics_out or cfg.autopilot:
            from repro.observe import JsonlSink, MetricsRegistry, TrainTelemetry

            self._metrics_sink = (JsonlSink(cfg.metrics_out)
                                  if cfg.metrics_out else None)
            self.telemetry = TrainTelemetry(
                MetricsRegistry(self._metrics_sink)
            )
        if cfg.autopilot:
            from repro.autotune import TrainAutopilot

            self._autopilot_policy = TrainAutopilot(
                self._autopilot_candidates()
            )
        # model-quality evaluator (repro.eval, DESIGN.md §9) — built ONLY
        # when the cadence is on; corpus stats are computed once here
        self._quality = None
        if cfg.quality_every > 0:
            from repro.eval import QualityEval

            self._quality = QualityEval.from_run_config(corpus, hyper, cfg)
        self.schedule = self._build_schedule()
        self._last_model_save: Optional[int] = None
        self._train_ckpt = None
        if cfg.train_checkpoint_dir:
            from repro.train.checkpoint import CheckpointManager

            self._train_ckpt = CheckpointManager(cfg.train_checkpoint_dir)

    def with_run_params(
        self,
        num_iterations: Optional[int] = None,
        eval_every: Optional[int] = None,
        target_perplexity: Optional[float] = None,
    ) -> "TrainSession":
        """A session sharing this one's prepared plan (backend aux, grid,
        compiled steps) with only run-length / eval schedule fields
        replaced — none of which the plan depends on. This is how the
        deprecated ``LDATrainer.train`` re-parameterizes per call without
        paying ``backend.prepare`` again."""
        cfg = self.cfg
        cfg = dataclasses.replace(
            cfg,
            num_iterations=cfg.num_iterations if num_iterations is None
            else num_iterations,
            eval_every=cfg.eval_every if eval_every is None else eval_every,
            target_perplexity=target_perplexity,
        )
        return TrainSession(self.corpus, self.hyper, cfg, plan=self.plan)

    # -- the session surface -----------------------------------------------
    def init(self, rng: jax.Array, init_topics=None):
        """Build the initial training state for this session's plan.

        Args:
            rng: a JAX PRNG key; seeds the topic-assignment init and the
                per-iteration sampling streams.
            init_topics: optional (E,) int32 initial topic per token
                (corpus edge order) — e.g. from ``repro.core.init``'s
                sparse initializers. Default: uniform random topics.

        Returns:
            The plan's state object — a ``CGSState`` (single-box: arrays
            ``n_wk (W, K)``, ``n_kd (D, K)``, ``n_k (K,)``, ``topic
            (E,)``) or the mesh plan's sharded equivalent. Treat it as
            opaque: pass it to ``step``/``run``/``metrics``/``save_model``.
        """
        return self.plan.init(rng, init_topics=init_topics)

    def step(self, state):
        """Run exactly one CGS iteration (every token resampled once).

        Args:
            state: the state returned by :meth:`init` or a previous
                ``step``.

        Returns:
            The post-iteration state, with ``state.iteration``
            incremented. No schedule actions fire — that is :meth:`run`'s
            job; ``step`` is the raw sampling move for callers that drive
            their own loop (benchmarks, tests).
        """
        return self.plan.step(state)

    def llh(self, state) -> float:
        """Joint log-likelihood of the current counts (one full pass)."""
        return self.plan.llh(state)

    def perplexity(self, state) -> float:
        """``exp(-llh / num_tokens)`` — one likelihood pass, lower is
        better."""
        return math.exp(-self.plan.llh(state) / self.plan.num_tokens)

    def metrics(self, state) -> Dict[str, float]:
        """Evaluate the state once; return the standard metric dict.

        Returns:
            ``{"llh", "perplexity", "change_rate"}`` — joint
            log-likelihood (one pass, perplexity derived from it, never a
            second pass) and the fraction of tokens whose topic changed
            in the last iteration (the paper's convergence signal).
        """
        llh = self.plan.llh(state)
        return {
            "llh": llh,
            "perplexity": math.exp(-llh / self.plan.num_tokens),
            "change_rate": self.plan.change_rate(state),
        }

    @property
    def row_pads(self) -> Tuple[int, int]:
        return self.plan.row_pads

    def save_model(self, state, directory: Optional[str] = None) -> str:
        """Checkpoint the trained model (N_wk/N_k + hyper) for serving —
        ``launch/serve_lda.py`` / ``FrozenLDAModel.from_checkpoint`` load
        exactly this artifact; the mesh plan un-permutes the grid's
        relabeled word ids first."""
        from repro.train.checkpoint import save_lda_model

        directory = directory or self.cfg.checkpoint_dir
        if not directory:
            raise ValueError("no checkpoint directory configured")
        n_wk, n_k = self.plan.model_arrays(state)
        extra = {"algorithm": self.cfg.algorithm}
        if self.cfg.mesh_shape is not None:
            extra["mesh"] = list(self.cfg.mesh_shape)
        path = save_lda_model(
            directory, n_wk, n_k, self.hyper,
            step=int(state.iteration), extra_metadata=extra,
        )
        self._last_model_save = int(state.iteration)
        return path

    def merge_duplicates(self, state):
        """Detect + merge duplicate topics (paper §4.3). Host-side
        detection on the current N_w|k; a trivial map is a no-op."""
        topic_map = duplicate_topic_map(
            self.plan.host_n_wk(state), self.cfg.merge_threshold
        )
        if (topic_map == np.arange(topic_map.shape[0])).all():
            return state
        return self.plan.merge(state, topic_map)

    # -- schedule construction ----------------------------------------------
    def _build_schedule(self) -> Schedule:
        cfg = self.cfg
        sched = Schedule()
        # structural events first, so evals/checkpoints on the same
        # iteration observe post-event state
        if cfg.exclusion_start > 0:
            sched.add(ScheduledAction(
                "exclusion_on",
                lambda ctx, st: (self.plan.enable_exclusion(), st)[1],
                at=cfg.exclusion_start,
            ))
        if cfg.rebuild_every > 0:
            sched.add(ScheduledAction(
                "rebuild", lambda ctx, st: self.plan.rebuild(st),
                every=cfg.rebuild_every,
            ))
            # with the autopilot on, row capacity belongs to policy (its
            # RowRepad decisions) — registering the measured re-pad too
            # would have two owners fighting over the same knob
            if (self.backend.needs_row_pads
                    and not (cfg.max_kw and cfg.max_kd)
                    and not cfg.autopilot):
                def _repad(ctx, st):
                    if self.plan.repad(st):
                        ctx.metrics["row_pads"] = self.plan.row_pads
                    return st

                sched.add(ScheduledAction(
                    "repad", _repad, every=cfg.rebuild_every,
                ))
        if cfg.autopilot:
            sched.add(ScheduledAction(
                "autopilot", self._autopilot_action,
                every=cfg.autopilot_every or cfg.rebuild_every or 10,
            ))
        if cfg.hyper_every > 0:
            # structural: evals/quality on the same iteration score the
            # post-update hypers (same convention as rebuild/merge)
            sched.add(ScheduledAction(
                "hyper", self._hyper_action, every=cfg.hyper_every,
            ))
        if cfg.merge_every > 0:
            sched.add(ScheduledAction(
                "merge", lambda ctx, st: self.merge_duplicates(st),
                every=cfg.merge_every,
            ))
        if cfg.eval_every > 0:
            def _eval(ctx, st):
                # one likelihood pass; perplexity derives from it (the
                # old trainer paid a SECOND full pass for the target
                # check) — ``metrics()`` is the single derivation
                ctx.metrics.update(self.metrics(st))
                if (cfg.target_perplexity is not None
                        and ctx.metrics["perplexity"]
                        <= cfg.target_perplexity):
                    ctx.stop = True
                return st

            sched.add(ScheduledAction("eval", _eval, every=cfg.eval_every))
        if cfg.quality_every > 0:
            sched.add(ScheduledAction(
                "quality", self._quality_action, every=cfg.quality_every,
            ))
        if cfg.checkpoint_dir and cfg.checkpoint_every > 0:
            sched.add(ScheduledAction(
                "model_checkpoint",
                lambda ctx, st: (self.save_model(st), st)[1],
                every=cfg.checkpoint_every,
            ))
        if self.cfg.train_checkpoint_dir and cfg.train_checkpoint_every > 0:
            sched.add(ScheduledAction(
                "train_checkpoint",
                lambda ctx, st: (self._save_train_ckpt(st), st)[1],
                every=cfg.train_checkpoint_every,
            ))
        if self.telemetry is not None:
            # last, so the record carries whatever the earlier actions
            # contributed this iteration (eval metrics, decisions)
            sched.add(ScheduledAction(
                "telemetry", self._telemetry_action,
                every=max(1, cfg.metrics_every),
            ))
        return sched

    # -- autopilot actuation (DESIGN.md §8.4) --------------------------------
    def _autopilot_candidates(self) -> Tuple[str, ...]:
        """Backends the autopilot may pick among: the configured one plus
        the three decomposition representatives (doc-side, word-side,
        hybrid), restricted to mesh-capable ones on a mesh plan."""
        cands = [self.cfg.algorithm]
        for name in ("zen_sparse", "sparselda", "zen_hybrid"):
            if name in cands or name not in algorithms.registered():
                continue
            if (self.cfg.mesh_shape is not None
                    and not algorithms.get(name).supports_shard_map):
                continue
            cands.append(name)
        return tuple(cands)

    def _autopilot_action(self, ctx: ActionContext, state):
        """Measure → decide → act, at a rebuild point. The safety
        contract: counts are rebuilt exactly from the assignments FIRST,
        so a backend swap or capacity change never bakes in count drift;
        the swap itself is the plan's repad re-jit move."""
        state = self.plan.rebuild(state)
        plan = self.plan
        if isinstance(plan, MeshPlan):
            # mesh widths are frozen into the compiled step — always
            # policy-owned when the backend uses padded rows
            pads_tunable = plan.backend.needs_row_pads
        else:
            # single-box auto pads (0) re-resolve every sweep already;
            # only explicit (possibly mis-sized) widths are worth tuning
            pads_tunable = (plan.backend.needs_row_pads
                            and all(p > 0 for p in plan.row_pads))
        decisions = self._autopilot_policy.decide(
            self.telemetry.window(),
            current_backend=plan.backend.name,
            current_pads=plan.row_pads,
            num_topics=self.hyper.num_topics,
            pads_tunable=pads_tunable,
        )
        from repro.autotune.policy import BackendSwitch, RowRepad

        for d in decisions:
            if isinstance(d, BackendSwitch):
                applied = plan.set_backend(d.backend, state)
                if applied:
                    self.backend = plan.backend
            elif isinstance(d, RowRepad):
                applied = plan.apply_row_pads(d.max_kw, d.max_kd)
            else:  # pragma: no cover - no other training decision types
                applied = False
            rec = d.to_record()
            rec.update(iteration=int(state.iteration), applied=applied)
            self.telemetry.emit_decision(rec)
            ctx.metrics.setdefault("autopilot", []).append(rec)
        return state

    # -- model quality + Alg. 5 hyper actions (DESIGN.md §9) -----------------
    def _quality_action(self, ctx: ActionContext, state):
        """Score the frozen model snapshot (coherence + left-to-right)
        into the iteration metrics; read-only, never touches state."""
        n_wk, n_k = self.plan.model_arrays(state)
        ctx.metrics.update(
            self._quality.evaluate(n_wk, n_k, int(state.iteration))
        )
        return state

    def _hyper_action(self, ctx: ActionContext, state):
        """One Alg. 5 hyper move: Minka fixed-point alpha + beta anneal
        against the CURRENT doc-topic counts. A changed hyper rebuilds
        whatever the plan compiled against the old one (``set_hyper``);
        an unchanged one is a recorded no-op."""
        from repro.core.hyper import optimize_hyper

        cfg = self.cfg
        n_kd = np.asarray(jax.device_get(state.n_kd))
        new_hyper = optimize_hyper(
            self.hyper, n_kd,
            update_alpha=cfg.hyper_alpha,
            beta_anneal=cfg.hyper_beta_anneal,
            beta_floor=cfg.hyper_beta_floor,
        )
        if new_hyper is not self.hyper:
            self.hyper = new_hyper
            self.plan.set_hyper(new_hyper)
            if self._quality is not None:
                self._quality.hyper = new_hyper  # l2r alpha_k follows
            ctx.metrics["hyper"] = {
                "alpha": new_hyper.alpha, "beta": new_hyper.beta,
            }
        return state

    def _telemetry_action(self, ctx: ActionContext, state):
        self.telemetry.record_iteration(
            self.plan, state, int(state.iteration), ctx.metrics
        )
        return state

    # -- elastic training checkpoints ---------------------------------------
    def _save_train_ckpt(self, state) -> None:
        self._train_ckpt.save(
            int(state.iteration), self.plan.checkpoint_tree(state), {}
        )

    def _maybe_restore(self, state):
        if self._train_ckpt is None:
            return state
        target = jax.tree_util.tree_map(lambda _: 0,
                                        self.plan.checkpoint_tree(state))
        got = self._train_ckpt.restore_latest(target)
        if got is None:
            return state
        tree, _meta, _step = got
        return self.plan.restore(state, tree)

    # -- the loop ------------------------------------------------------------
    def run(
        self,
        rng: Optional[jax.Array] = None,
        state=None,
        callback: Optional[Callable[[Any, Dict], None]] = None,
        init_topics=None,
    ):
        """Run to ``cfg.num_iterations`` (absolute), firing the schedule
        after every step. ``callback(state, metrics)`` is invoked each
        iteration with whatever the due actions contributed (empty dict on
        quiet iterations). Returns the final state."""
        cfg = self.cfg
        if state is None:
            if rng is None:
                raise ValueError("run() needs an rng or an initial state")
            state = self.init(rng, init_topics=init_topics)
        state = self._maybe_restore(state)
        if cfg.exclusion_start and int(state.iteration) >= cfg.exclusion_start:
            self.plan.enable_exclusion()  # resumed past the enable point
        ctx = ActionContext(session=self)
        restore_signals = self._install_signals(ctx)
        try:
            while int(state.iteration) < cfg.num_iterations and not ctx.stop:
                state = self.plan.step(state)
                ctx.metrics = {}
                state = self.schedule.fire(ctx, state, int(state.iteration))
                if callback is not None:
                    callback(state, ctx.metrics)
        finally:
            restore_signals()
        # final surfaces: model checkpoint if not already saved at this
        # iteration; training checkpoint on preemption-style stops
        if cfg.checkpoint_dir and self._last_model_save != int(state.iteration):
            self.save_model(state)
        if self._train_ckpt is not None and ctx.stop:
            self._save_train_ckpt(state)
        return state

    def _install_signals(self, ctx: ActionContext):
        """SIGTERM/SIGINT -> finish the current iteration, checkpoint, and
        return (preemption handling). Returns a restore callback — the
        previous handlers come back once the loop exits, so a library
        caller's Ctrl-C behaves normally between runs."""

        def handler(signum, frame):
            ctx.stop = True

        try:
            prev = {
                sig: signal.signal(sig, handler)
                for sig in (signal.SIGTERM, signal.SIGINT)
            }
        except ValueError:
            return lambda: None  # not in the main thread (tests)

        def restore():
            for sig, old in prev.items():
                try:
                    signal.signal(sig, old)
                except (ValueError, TypeError):
                    pass

        return restore
