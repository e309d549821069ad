"""Kernel suite v2 integration: dispatch policy, knob validation, the
jaxpr memory contract (no (T, K) gathered intermediate on the fused
path), kernels-on/off backend identity, forced-kernel mesh parity, and
the tile autotuner.

The memory claim of the tentpole is pinned structurally, not by timing:
tracing ``zen_pallas.cell_sweep`` with kernels forced on must produce a
jaxpr in which NO intermediate value (recursively, through pjit and the
pallas_call kernel body) has a (>=T, >=K) shape — the gathered-row
matrices are exactly what the fused kernel exists to eliminate. The
legacy path is the positive control: its jaxpr DOES contain them, so the
walker is proven able to see the thing it asserts absent.
"""
import dataclasses

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest
import test_mesh_parity

from repro import algorithms
from repro.algorithms.base import SamplerKnobs, kernel_dispatch, knobs_from
from repro.core.types import CGSState, LDAHyperParams
from repro.core import counts as counts_lib
from repro.data import synthetic_lda_corpus


# ---------------------------------------------------------------------------
# knob validation (satellite: reject bad tiles at config time)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "bad",
    [
        dict(bt=4),  # below the 8-sublane floor
        dict(bt=0),
        dict(bt=-8),
        dict(bk=64),  # below one lane
        dict(bk=129),  # not lane-aligned
        dict(bs=0),
        dict(bs=200),  # not lane-aligned
        dict(kernels="maybe"),
    ],
)
def test_knob_validation_rejects(bad):
    with pytest.raises(ValueError):
        SamplerKnobs(**bad)


def test_knob_validation_fires_through_replace_and_knobs_from():
    """The same check guards every construction route: direct, replace,
    and the config -> knobs derivation each driver uses."""
    good = SamplerKnobs()
    with pytest.raises(ValueError):
        dataclasses.replace(good, bk=100)

    from repro.core.distributed import DistConfig
    from repro.core.trainer import TrainConfig
    from repro.train.session import RunConfig

    for cfg in (RunConfig(bt=4), DistConfig(bt=4), TrainConfig(bt=4)):
        with pytest.raises(ValueError):
            knobs_from(cfg)


def test_kernel_knobs_plumb_through_every_config():
    """bs/kernels reach SamplerKnobs from all four driver configs."""
    from repro.core.distributed import DistConfig
    from repro.core.trainer import TrainConfig
    from repro.serving.lda_engine import LDAServeConfig
    from repro.train.session import RunConfig

    for cfg in (
        RunConfig(bs=256, kernels="off"),
        DistConfig(bs=256, kernels="off"),
        TrainConfig(bs=256, kernels="off"),
    ):
        kn = knobs_from(cfg)
        assert kn.bs == 256 and kn.kernels == "off", type(cfg).__name__
    assert TrainConfig(bs=256, kernels="off").to_run_config().kernels == "off"
    assert LDAServeConfig(kernels="off").knobs().kernels == "off"


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------

def test_kernel_dispatch_policy(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert kernel_dispatch("auto") == (jax.default_backend() == "tpu")
    assert kernel_dispatch("on") is True
    assert kernel_dispatch("off") is False
    with pytest.raises(ValueError):
        kernel_dispatch("sometimes")
    # the env var overrides the knob (read at call time, not import time)
    monkeypatch.setenv("REPRO_KERNELS", "on")
    assert kernel_dispatch("off") is True
    monkeypatch.setenv("REPRO_KERNELS", "off")
    assert kernel_dispatch("on") is False
    monkeypatch.setenv("REPRO_KERNELS", "bogus")
    with pytest.raises(ValueError):
        kernel_dispatch("auto")


# ---------------------------------------------------------------------------
# jaxpr memory contract: the fused path has no (T, K) intermediates
# ---------------------------------------------------------------------------

def _collect_avals(jaxpr, out):
    """All eqn output avals, recursing into sub-jaxprs (pjit bodies from
    the @jax.jit ops wrappers, scan/while carries, pallas kernel bodies)."""
    for eqn in jaxpr.eqns:
        out.extend(v.aval for v in eqn.outvars)
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                _collect_avals(sub, out)


def _sub_jaxprs(val):
    if isinstance(val, jax.extend.core.ClosedJaxpr):
        return [val.jaxpr]
    if isinstance(val, jax.extend.core.Jaxpr):
        return [val]
    if isinstance(val, (list, tuple)):
        subs = []
        for v in val:
            subs.extend(_sub_jaxprs(v))
        return subs
    return []


def test_fused_cell_path_never_materializes_token_by_topic(monkeypatch):
    """Tentpole acceptance: with kernels on, no value anywhere in the
    traced cell sweep has shape (>=T, >=K) — the gathered count rows (and
    anything else token-by-topic) stay virtual. The legacy path is the
    positive control proving the walker sees such values when they exist."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    # t > 8w, 8d (a count row padded to 1024 lanes is 8 rows of the
    # kernel's (rows * K / 128, 128) gather view), t > the 256-token tile,
    # and k < all row counts
    t, k, w, d = 320, 16, 37, 23
    be = algorithms.get("zen_pallas")
    hyper = LDAHyperParams(num_topics=k, alpha=0.1, beta=0.05)
    mask = jnp.ones((t,), bool)

    def trace(mode):
        kn = SamplerKnobs(kernels=mode)

        def fn(key, word, doc, z, n_wk, n_kd, n_k):
            return be.cell_sweep(
                key, word, doc, z, mask, n_wk, n_kd, n_k, hyper, w, kn
            )

        return jax.make_jaxpr(fn)(
            jax.random.key(0),
            jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
            jnp.zeros((t,), jnp.int32),
            jnp.zeros((w, k), jnp.int32), jnp.zeros((d, k), jnp.int32),
            jnp.zeros((k,), jnp.int32),
        )

    def token_by_topic(aval):
        shape = getattr(aval, "shape", ())
        return (len(shape) == 2 and isinstance(shape[0], int)
                and shape[0] >= t and shape[1] >= k)

    legacy = []
    _collect_avals(trace("off").jaxpr, legacy)
    assert any(token_by_topic(a) for a in legacy), \
        "positive control failed: legacy gather path should materialize (T, K)"

    fused = []
    _collect_avals(trace("on").jaxpr, fused)
    offenders = [a for a in fused if token_by_topic(a)]
    assert not offenders, offenders


def test_fused_infer_path_never_materializes_token_by_topic(monkeypatch):
    """Same contract for the serving sweep: (B*L, K) gathered rows exist
    only on the legacy path."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    b, l, k, w = 24, 16, 8, 30  # B*L = 384 tokens
    be = algorithms.get("zen_pallas")
    hyper = LDAHyperParams(num_topics=k, alpha=0.1, beta=0.05)
    mask = jnp.ones((b, l), bool)

    def trace(mode):
        kn = SamplerKnobs(kernels=mode)

        def fn(keys, words, z, n_kd, n_wk, n_k):
            return be.infer_sweep(
                keys, words, mask, z, n_kd, n_wk, n_k, hyper, kn
            )

        return jax.make_jaxpr(fn)(
            jax.random.split(jax.random.key(0), b),
            jnp.zeros((b, l), jnp.int32), jnp.zeros((b, l), jnp.int32),
            jnp.zeros((b, k), jnp.int32), jnp.zeros((w, k), jnp.int32),
            jnp.zeros((k,), jnp.int32),
        )

    def token_by_topic(aval):
        shape = getattr(aval, "shape", ())
        return (len(shape) == 2 and isinstance(shape[0], int)
                and shape[0] >= b * l and shape[1] >= k)

    legacy = []
    _collect_avals(trace("off").jaxpr, legacy)
    assert any(token_by_topic(a) for a in legacy)
    fused = []
    _collect_avals(trace("on").jaxpr, fused)
    offenders = [a for a in fused if token_by_topic(a)]
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# kernels on vs off through the real backends
# ---------------------------------------------------------------------------

def _tiny_problem(seed=0):
    corpus, _ = synthetic_lda_corpus(
        seed, num_docs=30, num_words=50, num_topics=8, avg_doc_len=20
    )
    hyper = LDAHyperParams(num_topics=8, alpha=0.1, beta=0.05)
    rng = np.random.default_rng(seed)
    z = jnp.asarray(
        rng.integers(0, 8, corpus.num_tokens).astype(np.int32)
    )
    n_wk, n_kd, n_k = counts_lib.build_counts(
        corpus.word, corpus.doc, z, corpus.num_words, corpus.num_docs, 8
    )
    zeros = jnp.zeros((corpus.num_tokens,), jnp.int32)
    state = CGSState(
        topic=z, prev_topic=z, n_wk=n_wk, n_kd=n_kd, n_k=n_k,
        rng=jax.random.key(3), iteration=jnp.int32(2),
        stale_iters=zeros, same_count=zeros,
    )
    return corpus, hyper, state


BIT_IDENTICAL_BACKENDS = ["zen_pallas", "zen_sparse", "sparselda",
                          "zen_hybrid"]


@pytest.mark.parametrize("alg", BIT_IDENTICAL_BACKENDS)
def test_sweep_dispatch_bit_identity(alg, monkeypatch):
    """For the backends whose kernel replaces an identical op sequence
    (fused gather+sample; cumsum/count/clamp/take row inversion), the
    kernels="on" sweep equals the kernels="off" sweep bit for bit."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    corpus, hyper, state = _tiny_problem()
    be = algorithms.get(alg)
    outs = {}
    for mode in ("off", "on"):
        knobs = be.resolve_cell_knobs(SamplerKnobs(kernels=mode), hyper)
        aux = be.prepare(corpus, hyper, knobs)
        outs[mode] = np.asarray(
            be.sweep(state, corpus, hyper, knobs, aux)
        )
    np.testing.assert_array_equal(outs["on"], outs["off"])


@pytest.mark.parametrize("alg", ["zen_cdf", "lightlda"])
def test_sweep_dispatch_distribution_equal(alg, monkeypatch):
    """zen_cdf (bk-tiled float carry) and lightlda (CDF inversion replaces
    the alias walk) are distribution-equal, not bitwise: the kernel sweep
    must be a valid draw — in range, and mostly agreeing with the legacy
    sweep from the same counts (same conditional, shared randomness for
    zen_cdf's term choice)."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    corpus, hyper, state = _tiny_problem()
    be = algorithms.get(alg)
    outs = {}
    for mode in ("off", "on"):
        knobs = be.resolve_cell_knobs(SamplerKnobs(kernels=mode), hyper)
        aux = be.prepare(corpus, hyper, knobs)
        outs[mode] = np.asarray(be.sweep(state, corpus, hyper, knobs, aux))
    for mode, z in outs.items():
        assert z.dtype == np.int32, (alg, mode)
        assert (z >= 0).all() and (z < hyper.num_topics).all(), (alg, mode)
    # same conditional, same target draws -> the paths disagree only where
    # round-off (zen_cdf) or proposal-chain divergence (lightlda) bites
    diff = float((outs["on"] != outs["off"]).mean())
    assert diff < 0.8, (alg, diff)


def test_zen_pallas_infer_dispatch_bit_identity(monkeypatch):
    """The serving sweep dispatches identically: fused == gathered."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    b, l, k, w = 6, 12, 8, 25
    rng = np.random.default_rng(5)
    be = algorithms.get("zen_pallas")
    hyper = LDAHyperParams(num_topics=k, alpha=0.1, beta=0.05)
    keys = jax.random.split(jax.random.key(11), b)
    words = jnp.asarray(rng.integers(0, w, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.random((b, l)) < 0.9)
    z = jnp.asarray(rng.integers(0, k, (b, l)), jnp.int32)
    n_kd = jnp.asarray(rng.integers(0, 6, (b, k)), jnp.int32)
    n_wk = jnp.asarray(rng.integers(0, 40, (w, k)), jnp.int32)
    n_k = jnp.asarray(np.asarray(n_wk).sum(0), jnp.int32)
    outs = {
        mode: np.asarray(be.infer_sweep(
            keys, words, mask, z, n_kd, n_wk, n_k, hyper,
            SamplerKnobs(kernels=mode),
        ))
        for mode in ("off", "on")
    }
    np.testing.assert_array_equal(outs["on"], outs["off"])


def test_zen_cdf_forced_kernel_training_trend(monkeypatch):
    """A short zen_cdf run with kernels forced on keeps its invariants and
    improves the likelihood — the CDF-search kernel is a drop-in sampler,
    not just a unit-level match."""
    monkeypatch.setenv("REPRO_KERNELS", "on")
    from repro.core import LDATrainer, TrainConfig

    corpus, hyper, state = _tiny_problem()
    tr = LDATrainer(corpus, hyper, TrainConfig(algorithm="zen_cdf"))
    l0 = tr.llh(state)
    st = state
    for _ in range(5):
        st = tr.step(st)
    st.check_invariants(corpus)
    assert tr.llh(st) > l0, (l0, tr.llh(st))


# ---------------------------------------------------------------------------
# forced-kernel mesh parity: the Alg. 2 backends through the UNCHANGED
# harness with the sparse kernel dispatched (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "alg", ["zen_sparse", "zen_hybrid", "sparselda", "lightlda"]
)
def test_forced_kernel_mesh_parity(alg, monkeypatch):
    """run_with_devices copies os.environ, so setting REPRO_KERNELS here
    forces kernel dispatch inside the subprocess's shard_map cells while
    the parity harness itself stays byte-for-byte unchanged."""
    monkeypatch.setenv("REPRO_KERNELS", "on")
    test_mesh_parity.test_mesh_matches_single_box(alg)


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------

def test_autotune_sweep_and_apply_best():
    from repro.kernels.autotune import (
        apply_best,
        autotune_cdf,
        autotune_fused,
        autotune_sparse,
    )

    rng = np.random.default_rng(0)
    t, k, w, d, j = 32, 16, 12, 8, 10
    n_wk = jnp.asarray(rng.integers(0, 30, (w, k)), jnp.int32)
    n_kd = jnp.asarray(rng.integers(0, 10, (d, k)), jnp.int32)
    word = jnp.asarray(rng.integers(0, w, (t,)), jnp.int32)
    doc = jnp.asarray(rng.integers(0, d, (t,)), jnp.int32)
    z = jnp.asarray(rng.integers(0, k, (t,)), jnp.int32)
    n_k = jnp.asarray(np.asarray(n_wk).sum(0) + 1, jnp.float32)
    alpha_k = jnp.asarray(rng.random(k) + 0.01, jnp.float32)
    term = jnp.asarray(rng.random(k) + 1e-3, jnp.float32)
    targets = jnp.asarray(rng.random(t) * 5, jnp.float32)
    vals = jnp.asarray(rng.random((t, j)), jnp.float32)
    topics = jnp.asarray(rng.integers(0, k, (t, j)), jnp.int32)

    timings = []
    timings += autotune_fused(
        n_wk, n_kd, word, doc, z, alpha_k, n_k, jnp.int32(7),
        beta=0.01, w_beta=0.16, bts=(8, 16), bks=(128,),
        iters=1, warmup=0,
    )
    timings += autotune_cdf(
        n_wk, word, term, targets, bts=(8, 16), bks=(128,),
        iters=1, warmup=0,
    )
    timings += autotune_sparse(
        vals, topics, targets, bts=(8,), bss=(128, 256),
        iters=1, warmup=0,
    )
    assert len(timings) == 6
    assert {tt.kernel for tt in timings} == \
        {"fused_sample", "cdf_search", "sparse_row"}
    assert all(tt.us_per_call > 0 and tt.tokens_per_sec > 0
               for tt in timings)

    tuned = apply_best(timings, SamplerKnobs())
    # winners land in the swept grid, and re-validation passed (no raise)
    assert tuned.bt in (8, 16)
    assert tuned.bk == 128
    assert tuned.bs in (128, 256)
    assert apply_best([], SamplerKnobs()) == SamplerKnobs()
