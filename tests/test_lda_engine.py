"""Batched LDA serving engine vs the single-doc ``cgs_infer`` oracle.

The engine's statistical contract (see ``repro/serving/lda_engine.py``):

* default (dense) backend, cdf sampling -> served theta is **bit-equal**
  to ``cgs_infer`` run with the same key, for any bucketing and any batch
  composition;
* native backends (``zen_cdf``, ``zen_pallas``) match the oracle
  statistically (dominant topic + posterior-mean distance);
* bucket padding and batch-mates never change a request's result;
* empty / unknown-vocabulary / over-long documents are handled;
* trained models round-trip through the model checkpoint.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import algorithms
from repro.core.inference import cgs_infer
from repro.core.trainer import LDATrainer, TrainConfig
from repro.core.types import LDAHyperParams
from repro.serving import (
    FrozenLDAModel,
    LDAEngine,
    LDAServeConfig,
    doc_completion_perplexity,
    docs_from_corpus,
)
from repro.train.checkpoint import load_lda_model, save_lda_model


def _sharp_model(k=4, w=40, weight=100):
    """Topics with disjoint vocabulary blocks (same as test_inference)."""
    n_wk = np.zeros((w, k), np.int32)
    block = w // k
    for t in range(k):
        n_wk[t * block : (t + 1) * block, t] = weight
    n_k = n_wk.sum(0).astype(np.int32)
    hyper = LDAHyperParams(num_topics=k, alpha=0.1, beta=0.01)
    return FrozenLDAModel(
        n_wk=jnp.asarray(n_wk), n_k=jnp.asarray(n_k), hyper=hyper
    )


def _mixed_docs(rng, n, w=40, lo=1, hi=24):
    return [
        rng.integers(0, w, size=rng.integers(lo, hi)).astype(np.int32)
        for _ in range(n)
    ]


def _serve_one(model, doc, key, *, buckets, algorithm="zen", num_sweeps=10,
               batch_mates=(), seed=0):
    eng = LDAEngine(
        model,
        LDAServeConfig(buckets=buckets, max_batch=8, num_sweeps=num_sweeps,
                       algorithm=algorithm),
        seed=seed,
    )
    uid = eng.submit(doc, key=key)
    for mate in batch_mates:
        eng.submit(mate)
    return {r.uid: r for r in eng.run_until_done()}[uid].theta


def test_engine_matches_oracle_bitwise():
    """64 mixed-length docs through the batched, bucketed engine in one
    process == cgs_infer per doc, to float tolerance (the chains are
    integer-identical; theta arithmetic is np vs jnp). Every doc's theta
    is checked against the oracle for a subset of docs covering all
    buckets (the eager oracle is the slow side); all 64 are served."""
    model = _sharp_model()
    rng = np.random.default_rng(0)
    docs = _mixed_docs(rng, 64)
    keys = [jax.random.key(100 + i) for i in range(len(docs))]
    eng = LDAEngine(
        model,
        LDAServeConfig(buckets=(8, 16, 32), max_batch=4, num_sweeps=10,
                       algorithm="zen"),
        seed=0,
    )
    uids = [eng.submit(d, key=k) for d, k in zip(docs, keys)]
    done = {r.uid: r for r in eng.run_until_done()}
    assert len(done) == len(docs) and eng.docs_done == 64
    for theta in (done[u].theta for u in uids):
        np.testing.assert_allclose(theta.sum(), 1.0, atol=1e-3)
    for i in range(0, len(docs), 4):
        oracle = np.asarray(
            cgs_infer(keys[i], model.n_wk, model.n_k, jnp.asarray(docs[i]),
                      model.hyper, num_sweeps=10)
        )
        np.testing.assert_allclose(done[uids[i]].theta, oracle, atol=1e-6)


@pytest.mark.parametrize("algorithm", ["zen", "zen_cdf"])
def test_bucket_padding_never_changes_results(algorithm):
    model = _sharp_model()
    doc = np.random.default_rng(2).integers(0, 40, size=10).astype(np.int32)
    key = jax.random.key(42)
    thetas = [
        _serve_one(model, doc, key, buckets=buckets, algorithm=algorithm)
        for buckets in [(16,), (32,), (64, 128)]
    ]
    for theta in thetas[1:]:
        np.testing.assert_array_equal(thetas[0], theta)


@pytest.mark.parametrize("algorithm", ["zen", "zen_cdf"])
def test_batch_composition_never_changes_results(algorithm):
    model = _sharp_model()
    rng = np.random.default_rng(3)
    doc = rng.integers(0, 40, size=9).astype(np.int32)
    key = jax.random.key(7)
    alone = _serve_one(model, doc, key, buckets=(16,), algorithm=algorithm)
    crowded = _serve_one(model, doc, key, buckets=(16,),
                         algorithm=algorithm,
                         batch_mates=_mixed_docs(rng, 5, lo=1, hi=14))
    np.testing.assert_array_equal(alone, crowded)


@pytest.mark.parametrize("algorithm", ["zen_cdf", "zen_pallas"])
def test_native_backends_match_oracle_statistically(algorithm):
    """Native infer_sweep overrides: dominant topic always recovered and
    theta within posterior-mean tolerance of the oracle."""
    model = _sharp_model()
    rng = np.random.default_rng(1)
    docs, doms = [], []
    for i in range(8):
        t = i % 4
        docs.append(
            rng.integers(t * 10, (t + 1) * 10, size=15).astype(np.int32)
        )
        doms.append(t)
    eng = LDAEngine(
        model,
        LDAServeConfig(buckets=(16, 32), max_batch=8, num_sweeps=15,
                       algorithm=algorithm),
        seed=3,
    )
    thetas = eng.infer_batch(docs)
    assert [int(np.argmax(t)) for t in thetas] == doms
    for i in (0, 5):
        oracle = np.mean(
            [
                np.asarray(cgs_infer(jax.random.key(s), model.n_wk,
                                     model.n_k, jnp.asarray(docs[i]),
                                     model.hyper, num_sweeps=15))
                for s in range(6)
            ],
            axis=0,
        )
        assert np.abs(oracle - thetas[i]).sum() < 0.15


def test_zen_pallas_sweeps_stay_random_with_vacant_slots():
    """Regression: the kernel seed must keep changing across sweeps even
    when batch mates finish early and their slots hold the engine's
    constant dummy key (a fixed seed degenerates the chain into an
    iterated deterministic map)."""
    model = _sharp_model()
    rng = np.random.default_rng(11)
    doc = rng.integers(0, 10, size=15).astype(np.int32)  # topic-0 block
    key = jax.random.key(5)
    oracle = np.mean(
        [
            np.asarray(cgs_infer(jax.random.key(s), model.n_wk, model.n_k,
                                 jnp.asarray(doc), model.hyper,
                                 num_sweeps=12))
            for s in range(6)
        ],
        axis=0,
    )
    mate = rng.integers(10, 20, size=8).astype(np.int32)
    for mate_sweeps in (12, 3):  # lockstep mate / mate finishes early
        eng = LDAEngine(
            model,
            LDAServeConfig(buckets=(16,), max_batch=4, num_sweeps=12,
                           algorithm="zen_pallas"),
            seed=0,
        )
        uid = eng.submit(doc, key=key)
        eng.submit(mate, num_sweeps=mate_sweeps)
        theta = {r.uid: r for r in eng.run_until_done()}[uid].theta
        assert int(np.argmax(theta)) == 0
        assert np.abs(oracle - theta).sum() < 0.2


@pytest.mark.parametrize("algorithm", ["zen", "zen_cdf", "zen_pallas"])
def test_sweep_keys_are_one_host_array_of_chain_key_bits(algorithm):
    """The keys a bucket sweep receives: one host uint32 array whose row
    for a live chain is ``key_data(split(key, num_sweeps)[sweeps_done])``
    and, for every other slot (never used, or evacuated by a cancel), the
    bits of ``key(0)`` — no per-slot device array is built."""
    model = _sharp_model()
    rng = np.random.default_rng(13)

    def doc():
        return rng.integers(0, 40, size=12).astype(np.int32)

    eng = LDAEngine(
        model,
        LDAServeConfig(buckets=(16,), max_batch=4, num_sweeps=5,
                       algorithm=algorithm),
        seed=0,
    )
    seen = []
    sweep_fn = eng._sweep_fn

    def recording_sweep_fn(sm, length):
        fn = sweep_fn(sm, length)

        def record(keys, *args):
            seen.append(keys)
            return fn(keys, *args)

        return record

    eng._sweep_fn = recording_sweep_fn
    key_a, key_b, key_c = (jax.random.key(s) for s in (21, 22, 23))
    eng.submit(doc(), num_sweeps=2)  # slot 0: ripe after two ticks
    eng.submit(doc(), key=key_a)  # slot 1
    cancelled = eng.submit_async(doc(), key=key_c)  # slot 2
    eng.step()
    eng.step()
    assert eng.cancel(cancelled)  # slot 2 evacuated after two sweeps
    eng.submit(doc(), key=key_b)  # takes slot 0, freed by the ripe chain
    eng.step()

    def bits(key, sweep):
        return np.asarray(jax.random.key_data(jax.random.split(key, 5)[sweep]))

    dummy = np.asarray(jax.random.key_data(jax.random.key(0)))
    keys = seen[-1]
    assert len(seen) == 3
    assert all(type(k) is np.ndarray and k.dtype == np.uint32 for k in seen)
    assert keys.shape == (4,) + dummy.shape
    np.testing.assert_array_equal(keys[0], bits(key_b, 0))  # first sweep
    np.testing.assert_array_equal(keys[1], bits(key_a, 2))  # mid-chain
    np.testing.assert_array_equal(keys[2], dummy)  # cancelled mid-chain
    np.testing.assert_array_equal(keys[3], dummy)  # never used
    np.testing.assert_array_equal(seen[0][1], bits(key_a, 0))
    np.testing.assert_array_equal(seen[1][2], bits(key_c, 1))


def test_every_registered_backend_serves():
    """The registry contract: every backend serves through the default
    ``infer_sweep`` derivation (overrides or not) with sane output."""
    assert algorithms.get("zen_cdf").native_infer
    assert algorithms.get("zen_pallas").native_infer
    assert not algorithms.get("zen").native_infer
    model = _sharp_model()
    doc = np.arange(10, dtype=np.int32)  # the topic-0 vocabulary block
    for name in algorithms.registered():
        eng = LDAEngine(
            model,
            LDAServeConfig(buckets=(16,), max_batch=2, num_sweeps=6,
                           algorithm=name),
            seed=0,
        )
        theta = eng.infer_batch([doc])[0]
        assert theta.shape == (4,), name
        np.testing.assert_allclose(theta.sum(), 1.0, atol=1e-3, err_msg=name)
        assert int(np.argmax(theta)) == 0, name


def test_edge_cases_empty_unknown_overlong():
    model = _sharp_model()
    eng = LDAEngine(
        model, LDAServeConfig(buckets=(8,), max_batch=2, num_sweeps=5),
        seed=0,
    )
    rng = np.random.default_rng(4)
    u_empty = eng.submit([])
    u_unknown = eng.submit([999, -3, 10_000])
    u_long = eng.submit(rng.integers(0, 40, size=50).astype(np.int32))
    u_mixed = eng.submit([2, 999, 3])  # unknown ids dropped, rest served
    done = {r.uid: r for r in eng.run_until_done()}
    assert set(done) == {u_empty, u_unknown, u_long, u_mixed}
    assert eng.docs_done == 4  # instant-path requests count as served

    prior = np.asarray(model.hyper.alpha_k(model.n_k))
    np.testing.assert_allclose(done[u_empty].theta, prior / prior.sum(),
                               atol=1e-6)
    assert done[u_unknown].dropped_unknown == 3
    np.testing.assert_allclose(done[u_unknown].theta.sum(), 1.0, atol=1e-3)
    assert done[u_long].truncated and done[u_long].words.shape == (8,)
    assert done[u_mixed].dropped_unknown == 1
    assert done[u_mixed].words.tolist() == [2, 3]
    np.testing.assert_allclose(done[u_mixed].theta.sum(), 1.0, atol=1e-3)


def test_zero_sweeps_matches_oracle_init():
    model = _sharp_model()
    doc = np.arange(6, dtype=np.int32)
    key = jax.random.key(9)
    eng = LDAEngine(
        model, LDAServeConfig(buckets=(8,), max_batch=2, num_sweeps=0),
        seed=0,
    )
    uid = eng.submit(doc, key=key)
    theta = {r.uid: r for r in eng.run_until_done()}[uid].theta
    oracle = np.asarray(
        cgs_infer(key, model.n_wk, model.n_k, jnp.asarray(doc), model.hyper,
                  num_sweeps=0)
    )
    np.testing.assert_allclose(theta, oracle, atol=1e-6)


def test_burn_in_thinning_posterior_mean():
    model = _sharp_model()
    rng = np.random.default_rng(5)
    docs = _mixed_docs(rng, 4, lo=6, hi=16)
    eng = LDAEngine(
        model,
        LDAServeConfig(buckets=(16,), max_batch=4, num_sweeps=12, burn_in=4,
                       thin=2),
        seed=1,
    )
    uids = [eng.submit(d) for d in docs]
    done = {r.uid: r for r in eng.run_until_done()}
    for uid in uids:
        req = done[uid]
        assert req.theta_samples == 4  # sweeps 6, 8, 10, 12
        np.testing.assert_allclose(req.theta.sum(), 1.0, atol=1e-3)


def test_queue_overflow_drains():
    """More docs than slots: continuous admission refills freed slots."""
    model = _sharp_model()
    rng = np.random.default_rng(6)
    docs = _mixed_docs(rng, 20, lo=1, hi=14)
    eng = LDAEngine(
        model,
        LDAServeConfig(buckets=(16,), max_batch=3, num_sweeps=4),
        seed=0,
    )
    thetas = eng.infer_batch(docs)
    assert thetas.shape == (20, 4)
    assert eng.docs_done == 20


def test_model_checkpoint_roundtrip(tmp_path, tiny_corpus, tiny_hyper):
    """Trainer -> save_model -> FrozenLDAModel.from_checkpoint -> serve."""
    trainer = LDATrainer(tiny_corpus, tiny_hyper, TrainConfig(
        algorithm="zen", checkpoint_dir=str(tmp_path / "ck"),
    ))
    state = trainer.train(jax.random.key(0), 3)
    n_wk, n_k, hyper, meta, step = load_lda_model(str(tmp_path / "ck"))
    np.testing.assert_array_equal(np.asarray(state.n_wk), n_wk)
    np.testing.assert_array_equal(np.asarray(state.n_k), n_k)
    assert hyper == tiny_hyper and step == 3
    assert meta["algorithm"] == "zen"

    model = FrozenLDAModel.from_checkpoint(str(tmp_path / "ck"))
    docs = docs_from_corpus(tiny_corpus)[:6]
    eng = LDAEngine(
        model, LDAServeConfig(buckets=(32, 64), num_sweeps=5), seed=0,
    )
    thetas = eng.infer_batch(docs)
    assert thetas.shape == (6, tiny_hyper.num_topics)
    np.testing.assert_allclose(thetas.sum(1), 1.0, atol=1e-3)


def test_load_lda_model_missing_or_wrong_kind(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_lda_model(str(tmp_path / "nope"))
    # a non-model checkpoint is rejected, not silently served
    from repro.train.checkpoint import CheckpointManager

    CheckpointManager(str(tmp_path / "lm")).save(0, {"n_k": np.zeros(2),
                                                     "n_wk": np.zeros((3, 2))})
    with pytest.raises(FileNotFoundError):
        load_lda_model(str(tmp_path / "lm"))


def test_save_load_lda_model_direct(tmp_path):
    model = _sharp_model()
    save_lda_model(str(tmp_path), np.asarray(model.n_wk),
                   np.asarray(model.n_k), model.hyper, step=7)
    n_wk, n_k, hyper, _meta, step = load_lda_model(str(tmp_path))
    np.testing.assert_array_equal(n_wk, np.asarray(model.n_wk))
    np.testing.assert_array_equal(n_k, np.asarray(model.n_k))
    assert hyper == model.hyper and step == 7


def test_doc_completion_perplexity_sane():
    """The held-out score prefers the true model over a flat one."""
    model = _sharp_model()
    rng = np.random.default_rng(8)
    docs = [
        rng.integers(t * 10, (t + 1) * 10, size=20).astype(np.int32)
        for t in (0, 1, 2, 3) for _ in range(3)
    ]
    cfg = LDAServeConfig(buckets=(16,), max_batch=8, num_sweeps=10)
    ppl = doc_completion_perplexity(LDAEngine(model, cfg, seed=0), docs)
    flat = FrozenLDAModel(
        n_wk=jnp.ones_like(model.n_wk), n_k=jnp.full_like(model.n_k, 40),
        hyper=model.hyper,
    )
    ppl_flat = doc_completion_perplexity(LDAEngine(flat, cfg, seed=0), docs)
    assert 0 < ppl < ppl_flat
    # sharp model: topic block has 10 live words -> ppl near 10, far from W=40
    assert ppl < 20
