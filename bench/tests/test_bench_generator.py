"""The LDA corpus generator: realised Zipf share, length mix, determinism."""
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import generator

CFG = {"num_words": 3000, "num_topics": 20, "avg_doc_len": 60}


def _corpus(seed, docs=400):
    lengths = generator.length_multiset(docs, 60.0, 0.8, 2, 400)
    word, doc, lens, model = generator.corpus(seed, CFG, docs, lengths, 4)
    return np.asarray(word), np.asarray(doc), lens, model


def test_length_multiset_mean_and_clip():
    lens = generator.length_multiset(20000, 332.0, 0.8, 16, 1024)
    assert lens.min() >= 16 and lens.max() <= 1024
    assert (np.diff(lens) >= 0).all()
    unclipped = generator.length_multiset(20000, 332.0, 0.8, 1, 10**9)
    assert unclipped.mean() == pytest.approx(332.0, rel=0.01)
    assert (lens == 1024).mean() == pytest.approx(0.036, abs=0.01)


def test_gap_multiset_mean():
    gaps = generator.gap_multiset(10000, 250.0)
    assert gaps.mean() == pytest.approx(1 / 250.0, rel=0.01)


def test_same_seed_same_corpus_and_every_seed_same_sizes():
    a = _corpus(7)
    b = _corpus(7)
    c = _corpus(2**31 + 11)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == c[0].shape
    np.testing.assert_array_equal(np.sort(a[2]), np.sort(c[2]))


def test_doc_ids_follow_lengths():
    word, doc, lens, _ = _corpus(3)
    np.testing.assert_array_equal(np.bincount(doc, minlength=len(lens)),
                                  lens)
    assert word.min() >= 0 and word.max() < CFG["num_words"]


def test_zipf_marginal_kept():
    word, _, _, model = _corpus(5, docs=3000)
    q = np.asarray(model.q, np.float64)
    freq = np.bincount(word, minlength=q.shape[0]) / word.shape[0]
    top = np.argsort(-q)[: q.shape[0] // 100]
    assert freq[top].sum() == pytest.approx(q[top].sum(), rel=0.05)
    head = np.argsort(-q)[:5]
    np.testing.assert_allclose(freq[head], q[head], rtol=0.15)


def test_slice_share_and_doc_length():
    cfg = {"num_words": 37763, "vocab_stride": 8, "avg_doc_len": 192}
    assert generator.slice_token_share(cfg) == pytest.approx(0.2686, abs=1e-3)
    assert generator.doc_length_mean(cfg) == pytest.approx(51.58, abs=0.05)
    assert generator.doc_length_mean({"num_words": 10, "avg_doc_len": 332}) \
        == 332.0


def test_ground_truth_counts_match_the_model():
    import jax

    _, _, _, model = _corpus(9)
    n_wk, n_k = generator.ground_truth_counts(
        jax.random.key(1), model.q, model.home, num_topics=20,
        total_tokens=2_000_000)
    n_wk = np.asarray(n_wk)
    assert abs(int(n_wk.sum()) - 2_000_000) < 5000
    np.testing.assert_array_equal(n_wk.sum(0), np.asarray(n_k))
    pi = n_wk.sum(0) / n_wk.sum()
    np.testing.assert_allclose(pi, np.asarray(model.pi), atol=2e-3)
