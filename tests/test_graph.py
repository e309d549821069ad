"""Graph partitioning (paper §4.1, Alg. 3 DBH+)."""
import numpy as np
import pytest

from repro.core.graph import (
    PARTITIONERS,
    dbh,
    dbh_plus,
    grid_partition,
    partition_metrics,
)
from repro.data import synthetic_corpus


@pytest.fixture(scope="module")
def skewed():
    c = synthetic_corpus(0, num_docs=400, num_words=600, avg_doc_len=40,
                         zipf_a=1.4)
    return c, np.asarray(c.word), np.asarray(c.doc)


def test_all_partitioners_valid(skewed):
    _, w, d = skewed
    for name, fn in PARTITIONERS.items():
        part = fn(w, d, 8)
        assert part.min() >= 0 and part.max() < 8, name
        m = partition_metrics(w, d, part, 8)
        assert m["edge_balance"] >= 1.0
        assert m["total_replication"] >= 1.0


def test_1d_partition_perfect_word_locality(skewed):
    _, w, d = skewed
    part = PARTITIONERS["edge_partition_1d"](w, d, 8)
    m = partition_metrics(w, d, part, 8)
    assert m["word_replication"] == 1.0


def test_dbh_beats_random_on_replication(skewed):
    _, w, d = skewed
    m_rand = partition_metrics(w, d, PARTITIONERS["random_vertex_cut"](w, d, 16), 16)
    m_dbh = partition_metrics(w, d, dbh(w, d, 16), 16)
    assert m_dbh["total_replication"] < m_rand["total_replication"]


def test_dbh_plus_improves_cold_edges():
    """Alg. 3: on a corpus with many cold edges, DBH+ lowers replication
    without hurting balance."""
    c = synthetic_corpus(1, num_docs=3000, num_words=2000, avg_doc_len=5,
                         zipf_a=1.5)
    w, d = np.asarray(c.word), np.asarray(c.doc)
    m_dbh = partition_metrics(w, d, dbh(w, d, 16), 16)
    m_plus = partition_metrics(w, d, dbh_plus(w, d, 16, threshold=8), 16)
    assert m_plus["total_replication"] <= m_dbh["total_replication"]
    assert m_plus["edge_balance"] <= m_dbh["edge_balance"] * 1.05


def test_grid_partition_roundtrip(skewed):
    corpus, w, d = skewed
    for balance in ("lpt", "hash"):
        grid = grid_partition(corpus, 2, 4, balance=balance)
        # every real token appears exactly once
        assert int(grid.mask.sum()) == corpus.num_tokens
        # relabeled ids stay within their shard's range
        rows = np.arange(8) // 4
        cols = np.arange(8) % 4
        for c_ in range(8):
            sel = grid.mask[c_]
            ws = grid.word[c_][sel]
            ds = grid.doc[c_][sel]
            assert (ws // grid.words_per_shard == cols[c_]).all()
            assert (ds // grid.docs_per_shard == rows[c_]).all()
        # permutations are injective
        assert np.unique(grid.word_perm).size == corpus.num_words
        assert np.unique(grid.doc_perm).size == corpus.num_docs


def test_lpt_balances_better_than_hash(skewed):
    corpus, _, _ = skewed
    g_lpt = grid_partition(corpus, 4, 4, balance="lpt")
    g_hash = grid_partition(corpus, 4, 4, balance="hash")
    assert g_lpt.padding_overhead <= g_hash.padding_overhead


def test_word_sorted_within_cell(skewed):
    """Word-by-word process order (paper §3.1) is the physical layout."""
    corpus, _, _ = skewed
    grid = grid_partition(corpus, 2, 2, sort_tokens_by="word")
    for c_ in range(4):
        ws = grid.word[c_][grid.mask[c_]]
        assert (np.diff(ws) >= 0).all()


# -- grid_partition against the plain algorithm it replaced --------------------

def _oracle_grid(corpus, rows, cols, balance, sort_tokens_by):
    """The partition as first written: an LPT loop over ``argmin``, a
    relabelling loop per bin, one three-key ``lexsort`` and a fill loop.
    Returns (word, doc, mask, word_perm, doc_perm, words_per_shard,
    docs_per_shard)."""
    from repro.core.graph import _hash

    word, doc = np.asarray(corpus.word), np.asarray(corpus.doc)

    def lpt(loads, bins):
        bin_load = np.zeros(bins, dtype=np.int64)
        assign = np.zeros(loads.shape[0], dtype=np.int32)
        for it in np.argsort(-loads, kind="stable"):
            b = int(np.argmin(bin_load))
            assign[it] = b
            bin_load[b] += int(loads[it])
        return assign

    if balance == "lpt":
        w_col = lpt(np.bincount(word, minlength=corpus.num_words), cols)
        d_row = lpt(np.bincount(doc, minlength=corpus.num_docs), rows)
    else:
        w_col = (_hash(np.arange(corpus.num_words)) % cols).astype(np.int32)
        d_row = (_hash(np.arange(corpus.num_docs), 17) % rows).astype(np.int32)

    def relabel(assign, bins):
        per = int(np.bincount(assign, minlength=bins).max())
        perm = np.empty(assign.shape[0], dtype=np.int64)
        for b in range(bins):
            ids = np.where(assign == b)[0]
            perm[ids] = b * per + np.arange(ids.size)
        return perm, per

    word_perm, wps = relabel(w_col, cols)
    doc_perm, dps = relabel(d_row, rows)
    new_word, new_doc = word_perm[word], doc_perm[doc]
    cell = (new_doc // dps) * cols + new_word // wps
    cells = rows * cols
    e_cell = int(np.bincount(cell, minlength=cells).max())
    e_cell = max(-(-e_cell // 8) * 8, 8)
    out_w = np.zeros((cells, e_cell), dtype=np.int32)
    out_d = np.zeros((cells, e_cell), dtype=np.int32)
    out_m = np.zeros((cells, e_cell), dtype=bool)
    order = np.lexsort((new_doc, new_word, cell) if sort_tokens_by == "word"
                       else (new_word, new_doc, cell))
    sw, sd, sc = new_word[order], new_doc[order], cell[order]
    for c in range(cells):
        lo, hi = np.searchsorted(sc, c), np.searchsorted(sc, c + 1)
        out_w[c, :hi - lo], out_d[c, :hi - lo] = sw[lo:hi], sd[lo:hi]
        out_m[c, :hi - lo] = True
        r, cc = divmod(c, cols)
        out_w[c, hi - lo:], out_d[c, hi - lo:] = cc * wps, r * dps
    return out_w, out_d, out_m, word_perm, doc_perm, wps, dps


def _sparse_corpus():
    """A skewed corpus whose vocabulary and document ids have gaps: words
    and documents with no token, at both ends and inside."""
    from repro.core.types import Corpus

    rng = np.random.default_rng(3)
    n = 3000
    word = np.minimum(rng.zipf(1.3, n), 200).astype(np.int32) * 2 + 5
    doc = rng.integers(0, 60, n).astype(np.int32) * 3 + 1
    return Corpus(word=word, doc=doc, num_words=420, num_docs=190)


@pytest.mark.parametrize("grid_shape", [(1, 4), (2, 2), (4, 1)])
@pytest.mark.parametrize("balance", ["lpt", "hash"])
@pytest.mark.parametrize("order", ["word", "doc"])
@pytest.mark.parametrize("which", ["skewed", "sparse"])
def test_grid_partition_equals_the_plain_algorithm(skewed, grid_shape,
                                                   balance, order, which):
    corpus = skewed[0] if which == "skewed" else _sparse_corpus()
    rows, cols = grid_shape
    g = grid_partition(corpus, rows, cols, balance=balance,
                       sort_tokens_by=order)
    want = _oracle_grid(corpus, rows, cols, balance, order)
    got = (g.word, g.doc, g.mask, g.word_perm, g.doc_perm,
           g.words_per_shard, g.docs_per_shard)
    for name, a, b in zip(("word", "doc", "mask", "word_perm", "doc_perm",
                           "words_per_shard", "docs_per_shard"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert g.num_words == corpus.num_words
