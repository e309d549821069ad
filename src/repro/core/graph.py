"""Corpus-graph partitioning (paper §4.1) — the data/model co-partitioner.

The corpus is the bipartite word-doc graph; distribution = partitioning it.
Host-side (numpy) because this is a data-pipeline step, exactly where the
paper runs it (a Spark stage before training).

Vertex-cut strategies implemented (paper's GraphX menu + its contribution):
  * random_vertex_cut  — hash(src, dst)
  * edge_partition_1d  — hash(word) (co-locates a word's edges)
  * edge_partition_2d  — "rectangle" grid partition, the 2*sqrt(P)
                          replication bound
  * dbh                — degree-based hashing [Xie et al.]: cut the
                          higher-degree endpoint
  * dbh_plus           — paper Alg. 3: like DBH, but when BOTH degrees are
                          below a threshold, co-locate with the *higher*-
                          degree endpoint instead (locality beats balance
                          for cold edges)

For the TPU SPMD runtime the 2D grid is the physical layout (DESIGN.md §2):
``grid_partition`` relabels words/docs so each mesh column owns a contiguous,
token-count-balanced word range (greedy LPT bin-packing — hot words spread
first) and each mesh row owns a contiguous doc range, then pads every cell
to a uniform edge count. Replication factor and balance metrics quantify
what DBH+ buys (evaluated in benchmarks/bench_partition.py).
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, Tuple

import numpy as np

from repro.core.types import Corpus


# ---------------------------------------------------------------------------
# Classic vertex-cut partitioners (edge -> partition id)
# ---------------------------------------------------------------------------

def _hash(x: np.ndarray, seed: int = 0x9E3779B9) -> np.ndarray:
    x = x.astype(np.uint64)
    x = (x ^ (x >> 16)) * np.uint64(0x45D9F3B + seed)
    x = (x ^ (x >> 13)) * np.uint64(0xC2B2AE35)
    return x ^ (x >> 16)


def random_vertex_cut(word: np.ndarray, doc: np.ndarray, p: int) -> np.ndarray:
    return ((_hash(word) ^ _hash(doc, 17)) % p).astype(np.int32)


def edge_partition_1d(word: np.ndarray, doc: np.ndarray, p: int) -> np.ndarray:
    return (_hash(word) % p).astype(np.int32)


def edge_partition_2d(word: np.ndarray, doc: np.ndarray, p: int) -> np.ndarray:
    rows = int(np.floor(np.sqrt(p)))
    while p % rows:
        rows -= 1
    cols = p // rows
    return ((_hash(doc) % rows) * cols + (_hash(word, 5) % cols)).astype(np.int32)


def dbh(word: np.ndarray, doc: np.ndarray, p: int) -> np.ndarray:
    """Degree-based hashing: assign the edge by hashing its lower-degree
    endpoint (i.e. the higher-degree vertex gets cut/replicated)."""
    w_deg = np.bincount(word, minlength=word.max() + 1)[word]
    d_deg = np.bincount(doc, minlength=doc.max() + 1)[doc]
    use_word = w_deg <= d_deg
    return np.where(
        use_word, _hash(word) % p, (_hash(doc, 17) % p)
    ).astype(np.int32)


def dbh_plus(
    word: np.ndarray, doc: np.ndarray, p: int, threshold: int = 8
) -> np.ndarray:
    """Paper Alg. 3 (DBH+): DBH, except when max(deg_w, deg_d) < threshold
    the edge follows the *higher*-degree endpoint — for cold edges locality
    (fewer replicas) matters more than cutting the bigger vertex."""
    w_deg = np.bincount(word, minlength=word.max() + 1)[word]
    d_deg = np.bincount(doc, minlength=doc.max() + 1)[doc]
    both_cold = np.maximum(w_deg, d_deg) < threshold
    # hot edges: hash lower-degree endpoint (cut the hub)
    use_word_hot = w_deg <= d_deg
    # cold edges: hash HIGHER-degree endpoint (keep the small star together)
    use_word_cold = w_deg >= d_deg
    use_word = np.where(both_cold, use_word_cold, use_word_hot)
    return np.where(
        use_word, _hash(word) % p, (_hash(doc, 17) % p)
    ).astype(np.int32)


PARTITIONERS = {
    "random_vertex_cut": random_vertex_cut,
    "edge_partition_1d": edge_partition_1d,
    "edge_partition_2d": edge_partition_2d,
    "dbh": dbh,
    "dbh_plus": dbh_plus,
}


def partition_metrics(
    word: np.ndarray, doc: np.ndarray, part: np.ndarray, p: int
) -> Dict[str, float]:
    """Balance + replication metrics (PowerGraph's cost model, paper §4.1):
    workload ∝ edges per partition; comms ∝ total vertex mirrors."""
    edges_per = np.bincount(part, minlength=p)
    # replication factor: how many partitions each vertex appears in
    wp = np.unique(np.stack([word, part]), axis=1).shape[1]
    dp = np.unique(np.stack([doc, part]), axis=1).shape[1]
    n_w = np.unique(word).size
    n_d = np.unique(doc).size
    return {
        "edge_balance": float(edges_per.max() / max(edges_per.mean(), 1e-9)),
        "word_replication": float(wp / n_w),
        "doc_replication": float(dp / n_d),
        "total_replication": float((wp + dp) / (n_w + n_d)),
    }


# ---------------------------------------------------------------------------
# SPMD grid partition (the physical layout for the TPU mesh)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GridPartition:
    """Relabeled, padded 2D layout of a corpus for a (data x model) mesh.

    Arrays are global-view; axis 0 is `data*model` cells ordered row-major
    (cell = row * model + col) so sharding over ('data','model') flattened
    works with a simple reshape.
    """

    word: np.ndarray  # (cells, e_cell) int32 — NEW (relabeled) word ids
    doc: np.ndarray  # (cells, e_cell) int32 — NEW doc ids
    mask: np.ndarray  # (cells, e_cell) bool — False on padding
    data_parallel: int
    model_parallel: int
    words_per_shard: int  # W_pad / model_parallel
    docs_per_shard: int  # D_pad / data_parallel
    word_perm: np.ndarray  # old -> new word id (W,)
    doc_perm: np.ndarray  # old -> new doc id (D,)

    @property
    def num_words(self) -> int:
        """The corpus's W (the smoothing mass counts these, not the pad)."""
        return self.word_perm.shape[0]

    @property
    def num_words_padded(self) -> int:
        return self.words_per_shard * self.model_parallel

    @property
    def num_docs_padded(self) -> int:
        return self.docs_per_shard * self.data_parallel

    @property
    def padding_overhead(self) -> float:
        return float(self.mask.size / max(self.mask.sum(), 1)) - 1.0


def _balanced_ranges(loads: np.ndarray, bins: int) -> np.ndarray:
    """Greedy LPT bin-packing: assign items (sorted by descending load) to
    the least-loaded bin, the lowest such bin on a tie. Returns bin id per
    item. This is the DBH+ insight applied to static ranges: hot items get
    spread first."""
    order = np.argsort(-loads, kind="stable")
    heap = [(0, b) for b in range(bins)]
    assign = np.empty(loads.shape[0], dtype=np.int32)
    for it, load in zip(order.tolist(), loads[order].tolist()):
        least, b = heap[0]
        assign[it] = b
        heapq.heapreplace(heap, (least + load, b))
    return assign


def _relabel(assign: np.ndarray, bins: int) -> Tuple[np.ndarray, int]:
    """Ids that make each bin's items contiguous, in their old order, in
    ranges of one uniform width (the fullest bin's count)."""
    counts = np.bincount(assign, minlength=bins)
    per = int(counts.max())
    order = np.argsort(assign, kind="stable")
    starts = np.cumsum(counts) - counts
    perm = np.empty(assign.shape[0], dtype=np.int64)
    perm[order] = (assign[order].astype(np.int64) * per
                   + np.arange(assign.shape[0]) - starts[assign[order]])
    return perm, per


def grid_partition(
    corpus: Corpus,
    data_parallel: int,
    model_parallel: int,
    e_cell_multiple: int = 8,
    balance: str = "lpt",  # lpt | hash
    sort_tokens_by: str = "word",  # word-by-word process order (paper §3.1)
) -> GridPartition:
    word = np.asarray(corpus.word)
    doc = np.asarray(corpus.doc)
    w_tok = np.bincount(word, minlength=corpus.num_words)
    d_tok = np.bincount(doc, minlength=corpus.num_docs)

    if balance == "lpt":
        w_col = _balanced_ranges(w_tok, model_parallel)
        d_row = _balanced_ranges(d_tok, data_parallel)
    else:
        w_col = (_hash(np.arange(corpus.num_words)) % model_parallel).astype(np.int32)
        d_row = (_hash(np.arange(corpus.num_docs), 17) % data_parallel).astype(np.int32)

    # Relabel so each column's words are contiguous & uniform-width.
    word_perm, words_per_shard = _relabel(w_col, model_parallel)
    doc_perm, docs_per_shard = _relabel(d_row, data_parallel)
    cells = data_parallel * model_parallel

    # Every token as one key whose bits order it by (cell, word, doc), or
    # by (cell, doc, word), made of a word's and a document's part; tokens
    # with equal keys are the same edge, so sorting the keys themselves
    # lays the cells out, and each cell's keys start at its own bound.
    wb = int(words_per_shard * model_parallel - 1).bit_length()
    db = int(docs_per_shard * data_parallel - 1).bit_length()
    cb = int(model_parallel - 1).bit_length()
    row, col = np.divmod(np.arange(cells + 1), model_parallel)
    if sort_tokens_by == "word":  # (row, new word, new doc)
        word_part = word_perm << db
        doc_part = (d_row.astype(np.int64) << (wb + db)) | doc_perm
        bounds = (row << (wb + db)) | (col * words_per_shard << db)
        shifts = (db, 0)
    else:  # (row, col, new doc, new word)
        word_part = (w_col.astype(np.int64) << (db + wb)) | word_perm
        doc_part = ((d_row.astype(np.int64) << (cb + db + wb))
                    | (doc_perm << wb))
        bounds = (row << (cb + db + wb)) | (col << (db + wb))
        shifts = (0, wb)
    key = word_part[word]
    key |= doc_part[doc]
    key.sort()
    starts = np.searchsorted(key, bounds)
    counts = np.diff(starts)

    e_cell = int(counts.max())
    e_cell = ((e_cell + e_cell_multiple - 1) // e_cell_multiple) * e_cell_multiple
    e_cell = max(e_cell, e_cell_multiple)

    out_w = np.empty((cells, e_cell), dtype=np.int32)
    out_d = np.empty((cells, e_cell), dtype=np.int32)
    out_m = np.zeros((cells, e_cell), dtype=bool)
    for c in range(cells):
        n = int(counts[c])
        part = key[starts[c]:starts[c] + n]
        out_w[c, :n] = (part >> shifts[0]) & ((1 << wb) - 1)
        out_d[c, :n] = (part >> shifts[1]) & ((1 << db) - 1)
        out_m[c, :n] = True
        # padding tokens point at the cell's own (word, doc) range so local
        # index arithmetic stays in-bounds; mask keeps them inert.
        r, cc = divmod(c, model_parallel)
        out_w[c, n:] = cc * words_per_shard
        out_d[c, n:] = r * docs_per_shard

    return GridPartition(
        word=out_w, doc=out_d, mask=out_m,
        data_parallel=data_parallel, model_parallel=model_parallel,
        words_per_shard=words_per_shard, docs_per_shard=docs_per_shard,
        word_perm=word_perm, doc_perm=doc_perm,
    )
