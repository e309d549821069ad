"""Distributed LDA runtime under shard_map (subprocess: own device count)."""
import pytest

from helpers import run_with_devices

COMMON = """
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from repro.data import synthetic_lda_corpus
from repro.core.types import LDAHyperParams
from repro.core.graph import grid_partition
from repro.core import counts as counts_lib
from repro.launch.mesh import make_mesh
from repro.core.distributed import (DistConfig, init_dist_state,
                                    make_dist_step, make_dist_llh,
                                    make_rebuild_counts)
corpus, _ = synthetic_lda_corpus(0, num_docs=50, num_words=80, num_topics=8,
                                 avg_doc_len=30)
hyper = LDAHyperParams(num_topics=8, alpha=0.1, beta=0.05)
"""


def test_distributed_counts_match_serial():
    """Distributed rebuild == single-box build_counts on the same data."""
    run_with_devices(COMMON + """
mesh = make_mesh((2, 2), ('data', 'model'))
grid = grid_partition(corpus, 2, 2)
state, data = init_dist_state(jax.random.key(0), mesh, grid, hyper)
# reference: flatten grid tokens and build on one box
w = jnp.asarray(grid.word.reshape(-1)); d = jnp.asarray(grid.doc.reshape(-1))
m = jnp.asarray(grid.mask.reshape(-1)); z = state.topic.reshape(-1)
n_wk, n_kd, n_k = counts_lib.build_counts(
    w, d, z, grid.num_words_padded, grid.num_docs_padded, 8, mask=m)
np.testing.assert_array_equal(np.asarray(state.n_wk), np.asarray(n_wk))
np.testing.assert_array_equal(np.asarray(state.n_kd), np.asarray(n_kd))
np.testing.assert_array_equal(np.asarray(state.n_k), np.asarray(n_k))
print('MATCH')
""")


@pytest.mark.parametrize("alg", ["zen_dense", "zen_cdf", "zen_dense_kernel"])
def test_distributed_invariants_and_convergence(alg):
    run_with_devices(COMMON + f"""
mesh = make_mesh((2, 2), ('data', 'model'))
grid = grid_partition(corpus, 2, 2)
E = int(grid.mask.sum())
state, data = init_dist_state(jax.random.key(0), mesh, grid, hyper)
step = make_dist_step(mesh, hyper, DistConfig(algorithm='{alg}', max_kd=8),
                      grid.words_per_shard, grid.docs_per_shard,
                      grid.num_words)
llh = make_dist_llh(mesh, hyper, grid.words_per_shard, grid.docs_per_shard,
                    grid.num_words)
l0 = float(llh(state, data))
for _ in range(10):
    state = step(state, data)
assert int(jnp.sum(state.n_k)) == E
np.testing.assert_array_equal(np.asarray(jnp.sum(state.n_wk, 0)),
                              np.asarray(state.n_k))
np.testing.assert_array_equal(np.asarray(jnp.sum(state.n_kd, 0)),
                              np.asarray(state.n_k))
l1 = float(llh(state, data))
assert l1 > l0, (l0, l1)
print('OK', l0, l1)
""", timeout=900)


def test_delta_compression_preserves_counts():
    """int16/int8 compressed psums keep exact totals on this workload."""
    run_with_devices(COMMON + """
mesh = make_mesh((2, 2), ('data', 'model'))
grid = grid_partition(corpus, 2, 2)
E = int(grid.mask.sum())
for dd in ('int16', 'int8'):
    state, data = init_dist_state(jax.random.key(0), mesh, grid, hyper)
    step = make_dist_step(mesh, hyper,
                          DistConfig(algorithm='zen_cdf', max_kd=8,
                                     delta_dtype=dd),
                          grid.words_per_shard, grid.docs_per_shard,
                          grid.num_words)
    for _ in range(6):
        state = step(state, data)
    assert int(jnp.sum(state.n_k)) == E, dd
print('COMPRESSION OK')
""")


def test_elastic_rescale():
    """Train on 2x2, checkpoint assignments, restore on 1x4 and 4x1 —
    counts rebuild correctly and training continues (DESIGN.md §3.2)."""
    run_with_devices(COMMON + """
mesh_a = make_mesh((2, 2), ('data', 'model'))
grid_a = grid_partition(corpus, 2, 2)
E = int(grid_a.mask.sum())
state, data = init_dist_state(jax.random.key(0), mesh_a, grid_a, hyper)
step = make_dist_step(mesh_a, hyper, DistConfig(algorithm='zen_cdf', max_kd=8),
                      grid_a.words_per_shard, grid_a.docs_per_shard,
                      grid_a.num_words)
for _ in range(4):
    state = step(state, data)
# checkpoint = per-token assignments keyed by ORIGINAL (word, doc) ids
def inverse_perm(perm, padded_size):
    inv = np.full(padded_size, -1, np.int64)
    inv[perm] = np.arange(perm.shape[0])
    return inv

z_grid = np.asarray(state.topic)
mask = grid_a.mask
w_flat = grid_a.word[mask]; d_flat = grid_a.doc[mask]; z_flat = z_grid[mask]
inv_wa = inverse_perm(grid_a.word_perm, grid_a.num_words_padded)
inv_da = inverse_perm(grid_a.doc_perm, grid_a.num_docs_padded)
wa = inv_wa[w_flat]; da = inv_da[d_flat]
key_a = wa * 10**6 + da
order_a = np.argsort(key_a, kind='stable')
saved = z_flat[order_a]

# "new cluster": different mesh shape
for shape in [(1, 4), (4, 1)]:
    mesh_b = make_mesh(shape, ('data', 'model'))
    grid_b = grid_partition(corpus, shape[0], shape[1])
    wb = grid_b.word[grid_b.mask]; db = grid_b.doc[grid_b.mask]
    inv_wb = inverse_perm(grid_b.word_perm, grid_b.num_words_padded)
    inv_db = inverse_perm(grid_b.doc_perm, grid_b.num_docs_padded)
    key_b = inv_wb[wb] * 10**6 + inv_db[db]
    np.testing.assert_array_equal(np.sort(key_a), np.sort(key_b))
    # tokens of identical (w,d) are exchangeable: assign saved z by key order
    order_b = np.argsort(key_b, kind='stable')
    z_b = np.zeros(key_b.shape[0], np.int32)
    z_b[order_b] = saved
    init_topics = np.zeros(grid_b.word.shape, np.int32)
    init_topics[grid_b.mask] = z_b
    state_b, data_b = init_dist_state(jax.random.key(1), mesh_b, grid_b,
                                      hyper, init_topics=init_topics)
    assert int(jnp.sum(state_b.n_k)) == E
    # identical global topic histogram after re-sharding
    np.testing.assert_array_equal(np.asarray(state_b.n_k),
                                  np.asarray(state.n_k))
    step_b = make_dist_step(mesh_b, hyper,
                            DistConfig(algorithm='zen_cdf', max_kd=8),
                            grid_b.words_per_shard, grid_b.docs_per_shard,
                            grid_b.num_words)
    state_b = step_b(state_b, data_b)  # continues training
    assert int(jnp.sum(state_b.n_k)) == E
print('ELASTIC OK')
""", timeout=900)


def test_three_axis_pod_mesh():
    run_with_devices(COMMON + """
mesh = make_mesh((2, 1, 2), ('pod', 'data', 'model'))
grid = grid_partition(corpus, 2, 2)  # pod*data rows = 2
E = int(grid.mask.sum())
state, data = init_dist_state(jax.random.key(0), mesh, grid, hyper)
step = make_dist_step(mesh, hyper, DistConfig(algorithm='zen_cdf', max_kd=8),
                      grid.words_per_shard, grid.docs_per_shard,
                      grid.num_words)
for _ in range(4):
    state = step(state, data)
assert int(jnp.sum(state.n_k)) == E
print('POD OK')
""")
