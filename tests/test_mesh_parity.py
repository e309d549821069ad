"""Cross-backend mesh/single-box parity harness (subprocess: own devices).

For EVERY registered backend with ``supports_shard_map`` this asserts, on a
2-device CPU mesh (``--xla_force_host_platform_device_count=2``) against a
single-box run started from the SAME initial assignment:

* count conservation after every distributed iteration (sum N_k == E and
  the N_wk / N_kd column sums equal N_k after the sync step);
* a non-increasing-perplexity trend on both paths (llh improves over the
  run from the shared starting point, and the two paths land in a common
  band after equal iterations);
* replay determinism: re-running the same jitted step from the same init
  yields bit-identical N_wk / N_k (same executable => same counts);
* for the deterministic Gumbel-max backends (zen_dense, zen_pallas), exact
  N_wk / N_k equality between the shard_map step and a host-side per-cell
  emulation of the paper's workflow (same keys, same local views, delta
  merge by hand) — the cell semantics ARE the spec.

The backend list is read from the registry at collection, so a newly
registered mesh-capable algorithm is covered with zero test changes.
"""
import pytest

from helpers import run_with_devices

from repro import algorithms

MESH_BACKENDS = [
    n for n in algorithms.registered() if algorithms.get(n).supports_shard_map
]
GUMBEL_EXACT = ["zen_dense", "zen_pallas"]

COMMON = """
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from repro import algorithms
from repro.data import synthetic_lda_corpus
from repro.core.types import CGSState, LDAHyperParams
from repro.core.graph import grid_partition
from repro.core import counts as counts_lib
from repro.launch.mesh import make_mesh
from repro.core.distributed import (DistConfig, init_dist_state,
                                    make_dist_step, resolve_dist_row_pads)

corpus, _ = synthetic_lda_corpus(0, num_docs=50, num_words=80, num_topics=8,
                                 avg_doc_len=30)
hyper = LDAHyperParams(num_topics=8, alpha=0.1, beta=0.05)
K = hyper.num_topics

mesh = make_mesh((1, 2), ('data', 'model'))
grid = grid_partition(corpus, 1, 2)
E = int(grid.mask.sum())
assert E == corpus.num_tokens

# one shared initial assignment: draw per-token topics on the grid, then
# transfer them to corpus token order via the (word, doc) key matching of
# the elastic-rescale test (tokens of one edge are exchangeable)
rng0 = np.random.default_rng(0)
init_grid = np.zeros(grid.word.shape, np.int32)
init_grid[grid.mask] = rng0.integers(0, K, size=E).astype(np.int32)

def inverse_perm(perm, padded_size):
    inv = np.full(padded_size, -1, np.int64)
    inv[perm] = np.arange(perm.shape[0])
    return inv

inv_w = inverse_perm(grid.word_perm, grid.num_words_padded)
inv_d = inverse_perm(grid.doc_perm, grid.num_docs_padded)
gw = inv_w[grid.word[grid.mask]]; gd = inv_d[grid.doc[grid.mask]]
key_grid = gw * 10**6 + gd
cw = np.asarray(corpus.word); cd = np.asarray(corpus.doc)
key_corpus = cw * 10**6 + cd
np.testing.assert_array_equal(np.sort(key_grid), np.sort(key_corpus))
z_corpus = np.zeros(E, np.int32)
z_corpus[np.argsort(key_corpus, kind='stable')] = \
    init_grid[grid.mask][np.argsort(key_grid, kind='stable')]

def single_box_state(key):
    z = jnp.asarray(z_corpus)
    n_wk, n_kd, n_k = counts_lib.build_counts(
        corpus.word, corpus.doc, z, corpus.num_words, corpus.num_docs, K)
    zeros = jnp.zeros((E,), jnp.int32)
    return CGSState(topic=z, prev_topic=z, n_wk=n_wk, n_kd=n_kd, n_k=n_k,
                    rng=key, iteration=jnp.int32(0),
                    stale_iters=zeros, same_count=zeros)

# ONE evaluator for both paths: the mesh state's counts mapped back to
# corpus ids, scored by the single-box llh (the dist llh has the same W in
# W*beta, but sums per cell, in another order)
from repro.core.likelihood import predictive_llh

def eval_dist(dist_state):
    n_wk = jnp.asarray(np.asarray(dist_state.n_wk)[grid.word_perm])
    n_kd = jnp.asarray(np.asarray(dist_state.n_kd)[grid.doc_perm])
    z = jnp.asarray(z_corpus)
    zeros = jnp.zeros((E,), jnp.int32)
    st = CGSState(topic=z, prev_topic=z, n_wk=n_wk, n_kd=n_kd,
                  n_k=dist_state.n_k, rng=jax.random.key(0),
                  iteration=jnp.int32(0), stale_iters=zeros,
                  same_count=zeros)
    return float(predictive_llh(st, corpus, hyper))

def eval_sb(st):
    return float(predictive_llh(st, corpus, hyper))

def ppl(llh_val):
    return float(np.exp(-llh_val / E))
"""


@pytest.mark.parametrize("alg", MESH_BACKENDS)
def test_mesh_matches_single_box(alg):
    run_with_devices(COMMON + f"""
from repro.core import LDATrainer, TrainConfig

ITERS = 8
alg = '{alg}'

# --- distributed run on the 2-device mesh ------------------------------
state, data = init_dist_state(jax.random.key(0), mesh, grid, hyper,
                              init_topics=init_grid)
dcfg = resolve_dist_row_pads(state,
                             DistConfig(algorithm=alg, max_kd=0, max_kw=0))
step = make_dist_step(mesh, hyper, dcfg, grid.words_per_shard,
                      grid.docs_per_shard, grid.num_words)
l0 = eval_dist(state)
mesh_llhs = [l0]
st = state
for _ in range(ITERS):
    st = step(st, data)
    # count conservation after EVERY sync step
    assert int(jnp.sum(st.n_k)) == E
    np.testing.assert_array_equal(np.asarray(jnp.sum(st.n_wk, 0)),
                                  np.asarray(st.n_k))
    np.testing.assert_array_equal(np.asarray(jnp.sum(st.n_kd, 0)),
                                  np.asarray(st.n_k))
    mesh_llhs.append(eval_dist(st))
l_mesh = mesh_llhs[-1]
assert l_mesh > l0, (l0, l_mesh)
# non-increasing perplexity trend: no point rises >2% above the best so far
best = ppl(mesh_llhs[0])
for v in mesh_llhs[1:]:
    assert ppl(v) <= best * 1.02, (mesh_llhs,)
    best = min(best, ppl(v))

# replay determinism: same jitted step, same init => identical counts
state2, _ = init_dist_state(jax.random.key(0), mesh, grid, hyper,
                            init_topics=init_grid)
st2 = state2
for _ in range(ITERS):
    st2 = step(st2, data)
np.testing.assert_array_equal(np.asarray(st.n_wk), np.asarray(st2.n_wk))
np.testing.assert_array_equal(np.asarray(st.n_k), np.asarray(st2.n_k))

# --- single-box run from the SAME initial assignment -------------------
tr = LDATrainer(corpus, hyper, TrainConfig(algorithm=alg,
                                           sampling_method='gumbel'))
sb = single_box_state(jax.random.key(7))
l0_sb = eval_sb(sb)
np.testing.assert_allclose(l0_sb, l0, rtol=1e-4)  # same init, same metric
sb_llhs = [l0_sb]
for _ in range(ITERS):
    sb = tr.step(sb)
    sb_llhs.append(eval_sb(sb))
sb.check_invariants(corpus)
l_sb = sb_llhs[-1]
assert l_sb > l0_sb, (l0_sb, l_sb)
best = ppl(sb_llhs[0])
for v in sb_llhs[1:]:
    assert ppl(v) <= best * 1.02, (sb_llhs,)
    best = min(best, ppl(v))
# equal iterations from one init land in a common band (trend agreement;
# 15% absorbs mixing-speed differences — e.g. lightlda's mesh proposal is
# locality-restricted and converges a little slower than single-box —
# while still catching a cell that samples garbage, which stalls at init)
assert abs(l_mesh - l_sb) / abs(l_sb) < 0.15, (l_mesh, l_sb)
print('PARITY OK', alg, l0, l_mesh, l_sb)
""", n_devices=2, timeout=900)


@pytest.mark.parametrize("alg", GUMBEL_EXACT)
def test_gumbel_cell_semantics_exact(alg):
    """shard_map step == host-side per-cell emulation, bit-for-bit.

    Reimplements the paper-Fig.-2 workflow on one device — per-cell keys,
    local id translation, cell_sweep on the local blocks, delta merge —
    and checks the distributed step produces EXACTLY the same N_wk / N_kd
    / N_k after the sync. Deterministic for the Gumbel-max backends."""
    run_with_devices(COMMON + f"""
alg = '{alg}'
backend = algorithms.get(alg)
state, data = init_dist_state(jax.random.key(0), mesh, grid, hyper,
                              init_topics=init_grid)
dcfg = DistConfig(algorithm=alg)
step = make_dist_step(mesh, hyper, dcfg, grid.words_per_shard,
                      grid.docs_per_shard, grid.num_words)
knobs = backend.resolve_cell_knobs(dcfg.knobs(), hyper)

rows, cols = 1, 2
wps, dps = grid.words_per_shard, grid.docs_per_shard
n_wk0 = np.asarray(state.n_wk); n_kd0 = np.asarray(state.n_kd)
n_k0 = np.asarray(state.n_k)
new_wk = n_wk0.copy(); new_kd = n_kd0.copy(); new_k = n_k0.copy()
base = jax.random.fold_in(state.rng, state.iteration)
for row in range(rows):
    for col in range(cols):
        cell = row * cols + col
        word = jnp.asarray(grid.word[cell]); doc = jnp.asarray(grid.doc[cell])
        mask = jnp.asarray(grid.mask[cell])
        z_old = state.topic[cell]
        word_l = word - col * wps
        doc_l = doc - row * dps
        dev = row * cols + col
        k_sample, _ = jax.random.split(jax.random.fold_in(base, dev))
        n_wk_l = jnp.asarray(n_wk0[col * wps:(col + 1) * wps])
        n_kd_l = jnp.asarray(n_kd0[row * dps:(row + 1) * dps])
        z_prop = backend.cell_sweep(
            k_sample, word_l, doc_l, z_old, mask, n_wk_l, n_kd_l,
            jnp.asarray(n_k0), hyper, grid.num_words, knobs)
        z_new = np.where(np.asarray(mask), np.asarray(z_prop),
                         np.asarray(z_old))
        live = np.asarray(mask)
        w_np = np.asarray(word); d_np = np.asarray(doc)
        zo = np.asarray(z_old)
        for t in np.nonzero(live & (z_new != zo))[0]:
            new_wk[w_np[t], zo[t]] -= 1; new_wk[w_np[t], z_new[t]] += 1
            new_kd[d_np[t], zo[t]] -= 1; new_kd[d_np[t], z_new[t]] += 1
            new_k[zo[t]] -= 1; new_k[z_new[t]] += 1

st = step(state, data)
np.testing.assert_array_equal(np.asarray(st.n_wk), new_wk)
np.testing.assert_array_equal(np.asarray(st.n_kd), new_kd)
np.testing.assert_array_equal(np.asarray(st.n_k), new_k)
print('EXACT OK', alg)
""", n_devices=2, timeout=900)
