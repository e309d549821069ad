"""The sampler-backend registry: parity + conservation across every
registered backend, capability flags, and error reporting (DESIGN.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algorithms
from repro.core import LDATrainer, TrainConfig
from repro.core import counts as counts_lib
from repro.launch.mesh import make_mesh


def test_registry_lists_all_expected_backends():
    names = algorithms.registered()
    for expected in ("zen", "std", "zen_sparse", "zen_hybrid", "sparselda",
                     "lightlda", "zen_cdf", "zen_pallas"):
        assert expected in names, names


def test_unknown_name_raises_with_registered_list():
    with pytest.raises(ValueError) as ei:
        algorithms.get("definitely_not_an_algorithm")
    msg = str(ei.value)
    assert "definitely_not_an_algorithm" in msg
    for name in algorithms.registered():
        assert name in msg


def test_aliases_resolve_to_the_same_entry():
    """One registry entry per backend: TrainConfig's 'zen_pallas' and
    DistConfig's legacy 'zen_dense_kernel' are the same object, as are the
    single-box 'zen' and the distributed 'zen_dense'."""
    assert algorithms.get("zen_pallas") is algorithms.get("zen_dense_kernel")
    assert algorithms.get("zen") is algorithms.get("zen_dense")
    # aliases are not double-listed
    assert "zen_dense_kernel" not in algorithms.registered()


def test_capability_flags():
    assert algorithms.get("zen_cdf").supports_shard_map
    assert algorithms.get("zen_pallas").supports_shard_map
    assert algorithms.get("zen").supports_shard_map
    # the padded-sparse backends are mesh-capable since their cell-local
    # refactor; only the textbook std path stays single-box
    for name in ("zen_sparse", "zen_hybrid", "sparselda", "lightlda"):
        assert algorithms.get(name).supports_shard_map, name
        assert algorithms.get(name).needs_row_pads, name
    assert not algorithms.get("std").supports_shard_map
    assert algorithms.get("lightlda").needs_doc_index


@pytest.mark.parametrize("name", algorithms.registered())
def test_backend_parity_on_tiny_corpus(name, key, tiny_corpus, tiny_hyper):
    """Every registered backend (including zen_pallas in interpret mode)
    produces valid topics and conserves n_k totals after the delta merge."""
    tr = LDATrainer(tiny_corpus, tiny_hyper, TrainConfig(algorithm=name))
    st = tr.init_state(key)

    # raw sweep output: one valid topic per token
    z_new = tr.sweep(st)
    z = np.asarray(z_new)
    assert z.shape == (tiny_corpus.num_tokens,)
    assert z.dtype == np.int32
    assert (z >= 0).all() and (z < tiny_hyper.num_topics).all()

    # delta merge conserves every total (the backend contract: the driver
    # owns the merge, so any backend output must keep counts consistent)
    d_wk, d_kd, d_k = counts_lib.delta_counts(
        tiny_corpus.word, tiny_corpus.doc, st.topic, z_new,
        tiny_corpus.num_words, tiny_corpus.num_docs, tiny_hyper.num_topics,
    )
    assert int(jnp.sum(st.n_k + d_k)) == tiny_corpus.num_tokens
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(st.n_wk + d_wk, axis=0)), np.asarray(st.n_k + d_k)
    )
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(st.n_kd + d_kd, axis=0)), np.asarray(st.n_k + d_k)
    )

    # two full trainer iterations end-to-end (the acceptance round trip)
    for _ in range(2):
        st = tr.step(st)
    st.check_invariants(tiny_corpus)


def test_zen_pallas_matches_ref_oracle(key, tiny_corpus, tiny_hyper):
    """Single-box zen_pallas sweep == kernels/ref.py oracle bit-for-bit
    (interpret mode on CPU; the same contract the TPU kernel satisfies)."""
    from repro.kernels.ref import zen_sample_ref

    tr = LDATrainer(tiny_corpus, tiny_hyper, TrainConfig(algorithm="zen_pallas"))
    st = tr.init_state(key)
    z_backend = tr.sweep(st)

    # reproduce the backend's seed derivation, then call the pure-jnp oracle
    k_cell = jax.random.fold_in(st.rng, st.iteration)
    seed = jax.random.randint(
        k_cell, (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
    )
    ref = zen_sample_ref(
        st.n_wk[tiny_corpus.word], st.n_kd[tiny_corpus.doc], st.topic,
        tiny_hyper.alpha_k(st.n_k), st.n_k.astype(jnp.float32), seed,
        beta=tiny_hyper.beta,
        w_beta=tiny_corpus.num_words * tiny_hyper.beta,
    )
    np.testing.assert_array_equal(np.asarray(z_backend), np.asarray(ref))


def test_dist_config_resolves_same_registry_entry(key, tiny_corpus, tiny_hyper):
    """DistConfig and TrainConfig reach zen_pallas through the same entry:
    a 1x1 mesh dist step runs the kernel backend and conserves counts."""
    from repro.core.distributed import (
        DistConfig, init_dist_state, make_dist_step,
    )
    from repro.core.graph import grid_partition

    mesh = make_mesh((1, 1), ("data", "model"))
    grid = grid_partition(tiny_corpus, 1, 1)
    e = int(grid.mask.sum())
    state, data = init_dist_state(key, mesh, grid, tiny_hyper)
    step = make_dist_step(
        mesh, tiny_hyper, DistConfig(algorithm="zen_pallas", max_kd=8),
        grid.words_per_shard, grid.docs_per_shard, grid.num_words,
    )
    for _ in range(2):
        state = step(state, data)
    assert int(jnp.sum(state.n_k)) == e
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(state.n_wk, 0)), np.asarray(state.n_k)
    )


def test_dist_step_rejects_single_box_only_backends(key, tiny_corpus, tiny_hyper):
    from repro.core.distributed import DistConfig, make_dist_step
    from repro.core.graph import grid_partition

    mesh = make_mesh((1, 1), ("data", "model"))
    grid = grid_partition(tiny_corpus, 1, 1)
    with pytest.raises(ValueError, match="shard_map"):
        make_dist_step(
            mesh, tiny_hyper, DistConfig(algorithm="std"),
            grid.words_per_shard, grid.docs_per_shard, grid.num_words,
        )


def test_hybrid_switch_uses_effective_rows():
    """Regression (crafted corpus): the hybrid's switch prices each
    constituent by the rows it will ACTUALLY sample — raw nnz clamped to
    the padded capacity it sparsifies at — not by global row density."""
    import jax.numpy as jnp

    from repro.algorithms.zen_hybrid import hybrid_route_doc_side

    k = 16
    # doc 0: 10 live topics; word 0: 12 live topics. On raw density the
    # doc side looks sparser (10 <= 12) — but with the word rows padded to
    # 4 slots the word side samples a 4-wide row and must win.
    n_kd = jnp.zeros((1, k), jnp.int32).at[0, :10].set(1)
    n_wk = jnp.zeros((1, k), jnp.int32).at[0, :12].set(1)
    word = jnp.zeros((3,), jnp.int32)
    doc = jnp.zeros((3,), jnp.int32)

    raw = hybrid_route_doc_side(n_wk, n_kd, word, doc, max_kw=16, max_kd=16)
    assert bool(raw.all())  # unclamped: doc side (the old global decision)
    clamped = hybrid_route_doc_side(n_wk, n_kd, word, doc, max_kw=4, max_kd=16)
    assert not bool(clamped.any())  # truncated word rows are cheaper: switch
    # symmetric: clamp the doc side instead and the doc side wins again
    back = hybrid_route_doc_side(n_wk, n_kd, word, doc, max_kw=4, max_kd=2)
    assert bool(back.all())


def test_hybrid_cell_sweep_composes_constituents_by_route(
    key, tiny_corpus, tiny_hyper
):
    """Integration: ZenHybrid.cell_sweep IS where(route, zen_sparse draw,
    sparselda draw) — same key, same blocks, same (clamped) widths. Run
    with a width split (max_kw < max_kd) so both routes are exercised and
    a cell_sweep that mis-passed widths or re-derived the route inline
    would produce different draws."""
    import dataclasses

    import jax.numpy as jnp

    from repro.algorithms.zen_hybrid import hybrid_route_doc_side
    from repro.core.init import random_init

    st = random_init(key, tiny_corpus, tiny_hyper)
    hybrid = algorithms.get("zen_hybrid")
    # clamp the doc side below K while word-row nnz spans [1, K] (zipf
    # vocabulary: rare words hold few topics), so both routes are taken
    knobs = dataclasses.replace(
        TrainConfig().knobs(), max_kw=tiny_hyper.num_topics,
        max_kd=tiny_hyper.num_topics // 2,
    )
    k_cell = jax.random.key(3)
    mask = jnp.ones(tiny_corpus.word.shape, bool)
    args = (k_cell, tiny_corpus.word, tiny_corpus.doc, st.topic, mask,
            st.n_wk, st.n_kd, st.n_k, tiny_hyper, tiny_corpus.num_words,
            knobs)
    z_hybrid = hybrid.cell_sweep(*args)

    route = hybrid_route_doc_side(
        st.n_wk, st.n_kd, tiny_corpus.word, tiny_corpus.doc,
        knobs.max_kw, knobs.max_kd,
    )
    assert bool(route.any()) and not bool(route.all())  # both routes live
    z_zen = algorithms.get("zen_sparse").cell_sweep(*args)
    z_alt = algorithms.get("sparselda").cell_sweep(*args)
    np.testing.assert_array_equal(
        np.asarray(z_hybrid), np.asarray(jnp.where(route, z_zen, z_alt))
    )


def test_shared_knobs_unify_all_driver_configs():
    """Every driver config builds its SamplerKnobs through the single
    ``algorithms.knobs_from`` derivation (RunConfig owns it; the
    deprecated TrainConfig/DistConfig shims delegate), and the
    token_chunk vocabulary is unified (0 = disabled everywhere)."""
    from repro.core.distributed import DistConfig
    from repro.train.session import RunConfig

    tk = TrainConfig().knobs()
    dk = DistConfig().knobs()
    rk = RunConfig().knobs()
    assert type(tk) is type(dk) is type(rk) is algorithms.SamplerKnobs
    assert tk.token_chunk == dk.token_chunk == rk.token_chunk == 0
    # RunConfig's None sampling_method = "plan default" (TrainSession
    # resolves it to cdf single-box / gumbel mesh — the two shims'
    # historical defaults, preserved)
    assert rk.sampling_method is None
    assert tk.sampling_method == "cdf" and dk.sampling_method == "gumbel"
    # identical field-for-field knobs from identical settings, whichever
    # config carries them
    assert TrainConfig(max_kw=32, token_chunk=128).knobs() == \
        RunConfig(max_kw=32, token_chunk=128,
                  sampling_method="cdf").knobs() == \
        DistConfig(max_kw=32, token_chunk=128,
                   sampling_method="cdf").knobs()
