"""Paper Figs. 3 + 4: every registered CGS backend — time/iteration and
log-likelihood after equal iterations, all on the shared substrate
("the only difference is the algorithm").

The sweep list IS the registry: a newly registered backend shows up here
with zero benchmark changes — on BOTH axes: the single-box sweep below,
and a mesh x backend sweep that times the distributed step for every
``supports_shard_map`` backend on a (1, 2) mesh. Both axes drive the same
``TrainSession`` API (mesh_shape selects the plan), so what is timed is
exactly what ``launch/train.py`` runs. The mesh axis runs in this process
over the devices JAX already has; with fewer than two it is skipped with
a printed reason (on CPU, start the run with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``). A failing cell
raises: the run exits non-zero instead of recording an error row."""
from __future__ import annotations

import time

import jax

from benchmarks.common import row
from repro import algorithms
from repro.core import LDAHyperParams
from repro.data import synthetic_lda_corpus
from repro.train.session import RunConfig, TrainSession


def mesh_sweep(iters: int = 5) -> None:
    """fig3 mesh axis: distributed step time for every mesh-capable
    backend on a (1, 2) data x model mesh of the devices present."""
    from repro.launch.mesh import mesh_backends

    n_dev = len(jax.devices())
    if n_dev < 2:
        print(f"# fig3 mesh axis skipped: {n_dev} device present, the "
              f"(1, 2) mesh needs 2")
        return
    corpus, _ = synthetic_lda_corpus(0, num_docs=400, num_words=800,
                                     num_topics=32, avg_doc_len=64)
    hyper = LDAHyperParams(num_topics=32, alpha=0.05, beta=0.01)
    platform = jax.devices()[0].platform
    for alg in mesh_backends():
        session = TrainSession(corpus, hyper,
                               RunConfig(algorithm=alg, mesh_shape=(1, 2)))
        state = session.init(jax.random.key(0))
        state = session.step(state)  # warm compile
        jax.block_until_ready(state.n_k)
        t0 = time.perf_counter()
        for _ in range(iters):
            state = session.step(state)
        jax.block_until_ready(state.n_k)
        row(f"fig3_mesh2dev_time_per_iter_{alg}",
            (time.perf_counter() - t0) / iters * 1e6, f"platform={platform}")


def main(iters: int = 10):
    corpus, _ = synthetic_lda_corpus(
        0, num_docs=400, num_words=800, num_topics=32, avg_doc_len=64
    )
    hyper = LDAHyperParams(num_topics=32, alpha=0.05, beta=0.01)
    results = {}
    for alg in algorithms.registered():
        session = TrainSession(
            corpus, hyper,
            RunConfig(algorithm=alg, max_kw=64, max_kd=64, num_mh=8),
        )
        st = session.init(jax.random.key(0))
        st = session.step(st)  # warm compile
        t0 = time.perf_counter()
        for _ in range(iters):
            st = session.step(st)
        dt = (time.perf_counter() - t0) / iters
        llh = session.llh(st)
        results[alg] = (dt, llh)
        row(f"fig3_time_per_iter_{alg}", dt * 1e6, f"llh={llh:.1f}")
    # headline ratios (paper: 2-6x over LightLDA, ~14x over SparseLDA for
    # the customized-scale corpora; CPU-vectorized small-corpus ratios are
    # reported as measured)
    z = results["zen_sparse"][0]
    row("fig3_speedup_vs_lightlda", 0.0,
        f"ratio={results['lightlda'][0] / z:.2f}")
    row("fig3_speedup_vs_sparselda", 0.0,
        f"ratio={results['sparselda'][0] / z:.2f}")
    row("fig4_llh_zen_minus_lightlda", 0.0,
        f"delta={results['zen_sparse'][1] - results['lightlda'][1]:.1f}")
    mesh_sweep()


if __name__ == "__main__":
    main()
