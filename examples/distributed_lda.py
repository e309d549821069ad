"""Multi-device distributed ZenLDA (the Fig. 2 workflow) in one process.

    python examples/distributed_lda.py [--devices 4]

One process drives every device JAX has. On a TPU host that is its chips;
on a CPU host the script asks XLA for ``--devices`` host devices before
JAX starts. The mesh is as close to square as the device count allows.
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4,
                    help="host devices to simulate when running on CPU")
    args = ap.parse_args()
    # only the host (CPU) platform reads this; it must precede JAX start-up
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices} "
        + os.environ.get("XLA_FLAGS", "")
    )
    import jax
    import jax.numpy as jnp

    from repro.core.types import LDAHyperParams
    from repro.data import synthetic_lda_corpus
    from repro.train.session import RunConfig, TrainSession

    n_dev = len(jax.devices())
    rows = max(1, n_dev // 2)
    cols = n_dev // rows
    corpus, _ = synthetic_lda_corpus(0, num_docs=400, num_words=600,
                                     num_topics=16, avg_doc_len=60)
    hyper = LDAHyperParams(num_topics=16, alpha=0.05, beta=0.01)
    cfg = RunConfig(algorithm="zen_cdf", mesh_shape=(rows, cols), max_kd=24,
                    delta_dtype="int16", num_iterations=20, eval_every=5)
    session = TrainSession(corpus, hyper, cfg)
    grid = session.plan.grid
    print(f"devices={n_dev} ({jax.devices()[0].platform}) mesh={rows}x{cols} "
          f"tokens={int(grid.mask.sum())} "
          f"pad_overhead={grid.padding_overhead:.2%}")
    state = session.init(jax.random.key(0))
    print(f"llh0 = {session.llh(state):.1f}")
    t0 = [time.time()]

    def cb(st, metrics):
        if metrics:
            print(f"iter {int(st.iteration):2d}  "
                  f"{(time.time() - t0[0]) * 1e3:6.1f} ms  "
                  f"llh {metrics['llh']:12.1f}")
        t0[0] = time.time()

    state = session.run(state=state, callback=cb)
    conserved = int(jnp.sum(state.n_k)) == int(grid.mask.sum())
    print("count conservation:", conserved)
    if not conserved:
        raise SystemExit("count conservation failed")


if __name__ == "__main__":
    main()
