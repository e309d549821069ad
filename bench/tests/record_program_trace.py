#!/usr/bin/env python3
"""Record the small chip trace of the program's own spans that
``test_bench_program_trace.py`` reads.

    python3 bench/tests/record_program_trace.py   # on a TPU

A small ``LDAEngine`` (the fused infer kernel, two buckets) driven by its
background ticker, so that its ``zen.engine.*`` spans lie on another
thread than the ``bench.window`` span in which the main thread submits
six documents and waits for them. Writes
``bench/tests/data/program.xplane.pb``.
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import Span, Window
    from bench.trace import find_xplane
    from repro.core.types import LDAHyperParams
    from repro.serving import FrozenLDAModel, LDAEngine, LDAServeConfig

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    w, k = 512, 128
    n_wk = jax.random.randint(jax.random.key(0), (w, k), 0, 5, jnp.int32)
    engine = LDAEngine(
        FrozenLDAModel(n_wk=n_wk, n_k=n_wk.sum(0),
                       hyper=LDAHyperParams(num_topics=k)),
        LDAServeConfig(buckets=(64, 128), max_batch=8, num_sweeps=3,
                       algorithm="zen_pallas"))
    engine.warm()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, w, n) for n in (20, 40, 64, 100, 128, 30)]
    out_dir = os.path.join(ROOT, ".bench_trace", "program")
    engine.start()
    try:
        with Window(out_dir):
            with Span("bench.submit"):
                tickets = [engine.submit_async(d) for d in docs]
            for t in tickets:
                engine.result(t, timeout=120)
    finally:
        engine.stop()
    dst = os.path.join(ROOT, "bench", "tests", "data", "program.xplane.pb")
    shutil.copy(find_xplane(out_dir), dst)
    print(dst, os.path.getsize(dst), "ticks", engine.ticks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
