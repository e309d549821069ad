#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_small_trace.py   # on a TPU

One ``bench.window`` span holding two ``bench.step`` spans, each a small
jitted step with the fused training kernel of ``repro.kernels.ops``, and a
``bench.wait`` span in which the device idles. Writes
``bench/tests/data/small.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench.harness import Span, Window
    from repro.kernels.ops import zen_fused_sample

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    w, d, k, t = 512, 64, 1024, 4096
    key = jax.random.key(0)
    n_wk = jax.random.randint(key, (w, k), 0, 5, jnp.int32)
    n_kd = jax.random.randint(key, (d, k), 0, 5, jnp.int32)
    word = jax.random.randint(key, (t,), 0, w, jnp.int32)
    doc = jax.random.randint(key, (t,), 0, d, jnp.int32)
    z = jax.random.randint(key, (t,), 0, k, jnp.int32)

    @jax.jit
    def step(z):
        out = zen_fused_sample(n_wk, n_kd, word, doc, z,
                               jnp.full((k,), 0.01), jnp.sum(n_wk, 0), 7,
                               beta=0.01, w_beta=w * 0.01)
        return (out + 1) % k

    jax.block_until_ready(step(z))
    out_dir = os.path.join(ROOT, ".bench_trace", "small")
    with Window(out_dir):
        for _ in range(2):
            with Span("bench.step"):
                z = jax.block_until_ready(step(z))
        with Span("bench.wait"):
            time.sleep(0.02)
    src = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(ROOT, "bench", "tests", "data", "small.xplane.pb")
    shutil.copy(src, dst)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
