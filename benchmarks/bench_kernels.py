"""Kernel suite v2 microbenchmarks (ISSUE 6): each new kernel vs its
pre-fusion baseline, across a small tile sweep, recorded as
``BENCH_kernels.json``.

Rows (CSV via common.row + JSON):

* ``fused_sample``  vs baseline = HBM gather + v1 ``zen_sample``
* ``fused_infer``   vs baseline = HBM gather + v1 ``zen_infer_sample``
* ``cdf_search``    vs baseline = (Ws, K) float CDF build + XLA bsearch
* ``sparse_row``    vs baseline = XLA cumsum/count/take over padded rows

Sizes are env-tunable (``BENCH_KERNELS_T`` / ``_K`` / ``_W`` / ``_D`` /
``_J``, tile lists ``_BTS`` / ``_BKS`` / ``_BSS`` as comma ints) and
default tiny so the CI smoke finishes in seconds; on CPU the kernels run
in interpret mode (recorded in the JSON — absolute numbers are only
meaningful on a real TPU, the *relative* tile sweep and the baseline
contrast are what the row exists to track).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _env_ints(name: str, default: tuple) -> tuple:
    raw = os.environ.get(name)
    return tuple(int(x) for x in raw.split(",")) if raw else default


def main() -> None:
    from repro.algorithms.zen_cdf import _bsearch_gather
    from repro.kernels.autotune import (
        autotune_cdf,
        autotune_fused,
        autotune_sparse,
    )
    from repro.kernels.ops import (
        default_interpret,
        zen_fused_infer_sample,
        zen_infer_sample,
        zen_sample,
    )

    t = _env_int("BENCH_KERNELS_T", 256)
    k = _env_int("BENCH_KERNELS_K", 128)
    w = _env_int("BENCH_KERNELS_W", 96)
    d = _env_int("BENCH_KERNELS_D", 64)
    j = _env_int("BENCH_KERNELS_J", 64)
    bts = _env_ints("BENCH_KERNELS_BTS", (64, 128))
    bks = _env_ints("BENCH_KERNELS_BKS", (128,))
    bss = _env_ints("BENCH_KERNELS_BSS", (128,))

    rng = np.random.default_rng(0)
    n_wk = jnp.asarray(rng.integers(0, 50, (w, k)), jnp.int32)
    n_kd = jnp.asarray(rng.integers(0, 20, (d, k)), jnp.int32)
    word = jnp.asarray(rng.integers(0, w, (t,)), jnp.int32)
    doc = jnp.asarray(rng.integers(0, d, (t,)), jnp.int32)
    z = jnp.asarray(rng.integers(0, k, (t,)), jnp.int32)
    seeds = jnp.asarray(rng.integers(0, 2**31 - 1, (t,)), jnp.int32)
    n_k = jnp.asarray(np.asarray(n_wk).sum(0) + 1, jnp.float32)
    alpha_k = jnp.asarray(rng.random(k) + 0.01, jnp.float32)
    seed = jnp.int32(7)
    beta, w_beta = 0.01, k * 0.01

    records = []

    def record(kernel, label, us, tok, baseline, bt=0, bk=0, bs=0):
        records.append(dict(
            kernel=kernel, label=label, us_per_call=us,
            tokens_per_sec=tok / us * 1e6, baseline=baseline,
            bt=bt, bk=bk, bs=bs,
            t=t, k=k, w=w, d=d, j=j,
            backend=jax.default_backend(),
            interpret=default_interpret(),
        ))
        row(f"kernels/{kernel}/{label}", us, f"tok/s={tok / us * 1e6:.0f}")

    # --- fused gather+sample vs gather-then-v1 ---------------------------
    bt0, bk0 = bts[0], bks[0]
    us = time_fn(
        lambda: zen_sample(
            n_wk[word], n_kd[doc], z, alpha_k, n_k, seed,
            beta=beta, w_beta=w_beta, bt=bt0, bk=bk0,
        )
    )
    record("fused_sample", "baseline_gather_v1", us, t, True, bt=bt0, bk=bk0)
    for tt in autotune_fused(
        n_wk, n_kd, word, doc, z, alpha_k, n_k, seed,
        beta=beta, w_beta=w_beta, bts=bts, bks=bks,
    ):
        record("fused_sample", f"bt{tt.bt}_bk{tt.bk}", tt.us_per_call, t,
               False, bt=tt.bt, bk=tt.bk)

    # --- fused infer variant vs gather-then-v1-infer ---------------------
    us = time_fn(
        lambda: zen_infer_sample(
            n_wk[word], n_kd[doc], z, seeds, alpha_k, n_k,
            beta=beta, w_beta=w_beta, bt=bt0, bk=bk0,
        )
    )
    record("fused_infer", "baseline_gather_v1", us, t, True, bt=bt0, bk=bk0)
    us = time_fn(
        lambda: zen_fused_infer_sample(
            n_wk, n_kd, word, doc, z, seeds, alpha_k, n_k,
            beta=beta, w_beta=w_beta, bt=bt0, bk=bk0,
        )
    )
    record("fused_infer", f"bt{bt0}_bk{bk0}", us, t, False, bt=bt0, bk=bk0)

    # --- cdf search vs materialized w_cdf + XLA bsearch ------------------
    term = jnp.asarray(rng.random(k) + 1e-3, jnp.float32)
    mass = jnp.sum(n_wk[word].astype(jnp.float32) * term[None, :], 1)
    targets = jnp.asarray(rng.random(t), jnp.float32) * mass

    @jax.jit
    def cdf_baseline():
        w_cdf = jnp.cumsum(
            n_wk.astype(jnp.float32) * term[None, :], axis=-1
        )
        return _bsearch_gather(w_cdf, word, targets)

    us = time_fn(cdf_baseline)
    record("cdf_search", "baseline_wcdf_bsearch", us, t, True)
    for tt in autotune_cdf(n_wk, word, term, targets, bts=bts, bks=bks):
        record("cdf_search", f"bt{tt.bt}_bk{tt.bk}", tt.us_per_call, t,
               False, bt=tt.bt, bk=tt.bk)

    # --- sparse row vs XLA cumsum/count/take -----------------------------
    vals = jnp.asarray(
        rng.random((t, j)) * (rng.random((t, j)) < 0.5), jnp.float32
    )
    topics = jnp.asarray(rng.integers(0, k, (t, j)), jnp.int32)
    s_targets = jnp.asarray(rng.random(t), jnp.float32) * jnp.sum(vals, 1)

    @jax.jit
    def sparse_baseline():
        cdf = jnp.cumsum(vals, axis=-1)
        pos = jnp.sum(cdf < s_targets[:, None], axis=-1)
        pos = jnp.minimum(pos, vals.shape[-1] - 1)
        return jnp.take_along_axis(topics, pos[:, None], axis=-1)[:, 0]

    us = time_fn(sparse_baseline)
    record("sparse_row", "baseline_xla", us, t, True)
    for tt in autotune_sparse(vals, topics, s_targets, bts=bts, bss=bss):
        record("sparse_row", f"bt{tt.bt}_bs{tt.bs}", tt.us_per_call, t,
               False, bt=tt.bt, bs=tt.bs)

    from benchmarks.common import bench_out_path

    with open(bench_out_path("BENCH_kernels.json"), "w") as f:
        json.dump(records, f, indent=2)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    main()
