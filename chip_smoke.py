#!/usr/bin/env python3
"""Chip smoke test: the main path of this repo on a TPU at the paper's
NYTimes widths (``repro.configs.zenlda.NYTIMES``: W=101,636, K=1,000,
average document length 332, ``zen_cdf`` with max_kd=128).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip phases only

One chip, one process, in order:

1. ``train_zen_cdf`` — ``TrainSession`` single-box, 3 iterations on a
   seeded synthetic corpus of 4,096 documents (the only cut: the paper's
   corpus has 299,752). Then one sweep each of ``zen_pallas`` and
   ``zen_sparse`` on the most of those documents whose compiled step
   ``memory_analysis()`` says fits the chip. After every step: count
   conservation (``check_invariants``) and a finite, rising llh.
2. ``kernels_vs_ref`` — after the zen_pallas sweep, one chunk of each
   kernel on that path against its ``kernels/ref.py`` oracle on the chip,
   bit for bit.
3. ``serve`` — the zen_cdf model is checkpointed, loaded back
   (``FrozenLDAModel.from_checkpoint``, as ``launch/serve_lda.py`` does)
   and serves requests through ``LDAEngine`` in latency mode and in
   throughput mode with ``zen_pallas``.

``--chips 4`` runs only what exists across chips: a 2x2 ``MeshPlan``
training run against a single-box run from the same initial assignment
(counts conserved exactly, llh inside the band of
``tests/test_mesh_parity.py``), then ``(1, 4)`` word-sharded serving
against single-host serving, bit-equal.

Every earlier output line is one JSON object; the last line is
``{"ok": true, "device": {...}}``. Without a TPU the script exits non-zero
before any phase, and any failed check or exception fails the run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the cut: 4,096 of the paper's 299,752 NYTimes documents (~1.36M tokens)
# keeps zen_cdf's unchunked (T, max_kd) per-token arrays at a few GB
DOCS = 4096
ITERS = 3  # training iterations of the zen_cdf runs
LLH_CHUNK = 1 << 16  # tokens per likelihood chunk: bounds its (T, K) terms
WORK_DIR = os.path.join(ROOT, ".chip_smoke")  # checkpoints (gitignored)
SERVE_BUCKET = 512  # one bucket fits every ~332-token request


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Phase:
    """Seconds of one phase, split into compile and run."""

    def __init__(self, name: str):
        self.name = name
        self.compile_s = 0.0
        self.run_s = 0.0

    def report(self, **extra) -> None:
        import jax

        peak = max(d.memory_stats()["peak_bytes_in_use"]
                   for d in jax.devices())
        log(phase=self.name, compile_s=round(self.compile_s, 3),
            run_s=round(self.run_s, 3), peak_bytes_in_use=peak, **extra)


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# -- corpus ----------------------------------------------------------------

def nytimes():
    from repro.configs.zenlda import NYTIMES

    return NYTIMES


def make_corpus(seed: int, num_docs: int):
    from repro.data import synthetic_corpus

    cfg = nytimes()
    return synthetic_corpus(seed, num_docs=num_docs,
                            num_words=cfg.num_words,
                            avg_doc_len=cfg.avg_doc_len, zipf_a=1.2)


def doc_prefix(corpus, num_docs: int):
    """The corpus cut to its first ``num_docs`` documents (the synthetic
    corpus stores each document's tokens contiguously, in doc order)."""
    import numpy as np

    from repro.core.types import Corpus

    e = int(np.searchsorted(np.asarray(corpus.doc), num_docs))
    return Corpus(word=corpus.word[:e], doc=corpus.doc[:e],
                  num_words=corpus.num_words, num_docs=num_docs)


def log_cut(corpus, seed: int) -> None:
    cfg = nytimes()
    log(cut=f"num_docs {corpus.num_docs} of the paper's "
            f"{cfg.docs_per_step:,} (NYTimes, configs/zenlda.py); "
            f"W={cfg.num_words} K={cfg.num_topics} "
            f"avg_doc_len={cfg.avg_doc_len} max_kd={cfg.max_kd} as published",
        tokens=corpus.num_tokens, docs=corpus.num_docs, seed=seed)


def run_config(algorithm: str, **kw):
    from repro.train.session import RunConfig

    return RunConfig(algorithm=algorithm, max_kd=nytimes().max_kd,
                     token_chunk=LLH_CHUNK, eval_every=0, **kw)


def step_memory(exe) -> int:
    m = exe.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# -- phase 1: training -------------------------------------------------------

def train(phase: Phase, session, state, iters: int, expect_kernels: bool):
    """``iters`` session steps with the per-step checks; returns the
    final state. The first step's compiled program is inspected."""
    import numpy as np

    corpus = session.corpus
    llh, dt = timed(lambda: session.llh(state))
    phase.run_s += dt
    check(math.isfinite(llh), f"{phase.name}: initial llh {llh}")
    llhs = [llh]
    for it in range(iters):
        t0 = time.perf_counter()
        exe, _ = session.plan.compiled_step(state)
        phase.compile_s += time.perf_counter() - t0
        calls = exe.as_text().count("tpu_custom_call")
        if it == 0:
            log(phase=phase.name, compiled_step_bytes=step_memory(exe),
                tpu_custom_calls=calls)
        if expect_kernels:
            check(calls > 0, f"{phase.name}: no Pallas kernel in the step")
        state, step_s = timed(lambda: session.step(state))
        phase.run_s += step_s
        state.check_invariants(corpus)  # count conservation
        llh, dt = timed(lambda: session.llh(state))
        phase.run_s += dt
        check(math.isfinite(llh) and llh > llhs[-1],
              f"{phase.name}: llh {llhs[-1]} -> {llh} is not rising")
        llhs.append(llh)
        log(phase=phase.name, iteration=it + 1, step_s=round(step_s, 3),
            llh=llh, tokens=corpus.num_tokens, counts_conserved=True)
    check(int(np.asarray(state.n_k).sum()) == corpus.num_tokens,
          f"{phase.name}: N_k total")
    return state


def fit_docs(corpus, hyper, algorithm: str, key, budget: int):
    """The most documents of ``corpus`` whose compiled ``algorithm`` step
    fits ``budget`` bytes, by ``memory_analysis()``: two small probes fix
    a linear model of step bytes in documents, the predicted count is
    compiled, and cut by a tenth until it fits. Returns (session, state,
    num_docs, step_bytes); the session keeps the compiled step."""
    from repro.train.session import TrainSession

    def measure(n):
        session = TrainSession(doc_prefix(corpus, n), hyper,
                               run_config(algorithm))
        state = session.init(key)
        exe, _ = session.plan.compiled_step(state)
        return session, state, step_memory(exe)

    full = corpus.num_docs
    p1, p2 = max(1, full // 32), max(2, full // 16)
    m1, m2 = measure(p1)[2], measure(p2)[2]
    slope = max(1.0, (m2 - m1) / (p2 - p1))
    n = min(full, int((budget - (m1 - slope * p1)) / slope))
    n = max(p2, n - n % 64 if n >= 128 else n)
    while True:
        session, state, used = measure(n)
        log(phase=f"fit_{algorithm}", num_docs=n, step_bytes=used,
            budget_bytes=budget, fits=used <= budget)
        if used <= budget:
            return session, state, n, used
        check(n > p2, f"fit_{algorithm}: no document count fits")
        n = max(p2, n * 9 // 10)


def phase_train(args, key):
    import jax

    from repro.core.types import LDAHyperParams
    from repro.train.session import TrainSession

    corpus = make_corpus(args.seed, DOCS)
    hyper = LDAHyperParams(num_topics=nytimes().num_topics)
    log_cut(corpus, args.seed)

    phase = Phase("train_zen_cdf")
    session = TrainSession(corpus, hyper, run_config("zen_cdf"))
    state, dt = timed(lambda: session.init(key))
    phase.run_s += dt
    state = train(phase, session, state, ITERS, expect_kernels=True)
    ckpt = os.path.join(WORK_DIR, "model")
    shutil.rmtree(ckpt, ignore_errors=True)
    _, dt = timed(lambda: session.save_model(state, ckpt))
    phase.run_s += dt
    phase.report(checkpoint_s=round(dt, 3))
    del session, state

    dev = jax.devices()[0]
    stats = dev.memory_stats()
    budget = int(0.9 * (stats["bytes_limit"] - stats["bytes_in_use"]))
    for algorithm in ("zen_pallas", "zen_sparse"):
        phase = Phase(f"train_{algorithm}")
        t0 = time.perf_counter()
        session, state, n, used = fit_docs(corpus, hyper, algorithm, key,
                                           budget)
        phase.compile_s += time.perf_counter() - t0
        log(phase=phase.name, num_docs_fit=n,
            tokens=session.corpus.num_tokens, step_bytes=used)
        state = train(phase, session, state, 1, expect_kernels=True)
        phase.report(num_docs=n)
        if algorithm == "zen_pallas":
            phase_kernels(hyper, session.corpus, state, args.seed)
        del session, state
    return ckpt


# -- phase 2: kernels against their oracles ----------------------------------

def phase_kernels(hyper, corpus, state, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.decompositions import precompute_zen_terms
    from repro.kernels import ops, ref
    from repro.kernels.zen_sampler import golden_seed

    phase = Phase("kernels_vs_ref")
    c = 4096  # one chunk of the zen_pallas sweep's tokens
    word, doc, z = corpus.word[:c], corpus.doc[:c], state.topic[:c]
    n_wk, n_kd, n_k = state.n_wk, state.n_kd, state.n_k
    alpha_k = hyper.alpha_k(n_k)
    n_k_f = n_k.astype(jnp.float32)
    w_beta = corpus.num_words * hyper.beta
    s = jnp.int32(seed + 12345)
    kw = dict(beta=hyper.beta, w_beta=w_beta)
    rng = np.random.default_rng(seed)
    slot = doc % 64
    seeds = golden_seed(jnp.uint32(seed), jnp.uint32(7),
                        jnp.arange(c, dtype=jnp.uint32))
    terms = precompute_zen_terms(n_k, hyper, corpus.num_words)
    mass = n_wk[word].astype(jnp.float32) @ terms.t4
    targets = jnp.asarray(rng.random(c), jnp.float32) * mass
    vals = jnp.asarray(rng.random((c, 256)), jnp.float32)
    topics = jnp.asarray(rng.integers(0, hyper.num_topics, (c, 256)),
                         jnp.int32)
    row_t = jnp.asarray(rng.random(c), jnp.float32) * vals.sum(1)
    cases = {
        "zen_fused_sample": (
            lambda: ops.zen_fused_sample(n_wk, n_kd, word, doc, z, alpha_k,
                                         n_k_f, s, **kw),
            lambda: ref.zen_fused_sample_ref(n_wk, n_kd, word, doc, z,
                                             alpha_k, n_k_f, s, **kw)),
        "zen_fused_infer_sample": (
            lambda: ops.zen_fused_infer_sample(n_wk, n_kd[:64], word, slot,
                                               z, seeds, alpha_k, n_k_f,
                                               **kw),
            lambda: ref.zen_fused_infer_sample_ref(n_wk, n_kd[:64], word,
                                                   slot, z, seeds, alpha_k,
                                                   n_k_f, **kw)),
        "cdf_row_search": (
            lambda: ops.cdf_row_search(n_wk, word, terms.t4, targets),
            lambda: ref.cdf_row_search_ref(n_wk, word, terms.t4, targets)),
        "sparse_row_sample": (
            lambda: ops.sparse_row_sample(vals, topics, row_t),
            lambda: ref.sparse_row_sample_ref(vals, topics, row_t)),
    }
    for name, (kernel, oracle) in cases.items():
        got, dt = timed(kernel)
        phase.run_s += dt
        want = jax.block_until_ready(oracle())
        mismatches = int(np.sum(np.asarray(got) != np.asarray(want)))
        log(phase=phase.name, kernel=name, tokens=c, mismatches=mismatches,
            first_call_s=round(dt, 3))
        check(mismatches == 0, f"{name}: {mismatches} tokens differ")
    phase.report()


# -- phase 3: serving --------------------------------------------------------

def serve_docs(seed: int, num_words: int, n: int):
    from repro.data import synthetic_corpus
    from repro.serving import docs_from_corpus

    docs = docs_from_corpus(synthetic_corpus(
        seed + 1, num_docs=n, num_words=num_words,
        avg_doc_len=nytimes().avg_doc_len, zipf_a=1.2))
    check(max(len(d) for d in docs) <= SERVE_BUCKET, "request too long")
    return docs


def check_thetas(thetas, k: int, what: str) -> None:
    import numpy as np

    thetas = np.asarray(thetas)
    check(thetas.shape[1:] == (k,) and np.isfinite(thetas).all(),
          f"{what}: thetas of shape {thetas.shape}")
    check(np.allclose(thetas.sum(1), 1.0, atol=1e-4),
          f"{what}: thetas do not sum to 1")


def phase_serve(ckpt: str, seed: int) -> None:
    import numpy as np

    from repro.serving import FrozenLDAModel, LDAEngine, LDAServeConfig

    model = FrozenLDAModel.from_checkpoint(ckpt)
    docs = serve_docs(seed, model.num_words, 16)
    for mode in ("latency", "throughput"):
        phase = Phase(f"serve_{mode}")
        cfg = LDAServeConfig(buckets=(SERVE_BUCKET,), max_batch=16,
                             num_sweeps=5, algorithm="zen_pallas", mode=mode)
        engine = LDAEngine(model, cfg, seed=seed)
        _, phase.compile_s = timed(engine.warm)
        t0 = time.perf_counter()
        tickets = [engine.submit_async(d) for d in docs]
        thetas = np.stack([engine.result(t) for t in tickets])
        phase.run_s = time.perf_counter() - t0
        check_thetas(thetas, model.num_topics, phase.name)
        top = [int(np.argmax(t)) for t in thetas[:4]]
        phase.report(requests=len(docs), top_topics=top)


# -- four chips ----------------------------------------------------------------

def corpus_order_topics(grid, init_grid, corpus):
    """The grid's initial assignment moved to corpus token order: tokens
    of one (word, doc) edge are exchangeable, so matching sorted edge keys
    gives the same counts on both plans."""
    import numpy as np

    def inverse(perm, size):
        inv = np.full(size, -1, np.int64)
        inv[perm] = np.arange(perm.shape[0])
        return inv

    inv_w = inverse(grid.word_perm, grid.num_words_padded)
    inv_d = inverse(grid.doc_perm, grid.num_docs_padded)
    scale = np.int64(corpus.num_docs + 1)
    key_grid = (inv_w[grid.word[grid.mask]] * scale
                + inv_d[grid.doc[grid.mask]])
    key_corpus = (np.asarray(corpus.word, np.int64) * scale
                  + np.asarray(corpus.doc))
    check(np.array_equal(np.sort(key_grid), np.sort(key_corpus)),
          "grid and corpus hold different tokens")
    z = np.zeros(corpus.num_tokens, np.int32)
    z[np.argsort(key_corpus, kind="stable")] = \
        init_grid[grid.mask][np.argsort(key_grid, kind="stable")]
    return z


def corpus_llh(corpus, hyper, n_wk, n_kd, n_k) -> float:
    """One evaluator for both plans: predictive llh of counts in corpus
    ids (the mesh state is mapped back through the grid's relabeling)."""
    import jax
    import jax.numpy as jnp

    from repro.core.likelihood import predictive_llh
    from repro.core.types import CGSState

    dev = jax.devices()[0]
    z = jnp.zeros((corpus.num_tokens,), jnp.int32)
    st = CGSState(topic=z, prev_topic=z,
                  n_wk=jax.device_put(n_wk, dev),
                  n_kd=jax.device_put(n_kd, dev),
                  n_k=jax.device_put(n_k, dev), rng=jax.random.key(0))
    return float(predictive_llh(st, corpus, hyper, token_chunk=LLH_CHUNK))


def device_bytes(arr):
    return {str(s.device.id): s.data.nbytes for s in arr.addressable_shards}


def phase_mesh_train(args, key):
    import jax
    import numpy as np

    from repro.core.types import LDAHyperParams
    from repro.train.session import TrainSession

    corpus = make_corpus(args.seed, DOCS)
    hyper = LDAHyperParams(num_topics=nytimes().num_topics)
    log_cut(corpus, args.seed)
    e = corpus.num_tokens

    phase = Phase("mesh_2x2_zen_cdf")
    mesh = TrainSession(corpus, hyper, run_config("zen_cdf",
                                                  mesh_shape=(2, 2)))
    grid = mesh.plan.grid
    rng = np.random.default_rng(args.seed)
    init_grid = np.where(
        grid.mask, rng.integers(0, hyper.num_topics, grid.word.shape), 0
    ).astype(np.int32)
    # the mesh step donates its state, the key buffer included: the
    # single-box run below gets a key of its own
    state, dt = timed(lambda: mesh.init(jax.random.key(args.seed),
                                        init_topics=init_grid))
    phase.run_s += dt
    layout = {"n_wk": device_bytes(state.n_wk),
              "n_kd": device_bytes(state.n_kd)}
    log(phase=phase.name, per_device_bytes=layout)
    for name, per_dev in layout.items():
        total = state.n_wk.nbytes if name == "n_wk" else state.n_kd.nbytes
        check(len(per_dev) == 4 and all(b > 0 for b in per_dev.values()),
              f"{name} is not on all four devices: {per_dev}")
        check(max(per_dev.values()) < total,
              f"{name} is whole on one device: {per_dev}")

    def mesh_llh(st):
        return corpus_llh(corpus, hyper,
                          np.asarray(st.n_wk)[grid.word_perm],
                          np.asarray(st.n_kd)[grid.doc_perm],
                          np.asarray(st.n_k))

    mesh_llhs = [mesh_llh(state)]
    for it in range(ITERS):
        state, dt = timed(lambda: mesh.step(state))
        if it == 0:
            phase.compile_s += dt  # the first call compiles the step
        else:
            phase.run_s += dt
        n_k = np.asarray(state.n_k)
        check(int(n_k.sum()) == e, "mesh: sum N_k != tokens")
        check(np.array_equal(np.asarray(state.n_wk).sum(0), n_k),
              "mesh: N_wk columns != N_k")
        check(np.array_equal(np.asarray(state.n_kd).sum(0), n_k),
              "mesh: N_kd columns != N_k")
        mesh_llhs.append(mesh_llh(state))
        log(phase=phase.name, iteration=it + 1, step_s=round(dt, 3),
            llh=mesh_llhs[-1], counts_conserved=True)
    check(mesh_llhs[-1] > mesh_llhs[0], f"mesh llh not rising: {mesh_llhs}")
    ckpt = os.path.join(WORK_DIR, "mesh_model")
    shutil.rmtree(ckpt, ignore_errors=True)
    mesh.save_model(state, ckpt)
    phase.report()
    del mesh, state

    phase = Phase("single_box_zen_cdf")
    single = TrainSession(corpus, hyper, run_config("zen_cdf"))
    st = single.init(key, init_topics=corpus_order_topics(
        grid, init_grid, corpus))
    sb_llhs = [corpus_llh(corpus, hyper, st.n_wk, st.n_kd, st.n_k)]
    check(math.isclose(sb_llhs[0], mesh_llhs[0], rel_tol=1e-4),
          f"different initial llh: {sb_llhs[0]} vs {mesh_llhs[0]}")
    for it in range(ITERS):
        st, dt = timed(lambda: single.step(st))
        if it == 0:
            phase.compile_s += dt
        else:
            phase.run_s += dt
        st.check_invariants(corpus)
        sb_llhs.append(corpus_llh(corpus, hyper, st.n_wk, st.n_kd, st.n_k))
    band = abs(mesh_llhs[-1] - sb_llhs[-1]) / abs(sb_llhs[-1])
    log(phase=phase.name, mesh_llh=mesh_llhs, single_box_llh=sb_llhs,
        relative_gap=band, band=0.15)
    check(sb_llhs[-1] > sb_llhs[0], f"single-box llh not rising: {sb_llhs}")
    check(band < 0.15, f"mesh and single-box llh {band:.3f} apart")
    phase.report()
    return ckpt


def phase_mesh_serve(ckpt: str, seed: int) -> None:
    import jax
    import numpy as np

    from repro.serving import FrozenLDAModel, LDAEngine, LDAServeConfig

    model = FrozenLDAModel.from_checkpoint(ckpt)
    docs = serve_docs(seed, model.num_words, 16)
    keys = [jax.random.key(seed + 100 + i) for i in range(len(docs))]
    base = dict(buckets=(SERVE_BUCKET,), max_batch=16, num_sweeps=5,
                algorithm="zen_pallas")
    out = {}
    for name, mesh_shape in (("single_host", None), ("sharded_1x4", (1, 4))):
        phase = Phase(f"serve_{name}")
        engine = LDAEngine(model, LDAServeConfig(mesh_shape=mesh_shape,
                                                 **base), seed=seed)
        if mesh_shape is not None:
            log(phase=phase.name,
                per_device_bytes={"n_wk": device_bytes(engine.model.n_wk)})
        _, phase.compile_s = timed(engine.warm)
        t0 = time.perf_counter()
        out[name] = np.stack([engine.infer_batch([d], key=k)[0]
                              for d, k in zip(docs, keys)])
        phase.run_s = time.perf_counter() - t0
        check_thetas(out[name], model.num_topics, phase.name)
        phase.report(requests=len(docs))
    diff = int(np.sum(out["single_host"] != out["sharded_1x4"]))
    log(phase="serve_sharded_vs_single", differing_theta_entries=diff)
    check(diff == 0, "sharded serving differs from single-host serving")


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: mesh training and "
                         "sharded serving only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, requests and initial topics")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.algorithms.base import kernel_dispatch
    from repro.kernels.ops import default_interpret

    check(default_interpret() is False, "kernels would be interpreted")
    check(kernel_dispatch("auto"), "backends would not dispatch kernels")
    log(devices=len(devices), kind=devices[0].device_kind,
        compile_cache=cache, jax=jax.__version__, interpret=False)
    key = jax.random.key(args.seed)
    t_start = time.perf_counter()
    try:
        if args.chips == 1:
            phase_serve(phase_train(args, key), args.seed)
        else:
            ckpt = phase_mesh_train(args, key)
            phase_mesh_serve(ckpt, args.seed)
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    log(total_s=round(time.perf_counter() - t_start, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
