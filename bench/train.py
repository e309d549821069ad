"""Training cells: ``TrainSession.step`` on a generated corpus.

Set-up builds one session and its state, and drives it through its first
``quality_iters`` steps with the window's own call; the assignments of the
first ``checked_steps`` of them are kept on the host for the reference,
and those of the last for the quality metric. The window then runs whole
steps until ``--seconds`` have passed. After the window the program's
state is freed and the reference (``bench/reference.py``) checks every
token's draw of the kept steps and the counts after them.
"""
from __future__ import annotations


import numpy as np

from bench import generator, reference
from bench.harness import Check, Span, device_peak_bytes

# tokens per block of the reference's passes: (block, K) f32 scores
_REF_BLOCK_ELEMS = 1 << 26


def build_corpus(seed: int, cfg: dict, traffic: dict):
    """(word, doc, lengths, stats) on the device for this cell."""
    lengths = generator.length_multiset(
        cfg["num_docs"], generator.doc_length_mean(cfg),
        traffic["length_sigma"], traffic["length_min"], traffic["length_max"])
    word, doc, lengths, _ = generator.corpus(
        seed, cfg, cfg["num_docs"], lengths, traffic["doc_topics"])
    freq = np.sort(np.bincount(np.asarray(word),
                               minlength=cfg["num_words"]))[::-1]
    top = max(1, cfg["num_words"] // 100)
    stats = {
        "tokens": int(lengths.sum()), "docs": int(lengths.shape[0]),
        "len_mean": float(lengths.mean()),
        "len_p50": float(np.percentile(lengths, 50)),
        "len_p95": float(np.percentile(lengths, 95)),
        "len_max": int(lengths.max()), "len_min": int(lengths.min()),
        "top1pct_word_share": float(freq[:top].sum() / freq.sum()),
    }
    return word, doc, lengths, stats


def run(ctx) -> dict:
    """One training run; returns the harness's result fields."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import Corpus, LDAHyperParams
    from repro.train.session import RunConfig, TrainSession

    cfg, traffic, seed = ctx.cfg, ctx.traffic, ctx.seed
    w_n, d_n, k = cfg["num_words"], cfg["num_docs"], cfg["num_topics"]
    checked = traffic["checked_steps"]
    quality_iters = traffic["quality_iters"]
    with Span("bench.generate", ctx.setup_split, "generate_s"):
        word, doc, lengths, stats = build_corpus(seed, cfg, traffic)
        jax.block_until_ready(word)
    ctx.log(corpus=stats)
    t = stats["tokens"]
    hyper = reference.hyper(cfg)
    with Span("bench.session", ctx.setup_split, "session_s"):
        corpus = Corpus(word=word, doc=doc, num_words=w_n, num_docs=d_n)
        session = TrainSession(
            corpus,
            LDAHyperParams(num_topics=k, alpha=hyper["alpha"],
                           beta=hyper["beta"],
                           alpha_prime=hyper["alpha_prime"],
                           asymmetric_alpha=hyper["asymmetric_alpha"]),
            RunConfig(algorithm=cfg["algorithm"],
                      token_chunk=cfg["token_chunk"]))
        state = session.init(reference.seed_key(seed))
        rng = state.rng
        kept = [np.asarray(state.topic)]
    with Span("bench.compile", ctx.setup_split, "compile_s"):
        exe, _ = session.plan.compiled_step(state)
        mem = exe.memory_analysis()
        ctx.log(step_memory={
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)})
        del exe
    with Span("bench.first_steps", ctx.setup_split, "first_steps_s"):
        for i in range(quality_iters):
            state = session.step(state)
            if i < checked:
                kept.append(np.asarray(state.topic))
            if i == checked - 1:
                kept_counts = (np.asarray(state.n_wk),
                               np.asarray(state.n_kd), np.asarray(state.n_k))
        z_quality = np.asarray(state.topic)
        jax.block_until_ready(state.topic)

    # -- the window ---------------------------------------------------------
    steps = 0
    with ctx.window() as win:
        while True:
            with Span("bench.step"):
                state = session.step(state)
                jax.block_until_ready(state.topic)
            steps += 1
            if win.elapsed() >= ctx.seconds:
                break
    window_s = win.seconds
    peak = device_peak_bytes()
    del state, session, corpus

    # -- the reference, after the window -----------------------------------
    hyper_t = tuple(sorted(hyper.items()))
    block = max(1024, _REF_BLOCK_ELEMS // k // 1024 * 1024)
    gaps, flips = [], 0
    for s in range(checked):
        seeds = reference.chunk_seeds(rng, s, t, cfg["token_chunk"])
        gap, n_flip = reference.draw_gaps(
            word, doc, jnp.asarray(kept[s]), jnp.asarray(kept[s + 1]), seeds,
            num_words=w_n, num_docs=d_n, k=k, chunk=cfg["token_chunk"],
            block=block, hyper_t=hyper_t)
        gaps.append(float(gap))
        flips += int(n_flip)
    mismatch = int(reference.count_mismatch(
        word, doc, jnp.asarray(kept[checked]),
        *(jnp.asarray(c) for c in kept_counts),
        num_words=w_n, num_docs=d_n, k=k))
    nll = float(reference.nll_per_token(
        word, doc, jnp.asarray(z_quality), num_words=w_n, num_docs=d_n, k=k,
        block=block, hyper_t=hyper_t))
    ctx.log(reference={"step_gaps": gaps, "not_argmax_tokens": flips,
                       "count_mismatch": mismatch, "nll_per_token": nll})
    checks = [
        Check("draw_gap_nats", max(gaps), ctx.limits["draw_gap_nats"]),
        Check("count_mismatch", mismatch, ctx.limits["count_mismatch"]),
    ]
    return {
        "attempted": steps, "failed": 0, "checks": checks,
        "memory_peak_bytes": peak, "window_s": window_s,
        "e2e": {"train_tokens_per_s": steps * t / window_s,
                "train_nll_per_token": nll},
        "layer_ctx": {"steps": steps, "tokens_per_step": t,
                      "window_s": window_s,
                      "tokens_per_s": steps * t / window_s},
    }
