"""Training cells: ``TrainSession.step`` on a generated corpus.

Set-up builds one session and its state, and drives it through its first
``quality_iters`` steps with the window's own call; the assignments of the
first ``checked_steps`` of them are kept on the host for the reference,
and those of the last for the quality metric. The window then runs whole
steps until ``--seconds`` have passed. After the window the program's
state is freed and the reference (``bench/reference.py``) checks every
token's draw of the kept steps and the counts after them.

A configuration with ``mesh_shape`` ``[rows, cols]`` trains on that grid
of chips (``MeshPlan``). The reference then checks each cell's live slots
under the mesh's key schedule against counts of the whole corpus, the
counts in the grid's relabelled ids, and that the grid's live slots hold
exactly the corpus's tokens (``grid_token_mismatch``, limit 0, stated in
the cell's limits file). Its blocks run on the cell's chips, a grid cell
on each.
"""
from __future__ import annotations

import time

import numpy as np

from bench import generator, program_trace, reference
from bench.harness import Check, Span, device_peak_bytes

# tokens per block of the reference's passes: (block, K) f32 scores
_REF_BLOCK_ELEMS = 1 << 26
# the mesh reference pads a grid cell's slots to a multiple of this, so
# that the seeds' grids, whose cells differ by a few slots, share programs
_REF_CELL_SLOTS = 1 << 20


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


def compiled_step(plan, state):
    """The plan's step as the one compiled program that its ``step`` runs
    (``memory_analysis()``, ``as_text()``): ``SingleBoxPlan``'s own
    ``compiled_step``; ``MeshPlan`` has none, so its jitted step is
    lowered with the plan's data."""
    if hasattr(plan, "compiled_step"):
        return plan.compiled_step(state)[0]
    return plan._step_fn.lower(state, plan._data).compile()


def grid_stats(grid) -> dict:
    """The grid's shape, and the share of padded slots in each cell."""
    return {"cells": int(grid.word.shape[0]), "e_cell": int(grid.word.shape[1]),
            "words_per_shard": int(grid.words_per_shard),
            "docs_per_shard": int(grid.docs_per_shard),
            "pad_share": [float(1.0 - m.mean()) for m in grid.mask]}


def run(ctx) -> dict:
    """One training run; returns the harness's result fields."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import Corpus, LDAHyperParams
    from repro.train.session import RunConfig, TrainSession

    cfg, traffic, seed = ctx.cfg, ctx.traffic, ctx.seed
    w_n, d_n, k = cfg["num_words"], cfg["num_docs"], cfg["num_topics"]
    mesh_shape = cfg.get("mesh_shape")
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
                      token_chunk=cfg["token_chunk"],
                      mesh_shape=tuple(mesh_shape) if mesh_shape else None))
        state = session.init(reference.seed_key(seed))
        # a copy: the mesh step donates its state, the key with it
        rng = jax.random.wrap_key_data(
            np.asarray(jax.random.key_data(state.rng)))
        kept = [np.asarray(state.topic)]
    grid = session.plan.grid if mesh_shape else None
    if grid is not None:
        ctx.log(grid=grid_stats(grid))
    scopes = None
    with Span("bench.compile", ctx.setup_split, "compile_s"):
        exe = compiled_step(session.plan, state)
        mem = exe.memory_analysis()
        ctx.log(step_memory={
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)})
        if ctx.trace:
            scopes = program_trace.scope_map(exe.as_text())
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
    t_ref = time.perf_counter()
    hyper_t = tuple(sorted(hyper.items()))
    block = max(1024, _REF_BLOCK_ELEMS // k // 1024 * 1024)
    if grid is None:
        checks, nll, found = _check_single_box(
            cfg, word, doc, rng, kept, kept_counts, z_quality, t, block,
            hyper_t, ctx.limits)
    else:
        checks, nll, found = _check_mesh(
            cfg, word, doc, grid, rng, kept, kept_counts, z_quality, block,
            hyper_t, ctx.limits,
            jax.local_devices()[:ctx.workload["chips"]])
    ctx.log(reference=dict(found, nll_per_token=nll,
                           seconds=time.perf_counter() - t_ref))
    return {
        "attempted": steps, "failed": 0, "checks": checks,
        "memory_peak_bytes": peak, "window_s": window_s,
        "e2e": {"train_tokens_per_s": steps * t / window_s,
                "train_nll_per_token": nll},
        "layer_ctx": {"steps": steps, "tokens_per_step": t,
                      "window_s": window_s,
                      "tokens_per_s": steps * t / window_s,
                      "scopes": scopes},
    }


def _check_single_box(cfg, word, doc, rng, kept, kept_counts, z_quality,
                      t, block, hyper_t, limits):
    """(checks, NLL, readings) of a single-box run: every token's draw of
    the kept steps, and the counts after them."""
    import jax.numpy as jnp

    w_n, d_n, k = cfg["num_words"], cfg["num_docs"], cfg["num_topics"]
    checked = len(kept) - 1
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
    checks = [
        Check("draw_gap_nats", max(gaps), limits["draw_gap_nats"]),
        Check("count_mismatch", mismatch, limits["count_mismatch"]),
    ]
    return checks, nll, {"step_gaps": gaps, "not_argmax_tokens": flips,
                         "count_mismatch": mismatch}


def _check_mesh(cfg, word, doc, grid, rng, kept, kept_counts, z_quality,
                block, hyper_t, limits, devices):
    """(checks, NLL, readings) of a mesh run. Kept topics are (cells,
    e_cell) grid slots. The draws of each cell's live slots are scored
    against counts of the whole corpus in corpus ids, with the
    configuration's W in the smoothing mass, the cells split over
    ``devices``; the counts after the kept steps are compared in corpus
    ids, and the program's rows that no corpus id maps to have to be
    empty."""
    import jax
    import jax.numpy as jnp

    w_n, d_n, k = cfg["num_words"], cfg["num_docs"], cfg["num_topics"]
    chunk = cfg["token_chunk"]
    checked = len(kept) - 1
    live = np.asarray(grid.mask)
    cells, e_cell = live.shape
    parts, t0 = {}, time.perf_counter()

    def lap(part):
        nonlocal t0
        t1 = time.perf_counter()
        parts[part] = t1 - t0
        t0 = t1

    gw, gd, bad_ids = reference.unrelabel(
        grid.word, grid.doc, live, grid.word_perm, grid.doc_perm, w_n, d_n)
    tokens = reference.grid_token_mismatch(word, doc, gw, gd, bad_ids)
    lap("grid_s")
    # corpus ids of every slot, padded; padding reads id 0 and no gap
    e_pad = -(-e_cell // _REF_CELL_SLOTS) * _REF_CELL_SLOTS

    def padded(x):
        return np.pad(x, ((0, 0), (0, e_pad - e_cell)))

    slot_w = np.zeros((cells, e_pad), np.int32)
    slot_d = np.zeros((cells, e_pad), np.int32)
    slot_live = padded(live)
    live_w, live_d = np.maximum(gw, 0), np.maximum(gd, 0)
    slot_w[slot_live], slot_d[slot_live] = live_w, live_d
    by_cell = reference.grid_sharding(devices)
    slots = jax.device_put((slot_w, slot_d, slot_live), by_cell)
    kw = dict(mesh=by_cell.mesh, num_words=w_n, num_docs=d_n, k=k,
              block=block, hyper_t=hyper_t)
    n_seeds = -(-e_pad // chunk) if chunk else 1
    gaps, flips = [], 0
    for s in range(checked):
        seeds = np.zeros((cells, n_seeds), np.int32)
        for c in range(cells):
            cs = reference.mesh_chunk_seeds(rng, s, c, e_cell, chunk)
            seeds[c, :cs.shape[0]] = cs
        g, f = reference.grid_draw_gaps(
            *slots, *jax.device_put((padded(kept[s]), padded(kept[s + 1]),
                                     seeds), by_cell),
            jnp.int32(e_cell), chunk=chunk, **kw)
        gaps.append(float(jnp.max(g)))
        flips += int(jnp.sum(f))
    lap("draws_s")
    # the counts in corpus ids; rows of grid ids no corpus id maps to
    # have to be empty
    n_wk, n_kd, n_k = kept_counts
    empty = [int(np.count_nonzero(np.delete(n, perm, axis=0)))
             for n, perm in ((n_wk, grid.word_perm), (n_kd, grid.doc_perm))]
    mismatch = sum(empty) + int(reference.count_mismatch(
        jnp.asarray(live_w), jnp.asarray(live_d),
        jnp.asarray(kept[checked][live]), jnp.asarray(n_wk[grid.word_perm]),
        jnp.asarray(n_kd[grid.doc_perm].astype(np.int32)), jnp.asarray(n_k),
        num_words=w_n, num_docs=d_n, k=k))
    lap("counts_s")
    llh = reference.grid_llh_sums(
        *slots, jax.device_put(padded(z_quality), by_cell), **kw)
    nll = -float(jnp.sum(llh)) / int(live.sum())
    lap("nll_s")
    checks = [
        Check("draw_gap_nats", max(gaps), limits["draw_gap_nats"]),
        Check("count_mismatch", mismatch, limits["count_mismatch"]),
        Check("grid_token_mismatch", tokens, limits["grid_token_mismatch"]),
    ]
    return checks, nll, {"step_gaps": gaps, "not_argmax_tokens": flips,
                         "count_mismatch": mismatch,
                         "grid_token_mismatch": tokens, "parts": parts}
