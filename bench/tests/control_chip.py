#!/usr/bin/env python3
"""Readings of the lower-precision control and of planted faults, at a
cell's own size, for the limits of ``bench/limits/<workload>.json``.

    python3 bench/tests/control_chip.py --workload <name> --seeds 1 2 3

The benchmark's own runs never run this. For each seed it prints one JSON
line with the number each check compares, as read for:

* ``control``: the reference put in the program's place with its
  conditional in bfloat16 (the configuration states float32);
* ``unchanged``: a step (or chain) that returns its state unchanged;
* ``half``: half of the tokens (or requests) left out;
* ``altered``: one token's topic, or one answer, altered;
* ``dropped`` (training): one token's count update lost.

Training faults are planted in the float32 reference put in the program's
place; the program itself only provides the initial state. Serving faults
are planted in the reference chains' answers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def train_readings(cfg, traffic, seed):
    import jax.numpy as jnp

    from bench import reference, train
    from repro.core.types import Corpus, LDAHyperParams
    from repro.train.session import RunConfig, TrainSession

    w_n, d_n, k = cfg["num_words"], cfg["num_docs"], cfg["num_topics"]
    word, doc, lengths, stats = train.build_corpus(seed, cfg, traffic)
    t = stats["tokens"]
    hyper = reference.hyper(cfg)
    session = TrainSession(
        Corpus(word=word, doc=doc, num_words=w_n, num_docs=d_n),
        LDAHyperParams(num_topics=k, alpha=hyper["alpha"], beta=hyper["beta"],
                       alpha_prime=hyper["alpha_prime"],
                       asymmetric_alpha=hyper["asymmetric_alpha"]),
        RunConfig(algorithm=cfg["algorithm"], token_chunk=cfg["token_chunk"]))
    state = session.init(reference.seed_key(seed))
    z0, rng = state.topic, state.rng
    del state, session
    hyper_t = tuple(sorted(hyper.items()))
    block = max(1024, train._REF_BLOCK_ELEMS // k // 1024 * 1024)
    kw = dict(num_words=w_n, num_docs=d_n, k=k, chunk=cfg["token_chunk"],
              block=block, hyper_t=hyper_t)

    def chain(dtype, fault=None):
        zs = [z0]
        for s in range(traffic["checked_steps"]):
            seeds = reference.chunk_seeds(rng, s, t, cfg["token_chunk"])
            z = reference.control_draws(word, doc, zs[-1], seeds,
                                        dtype=dtype, **kw)
            if fault == "unchanged":
                z = zs[-1]
            elif fault == "half":
                z = jnp.where(jnp.arange(t) < t // 2, z, zs[-1])
            elif fault == "altered" and s == 0:
                z = z.at[t // 3].set((z[t // 3] + 1) % k)
            zs.append(z)
        gaps = []
        for s in range(traffic["checked_steps"]):
            seeds = reference.chunk_seeds(rng, s, t, cfg["token_chunk"])
            gap, _ = reference.draw_gaps(word, doc, zs[s], zs[s + 1], seeds,
                                         **kw)
            gaps.append(float(gap))
        n_wk, n_kd, n_k = reference.counts(word, doc, zs[-1], w_n, d_n, k)
        if fault == "dropped":  # one token's count update lost
            i = t // 3
            zi = zs[-1][i]
            n_wk = n_wk.at[word[i], zi].add(-1)
            n_kd = n_kd.at[doc[i], zi].add(-1)
            n_k = n_k.at[zi].add(-1)
        mismatch = int(reference.count_mismatch(
            word, doc, zs[-1], n_wk, n_kd, n_k, num_words=w_n, num_docs=d_n,
            k=k))
        return {"draw_gap_nats": max(gaps), "step_gaps": gaps,
                "count_mismatch": mismatch}

    out = {"reference_f32": chain(jnp.float32),
           "control": chain(jnp.bfloat16)}
    for fault in ("unchanged", "half", "altered", "dropped"):
        out[fault] = chain(jnp.float32, fault)
    return out


def serve_readings(cfg, traffic, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import reference, serve

    n_wk, n_k, model = serve.frozen_model(seed, cfg)
    n = traffic["checked_requests"]
    docs = serve.request_docs(seed, cfg, traffic, model, n)
    key_data = np.asarray(jax.random.key_data(jax.random.split(
        jax.random.fold_in(reference.seed_key(seed), 4), n)))
    hyper = reference.hyper(cfg)
    hyper_t = tuple(sorted(hyper.items()))
    a_k = np.asarray(reference.alpha_k(n_k, hyper), np.float64)
    sweeps = traffic["engine"]["num_sweeps"]

    def answers(dtype, fault=None):
        width = 1024
        words = np.zeros((n, width), np.int32)
        mask = np.zeros((n, width), bool)
        for r, w in enumerate(docs):
            words[r, :len(w)] = w
            mask[r, :len(w)] = True
        nd = []
        for b in range(0, n, 64):
            keys = jax.random.wrap_key_data(jnp.asarray(key_data[b:b + 64]))
            nd.append(np.asarray(reference.serve_chains(
                keys, jnp.asarray(words[b:b + 64]),
                jnp.asarray(mask[b:b + 64]), n_wk, n_k,
                num_sweeps=0 if fault == "unchanged" else sweeps,
                hyper_t=hyper_t, dtype=dtype)))
        nd = np.concatenate(nd)
        if fault == "half":
            nd0 = []
            for b in range(0, n, 64):
                keys = jax.random.wrap_key_data(
                    jnp.asarray(key_data[b:b + 64]))
                nd0.append(np.asarray(reference.serve_chains(
                    keys, jnp.asarray(words[b:b + 64]),
                    jnp.asarray(mask[b:b + 64]), n_wk, n_k, num_sweeps=0,
                    hyper_t=hyper_t)))
            nd = np.where(np.arange(n)[:, None] % 2 == 0, nd,
                          np.concatenate(nd0))
        thetas = []
        for r, w in enumerate(docs):
            row = nd[r].astype(np.float64)
            if fault == "altered" and r == n // 2:
                top = int(np.argmax(row))
                row[top] -= 1
                row[(top + 1) % row.shape[0]] += 1
            theta = ((row + a_k) / (len(w) + a_k.sum())).astype(np.float32)
            thetas.append((r, w, theta))
        return thetas

    out = {}
    for name, dtype, fault in (("reference_f32", jnp.float32, None),
                               ("control", jnp.bfloat16, None),
                               ("unchanged", jnp.float32, "unchanged"),
                               ("half", jnp.float32, "half"),
                               ("altered", jnp.float32, "altered")):
        share = serve.check_sample(answers(dtype, fault), key_data, n_wk,
                                   n_k, hyper, sweeps, seed, n)
        out[name] = {"served_mismatch_share": share}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import run

    _, wl, cfg, traffic, _ = run.load_cell(args.workload)
    run.enable_cache()
    run.check_chips(wl["chips"])
    fn = train_readings if traffic["job"] == "train" else serve_readings
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **fn(cfg, traffic, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
