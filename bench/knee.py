#!/usr/bin/env python3
"""Sweep open-loop request rates on one engine to find the serving knee.

    python3 bench/knee.py --workload serve-nytimes-steady --seed 1 \
        --seconds 15 --rates 100 200 400 800

One process, one engine, one frozen model (as the cell builds them); for
each rate an open-loop window of ``--seconds`` with that cell's length mix,
then every request is awaited. Prints one JSON line per rate: completed
docs/s, p50/p95 latency from the due time, and the backlog when the window
closed. The knee is the highest rate whose completions keep up with the
offers and whose backlog does not grow. The benchmark's runs never run
this; the steady cell's rate is set from its output once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--grace", type=float, default=20.0,
                    help="seconds a late request is awaited after a window")
    ap.add_argument("--out", help="also append each line to this file")
    args = ap.parse_args(argv)

    from bench import reference, run, serve
    from bench.harness import percentile

    _, wl, cfg, traffic, _ = run.load_cell(args.workload)
    run.enable_cache()
    run.check_chips(wl["chips"])
    import jax
    import numpy as np

    from repro.core.types import LDAHyperParams
    from repro.serving.lda_engine import FrozenLDAModel, LDAEngine, \
        LDAServeConfig

    hyper = reference.hyper(cfg)
    n_wk, n_k, model = serve.frozen_model(args.seed, cfg)
    n_max = int(max(args.rates) * args.seconds) + 1
    docs = serve.request_docs(args.seed, cfg, traffic, model, n_max)
    eng = traffic["engine"]
    engine = LDAEngine(
        FrozenLDAModel(n_wk=n_wk, n_k=n_k, hyper=LDAHyperParams(
            num_topics=cfg["num_topics"], alpha=hyper["alpha"],
            beta=hyper["beta"], alpha_prime=hyper["alpha_prime"],
            asymmetric_alpha=hyper["asymmetric_alpha"])),
        LDAServeConfig(buckets=tuple(eng["buckets"]),
                       max_batch=eng["max_batch"],
                       num_sweeps=eng["num_sweeps"],
                       algorithm=cfg["algorithm"], mode=eng["mode"]),
        seed=args.seed % 2**31)
    engine.warm()
    engine.start()
    try:
        for rate in args.rates:
            n = int(round(rate * args.seconds))
            key_data = np.asarray(jax.random.key_data(jax.random.split(
                jax.random.fold_in(reference.seed_key(args.seed),
                                   int(rate)), n)))
            load = serve.Load(engine, docs, key_data)
            load.make_keys(n)
            offsets = serve.arrival_offsets(args.seed, n, args.seconds)
            t0 = serve.run_open(load, offsets, args.seconds)
            backlog = load.in_flight()
            load.await_all(time.monotonic() + args.grace)
            load.collect()
            lat = [(r.t_done - due) * 1e3 for _, r, due, _ in load.reqs
                   if r.done]
            in_win = sum(1 for _, r, _, _ in load.reqs
                         if r.done and r.t_done <= t0 + args.seconds)
            line = json.dumps({
                "rate": rate, "offered": n, "unsent": load.unsent,
                "done_in_window_per_s": in_win / args.seconds,
                "backlog_at_close": backlog,
                "failed": load.unsent + sum(not r.done
                                            for _, r, _, _ in load.reqs),
                "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
                "late_p95_ms": percentile(
                    [(s - d) * 1e3 for _, _, d, s in load.reqs], 95),
            })
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
            # let the engine drain before the next rate
            deadline = time.monotonic() + args.grace
            while engine.load and time.monotonic() < deadline:
                time.sleep(0.05)
    finally:
        engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
