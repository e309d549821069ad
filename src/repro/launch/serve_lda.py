"""LDA serving driver: restore a trained model, serve documents.

Loads the model checkpoint written by ``launch/train.py --checkpoint-dir``
(N_wk/N_k + hyper), builds the bucketed :class:`~repro.serving.LDAEngine`
in either execution mode, and pushes a libsvm stream or a synthetic load
through the async ticket front.

    PYTHONPATH=src python -m repro.launch.serve_lda \
        --checkpoint-dir /tmp/lda_ckpt \
        [--mode throughput|latency] \
        [--corpus path.libsvm | --synthetic-docs 64] \
        [--algorithm zen] [--buckets 32,64,128,256] [--max-batch 32] \
        [--sweeps 10] [--rtlda-sweeps 2] [--burn-in -1] [--thin 1] \
        [--tick-period 0] [--max-slot-wait 0] [--eval] [--show 5] \
        [--mesh-shape 1,2] [--replicas 1] \
        [--autopilot] [--autopilot-window 16] \
        [--metrics-out serve.jsonl] [--pace 0.002]

Every document goes through ``submit_async`` -> ``result``, so the driver
reports per-request latency percentiles (p50/p99 of submit-to-done) next
to throughput (docs/sec, decode dispatches) in both modes — the numbers
DESIGN.md §5.1 trades against each other. ``--tick-period > 0`` runs the
background admission ticker instead of caller-driven ticks. With
``--eval``, also prints the doc-completion held-out perplexity, the
serving-quality number.

``--follow`` turns the driver into the consuming half of the live
pipeline (DESIGN.md §7): a checkpoint watcher polls ``--checkpoint-dir``
every ``--watch-period`` seconds and hot-reloads each new model the
trainer commits (``launch/train.py --stream`` is the producing half); the
query load replays for ``--rounds`` rounds, printing the model versions
each round's requests decoded under.

Scaling flags (DESIGN.md §5.4): ``--mesh-shape 1,m`` serves the model
*sharded* — word rows laid over an m-way device mesh, every bucket sweep
a ``shard_map`` dispatch; ``--replicas n`` fronts n engine replicas with
the load-balancing :class:`~repro.serving.LDARouter` (one ticket
namespace, broadcast reloads). The two compose: each replica decodes
against the sharded model.
"""
import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--checkpoint-dir", required=True,
                    help="model checkpoint dir from train --checkpoint-dir")
    ap.add_argument("--mode", default="throughput",
                    choices=["throughput", "latency"],
                    help="chain CGS sweeps vs the deterministic RT-LDA "
                         "fast path (DESIGN.md §5.1)")
    ap.add_argument("--corpus", default=None,
                    help="libsvm documents to serve (docs are the queries)")
    ap.add_argument("--synthetic-docs", type=int, default=64,
                    help="synthetic query load (when --corpus is not given)")
    ap.add_argument("--synthetic-len", type=int, default=60)
    ap.add_argument("--algorithm", default="zen",
                    help="any registered sampler backend (throughput mode)")
    ap.add_argument("--buckets", default="32,64,128,256",
                    help="comma-separated bucket lengths")
    ap.add_argument("--max-batch", type=int, default=32,
                    help="slots per bucket")
    ap.add_argument("--sweeps", type=int, default=10,
                    help="chain sweeps per request (throughput mode)")
    ap.add_argument("--rtlda-sweeps", type=int, default=2,
                    help="fused deterministic passes (latency mode)")
    ap.add_argument("--burn-in", type=int, default=-1,
                    help="-1 = final-sweep theta; >=0 = posterior mean")
    ap.add_argument("--thin", type=int, default=1)
    ap.add_argument("--sampling-method", default="cdf",
                    choices=["cdf", "gumbel"])
    ap.add_argument("--tick-period", type=float, default=0.0,
                    help="> 0: run the background admission ticker at this "
                         "period (seconds); 0: drive ticks inline")
    ap.add_argument("--max-slot-wait", type=int, default=0,
                    help="ticks a request waits for its preferred bucket "
                         "before spilling into a wider one (0 = never)")
    ap.add_argument("--eval", action="store_true",
                    help="doc-completion held-out perplexity")
    ap.add_argument("--show", type=int, default=5,
                    help="print top topics for the first N docs")
    ap.add_argument("--follow", action="store_true",
                    help="watch --checkpoint-dir and hot-reload every new "
                         "model checkpoint while serving (live pipeline)")
    ap.add_argument("--watch-period", type=float, default=0.5,
                    help="checkpoint poll cadence in seconds (--follow)")
    ap.add_argument("--rounds", type=int, default=1,
                    help="serve the query load this many rounds (pair with "
                         "--follow to observe reloads between rounds)")
    ap.add_argument("--mesh-shape", default=None,
                    help="serve the model sharded over a device mesh, "
                         "e.g. 1,2 (data dim must be 1; throughput mode)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the serving router")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write windowed serving telemetry JSONL here")
    ap.add_argument("--autopilot", action="store_true",
                    help="derive tick_period / max_slot_wait / buckets "
                         "from the observed arrival process")
    ap.add_argument("--autopilot-window", type=int, default=0,
                    help="arrivals per telemetry window (0 = default 64); "
                         "smaller windows decide sooner on light loads")
    ap.add_argument("--pace", type=float, default=0.0,
                    help="> 0: open-loop load — sleep this many seconds "
                         "between submits (an arrival process the "
                         "autopilot can measure) instead of submitting "
                         "the whole round at once")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.data import synthetic_corpus
    from repro.data.corpus import load_libsvm
    from repro.observe import summarize_latencies
    from repro.serving import (
        FrozenLDAModel,
        LDAEngine,
        LDARouter,
        LDAServeConfig,
        doc_completion_perplexity,
        docs_from_corpus,
    )
    from repro.train.checkpoint import load_lda_model

    n_wk, n_k, hyper, _meta, step0 = load_lda_model(args.checkpoint_dir)
    model = FrozenLDAModel(
        n_wk=jnp.asarray(n_wk, jnp.int32),
        n_k=jnp.asarray(n_k, jnp.int32),
        hyper=hyper,
    )
    print(f"model: W={model.num_words} K={model.num_topics} "
          f"tokens={int(np.asarray(model.n_k).sum())} "
          f"step={step0} from {args.checkpoint_dir}")

    if args.corpus:
        corpus = load_libsvm(args.corpus)
    else:
        corpus = synthetic_corpus(args.seed + 1,
                                  num_docs=args.synthetic_docs,
                                  num_words=model.num_words,
                                  avg_doc_len=args.synthetic_len, zipf_a=1.2)
    docs = docs_from_corpus(corpus)

    cfg = LDAServeConfig(
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        max_batch=args.max_batch,
        num_sweeps=args.sweeps,
        burn_in=args.burn_in,
        thin=args.thin,
        algorithm=args.algorithm,
        sampling_method=args.sampling_method,
        mode=args.mode,
        rtlda_sweeps=args.rtlda_sweeps,
        tick_period=args.tick_period,
        max_slot_wait=args.max_slot_wait,
        mesh_shape=(tuple(int(d) for d in args.mesh_shape.split(","))
                    if args.mesh_shape else None),
        metrics_out=args.metrics_out,
        autopilot=args.autopilot,
        autopilot_window=args.autopilot_window,
    )
    engine = LDARouter(model, cfg, replicas=args.replicas, seed=args.seed)
    plan = (f"rtlda_sweeps={cfg.rtlda_sweeps} (deterministic)"
            if args.mode == "latency" else
            f"algorithm={args.algorithm} sweeps={cfg.num_sweeps}")
    print(f"engine: mode={args.mode} {plan} buckets={cfg.buckets} "
          f"max_batch={cfg.max_batch} replicas={args.replicas}")
    if cfg.mesh_shape is not None:
        sharded = engine.model  # ShardedFrozenLDAModel after wrap
        print(f"sharded: {sharded.num_shards} word shards x "
              f"{sharded.words_per_shard} rows "
              f"(W={sharded.num_words} padded to "
              f"{sharded.num_shards * sharded.words_per_shard})")

    # warm every bucket's jit cache (one doc per width) so the latency
    # distribution reflects steady-state serving, not XLA compilation
    engine.warm()

    if args.tick_period > 0:
        engine.start(args.tick_period)
    if args.follow:
        engine.watch_checkpoint_dir(
            args.checkpoint_dir, period=args.watch_period,
            initial_step=step0,
        )

    thetas = []
    for rnd in range(max(1, args.rounds)):
        sweeps0 = engine.sweeps_run
        t0 = time.perf_counter()
        tickets = []
        for d in docs:
            tickets.append(engine.submit_async(d))
            if args.pace > 0:
                time.sleep(args.pace)
        reqs = [engine.request(t) for t in tickets]  # refs survive the reap
        thetas = [engine.result(t) for t in tickets]
        dt = time.perf_counter() - t0

        stats = summarize_latencies(
            (r.t_done - r.t_submit) * 1e3 for r in reqs
        )
        versions = sorted({r.model_version for r in reqs})
        tag = f"round {rnd}  " if args.rounds > 1 else ""
        print(f"{tag}served {len(docs)} docs in {dt:.3f}s "
              f"({len(docs) / dt:.1f} docs/sec, "
              f"{engine.sweeps_run - sweeps0} bucket dispatches)  "
              f"model versions {versions}")
        print(f"latency ms: p50={stats['p50']:.2f} "
              f"p99={stats['p99']:.2f} max={stats['max']:.2f}")
        if args.follow and rnd < args.rounds - 1:
            time.sleep(args.watch_period)

    if args.autopilot:
        # surface where the measured knobs settled (replica 0 speaks for
        # a homogeneous fleet — every replica sees the same process)
        e0 = engine.engines[0] if hasattr(engine, "engines") else engine
        print(f"autopilot: tick_period={e0.tick_period * 1e3:.2f}ms "
              f"max_slot_wait={e0.max_slot_wait} "
              f"buckets={e0.bucket_widths} spills={e0.spills}")
    if args.metrics_out:
        print(f"telemetry: {args.metrics_out}")

    if args.follow:
        engine.stop_watching()
    if args.tick_period > 0:
        engine.stop()

    for i in range(min(args.show, len(docs))):
        top = np.argsort(-thetas[i])[:3]
        pretty = " ".join(f"k{t}:{thetas[i][t]:.3f}" for t in top)
        print(f"doc {i:4d} len {len(docs[i]):4d}  {pretty}")

    if args.eval:
        ppl = doc_completion_perplexity(
            LDAEngine(model, cfg, seed=args.seed + 7), docs
        )
        print(f"doc-completion perplexity: {ppl:.2f}")


if __name__ == "__main__":
    main()
