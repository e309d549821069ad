"""LDA training driver (launch-level CLI) — one ``TrainSession`` for both
paths.

Every run is a declarative ``RunConfig`` driving a ``TrainSession``
(DESIGN.md §6): the algorithm resolves once through the ``repro.algorithms``
registry, ``mesh_shape`` selects the execution plan (single-box vs the
shard_map mesh), and periodic events — llh/perplexity eval, model +
elastic training checkpoints, exclusion enablement, exact count rebuild,
padded-row re-resolution, duplicate-topic merging — fire from the
session's schedule. Every backend with ``supports_shard_map`` runs the
mesh plan; only backends without a cell sweep (std) fall back to
single-box. One process drives every local chip: on a four-chip TPU host
``--rows 2 --cols 2`` lays the mesh over all four; on CPU hosts pass
--host-devices to simulate N devices. The persistent compile cache goes
to ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the checkout.

    PYTHONPATH=src python -m repro.launch.train \
        --rows 2 --cols 2 --host-devices 4 --iters 50 \
        [--corpus path.libsvm] [--ckpt DIR] [--algorithm <registered-name>]
        [--delta-dtype int16] [--exclusion-start 30] [--rebuild-every 10]
    PYTHONPATH=src python -m repro.launch.train --config run.json
    PYTHONPATH=src python -m repro.launch.train --dump-config run.json ...
    PYTHONPATH=src python -m repro.launch.train --list-algorithms

``--config`` loads a ``RunConfig`` JSON (the ``to_json`` round-trip);
``--dump-config`` writes the resolved config and exits, so any CLI
invocation can be frozen into a reproducible run file.

``--checkpoint-dir`` writes *model* checkpoints (N_wk/N_k + hyper) on both
paths — the artifact ``launch/serve_lda.py`` serves from. ``--ckpt``
remains the elastic *training* checkpoint (assignments only; resumes
automatically).

``--stream`` switches to windowed online training (DESIGN.md §7): a
``CorpusSource`` (``--stream-source replay|libsvm:<path>|drift``) feeds a
``StreamingSession`` window by window, model checkpoints land on a
per-window cadence, and ``launch/serve_lda.py --follow`` hot-reloads them
into a running engine — the two commands form the live pipeline:

    PYTHONPATH=src python -m repro.launch.train --stream \
        --window-docs 64 --decay 0.02 --checkpoint-dir /tmp/lda_live
    PYTHONPATH=src python -m repro.launch.serve_lda \
        --checkpoint-dir /tmp/lda_live --follow
"""
import argparse
import os


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="load a RunConfig JSON (overrides per-field flags)")
    ap.add_argument("--dump-config", default=None, metavar="PATH",
                    help="write the resolved RunConfig JSON and exit")
    ap.add_argument("--rows", type=int, default=2, help="data-parallel rows")
    ap.add_argument("--cols", type=int, default=2, help="model-parallel cols")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="simulate N host devices (CPU bring-up)")
    ap.add_argument("--corpus", default=None, help="libsvm corpus path")
    ap.add_argument("--topics", type=int, default=64)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--algorithm", default="zen_cdf",
                    help="any name from --list-algorithms")
    ap.add_argument("--list-algorithms", action="store_true",
                    help="print the registered sampler backends and exit")
    ap.add_argument("--single-box", action="store_true",
                    help="force the single-box plan")
    ap.add_argument("--max-kd", type=int, default=None,
                    help="sparse doc-row width (default: auto — resolved "
                         "from the counts, and re-resolved on the "
                         "--rebuild-every cadence on the mesh plan)")
    ap.add_argument("--max-kw", type=int, default=None,
                    help="sparse word-row width (padded-sparse backends; "
                         "default: auto, like --max-kd)")
    ap.add_argument("--delta-dtype", default="int32",
                    choices=["int32", "int16", "int8"])
    ap.add_argument("--exclusion-start", type=int, default=0)
    ap.add_argument("--rebuild-every", type=int, default=0,
                    help="exact count rebuild + padded-row re-resolution "
                         "cadence (0 = never)")
    ap.add_argument("--merge-every", type=int, default=0,
                    help="duplicate-topic merge cadence (0 = never)")
    ap.add_argument("--merge-threshold", type=float, default=0.05)
    ap.add_argument("--ckpt", default=None,
                    help="elastic training checkpoints (assignments)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="model checkpoints (N_wk/N_k + hyper) for serving")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="model-checkpoint cadence (0 = final only)")
    ap.add_argument("--llh-every", type=int, default=10,
                    help="eval cadence (llh/perplexity)")
    ap.add_argument("--target-perplexity", type=float, default=None,
                    help="stop once eval perplexity reaches this")
    # -- model quality + Alg. 5 hyper opt (repro.eval, DESIGN.md §9) ------
    ap.add_argument("--quality-every", type=int, default=0,
                    help="model-quality eval cadence: UMass/NPMI "
                         "coherence (+ left-to-right with --l2r-docs)")
    ap.add_argument("--quality-top-n", type=int, default=10,
                    help="top words per topic entering coherence")
    ap.add_argument("--npmi-window", type=int, default=10,
                    help="NPMI sliding-window size (0 = UMass only)")
    ap.add_argument("--l2r-docs", type=int, default=0,
                    help="held-out docs for left-to-right eval (0 = skip)")
    ap.add_argument("--l2r-particles", type=int, default=20,
                    help="particles per left-to-right document")
    ap.add_argument("--hyper-every", type=int, default=0,
                    help="Alg. 5 hyper-opt cadence: Minka fixed-point "
                         "alpha + beta annealing (0 = off)")
    ap.add_argument("--beta-anneal", type=float, default=1.0,
                    help="beta *= this per hyper firing (1.0 = no anneal)")
    ap.add_argument("--synthetic-docs", type=int, default=1000,
                    help="synthetic corpus size (when --corpus is not given)")
    ap.add_argument("--synthetic-words", type=int, default=2000)
    ap.add_argument("--synthetic-len", type=int, default=80)
    # -- observability + autopilot (DESIGN.md §8) -------------------------
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write per-iteration telemetry JSONL here")
    ap.add_argument("--autopilot", action="store_true",
                    help="re-pick backend + row capacities from measured "
                         "sparsity on the --rebuild-every cadence")
    ap.add_argument("--autopilot-every", type=int, default=0,
                    help="autopilot decision cadence "
                         "(0 = --rebuild-every, else every 10)")
    # -- streaming mode (DESIGN.md §7) -----------------------------------
    ap.add_argument("--stream", action="store_true",
                    help="windowed online training (StreamingSession); "
                         "--iters becomes the absolute window budget "
                         "(0 = run to source exhaustion)")
    ap.add_argument("--stream-source", default=None,
                    help="replay | libsvm:<path> | drift[:<seed>] "
                         "(default: replay of --corpus, else drift)")
    ap.add_argument("--window-docs", type=int, default=64,
                    help="documents per stream window")
    ap.add_argument("--window-sweeps", type=int, default=2,
                    help="CGS sweeps per window visit")
    ap.add_argument("--decay", type=float, default=0.0,
                    help="forgetting factor: counts *= (1-decay) per "
                         "window transition (0 = never forget)")
    ap.add_argument("--epochs", type=int, default=1,
                    help="replay source: passes over the corpus")
    ap.add_argument("--num-windows", type=int, default=8,
                    help="drift source: stream length in windows")
    return ap


def run_stream(args, cfg) -> None:
    """The ``--stream`` path: build a ``CorpusSource`` from the config's
    spec string and drive a ``StreamingSession`` over it. Pairs with
    ``launch/serve_lda.py --follow`` watching the same
    ``--checkpoint-dir`` for the live train→serve pipeline."""
    import jax

    from repro.core.types import LDAHyperParams
    from repro.data import load_libsvm, synthetic_corpus
    from repro.data.stream import make_source
    from repro.train.online import StreamingSession

    spec = cfg.stream_source or ("replay" if args.corpus else "drift")
    corpus = None
    if spec == "replay":
        corpus = (load_libsvm(args.corpus) if args.corpus
                  else synthetic_corpus(0, num_docs=args.synthetic_docs,
                                        num_words=args.synthetic_words,
                                        avg_doc_len=args.synthetic_len,
                                        zipf_a=1.2))
    source = make_source(
        spec, cfg.window_docs,
        corpus=corpus,
        # chunked sources cannot infer the global vocabulary — take it
        # from --synthetic-words (the stable-vocabulary contract)
        num_words=args.synthetic_words,
        epochs=args.epochs,
        num_windows=args.num_windows,
    )
    hyper = LDAHyperParams(num_topics=args.topics)
    session = StreamingSession(source, hyper, cfg)
    print(f"stream  source={spec}  window_docs={cfg.window_docs}  "
          f"sweeps/window={cfg.window_sweeps}  decay={cfg.decay}  "
          f"algorithm={cfg.algorithm}")

    def cb(sess, m):
        print(f"window {m['window']:4d} ({m['uid']})  docs {m['docs']:5d}  "
              f"ppl {m['perplexity']:.1f}  {m['docs_per_sec']:.0f} docs/s  "
              f"resident kd {m['resident_kd_bytes'] / 1024:.1f} KiB")

    session.run(jax.random.key(0), callback=cb)
    print(f"stream finished at window {session.windows_done}")
    if cfg.checkpoint_dir:
        print(f"model checkpoints: {cfg.checkpoint_dir} "
              f"(follow with: python -m repro.launch.serve_lda "
              f"--checkpoint-dir {cfg.checkpoint_dir} --follow)")


def main() -> None:
    args = build_parser().parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax

    from repro import algorithms
    from repro.train.session import RunConfig, TrainSession
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.list_algorithms:
        for name, backend, aliases in algorithms.describe():
            mesh = "mesh+single-box" if backend.supports_shard_map \
                else "single-box"
            alias_s = f" (aliases: {', '.join(aliases)})" if aliases else ""
            print(f"{name:12s} {mesh}{alias_s}")
        return

    if args.config:
        with open(args.config) as f:
            cfg = RunConfig.from_json(f.read())
    else:
        backend = algorithms.get(args.algorithm)  # one registry resolution
        mesh_shape = None
        if backend.supports_shard_map and not args.single_box:
            mesh_shape = (args.rows, args.cols)
        elif not backend.supports_shard_map and not args.single_box:
            print(f"note: backend {args.algorithm!r} has no shard_map cell "
                  f"sweep; running the single-box plan")
        if args.stream and mesh_shape is not None:
            print("note: --stream runs the single-box windowed plan; "
                  "ignoring the mesh shape")
            mesh_shape = None
        if mesh_shape is None and args.delta_dtype != "int32":
            print("note: single-box plan ignores --delta-dtype")
        cfg = RunConfig(
            algorithm=args.algorithm,
            max_kd=args.max_kd or 0,  # 0 = auto-size from the counts
            max_kw=args.max_kw or 0,
            mesh_shape=mesh_shape,
            delta_dtype=args.delta_dtype,
            num_iterations=args.iters,
            eval_every=args.llh_every,
            target_perplexity=args.target_perplexity,
            exclusion_start=args.exclusion_start,
            rebuild_every=args.rebuild_every,
            merge_every=args.merge_every,
            merge_threshold=args.merge_threshold,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            train_checkpoint_dir=args.ckpt,
            train_checkpoint_every=(
                (1 if args.stream else 25) if args.ckpt else 0
            ),
            window_docs=args.window_docs if args.stream else 0,
            window_sweeps=args.window_sweeps,
            decay=args.decay if args.stream else 0.0,
            stream_source=(
                (args.stream_source
                 or ("replay" if args.corpus else "drift"))
                if args.stream else None
            ),
            metrics_out=args.metrics_out,
            autopilot=args.autopilot,
            autopilot_every=args.autopilot_every,
            quality_every=args.quality_every,
            quality_top_n=args.quality_top_n,
            quality_npmi_window=args.npmi_window,
            quality_l2r_docs=args.l2r_docs,
            quality_l2r_particles=args.l2r_particles,
            hyper_every=args.hyper_every,
            hyper_beta_anneal=args.beta_anneal,
        )

    if args.dump_config:
        with open(args.dump_config, "w") as f:
            f.write(cfg.to_json() + "\n")
        print(f"wrote {args.dump_config}")
        return

    if args.stream or cfg.stream_source:
        run_stream(args, cfg)
        return

    from repro.core.types import LDAHyperParams
    from repro.data import load_libsvm, synthetic_corpus

    if args.corpus:
        corpus = load_libsvm(args.corpus)
    else:
        corpus = synthetic_corpus(0, num_docs=args.synthetic_docs,
                                  num_words=args.synthetic_words,
                                  avg_doc_len=args.synthetic_len, zipf_a=1.2)
    hyper = LDAHyperParams(num_topics=args.topics)

    session = TrainSession(corpus, hyper, cfg)
    if cfg.mesh_shape is None:
        print(f"single-box  algorithm={cfg.algorithm}  "
              f"tokens={corpus.num_tokens}")
    else:
        grid = session.plan.grid
        rows, cols = cfg.mesh_shape
        print(f"mesh {rows}x{cols}  tokens={int(grid.mask.sum())}  "
              f"pad={grid.padding_overhead:.2%}")

    state = session.init(jax.random.key(0))
    if session.backend.needs_row_pads and cfg.mesh_shape is not None:
        kw, kd = session.row_pads
        print(f"padded-row widths: max_kw={kw} max_kd={kd}")

    def cb(st, metrics):
        if not metrics:
            return
        line = f"iter {int(st.iteration):4d}"
        if "llh" in metrics:
            line += (f"  llh {metrics['llh']:.1f}"
                     f"  ppl {metrics['perplexity']:.1f}"
                     f"  change {metrics['change_rate']:.3f}")
        if "coherence_umass" in metrics:
            line += f"  umass {metrics['coherence_umass']:.3f}"
        if "coherence_npmi" in metrics:
            line += f"  npmi {metrics['coherence_npmi']:.3f}"
        if "l2r_per_token" in metrics:
            line += f"  l2r/tok {metrics['l2r_per_token']:.3f}"
        if "hyper" in metrics:
            line += (f"  hyper a={metrics['hyper']['alpha']:.4f}"
                     f" b={metrics['hyper']['beta']:.4f}")
        if "row_pads" in metrics:
            kw, kd = metrics["row_pads"]
            line += f"  repad kw={kw} kd={kd}"
        for rec in metrics.get("autopilot", ()):
            line += (f"\n  autopilot {rec['decision']}"
                     f"{' applied' if rec['applied'] else ' (no-op)'}: "
                     f"{rec['reason']}")
        print(line)

    final = session.run(state=state, callback=cb)
    print(f"finished at iteration {int(final.iteration)}; "
          f"final llh {session.llh(final):.1f}")
    if cfg.autopilot:
        print(f"autopilot: final backend={session.plan.backend.name} "
              f"row_pads={session.row_pads}")
    if cfg.metrics_out:
        print(f"telemetry: {cfg.metrics_out}")
    if cfg.checkpoint_dir:
        print(f"model checkpoint: {cfg.checkpoint_dir} "
              f"(serve with: python -m repro.launch.serve_lda "
              f"--checkpoint-dir {cfg.checkpoint_dir})")


if __name__ == "__main__":
    main()
