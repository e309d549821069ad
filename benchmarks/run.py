"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py).

  fig3/4   ZenLDA vs LightLDA vs SparseLDA time + llh   (bench_algorithms)
  fig5/6   scalability: partitions and topic count       (bench_scaling)
  fig7/8   sparse initialization                         (bench_init)
  fig9     converged-token exclusion + §5.2 delta agg    (bench_exclusion)
  fig10    redundant-computation elimination (Alg. 5)    (bench_redundant)
  table1   per-algorithm work terms (complexity model)   (bench_table1)
  sec41    partitioner quality (DBH+ et al.)             (bench_partition)
  infer    serving throughput + latency/throughput frontier (bench_infer)
  kernels  kernel suite v2 vs pre-fusion baselines; writes
           BENCH_kernels.json                            (bench_kernels)
  streaming windowed online vs batch: docs/sec + resident doc-side
           state; writes BENCH_streaming.json            (bench_streaming)
  autopilot mis-configured vs hand-tuned vs autopilot recovery for
           training and serving; writes BENCH_autopilot.json
                                                         (bench_autopilot)
  quality  per-backend quality trajectories: UMass/NPMI coherence +
           left-to-right held-out llh; writes BENCH_quality.json
                                                         (bench_quality)

Machine-readable ``BENCH_*.json`` artifacts all land under one output
dir — ``--out-dir`` (or ``$BENCH_OUT_DIR``, default
``benchmarks/results/``) — never the repo root.
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section list (e.g. fig3,fig9)")
    ap.add_argument("--out-dir", default=None,
                    help="directory for BENCH_*.json artifacts "
                         "(default $BENCH_OUT_DIR or benchmarks/results)")
    args = ap.parse_args()
    if args.out_dir:
        os.environ["BENCH_OUT_DIR"] = args.out_dir
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sections = {
        "fig3": lambda: __import__("benchmarks.bench_algorithms",
                                   fromlist=["main"]).main(),
        "fig5": lambda: __import__("benchmarks.bench_scaling",
                                   fromlist=["main"]).main(),
        "fig7": lambda: __import__("benchmarks.bench_init",
                                   fromlist=["main"]).main(),
        "fig9": lambda: __import__("benchmarks.bench_exclusion",
                                   fromlist=["main"]).main(),
        "fig10": lambda: __import__("benchmarks.bench_redundant",
                                    fromlist=["main"]).main(),
        "table1": lambda: __import__("benchmarks.bench_table1",
                                     fromlist=["main"]).main(),
        "sec41": lambda: __import__("benchmarks.bench_partition",
                                    fromlist=["main"]).main(),
        "infer": lambda: __import__("benchmarks.bench_infer",
                                    fromlist=["main"]).main(),
        "kernels": lambda: __import__("benchmarks.bench_kernels",
                                      fromlist=["main"]).main(),
        "streaming": lambda: __import__("benchmarks.bench_streaming",
                                        fromlist=["main"]).main(),
        "autopilot": lambda: __import__("benchmarks.bench_autopilot",
                                        fromlist=["main"]).main(),
        "quality": lambda: __import__("benchmarks.bench_quality",
                                      fromlist=["main"]).main(),
    }
    wanted = args.only.split(",") if args.only else list(sections)
    print("name,us_per_call,derived")
    for name in wanted:
        sections[name]()


if __name__ == "__main__":
    main()
