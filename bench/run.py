#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell's configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``job`` names the module that
drives it), the limits of its checks (``bench/limits/<workload>.json``) and,
with ``--trace 1``, one reader per per-layer metric
(``bench/metrics/<metric>.py``), which reads the reduced trace and the
job's ``layer_ctx`` with ``chips``, the cell's chip count. Without a TPU,
or with fewer chips than the cell asks for, it exits with code 3 before
any work.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# the TPU runtime's own logs would go to a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark, workload, config, traffic, limits) for cell ``name``."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    workload = next((w for w in bench["workloads"] if w["name"] == name),
                    None)
    if workload is None:
        raise SystemExit(f"unknown workload {name!r}")
    conf = next(c for c in bench["configs"] if c["name"] == workload["config"])
    cfg = _load_json(ROOT, conf["file"])
    traffic = _load_json(BENCH, "traffic", workload["traffic"] + ".json")
    limits = _load_json(BENCH, "limits", name + ".json")
    return bench, workload, cfg, traffic, limits


def cell_metrics(bench: dict, name: str, kind: str):
    """The cell's metric entries of ``kind`` (``end_to_end``/``per_layer``):
    those that list it, or list no cells."""
    return [m for m in bench[kind]
            if name in m.get("workloads", [name])]


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_cache() -> None:
    """JAX's persistent compilation cache at its fixed place in the
    checkout; every program is cached, however fast it compiles."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def check_chips(need: int):
    """The devices, or exit 3 when there is no TPU or too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"bench: needs {need} TPU chip(s), found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devices


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, overrides=None) -> dict:
    """Run one cell; returns the result line as a dict (``checks`` last).

    ``overrides`` (tests only) maps ``cfg``/``traffic``/``limits`` to
    entries that replace the files' values, for a run at a small size."""
    from bench import harness, program_trace, trace as trace_lib, work

    bench, wl, cfg, traffic, limits = load_cell(workload)
    for part, extra in (overrides or {}).items():
        {"cfg": cfg, "traffic": traffic, "limits": limits}[part].update(extra)
    if require_chip:
        enable_cache()
    import jax

    devices = check_chips(wl["chips"]) if require_chip else jax.devices()
    ctx = harness.Context(ROOT, wl, cfg, traffic, limits, seed, seconds,
                          trace)
    job = importlib.import_module("bench." + traffic["job"])
    out = job.run(ctx)
    setup_s = ctx.win.t0 - T_START
    ctx.log(setup={"setup_s": setup_s, **ctx.setup_split})
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": wl["chips"], "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    result = {"correct": all(c.ok for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if not trace:
        values = dict(out["e2e"], setup_s=setup_s)
        for m in cell_metrics(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        red = trace_lib.reduce(trace_lib.load(
            trace_lib.find_xplane(ctx.trace_dir)))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        lctx = dict(out["layer_ctx"], trace=red, cfg=cfg, traffic=traffic,
                    chips=wl["chips"],
                    peaks=work.peaks(dev["kind"]) if require_chip else None)
        by_scope = program_trace.step_ms_by_scope(lctx)
        if by_scope:
            ctx.log(device_ms_by_scope=by_scope)
        for m in cell_metrics(bench, workload, "per_layer"):
            value = metric_reader(m["name"]).read(lctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["metrics"] = metrics
    result["device"] = dev
    return result, out["checks"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness

    result, checks = run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace))
    harness.print_result(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
