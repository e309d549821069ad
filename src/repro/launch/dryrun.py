import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is normal.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build abstract inputs + shardings (launch/specs.py)
  * jax.jit(step, in_shardings=..., out_shardings=...).lower(...).compile()
  * record memory_analysis / cost_analysis / collective bytes (roofline.py)
  * append the result to a JSON store so interrupted sweeps resume

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import numpy as np


RESULTS_PATH = os.environ.get("DRYRUN_RESULTS", "results/dryrun.json")


def _load_results(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def _save_results(path: str, results: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def build_step(cfg, kind: str, dims=None):
    """The function each cell lowers (closed over the config)."""
    from repro.configs.base import ArchConfig, LDAArchConfig
    from repro.models.model import decode_step, forward
    from repro.train.train_step import make_train_step

    if kind == "train":
        inner = make_train_step(cfg)

        def train_step(state, batch):
            return inner(state, batch)

        return train_step
    if kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = forward(
                params, cfg,
                tokens=batch.get("tokens"),
                embeds=batch.get("embeds"),
                positions=batch.get("positions"),
                enc_embeds=batch.get("enc_embeds"),
            )
            return logits

        return prefill_step
    if kind == "decode":
        def serve_step(params, token, caches):
            return decode_step(params, cfg, token, caches)

        return serve_step
    if kind == "lda":
        from repro import algorithms
        from repro.core.distributed import DistConfig, make_dist_step
        from repro.core.types import LDAHyperParams

        # fail fast (before lowering) on unknown / non-mesh backends — the
        # same registry entry the trainer and the mesh step resolve
        backend = algorithms.get(cfg.algorithm)
        if not backend.supports_shard_map:
            raise ValueError(
                f"LDA arch {cfg.name!r}: backend {cfg.algorithm!r} has no "
                f"shard_map cell sweep"
            )
        hyper = LDAHyperParams(num_topics=cfg.num_topics)
        dcfg = DistConfig(
            algorithm=cfg.algorithm, max_kd=cfg.max_kd,
            delta_dtype=cfg.delta_dtype,
        )

        def make(mesh):
            return make_dist_step(
                mesh, hyper, dcfg, dims["words_per_shard"],
                dims["docs_per_shard"], dims["num_words"],
            )

        return make
    raise ValueError(kind)


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    """Lower+compile one cell; returns the result record."""
    from repro.configs import SHAPES, get_config
    from repro.configs.base import LDAArchConfig
    from repro.launch import roofline
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import lda_cell_specs, lm_cell_specs

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    t0 = time.time()
    if isinstance(cfg, LDAArchConfig):
        kind, inputs, shardings, dims = lda_cell_specs(cfg, mesh)
        step = build_step(cfg, kind, dims)(mesh)
        lowered = step.lower(inputs["state"], inputs["data"])
    else:
        shape = SHAPES[shape_name]
        kind, inputs, shardings = lm_cell_specs(cfg, shape, mesh)
        step = build_step(cfg, kind)
        in_sh = tuple(shardings[k] for k in inputs)
        out_sh = None
        if kind == "train":
            # state out keeps the state-in layout (donation-compatible)
            out_sh = (shardings["state"], None)
        jitted = jax.jit(
            step,
            in_shardings=in_sh,
            out_shardings=out_sh,
            # donation matches production (train state / decode caches are
            # updated in place) and makes memory_analysis reflect reality
            donate_argnums=(0,) if kind == "train" else
                           ((2,) if kind == "decode" else ()),
        )
        lowered = jitted.lower(*inputs.values())
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = roofline.collective_bytes(compiled)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", 0.0) if cost else 0.0,
        "bytes_per_device": cost.get("bytes accessed", 0.0) if cost else 0.0,
        "collective_bytes_per_device": coll,
        "memory_analysis": roofline.memory_summary(mem),
    }
    return record


def main() -> None:
    from repro.configs import SHAPES, get_config, list_archs, shapes_for

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells already in the results store")
    ap.add_argument("--fit", action="store_true",
                    help="also depth-fit true per-step costs (single-pod "
                         "mesh; see rooffit.py) for the roofline table")
    ap.add_argument("--out", default=RESULTS_PATH)
    args = ap.parse_args()

    cells = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        names = shapes_for(cfg)
        if args.shape:
            names = [s for s in names if s == args.shape]
        for s in names:
            cells.append((arch, s))

    if args.list:
        for c in cells:
            print(f"{c[0]} x {c[1]}")
        print(f"total {len(cells)} cells")
        return

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    results = _load_results(args.out)
    for arch, shape in cells:
        for multi in meshes:
            key = f"{arch}|{shape}|{'multi' if multi else 'single'}"
            if key in results and results[key].get("ok") and not args.force:
                print(f"[skip] {key}")
                continue
            print(f"[cell] {key} ...", flush=True)
            try:
                rec = run_cell(arch, shape, multi)
                print(
                    f"  ok: compile {rec['compile_s']}s, "
                    f"flops/dev {rec['flops_per_device']:.3e}, "
                    f"coll B/dev {rec['collective_bytes_per_device']:.3e}",
                    flush=True,
                )
            except Exception as e:  # record failures: they are bugs to fix
                rec = {
                    "arch": arch, "shape": shape,
                    "mesh": "2x16x16" if multi else "16x16",
                    "ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                print(f"  FAIL: {rec['error']}", flush=True)
            results[key] = rec
            _save_results(args.out, results)
        if args.fit:
            from repro.configs.base import LDAArchConfig
            from repro.launch.mesh import make_production_mesh
            from repro.launch.rooffit import fit_cell

            fkey = f"{arch}|{shape}|fit"
            cfg = get_config(arch)
            if isinstance(cfg, LDAArchConfig):
                continue  # no scans: the raw record is already exact
            if fkey in results and results[fkey].get("ok") and not args.force:
                print(f"[skip] {fkey}")
                continue
            print(f"[fit ] {fkey} ...", flush=True)
            try:
                rec = fit_cell(arch, shape, make_production_mesh())
                rec["ok"] = True
                print(
                    f"  fitted flops/dev {rec['flops_per_device']:.3e}, "
                    f"coll B/dev {rec['collective_bytes_per_device']:.3e}",
                    flush=True,
                )
            except Exception as e:
                rec = {"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAIL: {rec['error']}", flush=True)
            results[fkey] = rec
            _save_results(args.out, results)


if __name__ == "__main__":
    main()
