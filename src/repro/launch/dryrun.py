import os
os.environ["XLA_FLAGS"] = (os.environ.get("EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run driver.

For every (architecture x input-shape) cell this lowers + compiles the real
jit program (train_step / prefill / serve_step) against the production mesh
— 16x16 single-pod and 2x16x16 multi-pod — using ShapeDtypeStruct inputs
(no allocation), then records:

  * memory_analysis()  — per-chip argument/output/temp bytes (fits-in-HBM proof)
  * cost_analysis()    — per-chip HLO FLOPs + bytes accessed
  * collective bytes   — parsed from the post-SPMD HLO text, per category
  * roofline terms     — compute / memory / collective seconds (v5e consts)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse
import json
import pathlib
import time

import jax

from ..configs.base import (SHAPES, ARCH_IDS, get_config, cell_applicable,
                            input_specs)
from . import steps
from .hlo_analysis import analyze
from .mesh import make_production_mesh

# --- TPU v5e hardware model -------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               microbatch: int | None = None):
    """Build + lower + compile one cell. Returns (record, compiled)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}, None

    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = input_specs(cfg, shape)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            # >=100B configs: bf16 grad accumulation + smaller microbatch,
            # or params+moments+grads+activations exceed 16 GB HBM per chip
            big = cfg.opt_state_dtype == "bfloat16"
            ts = steps.TrainSettings(
                microbatch=microbatch or (16 if big else 32),
                accum_dtype=cfg.opt_state_dtype)
            step, (p_sh, o_sh, b_sh), _ = steps.jit_train_step(
                cfg, mesh, ts, spec["batch"])
            lowered = step.lower(p_sh, o_sh, spec["batch"])
        elif shape.kind == "prefill":
            fn, (p_sh, b_sh), _ = steps.jit_prefill(
                cfg, mesh, shape, spec["batch"])
            lowered = fn.lower(p_sh, spec["batch"])
        else:  # decode
            fn, (p_sh, c_sh, b_sh), _ = steps.jit_serve_step(
                cfg, mesh, spec["cache"], spec["batch"])
            lowered = fn.lower(p_sh, spec["cache"], spec["batch"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    xla_ca = compiled.cost_analysis() or {}
    # loop-aware per-chip cost: XLA's cost_analysis counts while bodies ONCE;
    # analyze() multiplies by the known trip counts (layer scan, grad accum).
    cost = analyze(compiled.as_text())
    n_chips = mesh.devices.size

    terms = {
        "compute_s": cost.flops / PEAK_FLOPS,
        "memory_s": cost.bytes / HBM_BW,
        "collective_s": cost.collective_total / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    bound_s = max(terms.values())

    tokens = shape.batch * (shape.seq if shape.kind == "train" else
                            (shape.seq if shape.kind == "prefill" else 1))
    n_active = cfg.n_active_params()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    hlo_flops_global = cost.flops * n_chips
    ideal_model_s = model_flops / (n_chips * PEAK_FLOPS)

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_gb_per_chip": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes) / 1e9, 3),
            "fits_16gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                          + ma.temp_size_in_bytes) < 16e9,
        },
        "cost": {
            "flops_per_chip": cost.flops,
            "hbm_bytes_per_chip": cost.bytes,
            # stock XLA numbers for cross-check (undercount loops)
            "xla_flops_per_chip": float(xla_ca.get("flops", 0.0)),
            "xla_bytes_per_chip": float(xla_ca.get("bytes accessed", 0.0)),
        },
        "collectives": {
            "bytes": dict(cost.coll_bytes),
            "counts": dict(cost.coll_counts),
            "total_bytes": cost.collective_total,
        },
        "roofline": {
            **{k: round(v, 6) for k, v in terms.items()},
            "dominant": dominant,
            "bound_s": round(bound_s, 6),
            "model_flops": model_flops,
            "hlo_flops_global": hlo_flops_global,
            # MODEL_FLOPS / HLO_FLOPs: <1 means remat/attention/router
            # overhead; >1 would mean the analyzer missed compute.
            "useful_flops_ratio": round(
                model_flops / hlo_flops_global, 4) if hlo_flops_global else 0,
            # fraction of roofline: ideal model-compute time / bound time
            "roofline_frac": round(ideal_model_s / max(bound_s, 1e-12), 4),
        },
        "params": {"total": cfg.n_params(), "active": n_active},
    }
    return record, compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatch", type=int, default=None)
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    arches = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for arch in arches:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                try:
                    rec, _ = lower_cell(arch, shape, multi_pod=mp,
                                        microbatch=args.microbatch)
                except Exception as e:  # a failure here is a bug in our system
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}"}
                (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
                if "skipped" in rec:
                    print(f"[skip] {tag}: {rec['skipped']}", flush=True)
                elif "error" in rec:
                    print(f"[FAIL] {tag}: {rec['error'][:200]}", flush=True)
                else:
                    r = rec["roofline"]
                    m = rec["memory"]
                    print(f"[ok] {tag}: compile={rec['compile_s']}s "
                          f"peak={m['peak_gb_per_chip']}GB "
                          f"dom={r['dominant']} bound={r['bound_s']}s "
                          f"frac={r['roofline_frac']}", flush=True)


if __name__ == "__main__":
    main()
