#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (``PERF.md``).

    python bench/limits.py --workload spikformer_t4.bulk --seeds 1,2,3 --seconds 5

For each seed, in one process: one run of the cell at its own load with a
short window (``bench/run.py``'s ``run_cell``), whose sample of served
logits is compared with the reference, as every run does; and the control,
the reference computed with weights of ``control_bits`` (the next precision
below the configuration's), compared with the reference in the same way.
Prints one JSON line per seed: ``{"seed", "correct", "program": {...},
"control": {...}}``. Sound runs give the lower readings, the control the
upper ones. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, spec  # noqa: E402
from bench.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("limits: no TPU", file=sys.stderr)
        return 2
    mm = cell.model_module()
    for seed in (int(s) for s in args.seeds.split(",")):
        control = {}

        def on_check(params, images, served, ref):
            low = mm.reference_logits(params, cell.config, images,
                                      bits=cell.config["control_bits"])
            control.update(check.compare(low, ref))

        result, numbers = run_cell(cell, seed, args.seconds, False,
                                   on_check=on_check)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "program": {k: v["value"] for k, v in
                                      numbers.items()},
                          "control": control,
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
