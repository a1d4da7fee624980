#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep of offered rates.

    python bench/sweep.py --config spikformer_8_512_t4 --traffic camera --rates 15,20,25 --seconds 30

Runs an open-loop mix on a configuration (``bench/run.py``'s ``run_cell``;
the pair need not be a cell of ``BENCHMARK.json`` yet) at each rate in
turn, with the mix's other parameters unchanged, and prints one JSON line
per rate with its ``img_per_s``, ``p95_ms`` and whether the rate was
sustained: ``p95_ms`` within the mix's ``limit_ms`` and completions within
3% of the offered rate. The knee is the highest sustained rate; a cell's
mix is then fixed at about four fifths of it. Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.run import run_cell  # noqa: E402

METRICS = ({"name": "img_per_s", "unit": "img/s"},
           {"name": "p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated img/s")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    cell = spec.make_cell(f"{args.config}.{args.traffic}", args.config,
                          args.traffic, end_to_end=METRICS)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    limit = cell.traffic["limit_ms"]
    per = cell.traffic["images_per_request"]
    for rate in (float(r) for r in args.rates.split(",")):
        at = dataclasses.replace(cell, traffic={**cell.traffic,
                                                "rate_per_s": rate})
        result, _ = run_cell(at, args.seed, args.seconds, False)
        m = {k: v["value"] for k, v in result["metrics"].items()}
        print(json.dumps({
            "rate_per_s": rate, **m, "failed": result["failed"],
            "correct": result["correct"],
            "sustained": (result["failed"] == 0 and m["p95_ms"] <= limit
                          and m["img_per_s"] >= 0.97 * rate * per)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
