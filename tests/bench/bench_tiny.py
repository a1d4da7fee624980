"""A tiny copy of the benchmark's data files, for tests on the CPU: the
T=4 configuration cut to 32 px, dim 64, depth 2, with the committed
limits, and small closed- and open-loop mixes."""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = {"img_size": 32, "dim": 64, "depth": 2, "heads": 2, "num_classes": 10,
        "scs_channels": [8, 16, 32, 64], "check_images": 16}
CLOSED = {"arrivals": "closed", "clients": 2, "images_per_request": 2,
          "warmup_s": 0.2, "buckets": [4], "policy": {}}
OPEN = {"arrivals": "poisson", "rate_per_s": 40.0, "images_per_request": 1,
        "schedule_seed": 3, "warmup_s": 0.2, "buckets": [1, 2, 4],
        "policy": {"slo_ms": 250.0}}


def committed() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_bench(tmp_path: pathlib.Path, *, config: str = "spikformer_8_512_t4"):
    """Write a bench directory with cells ``tiny.closed`` and ``tiny.open``
    into ``tmp_path``; returns ``(benchmark dict, bench dir)``."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    cfg.update(TINY)
    for sub in ("configs", "traffic", "arrivals", "metrics"):
        (tmp_path / sub).mkdir(parents=True, exist_ok=True)
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "closed.json").write_text(json.dumps(CLOSED))
    (tmp_path / "traffic" / "open.json").write_text(json.dumps(OPEN))
    for sub in ("arrivals", "metrics"):
        for f in (ROOT / "bench" / sub).glob("*.py"):
            (tmp_path / sub / f.name).write_text(f.read_text())
    bench = committed()
    bench["workloads"] = [
        {"name": "tiny.closed", "config": "tiny", "traffic": "closed",
         "chips": 1, "why": "test"},
        {"name": "tiny.open", "config": "tiny", "traffic": "open",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.open"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench, tmp_path


def run_tiny(tmp_path, name="tiny.closed", seed=2 ** 31 + 17, seconds=0.6,
             **kw):
    from bench import run, spec
    bench, bench_dir = tiny_bench(tmp_path)
    cell = spec.resolve(name, bench, bench_dir=bench_dir)
    return run.run_cell(cell, seed, seconds, False, **kw)
