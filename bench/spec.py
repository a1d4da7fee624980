"""Resolve one benchmark cell from ``BENCHMARK.json`` by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     model sizes, weight dtype, model family
    bench/models/<family>.py        weights from the seed, the program under
                                    test, the plain reference, work counts
    bench/traffic/<traffic>.json    arrival process, rates, clients, buckets,
                                    policy
    bench/arrivals/<arrivals>.py    the arrival process a mix names
    bench/metrics/<metric>.py       ``read(run) -> float | None``

so a new cell is new files plus new entries, and no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple       # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple
    bench_dir: pathlib.Path = BENCH_DIR

    def model_module(self):
        return importlib.import_module(f"bench.models.{self.config['family']}")

    def arrivals(self):
        return arrival_process(self.traffic["arrivals"], self.bench_dir)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reported_metrics(bench: dict, cell: str) -> tuple[tuple, tuple]:
    """The end-to-end and per-layer metrics that ``cell`` reports. A
    per-layer metric without a ``workloads`` list is reported wherever the
    end-to-end metric it moves is."""
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, cell))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if (cell in m["workloads"] if "workloads" in m
                          else m["moves"] in names))
    return e2e, per_layer


def resolve(name: str, bench: dict | None = None,
            bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    bench = load_benchmark(bench_dir.parent) if bench is None else bench
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(by_name)}")
    w = by_name[name]
    e2e, per_layer = reported_metrics(bench, name)
    return make_cell(name, w["config"], w["traffic"], int(w["chips"]), e2e,
                     per_layer, bench_dir)


def make_cell(name: str, config: str, traffic: str, chips: int = 1,
              end_to_end=(), per_layer=(),
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """A cell from its configuration's and mix's names, whether or not
    ``BENCHMARK.json`` lists it (``bench/sweep.py`` sweeps a mix first)."""
    return Cell(
        name=name, chips=chips, config_name=config,
        config=json.loads((bench_dir / "configs" / f"{config}.json")
                          .read_text()),
        traffic_name=traffic,
        traffic=json.loads((bench_dir / "traffic" / f"{traffic}.json")
                           .read_text()),
        end_to_end=tuple(end_to_end), per_layer=tuple(per_layer),
        bench_dir=bench_dir)


def _load(path: pathlib.Path, prefix: str):
    """A module by its path (a name may hold a dot)."""
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arrival_process(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The arrival process ``bench/arrivals/<name>.py`` (``bench/traffic.py``
    says what it gives)."""
    return _load(bench_dir / "arrivals" / f"{name}.py", "bench_arrivals")


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _load(bench_dir / "metrics" / f"{name}.py", "bench_metric").read
