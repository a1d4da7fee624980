"""``BENCHMARK.json`` and the harness: every cell resolves its files by
name, the metrics' cells are consistent, a new cell, mix or metric is new
files plus entries, and a run without a TPU prints no result."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny import ROOT, committed, tiny_bench

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = committed()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    c = spec.resolve(cell)
    assert c.config["family"] and c.arrivals().LOOP in ("open", "closed")
    c.model_module()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_per_layer_metrics_name_cells_that_report_what_they_move():
    e2e = {c: {m["name"] for m in spec.reported_metrics(BENCH, c)[0]}
           for c in CELLS}
    for m in BENCH["per_layer"]:
        for c in m.get("workloads", CELLS):
            assert c in CELLS
            assert m["moves"] in e2e[c], (m["name"], c)
    for m in BENCH["end_to_end"]:
        assert all(c in CELLS for c in m.get("workloads", []))


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert json.loads((ROOT / c["file"]).read_text())
    assert {w["config"] for w in BENCH["workloads"]} == set(cfgs)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in BENCH["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25
    names = [x["name"] for x in BENCH["workloads"] + BENCH["configs"]]
    assert all(NAME.match(n) for n in names)
    # a full check with 24 cells fits its time
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_run_without_a_tpu_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "TPU" in p.stderr


NEW_METRIC = '''
def read(run):
    return float(len(run.window.due()))
'''


def test_new_cell_mix_and_metric_are_files_plus_entries(tmp_path):
    """A later change adds a configuration, a mix and a metric as new files
    and new entries; no file of the harness changes."""
    from bench import run
    bench, bench_dir = tiny_bench(tmp_path)
    (bench_dir / "traffic" / "trickle.json").write_text(json.dumps(
        {"arrivals": "poisson", "rate_per_s": 30.0, "images_per_request": 1,
         "schedule_seed": 9, "warmup_s": 0.2, "buckets": [1, 2],
         "policy": {}}))
    (bench_dir / "metrics" / "requests_due.py").write_text(NEW_METRIC)
    bench["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                               "traffic": "trickle", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "requests_due", "unit": "req",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny.trickle"]})
    cell = spec.resolve("tiny.trickle", bench, bench_dir=bench_dir)
    assert [m["name"] for m in cell.end_to_end] == ["img_per_s",
                                                    "setup_s",
                                                    "requests_due"]
    result, _ = run.run_cell(cell, 2 ** 31 + 5, 1.0, False)
    assert result["correct"]
    assert result["metrics"]["requests_due"]["value"] == 30.0
    assert result["metrics"]["requests_due"]["unit"] == "req"


ONOFF = '''
from bench import traffic

LOOP = "open"


def schedule(mix, seconds, stream):
    """Bursts: ``burst`` arrivals ``gap_s`` apart at the start of every
    ``period_s``."""
    out, t = [], 0.0
    while t < seconds:
        for k in range(mix["burst"]):
            if t + k * mix["gap_s"] < seconds:
                out.append(traffic.Arrival(t + k * mix["gap_s"], 1, len(out)))
        t += mix["period_s"]
    return out
'''


def test_new_arrival_process_is_a_file(tmp_path):
    """A later change adds an arrival process (here ON/OFF bursts) as a new
    file under ``arrivals/``, a mix that names it and a cell; no file of the
    harness changes."""
    from bench import run
    bench, bench_dir = tiny_bench(tmp_path)
    (bench_dir / "arrivals" / "onoff.py").write_text(ONOFF)
    (bench_dir / "traffic" / "bursts.json").write_text(json.dumps(
        {"arrivals": "onoff", "burst": 4, "gap_s": 0.01, "period_s": 0.25,
         "images_per_request": 1, "warmup_s": 0.25, "limit_ms": 1000.0,
         "buckets": [1, 2, 4], "policy": {}}))
    bench["workloads"].append({"name": "tiny.bursts", "config": "tiny",
                               "traffic": "bursts", "chips": 1, "why": "t"})
    cell = spec.resolve("tiny.bursts", bench, bench_dir=bench_dir)
    assert cell.arrivals().LOOP == "open"
    result, _ = run.run_cell(cell, 2 ** 31 + 9, 1.0, False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 16


def test_open_loop_mix_reports_its_tail(tmp_path):
    """An open-loop mix that is not a cell yet runs by its names, as
    ``bench/sweep.py`` runs it, and reports its 95th percentile."""
    from bench import run, sweep
    _, bench_dir = tiny_bench(tmp_path)
    cell = spec.make_cell("tiny.open", "tiny", "open",
                          end_to_end=sweep.METRICS, bench_dir=bench_dir)
    result, _ = run.run_cell(cell, 11, 1.0, False)
    m = result["metrics"]
    assert result["correct"] and result["attempted"] == 40
    assert 0 < m["p95_ms"]["value"] < 1000
    assert m["img_per_s"]["value"] > 0


def test_serving_client_readers():
    """``queue_wait_ms`` takes the median of the runtime's ``queue`` spans
    that end in the window; ``batch_fill_pct`` the window's share of real
    rows, from differences of ``stats()``."""
    from types import SimpleNamespace as NS
    spans = [NS(name="queue", t0=t - w, t1=t) for t, w in
             ((0.5, 9.0), (1.0, 0.010), (2.0, 0.030), (3.0, 0.020))]
    spans.append(NS(name="step", t0=1.0, t1=2.5))
    run = NS(window=NS(t0=0.9, t1=3.0), spans=spans,
             stats0={"images": 100, "total_rows": 120},
             stats1={"images": 130, "total_rows": 160})
    assert spec.metric_reader("queue_wait_ms")(run) == pytest.approx(20.0)
    assert spec.metric_reader("batch_fill_pct")(run) == pytest.approx(75.0)
    idle = NS(window=NS(t0=4.0, t1=5.0), spans=spans, stats0=run.stats1,
              stats1=run.stats1)
    assert spec.metric_reader("queue_wait_ms")(idle) is None
    assert spec.metric_reader("batch_fill_pct")(idle) is None
