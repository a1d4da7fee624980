"""The observability subsystem (``repro.obs``) and its serving hooks.

Four standards of proof, mirroring the serving tests:

* the TRACER is pinned exactly: ring wrap drops the OLDEST spans and
  counts them, and an injected fake clock pins the sync engine's full
  span table — timestamps and all, no tolerance;
* the HISTOGRAM is held to its documented contract: every percentile
  within ``error_bound`` of the exact nearest-rank order statistic of
  the same sample set, single samples exact, the empty window all-None;
* the span CHAIN is client-invariant: the same request trace through
  the sync engine, the async runtime, and a 2-replica fleet yields the
  identical per-rid lifecycle chain (timestamps differ, structure may
  not), and every request's spans name a batch that stepped;
* the runtime worker's spans TILE its timeline under a fake clock:
  idle -> hold -> place -> assemble (occupancy inside) -> step -> finish,
  each boundary shared exactly;
* EXPORT round-trips: the JSONL loader inverts the writer bit-exactly
  and refuses wrong-kind/wrong-version/truncated files loudly.
"""
import itertools
import json
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

from repro.core.spikformer import SpikformerConfig, init
from repro.events import EventStream, EventStreamSession
from repro.infer import (ExecutionPlan, MicroBatchEngine,
                         QueueDepthWatermark, SERVE_STATS_VERSION,
                         compile as infer_compile)
from repro.infer.engine import (Request, StepAccounting, latency_summary,
                                serve_stats)
from repro.obs import (LIFECYCLE, Counter, Gauge, LatencyHistogram,
                       MetricsRegistry, NULL_TRACER, NullTracer, Span,
                       SPANS_SCHEMA_VERSION, Tracer, load_spans_jsonl,
                       to_chrome_trace, write_chrome_trace,
                       write_spans_jsonl)
from repro.serve import (AsyncServeRuntime, ContinuousBatchingScheduler,
                         FleetScheduler, QueueFull, ServeFleet, ServePolicy)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "scripts"))
import trace_report  # noqa: E402


@pytest.fixture(scope="module")
def small():
    cfg = SpikformerConfig().scaled(img_size=16, dim=32, depth=1)
    params = init(jax.random.PRNGKey(0), cfg)
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2, 8)))
    model.warmup()
    imgs = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (11, 16, 16, 3), 0, 256, "uint8"))
    return cfg, model, imgs


class FakeClock:
    """Ticks 1.0 per call — pins span tables exactly."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# tracer: the ring contract
# ---------------------------------------------------------------------------

def test_ring_overflow_drops_oldest_and_counts():
    tr = Tracer(capacity=4, clock=FakeClock())
    for k in range(6):
        tr.span("test", f"s{k}", t0=float(k), t1=float(k) + 0.5)
    assert len(tr) == 4
    assert tr.dropped_spans == 2
    got = tr.spans()
    # chronological, oldest SURVIVING first: s0/s1 were overwritten
    assert [s.name for s in got] == ["s2", "s3", "s4", "s5"]
    assert got[0].t0 == 2.0 and got[0].t1 == 2.5
    assert all(isinstance(s, Span) for s in got)


def test_ring_clear_preserves_drop_account():
    tr = Tracer(capacity=2)
    for k in range(3):
        tr.span("test", "x", t0=0.0)
    assert tr.dropped_spans == 1
    tr.clear()
    assert len(tr) == 0 and tr.spans() == []
    assert tr.dropped_spans == 1          # loss is history, not contents
    tr.span("test", "y", t0=9.0)          # ring still usable after clear
    assert [s.name for s in tr.spans()] == ["y"]


def test_tracer_rejects_zero_capacity():
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


def test_tracer_injected_clock_stamps_instants():
    clock = FakeClock()
    tr = Tracer(capacity=8, clock=clock)
    tr.span("test", "bare")               # t0 defaults to the clock
    tr.counter("depth", 3, t=10.0)
    tr.counter("depth", 4)                # counter on the clock too
    bare, c1, c2 = tr.spans()
    assert bare.t0 == bare.t1 == 1.0      # instant on the injected clock
    assert (c1.category, c1.name, c1.t0, c1.value) == \
        ("counter", "depth", 10.0, 3.0)
    assert c2.t0 == 2.0 and c2.value == 4.0


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert isinstance(NULL_TRACER, NullTracer)
    NULL_TRACER.span("x", "y", t0=1.0)
    NULL_TRACER.counter("d", 1)
    assert NULL_TRACER.spans() == [] and len(NULL_TRACER) == 0
    assert NULL_TRACER.dropped_spans == 0
    assert LIFECYCLE == ("admit", "queue", "place", "assemble", "step",
                         "complete")
    assert NULL_TRACER.anchor is None


# ---------------------------------------------------------------------------
# metrics: counters, gauges, the bounded histogram
# ---------------------------------------------------------------------------

def test_counter_and_gauge_watermark():
    c = Counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge("depth")
    assert g.value is None and g.max is None
    for v in (3.0, 9.0, 2.0):
        g.set(v)
    assert g.value == 2.0 and g.max == 9.0  # burst peak survives the quiet


def test_queue_depth_watermark():
    w = QueueDepthWatermark()
    assert w.peak == 0                     # nothing observed yet
    for d in (3, 8, 1):
        w.observe(d)
    assert w.peak == 8
    shared = Gauge("queue_depth")
    w2 = QueueDepthWatermark(shared)
    w2.observe(5)
    assert shared.max == 5 and w2.peak == 5


def exact_nearest_rank(samples, q):
    """The exact order statistic the histogram approximates: nearest-rank
    over the sorted sample list (NOT numpy's interpolating percentile)."""
    s = sorted(samples)
    rank = max(1, int(np.ceil(q / 100.0 * len(s))))
    return s[rank - 1]


def test_histogram_percentiles_within_documented_error():
    rng = np.random.default_rng(42)
    # log-uniform latencies spanning 100us..1s — several decades, so the
    # bucket error bound is actually exercised
    samples = np.exp(rng.uniform(np.log(1e-4), np.log(1.0), 5000))
    h = LatencyHistogram()
    for v in samples:
        h.observe(float(v))
    assert h.count == 5000
    assert h.mean == pytest.approx(float(samples.sum()) / 5000)
    for q in (1, 10, 25, 50, 75, 90, 95, 99, 99.9):
        got = h.percentile(q)
        want = exact_nearest_rank(samples, q)
        assert abs(got - want) / want <= h.error_bound, \
            f"p{q}: {got} vs exact {want} beyond {h.error_bound:.3f}"
    assert h.error_bound == pytest.approx(0.05)


def test_histogram_empty_single_and_degenerate():
    h = LatencyHistogram()
    assert h.percentile(50) is None and h.mean is None
    assert h.summary() == {"latency_p50_s": None, "latency_p95_s": None,
                           "latency_p99_s": None, "latency_mean_s": None}
    h.observe(0.0123)                     # single sample: exact everywhere
    for q in (0, 50, 99, 100):
        assert h.percentile(q) == pytest.approx(0.0123)
    h2 = LatencyHistogram()
    h2.observe(0.0)                       # the empty-request latency
    assert h2.percentile(50) == 0.0       # clamped into observed [0, 0]
    h2.observe(1e9)                       # overflow bucket: the hi edge
    assert h2.percentile(100) == h2.hi    # stands in (off the log range)
    with pytest.raises(ValueError, match=">= 0"):
        h2.observe(-0.1)
    with pytest.raises(ValueError, match="growth"):
        LatencyHistogram(growth=1.0)
    with pytest.raises(ValueError, match="lo"):
        LatencyHistogram(lo=0.0)


def test_histogram_memory_is_fixed():
    h = LatencyHistogram()
    n_buckets = len(h.counts)
    for v in np.linspace(1e-5, 2.0, 1000):
        h.observe(float(v))
    assert len(h.counts) == n_buckets     # O(buckets) however many observed
    assert sum(h.counts) == h.count == 1000


def test_registry_get_or_create_and_type_conflict():
    r = MetricsRegistry()
    c = r.counter("drops")
    assert r.counter("drops") is c
    r.gauge("depth").set(4)
    r.histogram("lat").observe(0.01)
    with pytest.raises(TypeError, match="drops"):
        r.gauge("drops")
    with pytest.raises(TypeError, match="depth"):
        r.histogram("depth")
    assert r.names() == ["depth", "drops", "lat"]
    snap = r.snapshot()
    assert snap["drops"] == 0
    assert snap["depth"] == {"value": 4, "max": 4}
    assert snap["lat"]["count"] == 1
    assert snap["lat"]["latency_p50_s"] == pytest.approx(0.01)


# ---------------------------------------------------------------------------
# the sync engine's span table, pinned under a fake clock
# ---------------------------------------------------------------------------

def test_engine_span_table_pinned(small):
    _, model, imgs = small
    tr = Tracer(capacity=64)
    eng = MicroBatchEngine(model, tracer=tr, clock=FakeClock())
    eng.submit(imgs[:2])
    eng.run()
    table = [(s.category, s.name, s.t0, s.t1, s.rid, s.bucket, s.batch)
             for s in tr.spans()]
    assert table == [
        ("request", "admit", 1.0, 2.0, 0, None, None),
        ("counter", "queue_depth", 2.0, 2.0, None, None, None),
        ("batch", "place", 3.0, 4.0, None, 2, 0),
        ("request", "queue", 2.0, 5.0, 0, None, 0),
        ("batch", "assemble", 5.0, 6.0, None, 2, 0),
        ("batch", "step", 6.0, 7.0, None, 2, 0),
        ("counter", "occupancy", 6.0, 6.0, None, None, None),
        ("request", "complete", 2.0, 8.0, 0, None, 0),
    ]
    by_name = {s.name: s for s in tr.spans()}
    assert by_name["admit"].value == 2          # images admitted
    assert by_name["queue_depth"].value == 2.0
    assert by_name["step"].occupancy is not None
    assert tr.dropped_spans == 0


def test_engine_empty_request_chain_skips_queue(small):
    _, model, _ = small
    tr = Tracer(capacity=16)
    eng = MicroBatchEngine(model, tracer=tr, clock=FakeClock())
    req = eng.submit(np.zeros((0, 16, 16, 3), np.uint8))
    assert req.labels == []
    names = [(s.name, s.rid) for s in tr.spans()]
    assert names == [("admit", 0), ("complete", 0)]
    assert tr.spans()[0].value == 0             # zero-image admit
    # the report gate accepts the short chain for empty admits
    assert trace_report.check_complete(tr.spans(), 0) == []


def test_untraced_engine_emits_nothing(small):
    _, model, imgs = small
    eng = MicroBatchEngine(model)
    assert eng.tracer is NULL_TRACER
    eng.submit(imgs[:2])
    eng.run()
    assert len(eng.tracer) == 0


# ---------------------------------------------------------------------------
# chain determinism: identical per-rid lifecycle across every ServeClient
# ---------------------------------------------------------------------------

def chains(tracer):
    """{rid: [span names in append order]} over rid-scoped request spans."""
    out = {}
    for s in tracer.spans():
        if s.category == "request" and s.rid is not None:
            out.setdefault(s.rid, []).append(s.name)
    return out


def test_request_chains_identical_across_clients(small):
    _, model, imgs = small
    sizes = [2, 1, 3, 2]

    tr_eng = Tracer()
    eng = MicroBatchEngine(model, tracer=tr_eng)
    for k, n in enumerate(sizes):
        eng.submit(imgs[:n], rid=k)
    eng.run()

    tr_rt = Tracer()
    with AsyncServeRuntime(model, tracer=tr_rt) as rt:
        handles = [rt.submit(imgs[:n], rid=k) for k, n in enumerate(sizes)]
        for h in handles:
            h.result(timeout=60.0)

    tr_fl = Tracer()
    with ServeFleet(model, replicas=2, tracer=tr_fl) as fleet:
        handles = [fleet.submit(imgs[:n], rid=k)
                   for k, n in enumerate(sizes)]
        for h in handles:
            h.result(timeout=60.0)

    want = {k: ["admit", "queue", "complete"] for k in range(len(sizes))}
    assert chains(tr_eng) == want
    assert chains(tr_rt) == want
    assert chains(tr_fl) == want
    # fleet batch spans carry the executing replica's index
    step_replicas = {s.replica for s in tr_fl.spans()
                     if s.category == "batch" and s.name == "step"}
    assert step_replicas and step_replicas <= {0, 1}
    for tr in (tr_eng, tr_rt, tr_fl):
        assert tr.dropped_spans == 0
        assert trace_report.check_complete(tr.spans(), 0) == []


def serve_sizes(client, sizes):
    """Submit ``sizes`` images per request through a ServeClient and wait
    for every result."""
    handles = [client.submit(np.zeros((n, 16, 16, 3), np.uint8), rid=k)
               for k, n in enumerate(sizes)]
    if isinstance(client, MicroBatchEngine):
        client.run()
    for h in handles:
        h.result(timeout=60.0)


@pytest.mark.parametrize("client", ["engine", "runtime", "fleet"])
def test_request_spans_name_a_batch_that_stepped(small, client):
    """Every ``queue`` and ``complete`` span names the batch (by replica
    and batch id) that took the request's first image and the one that
    finished it; both batches have a ``step`` span, and the first was
    placed no later than the last."""
    _, model, _ = small
    tr = Tracer()
    if client == "engine":
        serve_sizes(MicroBatchEngine(model, tracer=tr), [2, 1, 3, 2, 5])
    elif client == "runtime":
        with AsyncServeRuntime(model, tracer=tr) as rt:
            serve_sizes(rt, [2, 1, 3, 2, 5])
    else:
        with ServeFleet(model, replicas=2, tracer=tr) as fleet:
            serve_sizes(fleet, [2, 1, 3, 2, 5])
    spans = tr.spans()
    steps = {(s.replica, s.batch) for s in spans
             if s.category == "batch" and s.name == "step"}
    placed = {(s.replica, s.batch): s.t0 for s in spans
              if s.category == "batch" and s.name == "place"}
    assert all(b is not None for _, b in steps) and set(placed) == steps
    linked = {}
    for s in spans:
        if s.category == "request" and s.name in ("queue", "complete"):
            assert (s.replica, s.batch) in steps, s
            linked.setdefault(s.rid, {})[s.name] = placed[s.replica, s.batch]
    assert sorted(linked) == [0, 1, 2, 3, 4]
    for rid, by in linked.items():
        assert by["queue"] <= by["complete"], rid
    # a batch's own spans carry its id too
    for s in spans:
        if s.category == "batch":
            assert (s.replica, s.batch) in steps, s
    assert trace_report.check_complete(spans, tr.dropped_spans) == []


class TickClock:
    """A fake clock safe across threads: each read advances 1 ms."""

    def __init__(self):
        self._ticks = itertools.count(1)

    def __call__(self):
        return next(self._ticks) * 1e-3


class BucketFourModel:
    """CompiledModel stand-in with one bucket of four 4x4 images."""
    buckets = (4,)

    def input_shape(self, bucket=None):
        return (bucket or 4, 4, 4, 3)

    def step(self, batch):
        return np.zeros((len(batch), 10), np.float32)


def worker_timeline(spans):
    return sorted((s for s in spans
                   if s.category in ("worker", "batch")
                   and s.name != "occupancy"), key=lambda s: s.t0)


def test_runtime_worker_spans_tile_its_timeline():
    """On a fake clock: one image waits out a 10 ms window (one ``hold``
    however often the worker wakes), four fill the bucket at once (no
    hold), and the worker's spans tile its timeline with shared
    boundaries: idle, hold, place, assemble (occupancy inside), step,
    finish."""
    tr = Tracer(capacity=256)
    rt = AsyncServeRuntime(BucketFourModel(),
                           policy=ServePolicy(max_wait_ms=10.0), tracer=tr)
    rt._clock = TickClock()       # the serving clock, made deterministic
    with rt:
        img = np.zeros((1, 4, 4, 3), np.uint8)
        rt.submit(img).result(timeout=30)
        rt.submit(np.repeat(img, 4, axis=0)).result(timeout=30)
    spans = tr.spans()
    line = worker_timeline(spans)
    names = " ".join(s.name for s in line)
    assert re.fullmatch(r"(idle )?hold place assemble step finish "
                        r"(idle )?place assemble step finish idle", names), \
        names
    for a, b in zip(line, line[1:]):
        assert a.t1 == b.t0, (a, b)          # no gap, no overlap
        assert a.t0 <= a.t1
    first_finish = next(s for s in line if s.name == "finish")
    for s in line:
        want = None if s.name == "idle" else int(s.t0 > first_finish.t0)
        assert s.batch == want, s
    occ = [s for s in spans
           if s.category == "batch" and s.name == "occupancy"]
    asm = {s.batch: s for s in line if s.name == "assemble"}
    assert [s.batch for s in occ] == [0, 1]
    for o in occ:
        assert asm[o.batch].t0 <= o.t0 <= o.t1 == asm[o.batch].t1
    req = {(s.rid, s.name): s.batch for s in spans if s.category == "request"}
    assert req[0, "queue"] == req[0, "complete"] == 0
    assert req[1, "queue"] == req[1, "complete"] == 1
    assert tr.dropped_spans == 0


def test_untraced_runtime_and_fleet_stay_silent(small):
    _, model, imgs = small
    with AsyncServeRuntime(model) as rt:
        rt.submit(imgs[:3]).result(timeout=60)
    with ServeFleet(model, replicas=2) as fleet:
        fleet.submit(imgs[:3]).result(timeout=60)
    assert rt.tracer is NULL_TRACER and fleet.tracer is NULL_TRACER
    assert len(NULL_TRACER) == 0


def test_queue_depth_peak_parity_engine_vs_runtime(small):
    _, model, imgs = small
    # 4 requests x 2 images fill the largest bucket exactly; a 5s window
    # with no SLO means the async worker provably holds all 8 before the
    # first dispatch — both clients must report the identical peak
    eng = MicroBatchEngine(model)
    for k in range(4):
        eng.submit(imgs[2 * (k % 2):2 * (k % 2) + 2], rid=k)
    eng.run()
    assert eng.stats()["queue_depth_peak"] == 8

    with AsyncServeRuntime(model,
                           policy=ServePolicy(max_wait_ms=5000.0)) as rt:
        handles = [rt.submit(imgs[2 * (k % 2):2 * (k % 2) + 2], rid=k)
                   for k in range(4)]
        for h in handles:
            h.result(timeout=60.0)
        assert rt.stats()["queue_depth_peak"] == 8


# ---------------------------------------------------------------------------
# scheduler inspectability: debug_state + publish
# ---------------------------------------------------------------------------

def test_scheduler_debug_state_and_publish():
    s = ContinuousBatchingScheduler((2, 8), ServePolicy())
    s.observe_step(2, 0.010, occupancy=0.10)    # sparse (< 0.35)
    s.observe_step(8, 0.040, occupancy=0.90)    # dense
    ds = s.debug_state()
    assert ds["buckets"] == [2, 8]
    assert set(ds["step_s"]) == {2, 8}
    assert set(ds["class_step_s"]) == {"2/sparse", "8/dense"}
    assert ds["occupancy_ewma"] is not None
    ds["step_s"].clear()                        # a copy, not the live table
    assert s.debug_state()["step_s"]

    reg = MetricsRegistry()
    s.publish(reg)
    assert reg.names() == [
        "scheduler/class_step_s/2/sparse", "scheduler/class_step_s/8/dense",
        "scheduler/occupancy_ewma", "scheduler/step_s/2",
        "scheduler/step_s/8",
    ]
    assert reg.gauge("scheduler/step_s/2").value == pytest.approx(0.010)


def test_fleet_scheduler_publishes_replica_tables():
    s = FleetScheduler((2, 8), ServePolicy(), n_replicas=2)
    s.observe_step(2, 0.010, occupancy=0.10, replica=1)
    ds = s.debug_state()
    assert ds["n_replicas"] == 2
    assert set(ds["replica_step_s"]) == {"1/2"}
    assert set(ds["replica_class_step_s"]) == {"1/2/sparse"}
    reg = MetricsRegistry()
    s.publish(reg, prefix="fleet/")
    names = set(reg.names())
    assert {"fleet/n_replicas", "fleet/replica_step_s/1/2",
            "fleet/replica_class_step_s/1/2/sparse"} <= names
    assert reg.gauge("fleet/n_replicas").value == 2.0


def test_fresh_scheduler_publishes_nothing_spurious():
    reg = MetricsRegistry()
    ContinuousBatchingScheduler((2, 8)).publish(reg)
    assert reg.names() == []        # no observations, no occupancy: silence


# ---------------------------------------------------------------------------
# serve_stats v3: histogram-backed latency fields
# ---------------------------------------------------------------------------

def fake_acct():
    acct = StepAccounting()
    acct.record_step(rows=2, bucket=2, busy_s=0.01, wall_s=0.02,
                     occupancy=0.5)
    return acct


def test_serve_stats_v3_histogram_vs_exact_list():
    assert SERVE_STATS_VERSION == 3
    lats = [0.002, 0.004, 0.008, 0.016, 0.032]
    hist = LatencyHistogram()
    done = []
    for k, v in enumerate(lats):
        hist.observe(v)
        r = Request(rid=k, images=np.zeros((1, 4, 4, 3), np.uint8))
        r.t_submit, r.t_done = 0.0, v
        done.append(r)
    via_hist = serve_stats(acct=fake_acct(), done=done, buckets=(2, 8),
                           latency_hist=hist)
    via_list = serve_stats(acct=fake_acct(), done=done, buckets=(2, 8))
    assert via_hist["stats_version"] == via_list["stats_version"] == 3
    assert set(via_hist) == set(via_list)     # same schema either way
    # the histogram path honors the documented contract: within one
    # bucket width of the exact nearest-rank order statistic
    for k, q in (("latency_p50_s", 50), ("latency_p95_s", 95),
                 ("latency_p99_s", 99)):
        want = exact_nearest_rank(lats, q)
        assert via_hist[k] == pytest.approx(want, rel=hist.error_bound)
    assert via_hist["latency_mean_s"] == pytest.approx(
        via_list["latency_mean_s"], abs=1e-6)     # the mean is exact
    assert via_hist["requests"] == 5


def test_serve_stats_empty_window_reports_absence():
    empty = serve_stats(acct=StepAccounting(), done=[], buckets=(2, 8),
                        latency_hist=LatencyHistogram())
    assert empty["latency_p50_s"] is None and empty["latency_mean_s"] is None
    assert empty["requests"] == 0 and empty["fps"] == 0.0
    # the exact-list path must also shrug off in-flight Nones
    assert latency_summary([None, None])["latency_p50_s"] is None
    assert latency_summary([])["latency_p99_s"] is None


# ---------------------------------------------------------------------------
# export: chrome trace structure + JSONL round trip
# ---------------------------------------------------------------------------

def traced_fixture():
    tr = Tracer(capacity=32)
    tr.span("request", "admit", t0=10.0, t1=10.1, rid=0, value=2)
    tr.span("request", "queue", t0=10.1, t1=10.3, rid=0, batch=0)
    tr.span("batch", "place", t0=10.1, t1=10.2, bucket=2, batch=0)
    tr.span("batch", "step", t0=10.3, t1=10.9, bucket=2, occupancy=0.4,
            value=2, replica=1, batch=0)
    tr.span("window", "encode", t0=10.0, t1=10.05, rid=3, value=7)
    tr.counter("queue_depth", 2, t=10.1)
    tr.span("request", "complete", t0=10.1, t1=11.0, rid=0, batch=0)
    return tr


def test_chrome_trace_structure():
    tr = traced_fixture()
    doc = to_chrome_trace(tr.spans(), dropped_spans=3)
    assert doc["otherData"] == {"spans_version": SPANS_SCHEMA_VERSION,
                                "dropped_spans": 3}
    ev = doc["traceEvents"]
    x = [e for e in ev if e["ph"] == "X"]
    counters = [e for e in ev if e["ph"] == "C"]
    meta = [e for e in ev if e["ph"] == "M"]
    assert len(x) == 6 and len(counters) == 1
    # timestamps rebased to the earliest span, in microseconds
    assert min(e["ts"] for e in x) == 0.0
    assert all(e["dur"] >= 0.0 for e in x)
    # one pid per replica: the step span ran on replica 1, rest on pid 0
    assert {e["pid"] for e in x} == {0, 1}
    by_name = {e["name"]: e for e in x}
    assert by_name["admit"]["tid"] == 10 + 0      # request lane
    assert by_name["place"]["tid"] == 1           # scheduler lane
    assert by_name["encode"]["tid"] == 10 + 3     # rid lane wins over window
    assert by_name["step"]["args"]["occupancy"] == 0.4
    assert by_name["step"]["args"]["batch"] == 0
    assert "batch" not in by_name["admit"]["args"]
    assert counters[0]["args"] == {"queue_depth": 2.0}
    proc_names = {e["pid"]: e["args"]["name"] for e in meta
                  if e["name"] == "process_name"}
    assert proc_names == {0: "replica 0", 1: "replica 1"}
    assert any(e["name"] == "thread_name" and e["args"]["name"] == "worker"
               for e in meta)


def test_jsonl_round_trip(tmp_path):
    tr = traced_fixture()
    path = tmp_path / "trace.jsonl"
    n = write_spans_jsonl(path, tr, meta={"mode": "test"})
    assert n == 7
    header, spans = load_spans_jsonl(path)
    assert header["kind"] == "repro.obs.spans"
    assert header["spans_version"] == SPANS_SCHEMA_VERSION
    assert header["dropped_spans"] == 0 and header["meta"] == {"mode": "test"}
    assert header["clock_anchor"] == {"perf_counter_s": tr.anchor[0],
                                      "time_ns": tr.anchor[1]}
    assert spans == tr.spans()                    # bit-exact inversion
    assert [s.batch for s in spans if s.name in ("queue", "complete")] \
        == [0, 0]
    # the perfetto writer emits valid JSON alongside, anchor included
    pf = tmp_path / "trace.perfetto.json"
    assert write_chrome_trace(pf, tr) == 7
    doc = json.loads(pf.read_text())
    assert len(doc["traceEvents"]) > 7
    assert doc["otherData"]["clock_anchor"] == header["clock_anchor"]
    assert doc["otherData"]["t_base_s"] == 10.0


def test_tracer_clock_anchor_pairs_perf_counter_with_real_time():
    import time
    before_pc, before_ns = time.perf_counter(), time.time_ns()
    tr = Tracer(capacity=4)
    after_pc, after_ns = time.perf_counter(), time.time_ns()
    pc, ns = tr.anchor
    assert before_pc <= pc <= after_pc and before_ns <= ns <= after_ns


def test_jsonl_loader_refuses_version_1(tmp_path):
    old = tmp_path / "v1.jsonl"
    old.write_text(json.dumps({"kind": "repro.obs.spans", "spans_version": 1,
                               "spans": 1, "dropped_spans": 0}) + "\n"
                   + json.dumps({"cat": "request", "name": "admit",
                                 "t0": 0.0, "t1": 0.1, "rid": 0,
                                 "replica": None, "bucket": None,
                                 "occ": None, "value": 1}) + "\n")
    with pytest.raises(ValueError, match="spans_version=1.*batch ids"):
        load_spans_jsonl(old)


def test_jsonl_loader_refuses_bad_files(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(ValueError, match="empty"):
        load_spans_jsonl(empty)
    wrong = tmp_path / "wrong.jsonl"
    wrong.write_text(json.dumps({"kind": "something.else"}) + "\n")
    with pytest.raises(ValueError, match="kind"):
        load_spans_jsonl(wrong)
    future = tmp_path / "future.jsonl"
    future.write_text(json.dumps({"kind": "repro.obs.spans",
                                  "spans_version": 99, "spans": 0}) + "\n")
    with pytest.raises(ValueError, match="spans_version"):
        load_spans_jsonl(future)
    trunc = tmp_path / "trunc.jsonl"
    trunc.write_text(json.dumps({"kind": "repro.obs.spans",
                                 "spans_version": SPANS_SCHEMA_VERSION,
                                 "spans": 5}) + "\n")
    with pytest.raises(ValueError, match="truncated"):
        load_spans_jsonl(trunc)


# ---------------------------------------------------------------------------
# trace_report: the analysis views and the CI gate
# ---------------------------------------------------------------------------

def test_trace_report_views():
    spans = traced_fixture().spans()
    phases = trace_report.phase_breakdown(spans)
    assert ("counter", "queue_depth") not in phases   # instants, not phases
    assert phases[("request", "complete")]["count"] == 1
    assert phases[("batch", "step")]["mean_s"] == pytest.approx(0.6)
    slow = trace_report.slowest_requests(spans, 3)
    assert [s.rid for s in slow] == [0]
    util = trace_report.replica_utilization(spans)
    assert util[1] == pytest.approx(0.6 / 1.0)        # step 0.6s over 1s wall


def test_trace_report_gate_catches_violations():
    ok = [Span("request", "admit", 0.0, 0.1, rid=0, value=2),
          Span("request", "queue", 0.1, 0.2, rid=0, batch=0),
          Span("request", "complete", 0.1, 0.3, rid=0, batch=0),
          Span("request", "admit", 0.0, 0.1, rid=1, value=0),
          Span("request", "complete", 0.1, 0.1, rid=1),
          Span("batch", "step", 0.2, 0.3, batch=0)]
    assert trace_report.check_complete(ok, 0) == []
    assert trace_report.check_complete(ok, dropped_spans=5)  # lossy: fails
    missing = [ok[0], ok[1], ok[5]]                   # admitted, never done
    problems = trace_report.check_complete(missing, 0)
    assert len(problems) == 1 and "complete" in problems[0]
    # a non-empty admit with no queue span is a broken chain too
    no_queue = [ok[0], ok[2], ok[5]]
    assert any("queue" in p for p in trace_report.check_complete(no_queue, 0))
    # a request whose batch never stepped, or whose span names no batch
    no_step = trace_report.check_complete(ok[:5], 0)
    assert len(no_step) == 2 and all("no step" in p for p in no_step)
    unlinked = [ok[0], ok[1]._replace(batch=None), ok[2], ok[5]]
    assert any("batch None" in p
               for p in trace_report.check_complete(unlinked, 0))


def test_trace_report_main_gate(tmp_path, capsys):
    tr = Tracer()
    tr.span("request", "admit", t0=0.0, t1=0.1, rid=0, value=1)
    tr.span("request", "queue", t0=0.1, t1=0.2, rid=0, batch=0)
    tr.span("batch", "hold", t0=0.1, t1=0.2, batch=0)
    tr.span("batch", "step", t0=0.2, t1=0.3, batch=0)
    tr.span("request", "complete", t0=0.1, t1=0.4, rid=0, batch=0)
    good = tmp_path / "good.jsonl"
    write_spans_jsonl(good, tr)
    assert trace_report.main([str(good), "--assert-complete"]) == 0
    out = capsys.readouterr().out
    assert re.search(r"rid=0 .* batch=0 hold=100\.000ms .* "
                     r"step=100\.000ms", out), out
    tr2 = Tracer()
    tr2.span("request", "admit", t0=0.0, t1=0.1, rid=0, value=1)
    bad = tmp_path / "bad.jsonl"
    write_spans_jsonl(bad, tr2)
    assert trace_report.main([str(bad), "--assert-complete"]) == 1
    assert trace_report.main([str(bad)]) == 0         # report-only never gates


# ---------------------------------------------------------------------------
# event session: window spans over a scripted client
# ---------------------------------------------------------------------------

class FakeHandle:
    def __init__(self, labels):
        self.labels = labels

    def result(self, timeout=None):
        return self.labels


class FakeClient:
    """Scripted ServeClient: labels synchronously, sheds on script."""

    def __init__(self, full_at=()):
        self.full_at = set(full_at)
        self.attempts = 0

    def submit(self, images, *, rid=None, on_image=None):
        k = self.attempts
        self.attempts += 1
        if k in self.full_at:
            raise QueueFull("scripted")
        if on_image is not None:
            for i in range(len(images)):
                on_image(k, i, k)
        return FakeHandle([k] * len(images))


def events_at(*t_us):
    t = np.asarray(t_us, np.int64)
    n = len(t)
    return EventStream(8, 8, np.full(n, 1), np.full(n, 1), t, np.full(n, 1))


def test_session_window_spans():
    tr = Tracer()
    s = EventStreamSession(FakeClient(full_at={1}), window_us=1_000,
                           height=8, width=8, tracer=tr)
    s.feed(events_at(100, 900, 1_100, 1_900, 2_100))  # closes windows 0, 1
    s.flush()                                         # closes window 2
    spans = [(sp.name, sp.rid) for sp in tr.spans()
             if sp.category == "window"]
    # window 0 served (encode + synchronous complete), window 1 shed,
    # window 2 served; rid is the WINDOW index
    assert spans == [("encode", 0), ("complete", 0),
                     ("encode", 1), ("shed", 1),
                     ("encode", 2), ("complete", 2)]
    enc0 = next(sp for sp in tr.spans() if sp.name == "encode")
    assert enc0.value == 2 and enc0.occupancy is not None  # 2 events in w0
    assert s.windows_shed == 1


def test_session_untraced_stays_silent():
    s = EventStreamSession(FakeClient(), window_us=1_000, height=8, width=8)
    assert s.tracer is NULL_TRACER
    s.feed(events_at(100, 1_100))
    s.flush()
    assert len(s.tracer) == 0 and s.windows[0]["label"] is not None
