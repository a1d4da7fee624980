"""Inference throughput: packed-bit datapath vs float reference, end to end.

Times the jit-compiled fixed-batch compiled step for both
backends over a sweep of (timesteps, weight_dtype) points — by default
T in {4, 16} x {float32, int8}, so the perf trajectory captures both the
plane-group loop overhead (T=16 -> 2 uint8 groups per neuron) and the int8
scale-folded route — and emits ONE JSON record (stdout; ``--out`` appends it
to the committed ``BENCH_infer.json`` trajectory at the repo root, so
successive PRs accumulate a perf history; ``benchmarks/compare_bench.py``
gates CI against it).

Three compiled models per point keep the comparison honest:
  * packed (auto-planned)     — the byte-LUT/unpack datapath being measured;
  * reference (route=unpack)  — the plain single-dot float graph, the
    throughput *denominator* (the planner's fold-order emulation would slow
    the reference and flatter the speedup, so it is never timed as baseline);
  * reference (auto-planned)  — the packed model's bit-exact partner, used
    only for the exactness probe. A benchmark of a wrong path is worthless.

On top of the per-step sweep, a SERVING sweep drives requests through the
micro-batching engine (multi-bucket dispatch) and records achieved fps vs
the paper's 30 fps target, p50/p95 latency, and pad waste — the
engine-level numbers production cares about, in the same trajectory.

A third layer, SERVING UNDER LOAD, replays open-loop Poisson arrival
traces at two rates through ``repro.serve.AsyncServeRuntime`` and records
what a closed-loop drain cannot: goodput, p99 latency, and SLO attainment
(``serving_load`` rows; ``compare_bench.py`` guards them non-lossy keyed
by (rps, replicas)). The same trajectory carries FLEET rows: one trace
replayed through ``ServeFleet`` at 1 and 2 paced replicas
(``pace_fps``-rate emulated cores), gated on goodput scaling and
attainment — the multi-replica serving claim, measured.

The EVENT WORKLOAD layer replays the committed synthetic DVS trace
(``benchmarks/traces/dvs_synth_mini.jsonl``) through 1 and 2 replicas and
records ``serving_events`` rows: the bursty ON/OFF arrival process of an
event camera, gated zero-drop, attainment 1.0, and deterministic (same
trace twice → identical ``labels_sha``; fleet labels match single-replica
labels).

A fourth layer, the PALLAS SWEEP, runs the Pallas kernel routes (VMEM
byte-LUT gather, grouped unpack-dot) against their CPU fold-order oracles
at a tail-timestep/odd-K shape. On a CPU host the kernels execute under
the Pallas interpreter, so each row carries ``interpret: true`` and its
timings measure the interpreter, never the accelerator — the gate is
exactness plus row presence, not speed.

  PYTHONPATH=src python benchmarks/infer_bench.py [--batch-size 8] [--out [f]]
  PYTHONPATH=src python benchmarks/infer_bench.py --smoke     # tiny, CI gate
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spike import (num_plane_groups, pack_timesteps,
                              structured_spikes)
from repro.core.spikformer import SpikformerConfig, init as spik_init
from repro.infer import (ExecutionPlan, MicroBatchEngine, chunk_occupancy,
                         compile as infer_compile)
from repro.launch.compile_cache import enable_compile_cache
from repro.kernels import lut_matmul as lut
from repro.kernels import ops
from repro.kernels.lut_matmul import sparse_budget
from repro.events import TRACE_VERSION, load_trace, replay_trace
from repro.obs import Tracer
from repro.serve import (AsyncServeRuntime, ServeFleet, ServePolicy,
                         image_maker, poisson_trace, run_open_loop,
                         run_replica_sweep)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_OUT = REPO_ROOT / "BENCH_infer.json"
DEFAULT_TRACE = REPO_ROOT / "benchmarks" / "traces" / "dvs_synth_mini.jsonl"


def benchmark_model(model, *, batches: int = 4, seed: int = 0,
                    repeats: int = 3) -> dict:
    """Throughput probe: images/sec over ``batches`` full compiled batches
    of random uint8 images at the largest bucket (compile excluded via
    warmup). The window is repeated ``repeats`` times and the best
    wall-time wins — the standard throughput convention, and the only way
    to get a stable number on a noisy shared machine."""
    compile_s = model.warmup()
    imgs = jax.random.randint(jax.random.PRNGKey(seed), model.input_shape(),
                              0, 256, jnp.uint8)
    wall = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for _ in range(batches):
            jax.block_until_ready(model._fwd(model.folded, imgs))
        wall = min(wall, time.perf_counter() - t0)
    n = batches * model.batch_size
    return {
        "backend": model.backend.name,
        "weight_dtype": model.weight_dtype,
        "batch_size": model.batch_size,
        "images": n,
        "repeats": repeats,
        "compile_s": round(compile_s, 3),
        "wall_s": round(wall, 4),
        "images_per_s": round(n / wall, 2),
    }


def run_point(params, cfg, *, timesteps: int, weight_dtype: str,
              batch_size: int, batches: int, repeats: int, seed: int) -> dict:
    """One sweep point: packed vs plain float reference at (T, weight_dtype),
    with the planned-reference exactness gate."""
    cfg = dataclasses.replace(cfg, timesteps=timesteps)
    plan = ExecutionPlan(weight_dtype=weight_dtype,
                         batch_buckets=(batch_size,))
    packed = infer_compile(params, cfg, plan, backend="packed")
    ref_plain = infer_compile(params, cfg, plan, backend="reference",
                              route="unpack")
    ref_planned = infer_compile(params, cfg, plan, backend="reference")

    # correctness gate: identical logits on one probe batch (the planned
    # reference is the packed model's bit-exact partner)
    probe = jax.random.randint(jax.random.PRNGKey(seed + 1),
                               packed.input_shape(), 0, 256, jnp.uint8)
    exact = bool((np.asarray(packed.logits(probe))
                  == np.asarray(ref_planned.logits(probe))).all())

    results = {
        "packed": benchmark_model(packed, batches=batches, seed=seed + 2,
                                  repeats=repeats),
        "reference": benchmark_model(ref_plain, batches=batches,
                                     seed=seed + 2, repeats=repeats),
    }
    lut_layers = sum(1 for r in packed.plan.routes.values() if r == "lut")
    return {
        "timesteps": timesteps,
        "weight_dtype": weight_dtype,
        "plane_groups": num_plane_groups(timesteps),
        "bit_exact": exact,
        "lut_layers": lut_layers,
        "planned_layers": len(packed.plan.routes),
        "packed": results["packed"],
        "reference": results["reference"],
        "packed_speedup": round(results["packed"]["images_per_s"]
                                / results["reference"]["images_per_s"], 3),
        # storage bytes per activation element between layers:
        # float spikes carry T fp32 values, packed carries ceil(T/8) uint8
        "activation_traffic_ratio": round(
            4.0 * timesteps / num_plane_groups(timesteps), 2),
    }


def _best_time(fn, *, repeats: int) -> float:
    """Best-of-N wall seconds for one already-jitted call."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run_occupancy_sweep(*, rates=(0.1, 0.2, 0.3), m: int, k: int, n: int,
                        repeats: int = 5, seed: int = 0) -> list:
    """Firing-rate sweep: dense byte-LUT vs zero-chunk-skipping gather on
    one spiking linear, at channel-structured spike rates (~10/20/30% —
    realistic trained-Spikformer occupancy, not the ~50% of random test
    weights). The sparse budget is sized the same way a compiled plan
    would: ``sparse_budget`` over the MEASURED chunk occupancy of the
    input. Each row carries an exactness flag — a fast wrong gather is
    worthless — and ``compare_bench.py`` gates the rows non-lossy.
    """
    t = 8
    key = jax.random.PRNGKey(seed + 7)
    kw_key, *rate_keys = jax.random.split(key, len(rates) + 1)
    w = jax.random.normal(kw_key, (k, n), jnp.float32)
    rows = []
    for rate, rk in zip(rates, rate_keys):
        x = structured_spikes(rk, t=t, shape=(m, k), rate=rate)
        occ = chunk_occupancy(x, t)
        c = -(-k // 8)
        budget = sparse_budget(c, occ)
        dense = jax.jit(lambda xx: ops.spike_linear(xx, w, None, t=t,
                                                    route="lut"))
        sparse = jax.jit(lambda xx: ops.spike_linear(xx, w, None, t=t,
                                                     route="lut_sparse",
                                                     occupancy=occ))
        d_out, s_out = dense(x), sparse(x)
        exact = bool((np.asarray(d_out) == np.asarray(s_out)).all())
        dense_s = _best_time(lambda: dense(x), repeats=repeats)
        sparse_s = _best_time(lambda: sparse(x), repeats=repeats)
        rows.append({
            "firing_rate": rate,
            "chunk_occupancy": round(occ, 4),
            "chunks": c,
            "max_chunks": budget,
            "m": m, "k": k, "n": n, "timesteps": t,
            "exact": exact,
            "dense_s": round(dense_s, 6),
            "sparse_s": round(sparse_s, 6),
            "sparse_speedup": round(dense_s / sparse_s, 3),
        })
    return rows


def run_pallas_sweep(*, t: int = 9, m: int = 24, k: int = 33, n: int = 12,
                     rate: float = 0.3, repeats: int = 3,
                     seed: int = 0) -> list:
    """Pallas-route rows: the real kernels (VMEM byte-LUT gather, grouped
    unpack-dot) vs their CPU fold-order oracles on one spiking linear at a
    deliberately awkward shape — tail timesteps (t=9 -> a 1-bit second
    plane group) and an odd K (33 -> a 1-lane tail chunk).

    Every row carries ``interpret``: on a CPU host the kernels run under
    the Pallas interpreter, so ``pallas_s`` times the interpreter, NOT an
    accelerator, and must never feed a speedup gate. What ``compare_bench``
    DOES gate: each row stays bit-exact against its CPU oracle (the same
    defined reduction fold, so equality is exact, not toleranced), and the
    (route, weight_dtype) rows are non-lossy vs the committed baseline.
    The float32 unpack route is reduction-order-tolerant by contract, so
    only routes with a bit-exactness contract appear here.
    """
    rng = np.random.default_rng(seed + 13)
    spikes = jnp.asarray(rng.random((t, m, k)) < rate, jnp.float32)
    x = pack_timesteps(spikes)
    interp = not ops.on_tpu()
    weights = {
        "float32": jnp.asarray(rng.standard_normal((k, n)), jnp.float32),
        "int8": jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8),
    }
    rows = []
    for route, wd in (("lut", "float32"), ("lut", "int8"),
                      ("unpack", "int8")):
        w = weights[wd]
        table = lut.build_lut(w) if route == "lut" else None
        pal = jax.jit(lambda xx, w=w, table=table, route=route:
                      ops.spike_linear(xx, w, None, t=t, pallas=True,
                                       route=route, table=table))
        cpu = jax.jit(lambda xx, w=w, table=table, route=route:
                      ops.spike_linear(xx, w, None, t=t, pallas=False,
                                       route=route, table=table))
        p_out, c_out = pal(x), cpu(x)
        exact = bool((np.asarray(p_out) == np.asarray(c_out)).all())
        rows.append({
            "route": route, "weight_dtype": wd,
            "timesteps": t, "m": m, "k": k, "n": n,
            "interpret": interp, "exact": exact,
            "pallas_s": round(_best_time(lambda: pal(x), repeats=repeats), 6),
            "cpu_s": round(_best_time(lambda: cpu(x), repeats=repeats), 6),
        })
    return rows


def serving_models(params, cfg, *, buckets):
    """Lazy cache of warmed multi-bucket packed models keyed by
    (timesteps, weight_dtype) — the engine-level serving sweep and the
    serving-under-load sweep share one compile per point instead of each
    paying their own."""
    cache = {}

    def get(timesteps: int, weight_dtype: str):
        key = (timesteps, weight_dtype)
        if key not in cache:
            c = dataclasses.replace(cfg, timesteps=timesteps)
            model = infer_compile(params, c,
                                  ExecutionPlan(backend="packed",
                                                weight_dtype=weight_dtype,
                                                batch_buckets=tuple(buckets)))
            cache[key] = (model, model.warmup())
        return cache[key]

    return get


def run_serving(model, compile_s: float, *, timesteps: int,
                weight_dtype: str, requests: int, seed: int) -> dict:
    """Engine-level serving point: Poisson-ish mixed-size requests through
    the micro-batching engine over a multi-bucket compiled model. Reports
    achieved fps vs the paper's 30 fps target, p50/p95 latency, and pad
    waste (the multi-bucket-dispatch metric)."""
    eng = MicroBatchEngine(model)
    rng = np.random.default_rng(seed + 3)
    shape = model.input_shape()[1:]
    for rid in range(requests):
        n = int(rng.integers(1, 4))          # 1-3 images per request
        eng.submit(rng.integers(0, 256, (n, *shape), dtype=np.uint8))
    eng.run()
    stats = eng.stats()
    return {
        "timesteps": timesteps,
        "weight_dtype": weight_dtype,
        "compile_s": round(compile_s, 3),
        **stats,
    }


def run_serving_load(model, *, timesteps: int, weight_dtype: str,
                     rates, duration_s: float, slo_ms: float,
                     seed: int) -> list:
    """Serving-under-load points: the SAME compiled model serves an
    open-loop Poisson trace at each arrival rate through the async runtime.
    Reports goodput, p99 latency, and SLO attainment — arrival-bounded
    numbers the closed-loop serving sweep cannot produce."""
    rows = []
    for rps in rates:
        policy = ServePolicy(max_wait_ms=10.0, slo_ms=slo_ms,
                             max_queue_images=512)
        trace = poisson_trace(rps=rps, duration_s=duration_s,
                              seed=seed + 5, images_per_request=(1, 3))
        with AsyncServeRuntime(model, policy=policy) as rt:
            metrics = run_open_loop(
                rt, trace, image_maker(model.input_shape()[1:],
                                       seed=seed + 6),
                slo_ms=slo_ms)
        stats = rt.stats()
        rows.append({
            "timesteps": timesteps,
            "weight_dtype": weight_dtype,
            "rps": rps,
            "duration_s": duration_s,
            **metrics,
            "pad_waste": stats["pad_waste"],
            "batches": stats["batches"],
        })
    return rows


def run_serving_overhead(model, *, timesteps: int, weight_dtype: str,
                         rps: float, duration_s: float, slo_ms: float,
                         seed: int) -> list:
    """Tracer-overhead row: the SAME open-loop Poisson trace served twice
    through ``AsyncServeRuntime`` — tracer off, then a live ``Tracer``
    recording every lifecycle span — and the goodput ratio between the
    runs. The arrival rate is deliberately sub-capacity, so goodput is
    arrival-bound on both runs and the ratio isolates the tracer's hot-path
    cost (ring append + counter samples) instead of compute jitter:
    a tracer that costs real throughput would push the ratio below
    ``compare_bench.py``'s 0.97 gate. The row also carries the span count
    and ``dropped_spans`` (must be 0 — a lossy ring under bench load means
    the default capacity is undersized)."""
    policy = ServePolicy(max_wait_ms=10.0, slo_ms=slo_ms,
                         max_queue_images=512)
    trace = poisson_trace(rps=rps, duration_s=duration_s, seed=seed + 9,
                          images_per_request=(1, 3))

    def once(tracer):
        with AsyncServeRuntime(model, policy=policy, tracer=tracer) as rt:
            return run_open_loop(
                rt, trace, image_maker(model.input_shape()[1:],
                                       seed=seed + 10),
                slo_ms=slo_ms)

    off = once(None)
    tracer = Tracer()
    on = once(tracer)
    return [{
        "timesteps": timesteps,
        "weight_dtype": weight_dtype,
        "rps": rps,
        "duration_s": duration_s,
        "requests_offered": off["requests_offered"],
        "goodput_fps_off": off["goodput_fps"],
        "goodput_fps_on": on["goodput_fps"],
        "overhead_ratio": (round(on["goodput_fps"] / off["goodput_fps"], 4)
                           if off["goodput_fps"] else None),
        "spans": len(tracer),
        "dropped_spans": tracer.dropped_spans,
    }]


def run_fleet_load(model, *, timesteps: int, weight_dtype: str,
                   rps: float, duration_s: float, slo_ms: float,
                   replica_counts, pace_fps: float, seed: int) -> list:
    """Fleet scaling points: ONE open-loop Poisson trace replayed through
    ``ServeFleet`` at each replica count, same payload bytes per run.

    Each replica is paced as a fixed-rate core at ``pace_fps`` images/s
    (the paper's deployment unit — one VESTA core sustains ~30 fps), so a
    single replica saturates below the offered rate and the sweep measures
    what the fleet adds: placement, admission, and goodput scaling —
    independent of how many host cores the bench machine has. Compute
    still runs (labels are real); ``pace_fps`` is recorded on every row.
    The admission bound is deliberately tight (2 max buckets) so overload
    resolves as rejections with attainment 1.0, never as dropped promises.
    """
    policy = ServePolicy(max_wait_ms=10.0, slo_ms=slo_ms,
                         max_queue_images=2 * max(model.buckets))
    trace = poisson_trace(rps=rps, duration_s=duration_s, seed=seed + 5,
                          images_per_request=(1, 3))
    rows = run_replica_sweep(
        lambda n: ServeFleet(model, replicas=n, policy=policy,
                             pace_fps=pace_fps).start(),
        trace,
        lambda: image_maker(model.input_shape()[1:], seed=seed + 6),
        replica_counts=replica_counts, slo_ms=slo_ms)
    return [{
        "timesteps": timesteps,
        "weight_dtype": weight_dtype,
        "rps": rps,
        "duration_s": duration_s,
        "pace_fps": pace_fps,
        **row,
    } for row in rows]


def run_serving_events(*, trace_path=None, slo_ms: float = 400.0,
                       seed: int = 0, replica_counts=(1, 2)) -> list:
    """Event-workload rows: the committed DVS mini-trace replayed through
    the serving stack at each replica count — the bursty ON/OFF arrival
    process a real event camera produces, not a Poisson approximation.

    Determinism is part of the measurement, not a side note. The
    single-replica point replays the SAME trace twice and records
    ``deterministic`` (within-run ``labels_sha`` equality); every
    multi-replica point records ``labels_match_single`` (its labels vs
    the single-replica replay's). Both flags plus zero drops / zero
    rejections / attainment 1.0 are gated by ``compare_bench.py`` — the
    trace is sized well under one replica's capacity on purpose, so any
    shed request is a serving bug, not an overload artifact."""
    path = pathlib.Path(trace_path or DEFAULT_TRACE)
    trace = load_trace(path)
    cfg = dataclasses.replace(
        SpikformerConfig().scaled(img_size=trace.height, dim=32, depth=1),
        in_channels=trace.channels)
    params = spik_init(jax.random.PRNGKey(seed), cfg)
    model = infer_compile(params, cfg,
                          ExecutionPlan(backend="packed",
                                        batch_buckets=(2, 8)))
    compile_s = model.warmup()
    policy = ServePolicy(max_wait_ms=10.0, slo_ms=slo_ms,
                         max_queue_images=64)

    def replay(n: int) -> dict:
        client = (ServeFleet(model, replicas=n, policy=policy).start()
                  if n > 1 else
                  AsyncServeRuntime(model, policy=policy).start())
        try:
            m = replay_trace(trace, client, slo_ms=slo_ms)
            m["queue_depth_peak"] = client.stats()["queue_depth_peak"]
        finally:
            client.close()
        return m

    rows, single_sha = [], None
    for n in replica_counts:
        m = replay(n)
        row = {
            "trace": path.name,
            "trace_version": TRACE_VERSION,
            "replicas": int(n),
            "windows": m["windows"],
            "trace_duration_s": m["trace_duration_s"],
            "compile_s": round(compile_s, 3),
            "slo_ms": slo_ms,
            "offered_rps": m["offered_rps"],
            "requests_offered": m["requests_offered"],
            "requests_accepted": m["requests_accepted"],
            "requests_rejected": m["requests_rejected"],
            "requests_dropped": m["requests_dropped"],
            "goodput_fps": m["goodput_fps"],
            "latency_p99_s": m["latency_p99_s"],
            "slo_attainment": m["slo_attainment"],
            "dispersion_index": m["dispersion_index"],
            "peak_to_mean_rate": m["peak_to_mean_rate"],
            "queue_depth_peak": m["queue_depth_peak"],
            "labels_sha": m["labels_sha"],
        }
        if n == min(replica_counts):
            again = replay(n)
            row["deterministic"] = again["labels_sha"] == m["labels_sha"]
            single_sha = m["labels_sha"]
        elif single_sha is not None:
            row["labels_match_single"] = m["labels_sha"] == single_sha
        rows.append(row)
    return rows


def run(*, batch_size: int = 8, batches: int = 4, repeats: int = 3,
        seed: int = 0, img_size: int = 32, dim: int = 64, depth: int = 2,
        mode: str = "full",
        sweep=((4, "float32"), (4, "int8"), (16, "float32"), (16, "int8")),
        serving_sweep=((4, "float32"), (16, "int8")),
        serving_requests: int = 24,
        load_point=(4, "float32"),
        load_rates=(64.0, 256.0),
        load_duration_s: float = 2.0,
        load_slo_ms: float = 100.0,
        fleet_replicas=(1, 2),
        fleet_rps: float = 40.0,
        fleet_pace_fps: float = 40.0,
        fleet_slo_ms: float = 1000.0,
        overhead_rps: float = 40.0,
        overhead_duration_s: float = 1.5,
        events_trace=None,
        events_replicas=(1, 2),
        events_slo_ms: float = 400.0,
        occupancy_rates=(0.1, 0.2, 0.3),
        occupancy_shape=(512, 256, 256),
        occupancy_repeats: int = 5,
        occupancy_only: bool = False) -> dict:
    om, ok, on = occupancy_shape
    occupancy_sweep = run_occupancy_sweep(
        rates=occupancy_rates, m=om, k=ok, n=on,
        repeats=occupancy_repeats, seed=seed)
    occ_exact = all(r["exact"] for r in occupancy_sweep)
    pallas_sweep = run_pallas_sweep(repeats=occupancy_repeats, seed=seed)
    pallas_exact = all(r["exact"] for r in pallas_sweep)

    if occupancy_only:
        # the fast-CI shape of the record: just the kernel-level sparsity
        # and pallas-route rows with their exactness gates, no model
        # compiles
        return {
            "bench": "infer_spikformer",
            "mode": mode,
            "backend_platform": jax.default_backend(),
            "machine": platform.machine(),
            "config": {"occupancy_shape": list(occupancy_shape),
                       "occupancy_rates": list(occupancy_rates)},
            "bit_exact": occ_exact and pallas_exact,
            "occupancy_sweep": occupancy_sweep,
            "pallas_sweep": pallas_sweep,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }

    cfg = SpikformerConfig().scaled(img_size=img_size, dim=dim, depth=depth)
    params = spik_init(jax.random.PRNGKey(seed), cfg)

    points = [run_point(params, cfg, timesteps=t, weight_dtype=wd,
                        batch_size=batch_size, batches=batches,
                        repeats=repeats, seed=seed)
              for t, wd in sweep]
    buckets = (max(1, batch_size // 4), batch_size)
    get_model = serving_models(params, cfg, buckets=buckets)
    serving = [run_serving(*get_model(t, wd), timesteps=t, weight_dtype=wd,
                           requests=serving_requests, seed=seed)
               for t, wd in serving_sweep]
    serving_load = run_serving_load(
        get_model(*load_point)[0],
        timesteps=load_point[0], weight_dtype=load_point[1],
        rates=load_rates, duration_s=load_duration_s,
        slo_ms=load_slo_ms, seed=seed)
    # fleet rows live in the same serving_load trajectory, keyed by their
    # "replicas" field (runtime rows carry none)
    serving_load += run_fleet_load(
        get_model(*load_point)[0],
        timesteps=load_point[0], weight_dtype=load_point[1],
        rps=fleet_rps, duration_s=max(load_duration_s, 2.0),
        slo_ms=fleet_slo_ms, replica_counts=fleet_replicas,
        pace_fps=fleet_pace_fps, seed=seed)
    serving_overhead = run_serving_overhead(
        get_model(*load_point)[0],
        timesteps=load_point[0], weight_dtype=load_point[1],
        rps=overhead_rps, duration_s=overhead_duration_s,
        slo_ms=load_slo_ms, seed=seed)
    # the event workload compiles its own DVS-shaped model (2 input
    # channels, sensor-sized), so it does not share the serving cache
    serving_events = run_serving_events(
        trace_path=events_trace, slo_ms=events_slo_ms,
        seed=seed, replica_counts=events_replicas)

    # PR-1-compatible trajectory fields come from the (4, float32) point
    # when the sweep carries one, else the first point
    base = next((p for p in points
                 if p["timesteps"] == 4 and p["weight_dtype"] == "float32"),
                points[0])
    record = {
        "bench": "infer_spikformer",
        "mode": mode,
        "backend_platform": jax.default_backend(),
        "machine": platform.machine(),
        "config": {"img_size": cfg.img_size, "dim": cfg.dim,
                   "depth": cfg.depth, "heads": cfg.heads,
                   "timesteps": base["timesteps"], "batch_size": batch_size,
                   "batches": batches,
                   "occupancy_shape": list(occupancy_shape),
                   "occupancy_rates": list(occupancy_rates)},
        "bit_exact": (all(p["bit_exact"] for p in points)
                      and occ_exact and pallas_exact),
        "packed": base["packed"],
        "reference": base["reference"],
        "packed_speedup": base["packed_speedup"],
        "activation_traffic_ratio": base["activation_traffic_ratio"],
        "sweep": points,
        "occupancy_sweep": occupancy_sweep,
        "pallas_sweep": pallas_sweep,
        "serving": serving,
        "serving_load": serving_load,
        "serving_overhead": serving_overhead,
        "serving_events": serving_events,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    return record


def append_trajectory(record: dict, path) -> None:
    """Append one record to the JSON-array trajectory file (created if
    missing). Each PR's full run adds one point; CI smoke runs compare
    against the latest committed point of the same mode."""
    path = pathlib.Path(path)
    history = []
    if path.exists():
        text = path.read_text()
        try:
            history = json.loads(text)
        except json.JSONDecodeError:
            # pre-PR-3 --out wrote one JSON object per line; absorb those
            # rather than crashing after a multi-minute sweep
            history = [json.loads(line) for line in text.splitlines() if line]
        if not isinstance(history, list):
            history = [history]
    history.append(record)
    path.write_text(json.dumps(history, indent=1) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    # None = "not passed": lets --smoke shrink only unspecified values while
    # an explicit flag always wins
    ap.add_argument("--batch-size", type=int, default=None, help="default 8")
    ap.add_argument("--batches", type=int, default=None, help="default 4")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timing windows per session; best wins")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config — CI gate that the sweep runs and "
                         "stays bit-exact, plus a coarse speedup ratio")
    ap.add_argument("--occupancy-only", action="store_true",
                    help="run ONLY the firing-rate sweep (dense vs "
                         "zero-chunk-skipping LUT) — the fast-CI sparsity "
                         "gate; no model compiles")
    ap.add_argument("--out", nargs="?", const=str(DEFAULT_OUT), default=None,
                    help="append the record to this JSON trajectory file "
                         f"(bare --out means {DEFAULT_OUT.name} at the "
                         "repo root)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # smoke still times 4-batch windows: a 1-batch window measures a single
    # dispatch and its speedup ratio is pure noise, useless even with a
    # loose comparison tolerance
    small = (2, 4) if args.smoke else (8, 4)
    mode = "smoke" if args.smoke else "full"
    if args.occupancy_only:
        mode = "occupancy_smoke" if args.smoke else "occupancy"
    kw = dict(batch_size=small[0] if args.batch_size is None
              else args.batch_size,
              batches=small[1] if args.batches is None else args.batches,
              repeats=args.repeats, seed=args.seed, mode=mode,
              occupancy_only=args.occupancy_only)
    if args.smoke:
        kw.update(img_size=16, dim=32, depth=1, serving_requests=6,
                  serving_sweep=((4, "float32"),),
                  # still two arrival rates: the acceptance contract is
                  # serving-under-load rows at >= 2 rates, smoke included
                  load_rates=(40.0, 120.0), load_duration_s=0.75,
                  load_slo_ms=150.0, overhead_duration_s=1.0,
                  # smaller single-layer shape, but the SAME 10/20/30%
                  # rates — the sparse-beats-dense gate holds in smoke too
                  occupancy_shape=(256, 256, 128), occupancy_repeats=3)

    record = run(**kw)
    print(json.dumps(record))
    if args.out:
        append_trajectory(record, args.out)
    if not record["bit_exact"]:
        raise SystemExit("packed/reference logits diverged — see record")
    return record


if __name__ == "__main__":
    main()
