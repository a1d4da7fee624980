"""Spikformer image-classification serving driver — a thin CLI over the
compile/serve split: ``repro.infer.compile`` builds the multi-bucket
``CompiledModel``, then either ``MicroBatchEngine`` drains a closed-loop
request queue through it (default) or — with ``--async`` —
``repro.serve.AsyncServeRuntime`` serves an OPEN-LOOP Poisson arrival
process at ``--rps`` for ``--duration`` seconds under an ``--slo-ms``
latency target. This is the paper's real-time classification serving loop:
VESTA sustains ~30 fps on Spikformer V2; the closed loop reports achieved
fps against that target, the open loop reports what a drain cannot —
goodput, p99 latency and SLO attainment under live load.

  PYTHONPATH=src python -m repro.launch.serve_spikformer --reduce \
      --requests 12 --buckets 2,8 --backend packed

  PYTHONPATH=src python -m repro.launch.serve_spikformer --reduce \
      --async --rps 60 --duration 3 --slo-ms 100

  PYTHONPATH=src python -m repro.launch.serve_spikformer --reduce --smoke
      # CI gate: a handful of requests, asserts all complete with correct
      # shapes and labels in range; with --async, asserts the open loop
      # sustains >= 30 fps with zero dropped-but-accepted requests
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from ..core.spikformer import SpikformerConfig, init as spik_init
from ..infer import ExecutionPlan, MicroBatchEngine, PAPER_FPS, compile
from ..infer.engine import Request
from ..obs import Tracer, write_chrome_trace, write_spans_jsonl
from ..serve import (AsyncServeRuntime, ServeFleet, ServePolicy,
                     image_maker, poisson_trace, run_open_loop)
from .compile_cache import enable_compile_cache

# Pre-split names, kept importable: ImageRequest is the engine Request;
# SpikformerEngine is a construct-from-params convenience over the split.
ImageRequest = Request


def make_tracer(args):
    """One ``Tracer`` when ``--trace-out`` asks for a trace, else None —
    clients built with ``tracer=None`` run the NULL_TRACER fast path."""
    return Tracer() if args.trace_out else None


def dump_trace(tracer, path, *, meta=None):
    """Write the span JSONL plus the Perfetto sibling (``.perfetto.json``
    next to the JSONL); prints where they landed and how lossy the ring
    was. Returns the summary row."""
    n = write_spans_jsonl(path, tracer, meta=meta)
    perfetto = (path[:-len(".jsonl")] + ".perfetto.json"
                if path.endswith(".jsonl") else path + ".perfetto.json")
    write_chrome_trace(perfetto, tracer)
    row = {"trace_out": path, "perfetto": perfetto, "spans": n,
           "dropped_spans": tracer.dropped_spans}
    print(json.dumps(row))
    return row


class SpikformerEngine(MicroBatchEngine):
    """Micro-batching classifier built straight from training params —
    the pre-split constructor shape, now compile() + MicroBatchEngine."""

    def __init__(self, params, cfg: SpikformerConfig, *, batch_size: int = 8,
                 buckets=None, backend: str = "packed",
                 weight_dtype: str | None = None):
        plan = ExecutionPlan(backend=backend, weight_dtype=weight_dtype,
                             batch_buckets=buckets or (batch_size,))
        super().__init__(compile(params, cfg, plan))

    @property
    def session(self):
        """The compiled model (named for the pre-split attribute)."""
        return self.model


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reduce", action="store_true",
                    help="reduced CPU config (32x32, dim 64, depth 2)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--images-per-request", type=int, default=3)
    ap.add_argument("--buckets", default=None,
                    help="comma-separated static batch buckets (default "
                         "2,8); the engine picks the cheapest per step")
    ap.add_argument("--backend", default=None,
                    choices=["packed", "reference"],
                    help="default packed")
    ap.add_argument("--weight-dtype", default=None,
                    choices=["float32", "int8"])
    ap.add_argument("--plan", default=None,
                    help="load a committed ExecutionPlan JSON (backend/"
                         "buckets flags still override)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="serve an open-loop Poisson arrival process through "
                         "AsyncServeRuntime instead of the closed-loop drain")
    ap.add_argument("--rps", type=float, default=60.0,
                    help="async: offered arrival rate, requests/second")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="async: seconds of open-loop arrivals")
    ap.add_argument("--slo-ms", type=float, default=100.0,
                    help="async: per-request latency target")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="async: continuous-batching window")
    ap.add_argument("--queue-depth", type=int, default=512,
                    help="async: admission bound, queued images")
    ap.add_argument("--replicas", type=int, default=1,
                    help="async: serve through a ServeFleet of this many "
                         "replicas (per-device on multi-device hosts, "
                         "thread-backed otherwise); 1 = single runtime")
    ap.add_argument("--pace-fps", type=float, default=None,
                    help="fleet: model each replica as a fixed-rate core "
                         "at this many images/second (labels stay real; "
                         "scaling curves measure placement, not host "
                         "cores)")
    ap.add_argument("--events", action="store_true",
                    help="serve the event-stream workload: replay a DVS "
                         "trace (--trace, or a synthesized one) through "
                         "the serving stack as per-window count frames")
    ap.add_argument("--trace", default=None,
                    help="events: path to a recorded JSONL event trace "
                         "(repro.events.trace format); the model is "
                         "compiled to the trace header's sensor shape")
    ap.add_argument("--trace-out", default=None,
                    help="write the request-lifecycle trace here as span "
                         "JSONL (a Perfetto-loadable .perfetto.json lands "
                         "next to it); works in every mode")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: few requests, assert completion/shapes")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        args.requests = min(args.requests, 5)
        args.images_per_request = min(args.images_per_request, 2)
        args.rps = min(args.rps, 60.0)
        args.duration = min(args.duration, 1.5)

    if args.events:
        return main_events(args)

    cfg = SpikformerConfig()
    if args.reduce:
        cfg = cfg.scaled()
    params = spik_init(jax.random.PRNGKey(args.seed), cfg)

    # a committed --plan replays as-is; explicit flags (only) override it
    plan = (ExecutionPlan.from_json(open(args.plan).read()) if args.plan
            else ExecutionPlan(batch_buckets=(2, 8)))
    over = {}
    if args.backend is not None:
        over["backend"] = args.backend
    if args.buckets is not None:
        over["batch_buckets"] = tuple(int(b) for b in args.buckets.split(","))
    if args.weight_dtype is not None:
        over["weight_dtype"] = args.weight_dtype
    if over:
        plan = dataclasses.replace(plan, **over)
    model = compile(params, cfg, plan)
    compile_s = model.warmup()

    if args.use_async:
        return main_async(model, args, compile_s)

    tracer = make_tracer(args)
    eng = MicroBatchEngine(model, tracer=tracer)

    rng = np.random.default_rng(args.seed + 1)
    for i in range(args.requests):
        imgs = rng.integers(0, 256, (args.images_per_request, cfg.img_size,
                                     cfg.img_size, cfg.in_channels),
                            dtype=np.uint8)
        eng.submit(ImageRequest(rid=i, images=imgs))

    done = eng.run()
    stats = eng.stats()
    if tracer is not None:
        dump_trace(tracer, args.trace_out, meta={"mode": "sync"})
    summary = {
        "backend": model.backend.name,
        "weight_dtype": model.weight_dtype,
        "compile_s": round(compile_s, 3),
        **stats,
    }
    print(json.dumps(summary))

    if args.smoke:
        # the CI contract: every request completed, every label well-formed
        assert len(done) == args.requests, (len(done), args.requests)
        for req in done:
            assert len(req.labels) == len(req.images)
            assert all(isinstance(lab, int)
                       and 0 <= lab < cfg.num_classes for lab in req.labels)
        assert stats["images"] == args.requests * args.images_per_request
        print(json.dumps({"smoke": "ok", "requests": len(done),
                          "pad_waste": stats["pad_waste"]}))
    return summary


def main_async(model, args, compile_s: float):
    """Open-loop serving: Poisson arrivals at --rps for --duration seconds
    through ``AsyncServeRuntime`` (or a ``ServeFleet`` of ``--replicas``),
    measured by ``repro.serve.loadgen``."""
    policy = ServePolicy(max_wait_ms=args.max_wait_ms, slo_ms=args.slo_ms,
                         max_queue_images=args.queue_depth)
    trace = poisson_trace(rps=args.rps, duration_s=args.duration,
                          seed=args.seed + 1,
                          images_per_request=(1, args.images_per_request))
    tracer = make_tracer(args)
    if args.replicas > 1:
        client = ServeFleet(model, replicas=args.replicas, policy=policy,
                            pace_fps=args.pace_fps, tracer=tracer)
    else:
        client = AsyncServeRuntime(model, policy=policy, tracer=tracer)
    with client:
        metrics = run_open_loop(
            client, trace, image_maker(model.input_shape()[1:],
                                       seed=args.seed + 2),
            slo_ms=args.slo_ms)
    if tracer is not None:
        dump_trace(tracer, args.trace_out,
                   meta={"mode": "fleet" if args.replicas > 1 else "async",
                         "replicas": args.replicas})
    summary = {
        "backend": model.backend.name,
        "weight_dtype": model.weight_dtype,
        "compile_s": round(compile_s, 3),
        "mode": ("fleet_open_loop" if args.replicas > 1
                 else "async_open_loop"),
        "replicas": args.replicas,
        "paper_fps": PAPER_FPS,
        **metrics,
        "runtime": client.stats(),
    }
    print(json.dumps(summary))

    if args.smoke:
        # the CI contract for the open loop: an accepted request is a
        # promise (zero dropped), labels are well-formed, and the paper's
        # real-time rate is sustained at the smoke arrival rate
        assert metrics["requests_dropped"] == 0, metrics
        assert metrics["requests_offered"] == len(trace)
        # smoke offers at most rps*duration (~90) requests against a
        # 512-image admission bound: a rejection here is a real bug
        assert metrics["requests_rejected"] == 0, metrics
        n_classes = model.cfg.num_classes
        for req in client.done:
            assert len(req.labels) == len(req.images)
            assert all(isinstance(lab, int) and 0 <= lab < n_classes
                       for lab in req.labels)
        assert metrics["completed_fps"] >= PAPER_FPS, metrics
        if args.replicas > 1:
            # fleet floor: N replicas sustain N x the single-replica
            # real-time rate, and the fleet kept every promise
            assert metrics["goodput_fps"] >= args.replicas * PAPER_FPS, \
                metrics
            health = client.health()
            assert all(r["failures"] == 0 for r in health["replicas"]), \
                health
        print(json.dumps({"smoke": "ok", "mode": summary["mode"],
                          "replicas": args.replicas,
                          "completed_fps": metrics["completed_fps"],
                          "goodput_fps": metrics["goodput_fps"],
                          "slo_attainment": metrics["slo_attainment"]}))
    return summary


def synth_event_trace(*, seed: int, height: int = 16, width: int = 16):
    """A deterministic in-memory stand-in when no --trace is given: a
    moving edge plus flicker bursts, windowed exactly as
    ``scripts/record_event_trace.py`` commits its fixture."""
    from ..events import (EventTrace, TraceArrival, flicker_burst_events,
                          merge_streams, moving_edge_events)
    window_us = 20_000
    duration_us = 800_000
    stream = merge_streams(
        moving_edge_events(height=height, width=width,
                           duration_us=duration_us // 4, seed=seed),
        flicker_burst_events(height=height, width=width,
                             duration_us=duration_us, seed=seed + 1,
                             bursts=3))
    arrivals = []
    for w in range(duration_us // window_us):
        ev = stream.slice_time(w * window_us, (w + 1) * window_us)
        if len(ev):
            arrivals.append(TraceArrival(
                t_s=(w + 1) * window_us / 1e6, window=w,
                events=ev.shift_time(-w * window_us)))
    return EventTrace(height=height, width=width, window_us=window_us,
                      bins=8, payload="events", arrivals=tuple(arrivals))


def main_events(args):
    """Event-stream serving: replay a DVS trace's windows (count frames at
    the recorded arrival times) through the runtime or fleet; in --smoke,
    additionally replay it TWICE and assert the labels are bit-identical
    — the trace-replay determinism contract, as a CI gate."""
    from ..events import load_trace, replay_trace
    trace = (load_trace(args.trace) if args.trace
             else synth_event_trace(seed=args.seed))
    if trace.height != trace.width:
        raise SystemExit(
            f"trace sensor is {trace.height}x{trace.width}; the Spikformer "
            f"front end serves square inputs — re-record or crop")
    cfg = dataclasses.replace(
        SpikformerConfig().scaled(img_size=trace.height, dim=32, depth=1),
        in_channels=trace.channels)
    params = spik_init(jax.random.PRNGKey(args.seed), cfg)
    plan = (ExecutionPlan.from_json(open(args.plan).read()) if args.plan
            else ExecutionPlan(batch_buckets=(2, 8)))
    over = {}
    if args.backend is not None:
        over["backend"] = args.backend
    if args.buckets is not None:
        over["batch_buckets"] = tuple(int(b) for b in args.buckets.split(","))
    if args.weight_dtype is not None:
        over["weight_dtype"] = args.weight_dtype
    if over:
        plan = dataclasses.replace(plan, **over)
    model = compile(params, cfg, plan)
    compile_s = model.warmup()
    policy = ServePolicy(max_wait_ms=args.max_wait_ms, slo_ms=args.slo_ms,
                         max_queue_images=args.queue_depth)

    def run_once(tracer=None):
        if args.replicas > 1:
            client = ServeFleet(model, replicas=args.replicas, policy=policy,
                                pace_fps=args.pace_fps, tracer=tracer)
        else:
            client = AsyncServeRuntime(model, policy=policy, tracer=tracer)
        with client:
            metrics = replay_trace(trace, client, slo_ms=args.slo_ms)
        metrics["runtime"] = client.stats()
        return metrics

    tracer = make_tracer(args)
    metrics = run_once(tracer)
    if tracer is not None:
        dump_trace(tracer, args.trace_out,
                   meta={"mode": "events", "replicas": args.replicas})
    summary = {
        "backend": model.backend.name,
        "weight_dtype": model.weight_dtype,
        "compile_s": round(compile_s, 3),
        "mode": "event_replay",
        "trace": args.trace or "synthetic",
        "sensor": [trace.height, trace.width, trace.channels],
        "window_us": trace.window_us,
        "replicas": args.replicas,
        **{k: v for k, v in metrics.items() if k != "labels"},
    }
    print(json.dumps(summary))

    if args.smoke:
        # the event-serving CI contract: every window served (zero drops,
        # zero shed at smoke rates), on time, and deterministically
        assert metrics["requests_dropped"] == 0, summary
        assert metrics["requests_rejected"] == 0, summary
        assert metrics["slo_attainment"] == 1.0, summary
        n_classes = cfg.num_classes
        for labs in metrics["labels"]:
            assert labs is not None and len(labs) == 1, labs
            assert 0 <= labs[0] < n_classes, labs
        replay = run_once()
        assert replay["labels_sha"] == metrics["labels_sha"], (
            "trace replay is not deterministic",
            replay["labels_sha"], metrics["labels_sha"])
        print(json.dumps({"smoke": "ok", "mode": "event_replay",
                          "windows": metrics["windows"],
                          "replicas": args.replicas,
                          "labels_sha": metrics["labels_sha"],
                          "slo_attainment": metrics["slo_attainment"],
                          "dispersion_index": metrics["dispersion_index"]}))
    return summary


if __name__ == "__main__":
    main()
