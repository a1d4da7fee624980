#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload spikformer_t4.bulk --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix,
each a data file under ``bench/``. The run:

1. enables JAX's persistent compilation cache at its fixed place in the
   checkout (``repro.launch.compile_cache``);
2. fails, printing no result, unless JAX finds a TPU with enough chips;
3. makes the weights on the device from ``--seed``, compiles the packed
   model at the mix's buckets through ``repro.infer.compile`` and warms
   those buckets;
4. drives the mix through ``repro.serve.AsyncServeRuntime`` and measures
   ``--seconds``; with ``--trace 1`` it also records a profiler trace of a
   few seconds in the middle of the window, and the runtime's spans;
5. frees the program and compares logits of served images, a sample drawn
   from the seed, with the plain reference (``bench/check.py``);
6. prints its numbers: information on standard error, the numbers compared
   beside their limits as the last lines there, and the result as one JSON
   object on the last line of standard output. ``--trace 0`` reports the
   cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import json
import os
import pathlib
import statistics
import sys
import tempfile
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, drive, spec, trace_reduce, work  # noqa: E402
from bench import traffic as gen  # noqa: E402

PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())
TRACE_AT = 0.35          # the trace starts this far into the window
TRACE_S = 5.0            # and lasts at most this long


def _process_start() -> float:
    """When this process started, on ``time.perf_counter``'s clock."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()


def info(**fields) -> None:
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    images: object          # the host batch, pad rows included
    logits: object          # the device result


class Capture:
    """The compiled model as the runtime sees it, with ``step`` waiting for
    its result (the runtime's worker waits for it next in any case) and
    keeping every batch, its logits and its host-clock span, for the
    comparison after the window and to put the trace on the host clock."""

    def __init__(self, model):
        self._model = model
        self.steps: list[Step] = []

    def __getattr__(self, name):
        return getattr(self._model, name)

    def step(self, images):
        t0 = time.perf_counter()
        out = self._model.step(images).block_until_ready()
        self.steps.append(Step(t0, time.perf_counter(), images, out))
        return out


class CompileCounter:
    """Backend compiles, with the host time each ended."""

    def __init__(self):
        import jax.monitoring
        from jax._src import dispatch
        self.times: list[float] = []
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._event:
            self.times.append(time.perf_counter())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t <= t1 for t in self.times)

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcWatch:
    """Python's cyclic collections, with when each started and how long
    it held the interpreter (every thread waits for it)."""

    def __init__(self):
        self.events: list[tuple[float, float, int]] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        else:
            self.events.append((self._t, now - self._t, info["generation"]))

    def between(self, t0, t1) -> dict:
        ev = [(d, g) for t, d, g in self.events if t0 <= t <= t1]
        return {"count": len(ev), "gen2": sum(g == 2 for _, g in ev),
                "s": sum(d for d, _ in ev),
                "max_s": max((d for d, _ in ev), default=0.0)}

    def close(self) -> None:
        gc.callbacks.remove(self._on)


@dataclasses.dataclass
class RunView:
    """What a metric's reader (``bench/metrics/<name>.py``) reads."""
    cell: spec.Cell
    window: drive.Window
    closed: bool
    img_per_s: float
    setup_s: float
    stats0: dict
    stats1: dict
    spans: list
    trace: trace_reduce.Reduced | None
    peaks: dict | None
    layers: object          # batch -> [work.Layer]

    def least_time_s(self, layers):
        return work.least_time_s(
            layers, ops_per_s=self.peaks[self.cell.config["peak"]],
            bytes_per_s=self.peaks["hbm_bytes_per_s"])


def _clock_mark():
    """A tiny program that marks the host clock on the trace's clock
    (``trace_reduce.clock_offset``); returns ``mark() -> host end``."""
    import jax
    import jax.numpy as jnp

    def bench_clock_mark(x):
        return x + 1

    fn = jax.jit(bench_clock_mark)
    x = jnp.zeros((8, 128), jnp.float32)
    fn(x).block_until_ready()                     # compiled in set-up

    def mark() -> float:
        fn(x).block_until_ready()
        return time.perf_counter()

    return mark


def _profile(t_start: float, seconds: float, trace_dir: str, mark,
             span: dict):
    """Record the device from ``t_start`` for ``seconds``, with the host
    tracer and the Python tracer off (``bench/trace_reduce.py`` says why).
    Fills ``span`` with the recorded window and the clock marks, both on
    the host clock."""
    import jax
    delay = t_start - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        span["marks"] = [mark()]
        lo = time.perf_counter()
        time.sleep(seconds)
        span["window"] = (lo, time.perf_counter())
        span["marks"].append(mark())
    finally:
        jax.profiler.stop_trace()


def _reduce_trace(trace_dir: str, steps, spans, span) -> trace_reduce.Reduced:
    """The trace, with the step calls and the runtime's spans on its clock
    as the host's side."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    pd = ProfileData.from_file(paths[0])
    off = trace_reduce.clock_offset(pd, span["marks"])
    lo, hi = span["window"]

    def ns(t):
        return t * 1e9 + off

    host = [(ns(s.t0), ns(s.t1), "CompiledModel.step") for s in steps
            if s.t1 >= lo and s.t0 <= hi]
    host += [(ns(s.t0), ns(s.t1), f"serve.{s.name}")
             for s in spans if s.category != "counter" and s.t1 > s.t0]
    # a program that ran when the window opened is in it only in part:
    # only steps called inside the window name their program's bucket
    return trace_reduce.reduce(
        pd, (ns(lo), ns(hi)), host_spans=host,
        steps=[(ns(s.t0), ns(s.t1), len(s.images)) for s in steps
               if lo <= s.t0 and s.t1 <= hi])


def _sample(steps, window, count: int, seed: int):
    """Images served in the window and their logits: ``count`` of their
    real rows (pad rows are all zero), drawn from the seed."""
    import numpy as np
    rows = [(i, r) for i, s in enumerate(steps)
            if window.t0 <= s.t1 <= window.t1
            for r in np.nonzero(s.images.reshape(len(s.images), -1)
                                .any(axis=1))[0]]
    rng = np.random.default_rng([seed % 2 ** 64, 7])
    pick = sorted(rng.choice(len(rows), min(count, len(rows)), replace=False)) \
        if rows else []
    images = np.stack([steps[rows[k][0]].images[rows[k][1]] for k in pick]) \
        if pick else None
    served = np.stack([np.asarray(steps[rows[k][0]].logits)[rows[k][1]]
                       for k in pick]) if pick else None
    return images, served


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             trace_dir: str | None = None, on_check=None) -> tuple[dict, dict]:
    """One run; returns the result line and the numbers compared.
    ``on_check(params, images, served, ref)``, if given, sees the sample
    and both sides' logits (``bench/limits.py`` reads the control there)."""
    import jax
    from repro.obs import Tracer
    from repro.serve import AsyncServeRuntime, ServePolicy

    mm = cell.model_module()
    cfg, tf = cell.config, cell.traffic
    devices = jax.devices()[:cell.chips]
    peaks = PEAKS.get(devices[0].device_kind)
    compiles, collections = CompileCounter(), GcWatch()

    t = time.perf_counter()
    params = jax.block_until_ready(mm.init_params(cfg, seed))
    params_s = time.perf_counter() - t
    model, built = mm.build(params, cfg, tf["buckets"])
    info(phase="setup", params_s=params_s, **built)

    pool = gen.image_pool(mm.image_shape(cfg), gen.pool_size(tf), seed)
    mark = _clock_mark() if trace else None
    capture = Capture(model)
    tracer = Tracer(capacity=1 << 18) if trace else None
    rt = AsyncServeRuntime(capture, policy=ServePolicy(**tf.get("policy", {})),
                           tracer=tracer)
    run = drive.Drive(rt, tf, cell.arrivals(), seconds, pool)
    # Set-up leaves some hundred thousand objects (traced and compiled
    # programs) to Python's collector. A full collection over them takes a
    # tenth of a second or more and holds every thread; left to chance, one
    # falls into some windows and not others. Collect set-up's garbage in
    # set-up, and exempt what survives from full collections until the
    # window has been served.
    gc.collect()
    gc.freeze()
    run.start()
    t0 = run.wait_open()
    stats0 = rt.stats()
    setup_s = t0 - T_PROCESS
    with tempfile.TemporaryDirectory() as tmp:
        tdir = trace_dir or tmp
        profiler, span = None, {}
        if trace:
            profiler = threading.Thread(target=_profile, args=(
                t0 + TRACE_AT * seconds, min(TRACE_S, 0.3 * seconds), tdir,
                mark, span))
            profiler.start()
        run.close()
        stats1 = rt.stats()
        if profiler is not None:
            profiler.join()
        window = run.drain()
        rt.close()
        spans = tracer.spans() if tracer is not None else []
        reduced = (_reduce_trace(tdir, capture.steps, spans, span)
                   if trace else None)

    closed = run.closed
    if closed:
        window = window.on_steps([(s.t1, int(s.images.reshape(
            len(s.images), -1).any(axis=1).sum())) for s in capture.steps],
            seconds)
    img_per_s = window.img_per_s()
    due = window.due()
    failed = sum(not r.ok for r in due)
    info(phase="window", seconds=window.seconds, requests=len(due),
         failed=failed, steps=sum(window.t0 < s.t1 <= window.t1
                                  for s in capture.steps),
         compiles_in_window=compiles.between(window.t0, window.t1),
         gc_in_window=collections.between(window.t0, window.t1),
         step_gap_max_s=max((b.t0 - a.t1 for a, b in zip(
             capture.steps, capture.steps[1:])
             if window.t0 <= a.t1 and b.t0 <= window.t1), default=None),
         generator_late_ms=(None if closed else {
             "p50": 1e3 * statistics.median(window.lateness_s),
             "p99": 1e3 * gen.nearest_rank(window.lateness_s, 0.99),
             "max": 1e3 * max(window.lateness_s),
             "over_10ms": sum(x > 0.01 for x in window.lateness_s)}),
         stats=stats1)
    compiles.close()
    collections.close()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    images, served = _sample(capture.steps, window, cfg["check_images"], seed)
    del capture, rt, run, model
    gc.unfreeze()
    gc.collect()
    numbers = {}
    ok = failed == 0 and images is not None
    if images is not None:
        t = time.perf_counter()
        ref = mm.reference_logits(params, cfg, images, bits=cfg["weight_bits"])
        numbers = check.compare(served, ref)
        ok = ok and check.distinct(ref) and check.judge(numbers, cfg["limits"])
        if on_check is not None:
            on_check(params, images, served, ref)
        info(phase="check", images=len(images),
             reference_s=time.perf_counter() - t)

    view = RunView(cell=cell, window=window, closed=closed,
                   img_per_s=img_per_s, setup_s=setup_s, stats0=stats0,
                   stats1=stats1, spans=spans, trace=reduced, peaks=peaks,
                   layers=lambda b: mm.layers(cfg, b))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"], cell.bench_dir)(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    checks = {k: {"value": v, "limit": cfg["limits"][k]}
              for k, v in numbers.items()}
    result = {"correct": bool(ok), "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["checks"] = checks
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after the run)")
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    if kind not in PEAKS:
        print(f"bench: device kind {kind!r} is not in bench/peaks.json "
              f"({sorted(PEAKS)})", file=sys.stderr)
        return 2
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), args.trace_dir)
    for name, c in checks.items():
        print(f"{name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
