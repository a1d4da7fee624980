"""Library-grade micro-batching over a ``CompiledModel`` — the serve half
of the compile/serve split.

Requests (each carrying one or more images) enter a queue; the engine
drains them through the model's jit-compiled fixed-shape steps, fusing
images from different requests into one batch. Multi-bucket dispatch is
the point: instead of always padding the backlog up to one fixed batch,
the engine picks the cheapest compiled bucket for it — with
``batch_buckets=(2, 8)`` a backlog of 2 runs the 2-bucket, not 2 padded
to 8 — so pad waste at low occupancy collapses. The engine accounts for
exactly that: ``stats()["pad_waste"]`` is padded
rows / total rows, the metric that motivates multi-bucket dispatch and
guards its regression.

    model = compile(params, cfg, ExecutionPlan(batch_buckets=(2, 8)))
    eng = MicroBatchEngine(model)
    eng.submit(images_u8)                  # -> Request (labels fill on run)
    eng.run()                              # drain the queue
    print(eng.stats())                     # fps, p50/p95 latency, pad_waste

This is the paper's real-time classification loop (VESTA sustains ~30 fps
on Spikformer V2); drivers compare ``stats()["fps"]`` against that target.
``repro.launch.serve_spikformer`` is the CLI wrapper.

This module also owns the pieces the engine SHARES with the asynchronous
continuous-batching runtime (``repro.serve.runtime``): submit-door request
validation (``validate_images``), batch assembly (``assemble_batch``),
per-step accounting (``StepAccounting``), the latency-percentile summary
(``latency_summary``), and the queue-depth watermark
(``QueueDepthWatermark``) — one implementation for the sync and async
serving paths, which is part of why an identical request trace produces
bit-identical labels through both.

Observability (``repro.obs``): every ServeClient accepts a ``tracer`` and
emits the canonical request lifecycle ``admit -> queue -> place ->
assemble -> step -> complete`` as spans, each batch-scoped span and each
request's ``queue``/``complete`` span naming its batch's id; completed-request latencies feed
a bounded ``LatencyHistogram`` so ``stats()`` percentiles cost O(buckets)
memory however long the server lives.
"""
from __future__ import annotations

import dataclasses
import time
import typing
from collections import deque

import numpy as np

from ..core.spike import popcount
from ..obs.metrics import Gauge, LatencyHistogram
from ..obs.trace import NULL_TRACER

PAPER_FPS = 30.0   # VESTA's reported real-time Spikformer V2 rate

# Version of the shared ``stats()`` schema every ServeClient implements.
# Bump when a shared key is renamed, its meaning changes, or a key every
# client must report is added; additive client-specific keys (replica
# table) do not bump it.
#   v2: ``queue_depth_peak`` joined the shared vocabulary — the queue-depth
#       high-watermark (max images queued at any submit), the backpressure
#       number bursty event-stream arrivals made necessary: a mean queue
#       depth hides a burst that grazed the admission bound.
#   v3: the ``latency_*`` fields are histogram-backed (``repro.obs.metrics.
#       LatencyHistogram``): same keys, same units, same ``None``-when-empty
#       contract, but percentiles now come from log-spaced buckets (<= 5%
#       documented relative error) instead of an unbounded sorted list —
#       a million-request server holds O(buckets) latency state. Meaning
#       changed (bounded approximation), so the version bumps.
SERVE_STATS_VERSION = 3


@typing.runtime_checkable
class ServeClient(typing.Protocol):
    """The one serving surface: sync engine, async runtime, and fleet all
    speak exactly this, so drivers (``repro.serve.loadgen``,
    ``benchmarks/infer_bench.py``) run against any of them without
    isinstance checks.

    * ``submit(images, *, rid=None, on_image=None)`` — keyword-only
      options; returns a ``Request`` whose ``result()`` yields the labels.
    * ``stats()`` — the versioned schema built by ``serve_stats``
      (``stats_version``, ``fps``, ``occupancy``, ``pad_waste``,
      ``latency_*``, ...).
    * ``close(timeout=None)`` — drain: every accepted request resolves
      before close returns.
    """

    def submit(self, images, *, rid: int | None = None,
               on_image=None) -> "Request": ...

    def stats(self) -> dict: ...

    def close(self, timeout: float | None = None) -> None: ...


@dataclasses.dataclass
class Request:
    """One classification request: n images in, n labels out.

    ``on_image(rid, index, label)`` is an optional streaming callback fired
    as each image's batch completes (possibly before the whole request)."""
    rid: int
    images: np.ndarray                  # (n, H, W, C) uint8
    labels: list = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_dequeue: float = 0.0              # first image leaves the queue
    t_done: float = 0.0
    on_image: object = None

    @property
    def latency_s(self) -> float | None:
        """Submit-to-done latency; ``None`` while the request is still in
        flight (``t_done`` unset) — the raw subtraction would report a
        nonsense negative number against a live ``t_submit``."""
        if not self.t_done:
            return None
        return self.t_done - self.t_submit

    def result(self, timeout: float | None = None) -> list:
        """The label list, blocking/draining as the serving path requires.

        On the sync engine the submitting thread IS the serving thread, so
        an incomplete request drains the engine (the hook the engine
        attached at submit) and returns. ``AsyncRequest`` overrides this
        with a real future wait. One spelling — ``req.result()`` — works
        against every ServeClient, which is what lets the open-loop load
        generator drive all of them."""
        if not self.t_done:
            drain = getattr(self, "_drain", None)
            if drain is not None:
                drain()
        if not self.t_done:
            raise RuntimeError(
                f"request {self.rid} is not complete and has no serving "
                "loop attached to drain it")
        return list(self.labels)


# ---------------------------------------------------------------------------
# Shared serve plumbing: the sync engine below and the async runtime in
# repro.serve.runtime both build on these, so batch shapes, pad accounting
# and latency reporting cannot drift between the two paths.
# ---------------------------------------------------------------------------

def validate_images(images, image_shape) -> np.ndarray:
    """Validate a request's images at the ``submit()`` door against the
    compiled model's input spec and return them as ``(n, H, W, C)`` uint8.

    A malformed request must fail HERE, with an error naming the expected
    per-image ``(H, W, C)`` — not several layers deep in a jitted step with
    a shape error about a tensor the caller never constructed. Accepted:
    uint8 directly; other integer dtypes if every pixel is in [0, 255]
    (cast); anything else (floats, bools) is rejected.
    """
    arr = np.asarray(images)
    image_shape = tuple(int(d) for d in image_shape)
    if arr.ndim != 4 or tuple(arr.shape[1:]) != image_shape:
        raise ValueError(
            f"request images have shape {tuple(arr.shape)}; this compiled "
            f"model expects (n, H, W, C) = (n, {image_shape[0]}, "
            f"{image_shape[1]}, {image_shape[2]})")
    if arr.dtype != np.uint8:
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"request images have dtype {arr.dtype}; expected uint8 "
                "pixel values in [0, 255]")
        if arr.size and (int(arr.min()) < 0 or int(arr.max()) > 255):
            raise ValueError(
                f"request images of dtype {arr.dtype} contain values "
                f"outside [0, 255]; cannot safely cast to uint8 pixels")
        arr = arr.astype(np.uint8)
    return arr


def batch_occupancy(images) -> float:
    """Fraction of set bits across a uint8 image batch — the serving-level
    spike-occupancy proxy (pixel bits are exactly what the SSSC front end
    consumes as value planes). Pass only the REAL rows of a padded batch;
    zero pad rows would dilute the measurement. Returns 0.0 for an empty
    batch."""
    arr = np.asarray(images, np.uint8)
    if not arr.size:
        return 0.0
    return popcount(arr) / (arr.size * 8)


def assemble_batch(images: list, bucket: int):
    """Stack per-image arrays and zero-pad up to the bucket shape.

    Returns ``(batch, pad)`` with ``batch.shape[0] == bucket`` and ``pad``
    the number of appended zero rows.
    """
    batch = np.stack(images)
    pad = bucket - len(images)
    if pad:
        batch = np.concatenate(
            [batch, np.zeros((pad, *batch.shape[1:]), batch.dtype)])
    return batch, pad


@dataclasses.dataclass
class StepAccounting:
    """Per-step serving accounting: batches, rows, pad waste, timing, and
    spike occupancy (rows-weighted, when steps measure it)."""
    batches: int = 0
    images: int = 0
    padded_rows: int = 0
    total_rows: int = 0
    busy_s: float = 0.0         # model-step compute only
    wall_s: float = 0.0         # whole steps incl. batch assembly
    occupancy_weighted: float = 0.0   # sum of per-step occupancy * rows
    occupancy_rows: int = 0           # rows with a measured occupancy

    def record_step(self, *, rows: int, bucket: int, busy_s: float,
                    wall_s: float, occupancy: float | None = None) -> None:
        self.batches += 1
        self.images += rows
        self.padded_rows += bucket - rows
        self.total_rows += bucket
        self.busy_s += busy_s
        self.wall_s += wall_s
        if occupancy is not None:
            self.occupancy_weighted += float(occupancy) * rows
            self.occupancy_rows += rows

    @property
    def pad_waste(self) -> float:
        """Padded rows / total rows across all steps so far — the cost
        multi-bucket dispatch exists to cut."""
        return self.padded_rows / self.total_rows if self.total_rows else 0.0

    @property
    def occupancy(self) -> float | None:
        """Rows-weighted mean spike occupancy over measured steps, ``None``
        when no step ever measured it (distinguishable from a true 0.0 —
        an all-dark batch is a measurement, absence is not)."""
        if not self.occupancy_rows:
            return None
        return self.occupancy_weighted / self.occupancy_rows

    @property
    def fps(self) -> float:
        """Images per second of step wall time (service capacity, not
        arrival-bounded throughput — the open-loop load generator measures
        the latter)."""
        return self.images / self.wall_s if self.wall_s else 0.0


def latency_summary(latencies_s, *, prefix: str = "latency_") -> dict:
    """p50/p95/p99/mean over per-request latencies, ``None`` when empty —
    the shared tail-latency report for engine/runtime/loadgen stats.

    Empty-safe by contract: a zero-completed-request window (and any
    ``None`` entries from still-in-flight requests that leaked into the
    iterable) reports all-``None`` fields — callers must never need to
    guard. A single sample reports that sample exactly.

    Values are seconds rounded to 6 decimals (microsecond precision):
    serving steps on small models land well under a millisecond, and the
    bench comparisons read these fields — rounding to 4 would collapse
    real sub-millisecond p50/p99 deltas into quantization noise."""
    lat = np.asarray([v for v in latencies_s if v is not None], np.float64)
    if not len(lat):
        return {f"{prefix}{k}": None for k in ("p50_s", "p95_s", "p99_s",
                                               "mean_s")}
    return {
        f"{prefix}p50_s": round(float(np.percentile(lat, 50)), 6),
        f"{prefix}p95_s": round(float(np.percentile(lat, 95)), 6),
        f"{prefix}p99_s": round(float(np.percentile(lat, 99)), 6),
        f"{prefix}mean_s": round(float(lat.mean()), 6),
    }


def serve_stats(*, acct: StepAccounting, done, buckets,
                queue_depth_peak: int = 0,
                latency_hist: LatencyHistogram | None = None,
                extra: dict | None = None) -> dict:
    """The versioned common ``ServeClient.stats()`` schema — ONE builder,
    so the shared keys (``fps``, ``occupancy``, ``pad_waste``,
    ``latency_*``, ``queue_depth_peak``) cannot drift between the sync
    engine, the async runtime, and the fleet. ``extra`` adds
    client-specific keys (rejections, per-replica table) without touching
    the shared vocabulary.

    ``latency_hist`` is the v3 percentile source: every client feeds its
    completed-request latencies into a bounded ``LatencyHistogram`` and
    passes it here, so the report costs O(buckets) however many requests
    the server has lived through. Without one (bare callers, old tests)
    the exact sorted-list path over ``done`` still works — same keys
    either way."""
    if latency_hist is not None:
        latency = latency_hist.summary()
    else:
        latency = latency_summary(r.latency_s for r in done)
    out = {
        "stats_version": SERVE_STATS_VERSION,
        "queue_depth_peak": int(queue_depth_peak),
        "requests": len(done),
        "images": acct.images,
        "batches": acct.batches,
        "buckets": list(buckets),
        "wall_s": round(acct.wall_s, 4),
        "fps": round(acct.fps, 2),
        "paper_fps": PAPER_FPS,
        "realtime": bool(acct.wall_s and acct.fps >= PAPER_FPS),
        "padded_rows": acct.padded_rows,
        "total_rows": acct.total_rows,
        "pad_waste": round(acct.pad_waste, 4),
        "occupancy": (None if acct.occupancy is None
                      else round(acct.occupancy, 4)),
        **latency,
    }
    if extra:
        out.update(extra)
    return out


class QueueDepthWatermark:
    """The queue-depth high-watermark every ServeClient reports as
    ``queue_depth_peak`` — ONE gauge-backed implementation shared by the
    sync engine, the async runtime, and the fleet, so the bookkeeping
    (formerly three copy-pasted ``max()`` updates) cannot drift between
    submit doors. ``observe`` after every enqueue; ``peak`` is the gauge's
    high-watermark."""

    __slots__ = ("gauge",)

    def __init__(self, gauge: Gauge | None = None):
        self.gauge = Gauge("queue_depth") if gauge is None else gauge

    def observe(self, depth: int) -> None:
        self.gauge.set(int(depth))

    @property
    def peak(self) -> int:
        return 0 if self.gauge.max is None else int(self.gauge.max)


class MicroBatchEngine:
    """Micro-batching classifier over a multi-bucket ``CompiledModel``.

    Implements the ``ServeClient`` protocol (submit / stats / close): the
    closed-loop member of the serving family — ``close()`` is a drain, and
    a ``result()`` on an incomplete request drains inline.

    ``tracer`` (a ``repro.obs.Tracer``) records the request lifecycle
    spans; ``clock`` is injected (default ``time.perf_counter``) so a test
    can pin the engine's full span table deterministically — the sync
    engine has no sleeping worker, so unlike the async runtime its clock
    is free to be fake."""

    def __init__(self, model, *, tracer=None, clock=time.perf_counter):
        self.model = model
        self.buckets = tuple(model.buckets)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._clock = clock
        self.queue: deque = deque()         # (request, image index)
        self.done: list[Request] = []
        self._pending: dict[int, int] = {}  # rid -> images left
        self._next_rid = 0
        self._next_batch = 0                # the next batch's id (spans)
        self._queue_depth = QueueDepthWatermark()
        self.latency_hist = LatencyHistogram()
        self.acct = StepAccounting()

    @property
    def queue_depth_peak(self) -> int:
        return self._queue_depth.peak

    # accounting attribute surface predates StepAccounting; keep it readable
    @property
    def batches(self) -> int:
        return self.acct.batches

    @property
    def images_done(self) -> int:
        return self.acct.images

    @property
    def padded_rows(self) -> int:
        return self.acct.padded_rows

    @property
    def total_rows(self) -> int:
        return self.acct.total_rows

    @property
    def busy_s(self) -> float:
        return self.acct.busy_s

    @property
    def wall_s(self) -> float:
        return self.acct.wall_s

    def submit(self, images, *, rid: int | None = None,
               on_image=None) -> Request:
        """Queue raw images (or a prebuilt ``Request``) — the ServeClient
        door, options keyword-only. Images are validated against the
        compiled model's input spec right here.

        ``rid`` names the request id for raw images; for a ``Request``
        instance it must agree with ``req.rid`` — silently ignoring a
        conflicting ``rid=`` would complete the request under an id the
        caller never sees again. ``on_image(rid, index, label)`` streams
        per-image completions, same contract as the async runtime."""
        t_enter = self._clock()
        if isinstance(images, Request):
            req = images
            if rid is not None and rid != req.rid:
                raise ValueError(
                    f"submit(rid={rid}) conflicts with the Request's own "
                    f"rid={req.rid}; drop the argument or pass raw images")
            if on_image is not None:
                req.on_image = on_image
            req.images = validate_images(req.images,
                                         self.model.input_shape()[1:])
        else:
            arr = validate_images(images, self.model.input_shape()[1:])
            if rid is None:
                rid = self._next_rid
            req = Request(rid=rid, images=arr, on_image=on_image)
        if req.rid in self._pending:
            # a silent overwrite would strand one of the two requests
            # (completion is counted per rid) — fail at the door instead
            raise ValueError(f"request id {req.rid} is already in flight")
        self._next_rid = max(self._next_rid, req.rid + 1)
        req.t_submit = self._clock()
        req.labels = [None] * len(req.images)
        # result() on a not-yet-run request drains this engine inline —
        # the sync spelling of the async future (see Request.result)
        req._drain = self.run
        tr = self.tracer
        if not len(req.images):
            # nothing to queue: complete immediately so run()/stats() see it
            req.t_done = req.t_submit
            self.done.append(req)
            self.latency_hist.observe(0.0)
            if tr.enabled:
                tr.span("request", "admit", t0=t_enter, t1=req.t_submit,
                        rid=req.rid, value=0)
                tr.span("request", "complete", t0=req.t_submit,
                        t1=req.t_done, rid=req.rid)
            return req
        self._pending[req.rid] = len(req.images)
        for i in range(len(req.images)):
            self.queue.append((req, i))
        self._queue_depth.observe(len(self.queue))
        if tr.enabled:
            tr.span("request", "admit", t0=t_enter, t1=req.t_submit,
                    rid=req.rid, value=len(req.images))
            tr.counter("queue_depth", len(self.queue), t=req.t_submit)
        return req

    def pick_bucket(self, backlog: int) -> int:
        """The bucket the next step should run: the largest bucket while
        the backlog covers it, else the first chunk of the model's exact
        pad-minimizing split of the remainder — so 3 queued images over
        buckets (2, 8) run 2 now + 2-with-one-pad next, never 3 padded
        to 8. (The early-out keeps a deep backlog O(1) per step instead
        of re-splitting the whole queue every batch.)"""
        if backlog >= self.buckets[-1]:
            return self.buckets[-1]
        return self.model.plan_chunks(backlog)[0][1]

    def step(self) -> int:
        """Classify one fused batch drawn across requests; returns #images."""
        if not self.queue:
            return 0
        tr = self.tracer
        bid = self._next_batch
        self._next_batch += 1
        t_start = self._clock()
        bucket = self.pick_bucket(len(self.queue))
        t_place = self._clock()
        if tr.enabled:
            tr.span("batch", "place", t0=t_start, t1=t_place, bucket=bucket,
                    batch=bid)
        work = [self.queue.popleft()
                for _ in range(min(bucket, len(self.queue)))]
        t_pop = self._clock()
        if tr.enabled:
            for req, _ in work:
                if not req.t_dequeue:     # first image leaving the queue
                    req.t_dequeue = t_pop
                    tr.span("request", "queue", t0=req.t_submit, t1=t_pop,
                            rid=req.rid, batch=bid)
        batch, _ = assemble_batch([req.images[i] for req, i in work], bucket)
        occ = batch_occupancy(batch[:len(work)])  # real rows only
        t0 = self._clock()
        if tr.enabled:
            tr.span("batch", "assemble", t0=t_pop, t1=t0, bucket=bucket,
                    occupancy=occ, value=len(work), batch=bid)
        logits = np.asarray(self.model.step(batch))
        busy_s = self._clock() - t0
        if tr.enabled:
            tr.span("batch", "step", t0=t0, t1=t0 + busy_s, bucket=bucket,
                    occupancy=occ, value=len(work), batch=bid)
            tr.counter("occupancy", occ, t=t0)
        labels = logits[:len(work)].argmax(axis=-1)
        now = self._clock()
        for (req, i), lab in zip(work, labels):
            req.labels[i] = int(lab)
            self._pending[req.rid] -= 1
            if self._pending[req.rid] == 0:
                del self._pending[req.rid]     # rid leaves "in flight"
                req.t_done = now
                self.done.append(req)
                self.latency_hist.observe(now - req.t_submit)
                if tr.enabled:
                    tr.span("request", "complete", t0=req.t_submit, t1=now,
                            rid=req.rid, batch=bid)
        self.acct.record_step(rows=len(work), bucket=bucket, busy_s=busy_s,
                              wall_s=self._clock() - t_start,
                              occupancy=occ)
        for (req, i), lab in zip(work, labels):
            if req.on_image is not None:
                try:
                    req.on_image(req.rid, i, int(lab))
                except Exception:
                    pass   # a streaming callback must not kill serving
        return len(work)

    def run(self) -> list[Request]:
        """Drain the queue; returns the completed requests. (Wall time is
        accumulated per step, so driving ``step()`` directly reports the
        same honest fps basis.)"""
        while self.queue:
            self.step()
        return self.done

    def close(self, timeout: float | None = None) -> None:
        """ServeClient close: drain the queue — every accepted request
        completes. (``timeout`` is accepted for signature parity; a sync
        drain either finishes or raises.)"""
        self.run()

    # -- accounting ---------------------------------------------------------

    @property
    def pad_waste(self) -> float:
        return self.acct.pad_waste

    def stats(self) -> dict:
        """Serving metrics over everything processed so far (the shared
        ServeClient schema)."""
        return serve_stats(acct=self.acct, done=self.done,
                           buckets=self.buckets,
                           queue_depth_peak=self.queue_depth_peak,
                           latency_hist=self.latency_hist)
