"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time by family, module executions and host-attributed idle gaps.

What the trace of a TPU run holds (read by hand on a v5e trace): one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per
executed HLO op, named by its HLO text (``%lut_gather_matmul.37 = f32[...]
custom-call(...)``), and whose line ``XLA Modules`` has one event per
executed program (``jit_fwd(<fingerprint>)``); and, where the host
tracer is on, a plane ``/host:CPU`` with a line per host thread, where
``jax.profiler.TraceAnnotation`` spans and the runtime's own events
(``XlaLinearize``, ``Transpose``, ``H2D Dispatch``, ...) sit. Device and
host times share one clock in nanoseconds.

The benchmark records with the host tracer off: on, it records every chunk
of the host's input layout transpose and slows that transpose about
fourfold, which would show as device idle time that an untraced run does
not have. The host's side then comes from the benchmark's own spans on the
host clock, put on the trace's clock by ``clock_offset``.

A Pallas kernel's custom call carries the name of the jitted entry point
that wraps it, so device time groups into kernel families by the op's base
name; every other op is XLA's own and is named ``xla:<base name>``.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

KERNEL_FAMILIES = ("lut_gather_matmul", "spike_matmul", "tflif_lut_matmul",
                   "tflif_fused", "stdp_attention")
_BASE = re.compile(r"%?([^\s=%]+?)(?:\.\d+)?(?:\s|=|$)")


def op_family(name: str) -> str:
    m = _BASE.match(name)
    base = m.group(1) if m else name.split()[0]
    return base if base in KERNEL_FAMILIES else f"xla:{base}"


@dataclasses.dataclass
class Module:
    start: float
    end: float
    family_ns: dict          # family -> device ns inside this execution
    bucket: int | None = None


@dataclasses.dataclass
class Reduced:
    window_ns: tuple          # (start, end) of the traced window
    devices: int
    busy_ns: float            # union of op intervals in the window, per chip
    family_ns: dict           # family -> device ns in the window, per chip
    modules: list             # executions wholly inside the window (chip 0)
    gaps: list                # [(ns, what the host did)], longest first
                              # (chip 0; a gap in pieces, one per host span)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def host_events(pd, extra=()):
    """(start, end, name) of the trace's host spans, without the Python
    tracer's per-call events, plus ``extra``."""
    out = list(extra)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("$"):
                    out.append((ev.start_ns, ev.end_ns, ev.name))
    return out


SHORT_GAP_NS = 10_000    # gaps shorter than this are not attributed


class _Hosts:
    """Host spans as arrays, to say what the host did during a gap."""

    def __init__(self, hosts):
        self.start = np.array([h[0] for h in hosts], np.float64)
        self.end = np.array([h[1] for h in hosts], np.float64)
        self.names = [h[2] for h in hosts]

    def split(self, s: float, e: float) -> list:
        """The gap ``[s, e)`` cut at every host span's edge inside it, each
        piece named by the shortest host span that covers it:
        ``[(ns, name)]``."""
        if e - s < SHORT_GAP_NS:
            return [(e - s, f"short gaps (< {SHORT_GAP_NS / 1e3:g} us)")]
        near = np.nonzero((self.start < e) & (self.end > s))[0]
        cuts = np.unique(np.clip(np.concatenate(
            [[s, e], self.start[near], self.end[near]]), s, e))
        out = []
        for a, b in zip(cuts[:-1], cuts[1:]):
            cover = near[(self.start[near] <= a) & (self.end[near] >= b)]
            if len(cover):
                dur = self.end[cover] - self.start[cover]
                name = self.names[int(cover[np.argmin(dur)])]
            else:
                name = "no host span"
            if out and out[-1][1] == name:
                out[-1] = (out[-1][0] + float(b - a), name)
            else:
                out.append((float(b - a), name))
        return out


MARK = "bench_clock_mark"    # a tiny program run at both ends of the trace


def clock_offset(pd, host_ends_s, device_prefix="/device:TPU:0") -> float:
    """Nanoseconds to add to a host-clock time (seconds) to put it on the
    trace's clock. The benchmark runs the program ``jit_<MARK>`` and waits
    for it at each end of the traced window, noting on the host clock when
    each wait returned (``host_ends_s``); each such program's end on the
    device pairs with one of those. Its error is the latency from the
    device's end of a program to the host's return from the wait, under a
    few milliseconds."""
    ends = [ev.end_ns for p in pd.planes if p.name == device_prefix
            for ln in p.lines if ln.name == "XLA Modules"
            for ev in ln.events if ev.name.startswith(f"jit_{MARK}")]
    if len(ends) != len(host_ends_s):
        raise ValueError(f"found {len(ends)} {MARK} programs in the trace "
                         f"for {len(host_ends_s)} marks")
    return float(np.median(np.sort(ends) - np.sort(host_ends_s) * 1e9))


def reduce(pd, window_ns, host_spans=(), steps=(),
           device_prefix="/device:TPU:") -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``; ``window_ns``: the traced
    window on the trace's clock; ``host_spans``: ``(start_ns, end_ns,
    name)`` of what the host was doing, to split idle gaps by (the trace's
    own host events join them); ``steps``: ``(start_ns, end_ns,
    bucket)`` of each step call, which gives each program execution its
    bucket."""
    labeler = _Hosts(host_events(pd, host_spans))
    lo, hi = window_ns
    planes = sorted((p for p in pd.planes if p.name.startswith(device_prefix)),
                    key=lambda p: p.name)
    if not planes:
        raise ValueError(f"no {device_prefix}* plane in the trace")
    busy_total, fam_total = 0.0, {}
    modules, gaps = [], []
    for i, plane in enumerate(planes):
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        ops = [(ev.start_ns, ev.end_ns, op_family(ev.name))
               for ev in lines.get("XLA Ops", [])]
        clipped = [(*_clip(s, e, lo, hi), f) for s, e, f in ops]
        clipped = [(s, e, f) for s, e, f in clipped if e > s]
        union = _union([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in union)
        for s, e, f in clipped:
            fam_total[f] = fam_total.get(f, 0.0) + (e - s)
        if i:
            continue
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps += labeler.split(s, e)
        for ev in lines.get("XLA Modules", []):
            if not (lo <= ev.start_ns and ev.end_ns <= hi):
                continue
            fam = {}
            for s, e, f in ops:
                if ev.start_ns <= s and e <= ev.end_ns:
                    fam[f] = fam.get(f, 0.0) + (e - s)
            mid = (ev.start_ns + ev.end_ns) / 2
            bucket = next((b for s, e, b in steps if s <= mid <= e), None)
            modules.append(Module(ev.start_ns, ev.end_ns, fam, bucket))
    n = len(planes)
    gaps.sort(key=lambda g: -g[0])
    return Reduced(window_ns=(lo, hi), devices=n, busy_ns=busy_total / n,
                   family_ns={f: v / n for f, v in fam_total.items()},
                   modules=modules, gaps=gaps)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: device time by family, and idle
    time by what the host was doing, each the ``top`` largest, seconds."""
    ops = sorted(red.family_ns.items(), key=lambda kv: -kv[1])[:top]
    idle: dict[str, float] = {}
    for ns, label in red.gaps:
        idle[label] = idle.get(label, 0.0) + ns
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
