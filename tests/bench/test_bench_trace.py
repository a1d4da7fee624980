"""The trace reduction (``bench/trace_reduce.py``) on a trace recorded on
a TPU v5e, with the host tracer on: one bucket-1 step of the T=4 model,
annotated ``bench.step b=1 #0``, inside an annotation ``bench.window``. The
pinned numbers were read off the trace by hand."""
import gzip
import re

import pytest

from bench_tiny import ROOT

from bench import trace_reduce as tr

TRACE = ROOT / "tests" / "bench" / "data" / "v5e_t4_bucket1_step.xplane.pb.gz"


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(gzip.decompress(
        TRACE.read_bytes()))


def annotations(profile):
    return {ev.name: (ev.start_ns, ev.end_ns) for p in profile.planes
            if p.name.startswith("/host:") for ln in p.lines
            for ev in ln.events if ev.name.startswith("bench.")}


@pytest.fixture(scope="module")
def reduced(profile):
    ann = annotations(profile)
    steps = [(s, e, int(re.search(r"b=(\d+)", n).group(1)))
             for n, (s, e) in ann.items() if n.startswith("bench.step")]
    return tr.reduce(profile, ann["bench.window"], steps=steps)


def test_window_busy_and_idle(reduced):
    assert reduced.devices == 1
    assert reduced.window_s == pytest.approx(0.041157489, abs=1e-9)
    assert reduced.busy_s == pytest.approx(0.028883295, abs=1e-9)
    # the ops of the one step run back to back: busy is the program's span
    (m,) = reduced.modules
    assert m.bucket == 1
    assert reduced.busy_s * 1e9 == pytest.approx(m.end - m.start, rel=1e-4)


def test_device_time_by_kernel_family(reduced):
    fam = reduced.family_ns
    assert fam["lut_gather_matmul"] == pytest.approx(20_837_815, abs=1)
    assert fam["spike_matmul"] == pytest.approx(643_113, abs=1)
    assert fam["stdp_attention"] == pytest.approx(304_376, abs=1)
    assert fam["tflif_fused"] == pytest.approx(118_274, abs=1)
    assert "tflif_lut_matmul" not in fam          # the fused MLP never runs
    assert fam["xla:shift-left_reduce_fusion"] == pytest.approx(5_778_008,
                                                                abs=1)
    assert sum(fam.values()) == pytest.approx(reduced.busy_ns, rel=1e-9)
    (m,) = reduced.modules
    assert m.family_ns["lut_gather_matmul"] == pytest.approx(20_837_815, abs=1)


def test_idle_gaps_are_attributed_to_the_host(reduced):
    idle = sum(ns for ns, _ in reduced.gaps)
    assert idle == pytest.approx(reduced.window_ns[1] - reduced.window_ns[0]
                                 - reduced.busy_ns, abs=1)
    out = tr.breakdown(reduced)
    idle_by = dict(out["idle_gaps"])
    # the window opens and closes on the host's sleeps; inside the step
    # call the host lays the batch out for the device (its transpose)
    assert {k for k, _ in out["idle_gaps"][:3]} == {
        "bench.window", "bench.step b=1 #0", "Transpose"}
    assert idle_by["Transpose"] > 2e-3
    assert sum(idle_by.values()) <= idle / 1e9 + 1e-9   # the top ten
    assert out["device_ops"][0][0] == "lut_gather_matmul"
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) <= 10


def test_a_gap_splits_by_the_innermost_host_span():
    k = 1e6
    hosts = tr._Hosts([(0, 100 * k, "step"), (10 * k, 40 * k, "assemble"),
                       (50 * k, 60 * k, "copy")])
    assert tr._Hosts.split(hosts, -50 * k, 100 * k) == [
        (50 * k, "no host span"), (10 * k, "step"), (30 * k, "assemble"),
        (10 * k, "step"), (10 * k, "copy"), (40 * k, "step")]
    assert hosts.split(0, 5e3) == [(5e3, "short gaps (< 10 us)")]


def test_host_spans_take_part(profile):
    lo, hi = annotations(profile)["bench.window"]
    red = tr.reduce(profile, (lo, hi),
                    host_spans=[(lo, lo + 3e6, "serve.assemble")])
    assert any(label == "serve.assemble" for _, label in red.gaps)
    assert all(m.bucket is None for m in red.modules)   # no steps given


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_clock_offset_pairs_the_mark_programs(profile):
    off = 1.234e12
    host = [10.0, 15.5]
    events = [_Obj(name="jit_bench_clock_mark(123)",
                   end_ns=t * 1e9 + off - lat)
              for t, lat in zip(host, (1e5, 3e5))]
    events.append(_Obj(name="jit_fwd(456)", end_ns=12e9 + off))
    pd = _Obj(planes=[_Obj(name="/device:TPU:0", lines=[
        _Obj(name="XLA Modules", events=events)])])
    assert tr.clock_offset(pd, host) == pytest.approx(off - 2e5)
    with pytest.raises(ValueError):
        tr.clock_offset(profile, host)      # recorded without the marks


@pytest.mark.parametrize("name, family", [
    ("%lut_gather_matmul.37 = f32[4,896,256]{2,1,0} custom-call(...)",
     "lut_gather_matmul"),
    ("%spike_matmul = f32[8] custom-call()", "spike_matmul"),
    ("%tflif_fused.12 = u8[1,2]{1,0} custom-call(...)", "tflif_fused"),
    ("%shift-left_reduce_fusion.18 = u32[12544,2,2] fusion(...)",
     "xla:shift-left_reduce_fusion"),
    ("%copy-start.59 = (u32[8]) copy-start(...)", "xla:copy-start"),
])
def test_op_family(name, family):
    assert tr.op_family(name) == family
