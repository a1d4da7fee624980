"""The event-stream workload (``repro.events``).

Three layers, each pinned to an existing contract rather than trusted:

* the ENCODER is proved against the packing oracle — for every tail
  shape (T in {1, 8, 9, 16, 17}), both polarities, and the empty window,
  ``encode_events_to_plane_groups`` must be bit-identical to
  ``core.spike.pack_timesteps`` of a dense rasterization of the same
  events, and its occupancy readout must agree with the jax-side
  ``infer.backends.chunk_occupancy`` the sparse route calibrates from;
* the SESSION is checked against the serving protocol with a scripted
  fake client (watermark windowing, late-event rejection, QueueFull
  shedding — all deterministic, no threads) and end-to-end against the
  real async runtime (labels land, capture→save→load→replay closes the
  loop);
* the TRACE format replays deterministically: the committed fixture
  through one runtime twice → bit-identical labels; through a
  2-replica fleet → the same labels again; and a re-recorded file is
  byte-identical to what was loaded.
"""
import dataclasses
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core.spike import pack_timesteps, packed_occupancy
from repro.core.spikformer import SpikformerConfig, init
from repro.events import (POLARITIES, EventStream, EventStreamSession,
                          EventTrace, TraceArrival, empty_stream,
                          encode_events_to_plane_groups, events_to_frame,
                          flicker_burst_events, labels_checksum, load_trace,
                          merge_streams, moving_edge_events, rasterize_events,
                          record_trace, replay_trace, trace_to_load,
                          window_occupancy)
from repro.infer import ExecutionPlan, chunk_occupancy
from repro.infer import compile as infer_compile
from repro.serve import AsyncServeRuntime, QueueFull, ServeFleet, ServePolicy

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURE = REPO_ROOT / "benchmarks" / "traces" / "dvs_synth_mini.jsonl"

H = W = 16


def busy_stream(duration_us=20_000, seed=0):
    """A merged moving-edge + flicker stream that exercises both
    polarities and both sparse and dense windows."""
    return merge_streams(
        moving_edge_events(height=H, width=W, duration_us=duration_us,
                           seed=seed),
        flicker_burst_events(height=H, width=W, duration_us=duration_us,
                             seed=seed + 1, bursts=2, events_per_burst=150))


# ---------------------------------------------------------------------------
# encoder: bit-exact against the dense rasterize + pack oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [1, 8, 9, 16, 17])
def test_encode_bit_exact_vs_pack_timesteps(t):
    ev = busy_stream()
    window_us = 20_000 // t
    direct = encode_events_to_plane_groups(ev, t=t, window_us=window_us)
    dense = rasterize_events(ev, t=t, window_us=window_us)
    oracle = np.asarray(pack_timesteps(jax.numpy.asarray(dense)))
    assert direct.shape == (-(-t // 8), H, W, POLARITIES)
    assert direct.dtype == np.uint8
    np.testing.assert_array_equal(direct, oracle)
    assert direct.any(), "a busy stream must set bits"
    # both polarities present in the encoding, not just in the stream
    assert direct[..., 0].any() and direct[..., 1].any()


def test_encode_empty_window():
    ev = empty_stream(H, W)
    planes = encode_events_to_plane_groups(ev, t=9, window_us=100)
    assert planes.shape == (2, H, W, POLARITIES)
    assert not planes.any()
    oracle = np.asarray(pack_timesteps(jax.numpy.asarray(
        rasterize_events(ev, t=9, window_us=100))))
    np.testing.assert_array_equal(planes, oracle)


def test_encode_trailing_bits_stay_zero():
    # t=9: the second group may only ever use bit 0 — the packing
    # invariant every popcount readout relies on
    planes = encode_events_to_plane_groups(busy_stream(), t=9,
                                           window_us=20_000 // 9)
    assert not (planes[1] & 0xFE).any()


def test_encode_window_slicing_and_t0():
    ev = busy_stream()
    # events outside [t0, t0 + t*window_us) are ignored, not wrapped
    tight = encode_events_to_plane_groups(ev, t=4, window_us=1_000)
    full = encode_events_to_plane_groups(
        ev.slice_time(0, 4_000), t=4, window_us=1_000)
    np.testing.assert_array_equal(tight, full)
    # a shifted stream with a matching t0 encodes identically
    shifted = encode_events_to_plane_groups(
        ev.shift_time(7_000), t=4, window_us=1_000, t0_us=7_000)
    np.testing.assert_array_equal(tight, shifted)


def test_encoder_validates_arguments():
    ev = empty_stream(H, W)
    with pytest.raises(ValueError, match="t must be"):
        encode_events_to_plane_groups(ev, t=0, window_us=10)
    with pytest.raises(ValueError, match="window_us"):
        encode_events_to_plane_groups(ev, t=8, window_us=0)


# ---------------------------------------------------------------------------
# EventStream: loud validation at the door
# ---------------------------------------------------------------------------

def test_event_stream_validation():
    z = np.zeros(2, np.int64)
    with pytest.raises(ValueError, match="parallel"):
        EventStream(H, W, z, z, z, np.zeros(3, np.int64))
    with pytest.raises(ValueError, match="x values"):
        EventStream(H, W, np.array([0, W]), z, z, z)
    with pytest.raises(ValueError, match="y values"):
        EventStream(H, W, z, np.array([-1, 0]), z, z)
    with pytest.raises(ValueError, match="polarity values"):
        EventStream(H, W, z, z, z, np.array([0, 2]))
    with pytest.raises(ValueError, match="sorted non-decreasing"):
        EventStream(H, W, z, z, np.array([5, 3]), z)
    with pytest.raises(ValueError, match="at least 1x1"):
        EventStream(0, W, z, z, z, z)


def test_slice_shift_merge():
    ev = busy_stream()
    part = ev.slice_time(5_000, 10_000)
    assert len(part) and all(5_000 <= t < 10_000 for t in part.t_us)
    back = part.shift_time(-5_000)
    assert int(back.t_us[0]) == int(part.t_us[0]) - 5_000
    m = merge_streams(ev.slice_time(0, 5_000), ev.slice_time(5_000, 99_999))
    np.testing.assert_array_equal(m.t_us, ev.t_us)
    np.testing.assert_array_equal(m.x, ev.x)
    with pytest.raises(ValueError, match="different sensors"):
        merge_streams(ev, empty_stream(H, W + 1))


def test_generators_deterministic():
    a = moving_edge_events(height=H, width=W, duration_us=10_000, seed=3)
    b = moving_edge_events(height=H, width=W, duration_us=10_000, seed=3)
    np.testing.assert_array_equal(a.t_us, b.t_us)
    np.testing.assert_array_equal(a.x, b.x)
    c = moving_edge_events(height=H, width=W, duration_us=10_000, seed=4)
    assert len(a) != len(c) or not np.array_equal(a.t_us, c.t_us)
    f1 = flicker_burst_events(height=H, width=W, duration_us=10_000, seed=3)
    f2 = flicker_burst_events(height=H, width=W, duration_us=10_000, seed=3)
    np.testing.assert_array_equal(f1.x, f2.x)
    # construction re-validates bounds, so reaching here means in-range;
    # still pin the timestamps inside the requested duration
    assert int(a.t_us[-1]) < 10_000 and int(f1.t_us[-1]) < 10_000


# ---------------------------------------------------------------------------
# readouts: occupancy agreement with the jax side, count frames
# ---------------------------------------------------------------------------

def test_window_occupancy_matches_jax_chunk_occupancy():
    for t in (8, 9, 16):
        planes = encode_events_to_plane_groups(
            busy_stream(), t=t, window_us=20_000 // t)
        ours = window_occupancy(planes, t=t)
        jaxs = chunk_occupancy(jax.numpy.asarray(planes), t)
        assert ours == pytest.approx(jaxs, abs=1e-6), t
        assert 0.0 < ours <= 1.0
    with pytest.raises(ValueError, match="plane groups"):
        window_occupancy(np.zeros((1, H, W, 2), np.uint8), t=9)


def test_packed_occupancy_firing_rate():
    # one event -> one bit: firing rate is exactly bits / (t * neurons)
    ev = EventStream(H, W, np.array([2]), np.array([3]),
                     np.array([0]), np.array([1]))
    planes = encode_events_to_plane_groups(ev, t=8, window_us=10)
    assert packed_occupancy(planes, 8) == pytest.approx(
        1.0 / (8 * H * W * POLARITIES))
    assert packed_occupancy(np.zeros((1, H, W, 2), np.uint8), 8) == 0.0


@pytest.mark.parametrize("t", [4, 8, 16])
def test_packed_occupancy_matches_unpacked_sum_exactly(t):
    rng = np.random.default_rng(t)
    spikes = rng.random((t, H, W, POLARITIES)) < 0.2
    planes = pack_timesteps(spikes)
    packed = np.asarray(planes)
    neurons = packed.size // packed.shape[0]
    unpacked = float(np.unpackbits(packed.reshape(-1)).sum()) / (t * neurons)
    assert packed_occupancy(planes, t) == unpacked
    assert packed_occupancy(planes, t) == spikes.mean()


def test_events_to_frame_counts_and_clip():
    n = 7
    ev = EventStream(H, W, np.full(n, 4), np.full(n, 5),
                     np.arange(n), np.full(n, 1))
    frame = events_to_frame(ev)
    assert frame.shape == (H, W, POLARITIES) and frame.dtype == np.uint8
    assert frame[5, 4, 1] == n and frame.sum() == n
    assert events_to_frame(ev, clip=3)[5, 4, 1] == 3
    with pytest.raises(ValueError, match="clip"):
        events_to_frame(ev, clip=0)


# ---------------------------------------------------------------------------
# session: windowing semantics against a scripted fake client
# ---------------------------------------------------------------------------

class FakeHandle:
    def __init__(self, labels):
        self.labels = labels

    def result(self, timeout=None):
        return self.labels


class FakeClient:
    """Scripted ServeClient: labels each image with a running counter,
    synchronously, and raises QueueFull for windows in ``full_at``."""

    def __init__(self, full_at=()):
        self.full_at = set(full_at)
        self.attempts = 0
        self.submissions = []

    def submit(self, images, *, rid=None, on_image=None):
        k = self.attempts
        self.attempts += 1
        if k in self.full_at:
            raise QueueFull("scripted")
        self.submissions.append(np.asarray(images))
        if on_image is not None:
            for i in range(len(images)):
                on_image(k, i, k)
        return FakeHandle([k] * len(images))


def session_over(client, **kw):
    kw.setdefault("window_us", 1_000)
    kw.setdefault("height", H)
    kw.setdefault("width", W)
    return EventStreamSession(client, **kw)


def events_at(*t_us, x=1, y=1, p=1):
    t = np.asarray(t_us, np.int64)
    n = len(t)
    return EventStream(H, W, np.full(n, x), np.full(n, y), t, np.full(n, p))


def test_session_watermark_windowing():
    client = FakeClient()
    seen = []
    s = session_over(client, on_window=lambda w, lab: seen.append((w, lab)))
    s.feed(events_at(100, 900))            # window 0, still open
    assert not client.submissions
    s.feed(events_at(1_100))               # watermark crosses 1_000: closes 0
    assert len(client.submissions) == 1
    assert client.submissions[0].shape == (1, H, W, POLARITIES)
    assert client.submissions[0][0, 1, 1, 1] == 2       # both window-0 events
    s.feed(events_at(5_500))               # closes 1..4; 1-4 empty -> skipped
    assert len(client.submissions) == 2
    s.close()                              # flush window 5
    assert len(client.submissions) == 3
    st = s.stats()
    assert st["windows_submitted"] == 3 and st["windows_empty"] == 3
    assert st["windows_closed"] == 6 and st["events_seen"] == 4
    assert s.labels() == {0: 0, 1: 1, 5: 2}
    assert seen == [(0, 0), (1, 1), (5, 2)]
    assert len(s.occupancy_trace()) == 3
    assert all(0 < occ <= 1 for occ in s.occupancy_trace())


def test_session_submit_empty_serves_quiet_windows():
    client = FakeClient()
    s = session_over(client, submit_empty=True)
    s.feed(events_at(100))
    s.feed(events_at(3_500))               # closes 0, 1, 2 (1 and 2 empty)
    assert len(client.submissions) == 3
    assert not client.submissions[1].any()
    assert s.stats()["windows_empty"] == 0


def test_session_late_events_raise():
    s = session_over(FakeClient())
    s.feed(events_at(2_500))               # closes 0 and 1
    with pytest.raises(ValueError, match="precedes the open window"):
        s.feed(events_at(1_500))
    # equal-time and later events are fine
    s.feed(events_at(2_600))


def test_session_sheds_on_queue_full():
    client = FakeClient(full_at={1})
    s = session_over(client)
    s.feed(events_at(500))
    s.feed(events_at(1_500))               # closes 0 (submitted)
    s.feed(events_at(2_500))               # closes 1 (shed)
    s.close()
    assert s.windows_shed == 1
    assert [r["shed"] for r in s.windows] == [False, True, False]
    assert s.windows[1]["label"] is None
    st = s.stats()
    assert st["windows_submitted"] == 2 and st["windows_shed"] == 1


def test_session_validates_construction_and_sensor():
    with pytest.raises(ValueError, match="window_us"):
        session_over(FakeClient(), window_us=0)
    with pytest.raises(ValueError, match="bins"):
        session_over(FakeClient(), bins=3)   # 3 does not divide 1000
    s = session_over(FakeClient())
    with pytest.raises(ValueError, match="sensor"):
        s.feed(empty_stream(H, W + 1).shift_time(0))
    with pytest.raises(ValueError, match="capture=False"):
        s.save_trace("/tmp/never_written.jsonl")


def test_session_capture_records_window_relative_events(tmp_path):
    fake_now = [0.0]
    client = FakeClient()
    s = session_over(client, capture=True, clock=lambda: fake_now[0])
    s.feed(events_at(100, 800))
    fake_now[0] = 0.5
    s.feed(events_at(1_200))
    s.close()
    assert [w for _, w, _ in s.captured] == [0, 1]
    t_s, w, ev = s.captured[0]
    assert list(ev.t_us) == [100, 800]     # window 0: already relative
    _, _, ev1 = s.captured[1]
    assert list(ev1.t_us) == [200]         # 1_200 relative to window 1
    path = tmp_path / "cap.jsonl"
    assert s.save_trace(path) == 2
    loaded = load_trace(path)
    assert loaded.window_us == 1_000 and len(loaded.arrivals) == 2
    np.testing.assert_array_equal(loaded.arrivals[1].events.t_us, [200])


# ---------------------------------------------------------------------------
# trace format: roundtrip, loud failures, checksum
# ---------------------------------------------------------------------------

def test_trace_roundtrip_byte_identical(tmp_path):
    p1, p2 = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    ev = busy_stream(duration_us=900)
    arrivals = [TraceArrival(t_s=0.1, window=0, events=ev),
                TraceArrival(t_s=0.3, window=2,
                             events=empty_stream(H, W))]
    record_trace(p1, height=H, width=W, window_us=1_000, bins=8,
                 arrivals=arrivals, meta={"k": 1})
    t = load_trace(p1)
    assert (t.height, t.width, t.window_us, t.bins) == (H, W, 1_000, 8)
    assert t.payload == "events" and t.meta == {"k": 1}
    assert t.duration_s == pytest.approx(0.3)
    np.testing.assert_array_equal(t.arrivals[0].events.x, ev.x)
    record_trace(p2, height=t.height, width=t.width, window_us=t.window_us,
                 bins=t.bins, arrivals=t.arrivals, meta=t.meta)
    assert p1.read_bytes() == p2.read_bytes()


def test_trace_load_fails_loud(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text("")
    with pytest.raises(ValueError, match="empty trace"):
        load_trace(p)
    p.write_text(json.dumps({"kind": "something_else"}) + "\n")
    with pytest.raises(ValueError, match="not a 'event_serve_trace'"):
        load_trace(p)
    p.write_text(json.dumps({"kind": "event_serve_trace",
                             "trace_version": 99}) + "\n")
    with pytest.raises(ValueError, match="trace_version=99"):
        load_trace(p)


def test_record_trace_validates(tmp_path):
    p = tmp_path / "t.jsonl"
    with pytest.raises(ValueError, match="payload"):
        record_trace(p, height=H, width=W, window_us=1_000, bins=8,
                     arrivals=[], payload="frames")
    bad = [TraceArrival(t_s=0.2), TraceArrival(t_s=0.1)]
    with pytest.raises(ValueError, match="time order"):
        record_trace(p, height=H, width=W, window_us=1_000, bins=8,
                     arrivals=bad, payload="counts")
    with pytest.raises(ValueError, match="no events"):
        record_trace(p, height=H, width=W, window_us=1_000, bins=8,
                     arrivals=[TraceArrival(t_s=0.1)])


def test_counts_payload_roundtrip_and_load(tmp_path):
    p = tmp_path / "counts.jsonl"
    arrivals = [TraceArrival(t_s=0.01, n_images=2),
                TraceArrival(t_s=0.02, n_images=1)]
    record_trace(p, height=H, width=W, window_us=1_000, bins=8,
                 arrivals=arrivals, payload="counts",
                 meta={"image_seed": 5})
    t = load_trace(p)
    assert [a.n_images for a in t.arrivals] == [2, 1]
    load, make = trace_to_load(t)
    assert [a.n_images for a in load] == [2, 1]
    imgs = make(0, 2)
    assert imgs.shape == (2, H, W, POLARITIES) and imgs.dtype == np.uint8


def test_trace_to_load_events_payload_is_replay_stable():
    ev = busy_stream(duration_us=900)
    t = EventTrace(height=H, width=W, window_us=1_000, bins=8,
                   payload="events",
                   arrivals=(TraceArrival(t_s=0.1, events=ev),))
    _, make1 = trace_to_load(t)
    _, make2 = trace_to_load(t)
    np.testing.assert_array_equal(make1(0, 1), make2(0, 1))
    np.testing.assert_array_equal(make1(0, 1)[0], events_to_frame(ev))


def test_labels_checksum_stable():
    a = labels_checksum([[1, 2], None, [3]])
    assert a == labels_checksum([[1, 2], None, [3]])
    assert len(a) == 16
    assert a != labels_checksum([[1, 2], None, [4]])


# ---------------------------------------------------------------------------
# end to end: the committed fixture replays deterministically through the
# real serving stack, 1 runtime and a 2-replica fleet
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dvs_model():
    trace = load_trace(FIXTURE)
    cfg = dataclasses.replace(
        SpikformerConfig().scaled(img_size=trace.height, dim=32, depth=1),
        in_channels=trace.channels)
    params = init(jax.random.PRNGKey(0), cfg)
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2, 8)))
    model.warmup()
    return trace, model


def test_fixture_is_committed_and_bursty():
    trace = load_trace(FIXTURE)
    assert trace.payload == "events" and trace.arrivals
    assert (trace.height, trace.width) == (16, 16)
    # the fixture's reason to exist: a bursty (non-Poisson) arrival gap
    # structure — silent stretches between event bursts
    gaps = np.diff([a.t_s for a in trace.arrivals])
    assert gaps.max() > 3 * np.median(gaps)


def test_replay_fixture_deterministic_one_runtime(dvs_model):
    trace, model = dvs_model
    policy = ServePolicy(max_wait_ms=10.0, slo_ms=2_000.0,
                         max_queue_images=64)

    def once():
        with AsyncServeRuntime(model, policy=policy) as rt:
            return replay_trace(trace, rt, slo_ms=2_000.0)

    m1, m2 = once(), once()
    assert m1["requests_dropped"] == 0 and m1["requests_rejected"] == 0
    assert m1["windows"] == len(trace.arrivals)
    assert all(lab is not None and len(lab) == 1 for lab in m1["labels"])
    assert m1["labels_sha"] == m2["labels_sha"]
    assert m1["labels"] == m2["labels"]
    assert m1["dispersion_index"] is not None


def test_replay_fixture_fleet_matches_single_replica(dvs_model):
    trace, model = dvs_model
    policy = ServePolicy(max_wait_ms=10.0, slo_ms=2_000.0,
                         max_queue_images=64)
    with AsyncServeRuntime(model, policy=policy) as rt:
        single = replay_trace(trace, rt, slo_ms=2_000.0)
    with ServeFleet(model, replicas=2, policy=policy) as fleet:
        dual = replay_trace(trace, fleet, slo_ms=2_000.0)
    assert dual["requests_dropped"] == 0 and dual["requests_rejected"] == 0
    assert dual["labels_sha"] == single["labels_sha"]
    assert dual["labels"] == single["labels"]


def test_session_capture_replay_reproduces_live_labels(dvs_model, tmp_path):
    """The full loop: a live session over the real runtime, captured,
    saved, loaded, replayed — the replay's labels equal the live run's."""
    trace, model = dvs_model
    policy = ServePolicy(max_wait_ms=10.0, slo_ms=2_000.0,
                         max_queue_images=64)
    stream = busy_stream(duration_us=60_000, seed=9)
    with AsyncServeRuntime(model, policy=policy) as rt:
        s = EventStreamSession(rt, window_us=20_000, height=H, width=W,
                               capture=True)
        s.feed(stream)
        s.close()
        live = [[s.windows[k]["label"]] for k in range(len(s.windows))]
        path = tmp_path / "live.jsonl"
        s.save_trace(path)
    with AsyncServeRuntime(model, policy=policy) as rt2:
        m = replay_trace(load_trace(path), rt2, slo_ms=2_000.0)
    assert m["labels"] == live
