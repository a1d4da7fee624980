"""The traffic generator and the driver: determinism from the seed, the
same work for every seed, latency from the due time, and a closed loop
that keeps its stated number of requests outstanding."""
import itertools
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)

from bench import drive, spec
from bench import traffic as gen

POISSON = spec.arrival_process("poisson")
CLOSED = spec.arrival_process("closed")
CAMERA = {"arrivals": "poisson", "rate_per_s": 25.0, "images_per_request": 2,
          "schedule_seed": 11, "warmup_s": 1.0, "buckets": [1, 2, 4, 8],
          "policy": {}}
BIG = 2 ** 31 + 123456789


@pytest.mark.parametrize("seed", [0, 7, BIG, 2 ** 40 + 5])
def test_open_arrivals_are_deterministic(seed):
    mix = {**CAMERA, "schedule_seed": seed}
    a = POISSON.schedule(mix, 30.0, 0)
    assert a == POISSON.schedule(mix, 30.0, 0)
    assert len(a) == 750
    assert all(0.0 <= x.t_s < 30.0 for x in a)
    assert [x.t_s for x in a] == sorted(x.t_s for x in a)
    assert a[0].t_s == 0.0
    assert all(x.n_images == 2 and x.first_image == 2 * i
               for i, x in enumerate(a))


def test_every_seed_offers_the_same_work_in_another_order():
    a, b = (POISSON.schedule({**CAMERA, "schedule_seed": s}, 30.0, 0)
            for s in (1, BIG))
    gaps = [np.diff([x.t_s for x in arr] + [30.0]) for arr in (a, b)]
    assert not np.allclose(gaps[0], gaps[1])
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]))


def test_a_schedule_seed_fixes_the_order_for_every_seed():
    """The schedule is the mix's alone (its ``schedule_seed`` is required);
    the run's seed chooses only the images."""
    assert POISSON.schedule(CAMERA, 30.0, 0) == POISSON.schedule(
        dict(CAMERA), 30.0, 0)
    assert POISSON.schedule(CAMERA, 30.0, 1) != POISSON.schedule(
        CAMERA, 30.0, 0)
    with pytest.raises(KeyError):
        POISSON.schedule({k: v for k, v in CAMERA.items()
                          if k != "schedule_seed"}, 30.0, 0)
    pools = [gen.image_pool((8, 8, 3), 16, s) for s in (1, BIG)]
    assert not np.array_equal(*pools)


def test_warmup_precedes_the_window():
    w = gen.warmup_arrivals(POISSON, CAMERA)
    assert len(w) == 25 and all(-1.0 <= x.t_s < 0.0 for x in w)
    assert gen.warmup_arrivals(POISSON, {**CAMERA, "warmup_s": 0}) == []


def test_closed_process_repeats_its_request():
    mix = {"arrivals": "closed", "clients": 3, "images_per_request": 5}
    assert CLOSED.LOOP == "closed" and CLOSED.clients(mix) == 3
    assert list(itertools.islice(CLOSED.requests(mix, 2), 4)) == [5] * 4


def test_images_are_deterministic():
    p1 = gen.image_pool((8, 8, 3), 16, BIG)
    assert np.array_equal(p1, gen.image_pool((8, 8, 3), 16, BIG))
    assert not np.array_equal(p1, gen.image_pool((8, 8, 3), 16, BIG + 1))
    assert np.array_equal(gen.take(p1, 15, 2), p1[[15, 0]])


def test_nearest_rank():
    assert gen.nearest_rank(range(1, 101), 0.95) == 95
    assert gen.nearest_rank([1.0, float("inf")], 0.95) == float("inf")


class FakeClient:
    """Answers each request after ``service_s`` in a worker thread and
    records how many requests were outstanding at every submit."""

    def __init__(self, service_s=0.002):
        self.service_s = service_s
        self.lock = threading.Lock()
        self.outstanding = 0
        self.seen = []

    def submit(self, images):
        fut = Future()
        with self.lock:
            self.outstanding += 1
            self.seen.append(self.outstanding)

        def answer():
            time.sleep(self.service_s)
            with self.lock:
                self.outstanding -= 1
            fut.set_result([0] * len(images))

        threading.Thread(target=answer, daemon=True).start()
        return type("Handle", (), {"future": fut})


def test_closed_loop_keeps_its_clients_outstanding():
    traffic = {"arrivals": "closed", "clients": 6, "images_per_request": 3,
               "warmup_s": 0.05, "buckets": [8]}
    client = FakeClient()
    d = drive.Drive(client, traffic, CLOSED, 0.5,
                    gen.image_pool((4, 4, 3), 64, 5))
    d.start()
    t0 = d.wait_open()
    d.close()
    w = d.drain()
    assert max(client.seen) <= 6
    assert np.median(client.seen) == 6      # each answer is followed at
    assert w.t0 == t0 and w.t1 > t0        # once by that client's next
    assert all(r.ok and r.n == 3 for r in w.records)


def test_open_loop_times_latency_from_the_due_time():
    traffic = {"arrivals": "poisson", "rate_per_s": 200.0,
               "images_per_request": 1, "schedule_seed": BIG,
               "warmup_s": 0.05, "buckets": [1]}
    client = FakeClient(service_s=0.01)
    d = drive.Drive(client, traffic, POISSON, 0.5,
                    gen.image_pool((4, 4, 3), 64, 1))
    d.start()
    d.wait_open()
    d.close()
    w = d.drain()
    due = w.due()
    assert len(due) == 100
    lat = w.latencies_ms()
    assert all(x >= 10.0 for x in lat)      # service time at least
    assert all(r.sent >= r.due for r in due)
    assert len(w.lateness_s) == 100


def test_closed_window_is_whole_steps():
    recs = [drive.Record(due=0.0, n=4, done=1.0001, ok=True, answered=True)]
    w = drive.Window(t0=1.0001, t1=5.0, records=recs, lateness_s=[])
    steps = [(0.5, 8), (1.0, 8), (2.0, 8), (3.0, 8), (4.0, 8), (4.5, 8),
             (5.2, 8)]
    s = w.on_steps(steps, 3.6)
    assert (s.t0, s.t1) == (1.0, 4.5)       # the opening step is left out
    assert s.img_per_s() == pytest.approx(4 * 8 / 3.5)
