"""Drive a serving client with a traffic mix and time every request.

The mix's arrival process (``bench/traffic.py``) says which loop it runs.
Open loop: each arrival is submitted at its due time whatever has
completed; latency runs from the due time, so a stall also counts against
the requests queued behind it, and how late the generator itself ran is
recorded. Closed loop: each client submits its next request the moment its
previous one completes.

The window: an open loop's opens at the first due time after the warm-up
and lasts ``seconds``. A closed loop's opens at the first completion after
the warm-up; ``Window.on_steps`` then puts it on the ends of whole steps,
so that its rate is whole steps over the time they took (the requests of
one step complete together, and the first of them opens the window).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

from bench import traffic as gen

CLOCK = time.perf_counter
DRAIN_S = 60.0          # an answer may come this long after the window


@dataclasses.dataclass
class Record:
    due: float
    n: int
    sent: float = math.nan
    done: float | None = None      # when its result came, on CLOCK
    ok: bool = False
    answered: bool = False         # resolved, or refused at the door


@dataclasses.dataclass
class Window:
    t0: float
    t1: float                       # close; a closed loop's last step's end
    records: list
    lateness_s: list                # open loop: sent - due, window requests
    step_images: list | None = None  # closed loop: [(step end, images)]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def due(self) -> list:
        return [r for r in self.records if self.t0 <= r.due < self.t1]

    def on_steps(self, steps, seconds: float) -> "Window":
        """A closed loop's window on whole steps: ``steps`` is
        ``[(end, images)]`` of every served step. The window opens at the
        end of the step whose results opened it and closes at the end of
        the last step within ``seconds`` of that."""
        t0 = max(e for e, _ in steps if e <= self.t0)
        t1 = max(e for e, _ in steps if e <= t0 + seconds)
        return dataclasses.replace(self, t0=t0, t1=t1, step_images=steps)

    def img_per_s(self) -> float:
        """Images whose results came inside the window, over its length: a
        closed loop's whole steps after its opening one, an open loop's
        completed requests."""
        if self.step_images is not None:
            n = sum(k for e, k in self.step_images if self.t0 < e <= self.t1)
        else:
            n = sum(r.n for r in self.records if r.ok and r.done is not None
                    and self.t0 <= r.done <= self.t1)
        return n / self.seconds

    def latencies_ms(self) -> list:
        """Due to result, per request due in the window. One that failed or
        never came counts as waiting until the drain gave up, above any
        limit."""
        never = self.t0 + (self.t1 - self.t0) + DRAIN_S
        return [((r.done if r.ok and r.done is not None else never) - r.due)
                * 1e3 for r in self.due()]


def _submit(client, rec: Record, images, on_done) -> None:
    from repro.serve import QueueFull
    rec.sent = CLOCK()
    try:
        handle = client.submit(images)
    except QueueFull:
        rec.answered = True
        on_done(rec)
        return

    def done(fut, rec=rec):
        rec.done = CLOCK()
        rec.ok = fut.exception() is None
        rec.answered = True
        on_done(rec)

    handle.future.add_done_callback(done)


class Drive:
    """Runs one mix against ``client`` in background threads: ``start``,
    then ``wait_open`` (until the window opens), ``close`` (until it
    closes) and ``drain`` (until every answer has come, or ``DRAIN_S``
    passed)."""

    def __init__(self, client, traffic: dict, process, seconds: float,
                 pool):
        self.client, self.traffic, self.process = client, traffic, process
        self.seconds, self.pool = seconds, pool
        self.closed = process.LOOP == "closed"
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._completions: list[float] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self.t0 = self.t1 = None

    def _on_done(self, rec: Record) -> None:
        with self._cv:
            if rec.ok:
                self._completions.append(rec.done)
            self._cv.notify_all()

    # -- open loop ----------------------------------------------------------

    def _open(self, start: float, arrivals) -> None:
        for a in arrivals:
            due = start + a.t_s
            delay = due - CLOCK()
            if delay > 0:
                time.sleep(delay)
            rec = Record(due=due, n=a.n_images)
            with self._lock:
                self.records.append(rec)
            _submit(self.client, rec, gen.take(self.pool, a.first_image,
                                               a.n_images), self._on_done)

    # -- closed loop --------------------------------------------------------

    def _client(self, c: int) -> None:
        sizes = self.process.requests(self.traffic, c)
        first = c * 7919
        while not self._stop.is_set():
            n = next(sizes)
            rec = Record(due=CLOCK(), n=n)
            with self._lock:
                self.records.append(rec)
            came = threading.Event()
            _submit(self.client, rec, gen.take(self.pool, first, n),
                    lambda r, came=came: (self._on_done(r), came.set()))
            first += n
            came.wait()
            if not rec.ok:
                return

    # -- the window ---------------------------------------------------------

    def start(self) -> None:
        warm = float(self.traffic.get("warmup_s", 0.0))
        if self.closed:
            self._start_s = CLOCK()
            self._open_after = self._start_s + warm
            target = [(self._client, (c,))
                      for c in range(self.process.clients(self.traffic))]
        else:
            arrivals = (gen.warmup_arrivals(self.process, self.traffic)
                        + self.process.schedule(self.traffic, self.seconds,
                                                0))
            self.t0 = CLOCK() + warm + 0.05
            self.t1 = self.t0 + self.seconds
            target = [(self._open, (self.t0, arrivals))]
        for fn, args in target:
            th = threading.Thread(target=fn, args=args, daemon=True)
            th.start()
            self._threads.append(th)

    def wait_open(self) -> float:
        """Block until the window opens; returns its start on CLOCK."""
        if not self.closed:
            delay = self.t0 - CLOCK()
            if delay > 0:
                time.sleep(delay)
            return self.t0
        with self._cv:
            while True:
                after = [t for t in self._completions if t >= self._open_after]
                if after:
                    self.t0 = min(after)
                    return self.t0
                if not self._cv.wait(timeout=DRAIN_S):
                    raise RuntimeError("no request completed in "
                                       f"{DRAIN_S} s of warm-up")

    def close(self) -> None:
        """Block until the window closes; closed-loop clients then stop."""
        delay = self.t0 + self.seconds - CLOCK()
        if delay > 0:
            time.sleep(delay)
        self._stop.set()

    def drain(self) -> Window:
        """Wait for every answer (up to ``DRAIN_S`` past the close)."""
        close = self.t0 + self.seconds
        deadline = close + DRAIN_S
        for th in self._threads:
            th.join(max(0.0, deadline - CLOCK()))
        with self._cv:
            while (not all(r.answered for r in self.records)
                   and CLOCK() < deadline):
                self._cv.wait(timeout=max(0.0, deadline - CLOCK()))
            records = list(self.records)
        window = Window(self.t0, close, records, [])
        if not self.closed:
            window.lateness_s = [r.sent - r.due for r in window.due()]
        return window
