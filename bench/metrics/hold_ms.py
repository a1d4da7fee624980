"""Median time the serving runtime held a batch open before dispatching
it: over the batches whose ``place`` span ends in the window, the ``hold``
span with the same batch id (``repro.obs``); a batch dispatched at its
first decision held 0 ms. A program whose spans carry no batch id reports
nothing."""
import statistics


def _key(s):
    return getattr(s, "replica", None), getattr(s, "batch", None)


def read(run):
    held = {}
    for s in run.spans:
        if s.name == "hold" and _key(s)[1] is not None:
            held[_key(s)] = held.get(_key(s), 0.0) + (s.t1 - s.t0) * 1e3
    placed = [_key(s) for s in run.spans
              if s.name == "place" and _key(s)[1] is not None
              and run.window.t0 <= s.t1 <= run.window.t1]
    return statistics.median(held.get(k, 0.0) for k in placed) \
        if placed else None
