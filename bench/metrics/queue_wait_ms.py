"""Median wait from admission to dispatch of the requests dispatched in
the window: the serving runtime's ``queue`` spans (``repro.obs``)."""
import statistics


def read(run):
    waits = [(s.t1 - s.t0) * 1e3 for s in run.spans
             if s.name == "queue" and run.window.t0 <= s.t1 <= run.window.t1]
    return statistics.median(waits) if waits else None
