"""Median host time to assemble a batch (gather, stack, pad and the
occupancy readout): over the steps whose ``step`` span ends in the window,
the ``assemble`` span with the same batch id (``repro.obs``). A program
whose spans carry no batch id reports nothing."""
import statistics


def _key(s):
    return getattr(s, "replica", None), getattr(s, "batch", None)


def read(run):
    assembled = {_key(s): (s.t1 - s.t0) * 1e3 for s in run.spans
                 if s.name == "assemble" and _key(s)[1] is not None}
    times = [assembled[_key(s)] for s in run.spans
             if s.name == "step" and _key(s) in assembled
             and run.window.t0 <= s.t1 <= run.window.t1]
    return statistics.median(times) if times else None
