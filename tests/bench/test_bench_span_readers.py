"""The readers that match serving spans by batch id: ``hold_ms`` takes the
median hold of the batches placed in the window (0 for a batch never
held), ``assemble_ms`` the median assembly of the batches stepped in it.
Both read nothing from spans that carry no batch id."""
from types import SimpleNamespace as NS

import pytest

from bench import spec

READERS = ("hold_ms", "assemble_ms")


def _batch(b, t, hold, asm, step, replica=None):
    """Batch ``b``'s spans, placed at ``t``: its hold (if any) ends there,
    then place, assemble, step."""
    out = [NS(name="hold", t0=t - hold, t1=t, batch=b, replica=replica)
           ] if hold else []
    return out + [
        NS(name="place", t0=t, t1=t + 1e-4, batch=b, replica=replica),
        NS(name="assemble", t0=t + 1e-4, t1=t + 1e-4 + asm, batch=b,
           replica=replica),
        NS(name="step", t0=t + 1e-4 + asm, t1=t + 1e-4 + asm + step,
           batch=b, replica=replica)]


TRACED = (_batch(0, 0.55, 0.004, 0.050, 0.35)     # placed before t0
          + _batch(1, 1.0, 0.020, 0.060, 0.3)
          + _batch(2, 1.5, 0.0, 0.070, 0.3)       # dispatched at once
          + _batch(3, 2.0, 0.025, 0.080, 0.3)
          + _batch(4, 2.9, 0.010, 0.090, 0.3)     # steps after t1
          + _batch(1, 2.2, 0.001, 0.001, 0.01, replica=1))


@pytest.mark.parametrize("name, want", [
    # holds of batches 1, 2, 3, 4 and replica 1's batch 1: 20, 0, 25, 10, 1
    ("hold_ms", 10.0),
    # assembly of batches 0 (its step ends in the window), 1, 2, 3 and
    # replica 1's batch 1: 50, 60, 70, 80, 1
    ("assemble_ms", 60.0),
])
def test_batch_span_reader_takes_the_window_median(name, want):
    run = NS(window=NS(t0=0.9, t1=3.0), spans=TRACED)
    assert spec.metric_reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_batch_span_reader_is_silent_outside_the_window(name):
    run = NS(window=NS(t0=4.0, t1=5.0), spans=TRACED)
    assert spec.metric_reader(name)(run) is None


@pytest.mark.parametrize("name", READERS)
def test_batch_span_reader_reads_nothing_without_batch_ids(name):
    """The spans of a program that links no request to a batch."""
    spans = [NS(name="queue", t0=t - w, t1=t) for t, w in
             ((0.5, 9.0), (1.0, 0.010), (2.0, 0.030), (3.0, 0.020))]
    spans += [NS(name="hold", t0=0.95, t1=1.0), NS(name="place", t0=1.0,
              t1=1.001), NS(name="assemble", t0=1.001, t1=1.05),
              NS(name="step", t0=1.05, t1=2.5)]
    run = NS(window=NS(t0=0.9, t1=3.0), spans=spans)
    assert spec.metric_reader(name)(run) is None
