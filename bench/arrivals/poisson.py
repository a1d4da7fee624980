"""Open loop: Poisson-shaped arrivals, fixed in amount and in order.

Mix keys: ``rate_per_s``, ``images_per_request`` and ``schedule_seed``.
A window of ``seconds`` holds ``round(rate_per_s * seconds)`` arrivals of
``images_per_request`` images each. The gaps between them are the
quantiles of the exponential distribution at the rate, so every run offers
the same work. A queue's tail depends on how the gaps cluster as well, so
their order is drawn from the mix's ``schedule_seed`` and every run replays
one schedule (as MLPerf's LoadGen fixes its schedule seed); the run's seed
chooses the images and the weights.
"""
from __future__ import annotations

import numpy as np

from bench import traffic

LOOP = "open"


def schedule(mix: dict, seconds: float, stream: int) -> list:
    rate = float(mix["rate_per_s"])
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = traffic.rng(mix["schedule_seed"], stream).permutation(gaps)
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    times *= seconds / gaps.sum()
    size = int(mix["images_per_request"])
    return [traffic.Arrival(float(t), size, i * size)
            for i, t in enumerate(times)]
