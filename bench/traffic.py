"""The traffic generator. A mix is a data file, ``bench/traffic/<mix>.json``,
that names its arrival process; the process is a file of its own,
``bench/arrivals/<arrivals>.py``, found by that name::

    {"arrivals": "poisson", "rate_per_s": 19.0, "images_per_request": 1,
     "schedule_seed": 20260315, "warmup_s": 3.0, "limit_ms": 250.0,
     "buckets": [1, 2, 4, 8], "policy": {"slo_ms": 250.0}}

    {"arrivals": "closed", "clients": 8, "images_per_request": 8,
     "warmup_s": 1.0, "buckets": [32], "policy": {}}

A process drives one of the two loops of ``bench/drive.py``. An open-loop
process (``LOOP = "open"``) gives ``schedule(mix, seconds, stream)``: the
``Arrival`` s of a span of ``seconds``, due in ``[0, seconds)``, whatever is
answered (``stream`` 0 is the window, 1 the warm-up). A closed-loop process
(``LOOP = "closed"``) gives ``clients(mix)`` and ``requests(mix, client)``,
each client's request sizes, endless; a client keeps one request
outstanding. ``buckets`` and ``policy`` (``repro.serve.ServePolicy``
fields) are how the served model is set up for the mix. The run's seed
chooses the images; the arrivals are the mix's alone.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    t_s: float          # due time, seconds after the window opens
    n_images: int
    first_image: int    # index into the image pool


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def warmup_arrivals(process, mix: dict) -> list[Arrival]:
    """Arrivals of the warm-up before the window, at negative due times."""
    w = float(mix.get("warmup_s", 0.0))
    if w <= 0:
        return []
    return [dataclasses.replace(a, t_s=a.t_s - w)
            for a in process.schedule(mix, w, 1)]


def image_pool(shape: tuple, count: int, seed: int) -> np.ndarray:
    """``count`` random uint8 images; requests take consecutive images from
    the pool (cyclically), so a run draws no random numbers in its window."""
    return rng(seed, 99).integers(0, 256, (count, *shape), dtype=np.uint8)


def pool_size(traffic: dict) -> int:
    """Enough distinct images that no bucket repeats one, and that a
    sample over the window draws from many."""
    return max(8 * max(traffic["buckets"]), 8 * traffic["images_per_request"],
               256)


def take(pool: np.ndarray, first: int, n: int) -> np.ndarray:
    idx = (first + np.arange(n)) % len(pool)
    return pool[idx]


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (``inf`` entries allowed)."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]
