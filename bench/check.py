"""The comparison that decides ``correct``: the served step's logits against
the plain reference's, image by image.

One number, ``logit_gap``: the largest difference of any logit of any
image in the sample, scaled by the spread of that image's reference logits
(their standard deviation over the classes). Its limit is in the
configuration file (``limits``), set from readings of sound runs and of the
lower-precision control (``PERF.md``). How far the served label's
reference logit lies below the reference's best was tried as a second
number and dropped: the control leaves every label of a sample unchanged on
some seeds, so it has no upper reading.
"""
from __future__ import annotations

import numpy as np


def compare(served: np.ndarray, ref: np.ndarray) -> dict:
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    gap = np.abs(served - ref).max(axis=1) / ref.std(axis=1)
    return {"logit_gap": float(np.max(gap))}


def distinct(ref: np.ndarray) -> bool:
    """The reference gives different logits to different images: a network
    whose spikes died out answers every image alike, and any comparison
    with it passes."""
    ref = np.asarray(ref)
    return len(ref) < 2 or bool(np.any(ref != ref[:1]))


def judge(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
