"""Closed loop: ``clients`` clients, each with one request of
``images_per_request`` images outstanding; a client sends its next request
the moment its previous one is answered."""
from __future__ import annotations

import itertools

LOOP = "closed"


def clients(mix: dict) -> int:
    return int(mix["clients"])


def requests(mix: dict, client: int):
    """Sizes of one client's requests, endless."""
    return itertools.repeat(int(mix["images_per_request"]))
