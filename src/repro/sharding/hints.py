"""Activation sharding hints.

``shard_hint(x, "dp", None, "model")`` pins a tensor's layout when a mesh
context is active and the dims divide evenly; otherwise it is a no-op, so
model code stays runnable on a single CPU device. "dp" expands to the
("pod", "data") axis group on multi-pod meshes.

These hints are what keep XLA's SPMD propagation from replicating the big
activations (fp32 logits, attention heads) — without them the 49k-152k-vocab
unembed replicates onto every chip.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P, get_abstract_mesh


def shard_hint(x, *dims):
    am = get_abstract_mesh()
    if am.empty:
        return x
    names = am.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    spec = []
    for d, size in zip(dims, x.shape):
        if d is None:
            spec.append(None)
        elif d == "dp":
            dpsize = math.prod(am.shape[a] for a in dp)
            ok = dp and size % dpsize == 0
            spec.append((dp if len(dp) > 1 else dp[0]) if ok else None)
        else:
            ok = d in names and size % am.shape[d] == 0
            spec.append(d if ok else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
