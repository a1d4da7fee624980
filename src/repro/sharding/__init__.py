"""Sharding: path-based parameter rules + activation hints.

The public surface, in two layers:

* ``rules`` — path -> PartitionSpec tables for parameters, optimizer
  state, batches and caches (FSDP x TP storage layout), plus the serving
  fleet's data-parallel axis: ``serving_mesh()`` / ``replica_devices()``
  assign whole-model replicas to devices (``repro.serve.fleet`` consumes
  these; on a single-device host the assignment degrades to thread-backed
  ``None`` entries).
* ``hints`` — ``shard_hint`` activation layout pins that no-op without an
  active mesh, so model code runs unchanged on one CPU device.

Mesh contexts are plain ``jax.set_mesh`` / ``jax.sharding.get_abstract_mesh``;
meshes come from ``repro.launch.mesh`` (``Auto`` axes, so hints steer the
partitioner instead of asserting).
"""
from . import hints, rules
from .hints import shard_hint
from .rules import (batch_shardings, cache_shardings, dp_axes,
                    opt_state_shardings, param_shardings, replica_devices,
                    serving_mesh, spec_for)

__all__ = [
    # submodules
    "rules", "hints",
    # rule tables + fleet placement
    "dp_axes", "spec_for", "param_shardings", "opt_state_shardings",
    "batch_shardings", "cache_shardings", "serving_mesh", "replica_devices",
    # activation hints
    "shard_hint",
]
