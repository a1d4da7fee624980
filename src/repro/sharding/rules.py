"""Path-based sharding rules: parameter paths -> PartitionSpecs.

Storage layout is FSDP x TP (ZeRO-3 style): 2-D weights shard their input dim
over the data(+pod) axes and their output dim over the model axis; MoE expert
tensors shard the expert dim over data(+pod) (expert parallelism) and the
hidden dim over model. Rules match on path *suffixes* and specify trailing
dims only — stacked-layer leading dims (L, ...) are padded with None
automatically, so the same table covers scanned and unrolled models.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.module import map_with_path


def dp_axes(mesh: Mesh):
    """The data-parallel axis group: ('pod','data') on multi-pod meshes."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def serving_mesh(devices=None) -> Mesh:
    """A 1-D ``("data",)`` mesh over the host's devices — the axis a
    serving fleet replicates over. Inference replicas are pure data
    parallelism (whole-model copies, batches split across them), so the
    fleet consumes only this axis; the FSDP x TP rule table above is the
    training/large-model story."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if not devices:
        raise ValueError("serving_mesh needs at least one device")
    return Mesh(np.asarray(devices), ("data",))


def replica_devices(n: int, mesh: Mesh | None = None) -> list:
    """Device assignment for ``n`` data-parallel serving replicas: replica
    ``i`` serves from device ``i % mesh_size`` along the data axis of
    ``mesh`` (default: ``serving_mesh()`` over the host).

    On a single-device host every entry is ``None`` — the fleet's
    thread-backed mode, where replicas share the default device (and the
    jitted step; see ``repro.infer.compile.replicate_model``) instead of
    paying a pointless device_put onto the device they are already on."""
    if n < 1:
        raise ValueError(f"need n >= 1 replicas, got {n!r}")
    mesh = serving_mesh() if mesh is None else mesh
    devs = list(np.asarray(mesh.devices).flat)
    if len(devs) <= 1:
        return [None] * n
    return [devs[i % len(devs)] for i in range(n)]


# (regex on path, spec builder over (dp,)) — first match wins
_RULES = [
    (r"embed/embedding$",              lambda dp: ("model", dp)),
    (r"head/kernel$",                  lambda dp: (dp, "model")),
    (r"(wq|wk|wv)/kernel$",            lambda dp: (dp, "model")),
    (r"wo/kernel$",                    lambda dp: ("model", dp)),
    (r"(gate|up)/kernel$",             lambda dp: (dp, "model")),
    (r"down/kernel$",                  lambda dp: ("model", dp)),
    (r"moe/router$",                   lambda dp: (dp, None)),
    (r"moe/w_(gate|up)$",              lambda dp: (dp, None, "model")),
    (r"moe/w_down$",                   lambda dp: (dp, "model", None)),
    (r"ssm/in_proj$",                  lambda dp: (dp, None)),
    (r"ssm/out_proj$",                 lambda dp: (None, dp)),
]


def spec_for(path: str, shape: tuple, mesh: Mesh) -> P:
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else dp[0]
    for pat, builder in _RULES:
        if re.search(pat, path):
            trailing = builder(dp)
            lead = (None,) * (len(shape) - len(trailing))
            spec = lead + tuple(trailing)
            # verify divisibility; drop axes that don't divide evenly
            fixed = []
            for dim, ax in zip(shape, spec):
                if ax is None:
                    fixed.append(None)
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                fixed.append(ax if dim % size == 0 else None)
            return P(*fixed)
    return P()  # replicate (norm scales, biases, small vectors)


def param_shardings(mesh: Mesh, params_shapes):
    """params_shapes: pytree of ShapeDtypeStructs (from jax.eval_shape)."""
    return map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path, leaf.shape, mesh)),
        params_shapes)


def opt_state_shardings(mesh: Mesh, opt_shapes):
    """Moments share the param rules (paths are nested under m/ and v/)."""
    def fn(path, leaf):
        clean = re.sub(r"^(m|v)/", "", path)
        if path == "step":
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec_for(clean, leaf.shape, mesh))
    return map_with_path(fn, opt_shapes)


def batch_shardings(mesh: Mesh, batch_shapes):
    """Inputs: shard the batch dim over dp when divisible, else replicate."""
    dp = dp_axes(mesh)
    dpsize = 1
    for a in dp:
        dpsize *= mesh.shape[a]
    dp_spec = dp if len(dp) > 1 else dp[0]

    def fn(path, leaf):
        shape = leaf.shape
        if not shape:
            return NamedSharding(mesh, P())
        if "mrope" in path:  # (3, B, S)
            if shape[1] % dpsize == 0:
                return NamedSharding(mesh, P(None, dp_spec))
            return NamedSharding(mesh, P())
        if shape[0] % dpsize == 0:
            return NamedSharding(mesh, P(dp_spec))
        return NamedSharding(mesh, P())
    return map_with_path(fn, batch_shapes)


def cache_shardings(mesh: Mesh, cache_shapes):
    """Decode caches: KV (B, KV, S, dh) -> batch over dp if divisible, S over
    model (sequence-sharded cache => per-chip cache bytes / 16). SSM states
    shard batch only. `positions` vectors replicate."""
    dp = dp_axes(mesh)
    dpsize = 1
    for a in dp:
        dpsize *= mesh.shape[a]
    dp_spec = dp if len(dp) > 1 else dp[0]

    def fn(path, leaf):
        shape = leaf.shape
        if not shape:
            return NamedSharding(mesh, P())
        if path.endswith("positions"):
            # per-row slot positions (..., B, length): batch over dp
            if len(shape) >= 2 and shape[-2] % dpsize == 0:
                return NamedSharding(mesh, P(
                    *(None,) * (len(shape) - 2), dp_spec, None))
            return NamedSharding(mesh, P())
        b_ok = shape[-4] % dpsize == 0 if len(shape) >= 4 else False
        if re.search(r"(kv/k|kv/v|cross_k|cross_v)$", path) and len(shape) >= 4:
            seq_ok = shape[-2] % mesh.shape["model"] == 0
            lead = (None,) * (len(shape) - 4)
            return NamedSharding(mesh, P(
                *lead, dp_spec if b_ok else None, None,
                "model" if seq_ok else None, None))
        # ssm / conv states: batch over dp. State is (..., B, H, P, N) and
        # conv buffer is (..., B, k-1, C) — locate B from the right so the
        # same rule covers stacked (scan) and per-layer (unrolled) trees.
        if path.endswith("ssm"):
            bidx = len(shape) - 4
        elif path.endswith("conv"):
            bidx = len(shape) - 3
        else:
            return NamedSharding(mesh, P())
        if bidx >= 0 and shape[bidx] % dpsize == 0:
            spec = [None] * len(shape)
            spec[bidx] = dp_spec
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())
    return map_with_path(fn, cache_shapes)
