"""int8 weight quantization for the packed inference datapath.

VESTA's PE multiplies one 8-bit-integer weight by one binary spike; the float
route of this reproduction uses f32 weights only because they fall out of BN
folding. This module closes the gap: every BN-folded kernel is quantized to
int8 with a per-output-channel symmetric scale, and — the part that keeps the
datapath integer — the scale is never applied to the accumulators. Instead it
is folded into the LIF threshold comparison:

    acc      = sum_k spike_k * wq[k, n]              (exact small integers)
    fires    <=>  h(acc*s + bias) >= v_th
             <=>  h(acc  + bias/s) >= v_th / s       (LIF dynamics are
                                                      per-channel linear)

so the packed route runs LIF on the raw integer accumulators with a
per-channel bias ``bias/s`` and threshold ``v_th/s`` (see
``kernels.ops.tflif_pack``'s vector ``v_th``). The LIF recurrence
``h = v + (x + b - v)/tau``, the hard reset, and the comparison are all
homogeneous of degree 1 in (x, b, v, v_th), so the rescaled dynamics fire on
exactly the same set of timesteps.

The exactness reference for this route is the *float emulation*: the same
quantized integer weights run through the float graph with the same
scale-folded bias/threshold (``FloatBackend`` with a quantized tree). The two
are bit-identical on CPU; quantization *error* vs the original float weights
is a model-accuracy question, measured end-to-end, not hidden in kernels.

STDP attention has no weights (binary q/k/v), and the classifier head runs on
float rates — both stay untouched.
"""
from __future__ import annotations

import jax.numpy as jnp

WEIGHT_DTYPES = ("float32", "int8")


def folded_layers(folded):
    """``(path, layer)`` of every conv/linear layer dict (one holding a
    ``kernel``) of a folded tree, in tree order, below the top-level keys
    other than the float ``head``."""
    def walk(node, path):
        if "kernel" in node:
            yield path, node
            return
        for key, child in node.items():
            if isinstance(child, dict):
                yield from walk(child, f"{path}/{key}")

    for key, node in folded.items():
        if key != "head" and isinstance(node, dict):
            yield from walk(node, key)


def map_folded_layers(folded, fn):
    """Apply ``fn(path, layer) -> layer`` to every conv/linear layer dict of
    a folded tree (``folded_layers``), rebuilding the dicts above them and
    passing everything else (the head, ...) through untouched. The ONE
    place the folded-tree layer schema is walked — quantization, route
    planning, and annotation stripping all go through here, for any
    network."""
    def walk(node, path):
        if "kernel" in node:
            return fn(path, node)
        return {key: walk(child, f"{path}/{key}")
                if isinstance(child, dict) else child
                for key, child in node.items()}

    return {key: walk(node, key)
            if key != "head" and isinstance(node, dict) else node
            for key, node in folded.items()}


def quantize_layer(layer):
    """{kernel, bias} -> {kernel: int8, scale: (N,) f32, bias} per-channel
    symmetric quantization over the output-channel (last) axis."""
    w = layer["kernel"].astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = jnp.where(amax > 0, amax / 127.0, jnp.float32(1.0))
    wq = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {"kernel": wq, "scale": scale, "bias": layer["bias"]}


def quantize_folded(folded):
    """Quantize a ``fold_inference_params`` tree to int8 weights.

    Every conv and linear gains a ``scale`` leaf and an int8 ``kernel``;
    the float head is passed through unchanged. Backends
    detect the ``scale`` leaf and switch to the threshold-folded LIF.
    """
    return map_folded_layers(folded, lambda _, layer: quantize_layer(layer))
