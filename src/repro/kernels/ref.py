"""Pure-jnp oracles for every Pallas kernel (the ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.spike import num_plane_groups


def spike_matmul_ref(x_packed, w, *, mode: str = "per_plane"):
    """x_packed: (M, K) or (G, M, K) uint8; w: (K, N).

    2D input -> (8, M, N) per-plane / (M, N) shift_sum, as the Pallas kernel.
    3D input (plane groups, mode="per_plane" only) -> (G, 8, M, N)."""
    if x_packed.ndim == 3:
        assert mode == "per_plane", "plane groups are temporal: per_plane only"
        return jnp.stack([spike_matmul_ref(xg, w, mode=mode)
                          for xg in x_packed])
    bits = ((x_packed[None, :, :] >> jnp.arange(8, dtype=jnp.uint8)[:, None, None])
            & jnp.uint8(1)).astype(jnp.float32)           # (8, M, K)
    per_plane = jnp.einsum("pmk,kn->pmn", bits, w.astype(jnp.float32))
    if mode == "per_plane":
        return per_plane
    scales = (2.0 ** jnp.arange(8, dtype=jnp.float32)).reshape(8, 1, 1)
    return (per_plane * scales).sum(axis=0)


def tflif_ref(x, bias=None, *, tau: float = 2.0, v_th=1.0):
    """x: (T, ...) -> (G, ...) uint8 packed spikes, G = ceil(T/8); bit j of
    group g is the spike at timestep 8g+j. The membrane state is carried
    across group boundaries (one sequential scan over all T). ``bias`` and
    ``v_th`` are scalars or arrays broadcastable against ``x.shape[1:]``
    (per-neuron thresholds carry the int8 weight-scale fold).

    Runs natively on any rank — flattening the neuron axes in-graph forces
    XLA CPU's fusion emitter into reshape-chasing loop nests that cost ~10x;
    broadcasting over the natural trailing axes vectorizes cleanly, and
    broadcast shape never changes per-element IEEE results, so exactness
    contracts are unaffected.
    """
    t_steps = x.shape[0]
    lead = x.shape[1:]
    groups = num_plane_groups(t_steps)
    if bias is None:
        bias = jnp.float32(0.0)
    v_th = jnp.asarray(v_th, jnp.float32)
    v = jnp.zeros(lead, jnp.float32)
    out = []
    for g in range(groups):
        packed = jnp.zeros(lead, jnp.uint8)
        for j in range(min(8, t_steps - 8 * g)):
            h = v + (x[8 * g + j].astype(jnp.float32) + bias - v) / tau
            s = h >= v_th
            v = jnp.where(s, 0.0, h)
            packed = packed | (s.astype(jnp.uint8) << jnp.uint8(j))
        out.append(packed)
    return jnp.stack(out)


def stdp_attention_ref(q, k, v, *, scale: float):
    """q, k, v: (BH, N, Dh) -> (Q Kt) V * scale."""
    s = jnp.einsum("bnd,bmd->bnm", q.astype(jnp.float32), k.astype(jnp.float32))
    return jnp.einsum("bnm,bmd->bnd", s, v.astype(jnp.float32)) * scale


def flash_attention_ref(q, k, v, *, scale: float, causal: bool = True):
    """q: (BH, Nq, Dh); k, v: (BH, Nkv, Dh). Exact softmax attention."""
    nq, nkv = q.shape[1], k.shape[1]
    s = jnp.einsum("bnd,bmd->bnm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qpos = (nkv - nq) + jnp.arange(nq)[:, None]
        kpos = jnp.arange(nkv)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnm,bmd->bnd", p, v.astype(jnp.float32))


def qk_token_attention_ref(q, k, *, heads: int):
    """(..., D) uint8 packed Q, K -> (..., D) uint8 packed X': the OR of
    each head's Q bytes, ANDed into that head's K bytes."""
    *lead, d = q.shape
    a = jnp.bitwise_or.reduce(q.reshape(*lead, heads, d // heads), axis=-1)
    return k & jnp.repeat(a, d // heads, axis=-1)
