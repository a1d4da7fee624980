"""The unified PE: VESTA's four dataflows expressed on one engine.

All four computational layer types of the spiking transformer reduce to ONE
primitive — a weight-stationary matmul over binary planes — differing only in
(a) where the planes come from and (b) how planes are reduced:

  WSSL  planes = T timesteps of spikes,   per-plane outputs (weight stationary)
  ZSC   planes = T timesteps of spikes,   conv2x2/s2 == space-to-depth + WSSL
  SSSC  planes = 8 bit-planes of a uint8, outputs summed with scales 2^k
  STDP  planes = T timesteps,             (Q Kt) V fused tile-wise, no softmax

QKFormer (``core.qkformer``) adds layout and attention ops around the same
primitive: ``im2col`` (a 3x3 conv becomes WSSL/SSSC rows), ``max_pool``
over ``pool_windows`` and Q-K token attention (``qkta``). ``LayerSpec`` is
the entry of a network's layer list, which the compile passes read.

This module is the float/differentiable reference used for training (spikes
are {0,1} floats carrying surrogate gradients). The packed-bit inference path
lives in ``repro.kernels`` (Pallas, `spike_matmul` / `stdp_attention`), with
``repro.kernels.ops`` dispatching between them.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .lif import tflif
from .spike import space_to_depth, bitplanes_u8

# the dataflows that are a spiking matmul (the others have no weights)
MATMUL_OPS = ("sssc", "zsc", "conv", "wssl")


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of a network's inference forward, as the model's layer
    list gives it, in call order.

    ``path`` is the layer's key path in the folded tree and the name of its
    ``jax.named_scope``. ``op`` is its dataflow: "sssc" (a matmul over the
    8 value planes of uint8 pixels), "zsc" (2x2/s2 spike conv), "conv"
    (k x k stride-1 spike conv), "wssl" (spike linear), or a weightless
    "stdp" / "qkta" attention. A matmul layer computes ``rows`` output
    positions an image, each a ``k``-long contraction into ``n`` outputs;
    the compile passes plan and check its weights from these numbers.
    """
    path: str
    op: str
    rows: int = 0
    k: int = 0
    n: int = 0

    @property
    def matmul(self) -> bool:
        return self.op in MATMUL_OPS


def wssl(spikes, kernel, bias=None, *, compute_dtype=jnp.float32):
    """Weight-Stationary Spiking Linear.

    spikes: (T, ..., D) binary; kernel: (D, F). The T axis is folded into the
    row dimension so one weight fetch serves all timesteps (the paper computes
    one output column per weight column across the whole T-fused input map;
    XLA's matmul does the same weight-stationary loop once T is folded).
    """
    t = spikes.shape[0]
    lead = spikes.shape[1:-1]
    d = spikes.shape[-1]
    x = spikes.reshape((-1, d)).astype(compute_dtype)
    y = x @ kernel.astype(compute_dtype)
    if bias is not None:
        y = y + bias.astype(compute_dtype)
    return y.reshape((t, *lead, kernel.shape[-1]))


def zsc(spikes, kernel, bias=None, *, compute_dtype=jnp.float32):
    """Zig-Zag Spiking Convolution: 2x2/stride-2 conv over spike inputs.

    spikes: (T, B, H, W, C); kernel: (2, 2, C, F). The zig-zag placement of
    2x2 input submatrices across timesteps == space-to-depth so that every
    output pixel is one row of a T-fused matmul (full PE utilization).
    """
    x = space_to_depth(spikes, 2)                       # (T,B,H/2,W/2,4C)
    k = kernel.reshape((-1, kernel.shape[-1]))          # (4C, F)
    return wssl(x, k, bias, compute_dtype=compute_dtype)


def sssc(image_u8, kernel, bias=None, *, compute_dtype=jnp.float32):
    """Shift-and-Sum Spiking Convolution: first-layer 2x2/s2 conv on uint8.

    image_u8: (B, H, W, C) uint8; kernel: (2, 2, C, F). The 8-bit input is
    decomposed into 8 binary planes which run through the SAME binary datapath
    as WSSL/ZSC, then partial results are summed with shifts:
        y = sum_k 2^k * (plane_k . W)
    Output is (B, H/2, W/2, F) — identical to an 8-bit conv. Because the image
    is constant across timesteps, SSSC runs once and the result is reused for
    all T (paper Sec. II-D).
    """
    x = space_to_depth(image_u8, 2)                     # (B,H/2,W/2,4C) uint8
    return shift_sum_linear(x, kernel, bias, compute_dtype=compute_dtype)


def shift_sum_linear(x_u8, kernel, bias=None, *, compute_dtype=jnp.float32):
    """SSSC's matmul over uint8 values already laid out as rows: (..., K)
    uint8, kernel (K, F) -> (..., F), ``sum_k 2^k (plane_k . W)``."""
    planes = bitplanes_u8(x_u8, dtype=compute_dtype)    # (8, ..., K)
    k = kernel.reshape((-1, kernel.shape[-1]))
    per_plane = wssl(planes, k, None, compute_dtype=compute_dtype)  # (8,...,F)
    scales = (2.0 ** jnp.arange(8, dtype=compute_dtype)).reshape(
        (8,) + (1,) * (per_plane.ndim - 1))
    y = (per_plane * scales).sum(axis=0)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def im2col(x):
    """The patches of a 3x3, stride-1 convolution zero-padded to keep its
    size: (..., H, W, C) -> (..., H, W, 9C), ordered (dy, dx, c) as a
    (3, 3, C, F) kernel reshaped to (9C, F). Pure layout, so the same call
    serves float spike trains, packed plane groups (a zero byte is a silent
    neuron at every timestep) and uint8 images (a zero pixel)."""
    h, w = x.shape[-3], x.shape[-2]
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)])
    return jnp.concatenate([xp[..., dy:dy + h, dx:dx + w, :]
                            for dy in range(3) for dx in range(3)], axis=-1)


def pool_windows(x):
    """The 9 strided views of a 3/2/1 pooling window over (..., H, W, C),
    zero-padded: each (..., ceil(H/2), ceil(W/2), C). A zero pad never
    wins a max of binary values, since every window holds a real position."""
    ho, wo = -(-x.shape[-3] // 2), -(-x.shape[-2] // 2)
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)])
    return [xp[..., dy:dy + 2 * ho - 1:2, dx:dx + 2 * wo - 1:2, :]
            for dy in range(3) for dx in range(3)]


def max_pool(spikes):
    """Spiking 3/2/1 max-pool over (T, B, H, W, C) float spike trains."""
    return functools.reduce(jnp.maximum, pool_windows(spikes))


def qkta(q, k, *, heads: int, v_th: float):
    """Q-K token attention (QKFormer's QKTA) over float spike trains
    (T, B, N, D): per head h, a LIF with threshold ``v_th`` over the count
    of Q's spikes in the head's channels gives the token mask
    A[t, b, n, h]; the output keeps K where its head's mask fired,
    X'[t, b, n, c] = A[t, b, n, h(c)] * K[t, b, n, c]. No V, and linear in
    the token count."""
    *lead, d = q.shape
    counts = q.reshape(*lead, heads, d // heads).sum(axis=-1)
    a = tflif(counts, v_th=v_th)                        # (T, B, N, H)
    return jnp.repeat(a, d // heads, axis=-1) * k


def stdp(q, k, v, *, scale: float, compute_dtype=jnp.float32):
    """Spiking Tile-wise Dot Product: softmax-free attention (Q Kt) V * scale.

    q, k, v: (T, B, H, N, Dh) binary spikes. Since spike attention has no
    softmax, each V column can be consumed as soon as it is produced; the
    reference computes Kt V first — an exactly equivalent associativity choice
    ((Q Kt) V == Q (Kt V)) that, like the paper's tiling, never materializes
    the N x N score matrix when N > Dh. The Pallas kernel
    (``kernels.stdp_attention``) implements the tile-fused streaming version.
    """
    qf = q.astype(compute_dtype)
    kf = k.astype(compute_dtype)
    vf = v.astype(compute_dtype)
    ctx = jnp.einsum("tbhnd,tbhnf->tbhdf", kf, vf)       # (T,B,H,Dh,Dh')
    out = jnp.einsum("tbhnd,tbhdf->tbhnf", qf, ctx) * scale
    return out
