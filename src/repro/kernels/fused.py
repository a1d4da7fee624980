"""Fused pack -> TFLIF -> byte-LUT matmul Pallas kernel.

The inter-layer contract of the packed datapath is "1 bit per activation in
HBM"; this kernel closes the last gap in it. For a producer/consumer linear
pair (the encoder MLP's fc1 -> fc2 is the shape in the model), the unfused
route writes fc1's packed spikes to HBM, reads them back, bit-transposes
them into LUT index bytes, and gathers. Here all of that happens in VMEM
inside ONE kernel invocation:

    fc1 accumulators (T, bm, K)  --LIF-->  spike bits (in VREGs)
        --pack-->  packed planes (G, bm, K)   [written once, for telemetry
                                               and the residual consumer]
        --index-->  chunk index bytes (bm, C) per timestep
        --gather-->  fc2 accumulators (T, bm, N)

The *unpacked* (T, bm, K) spike tensor never exists outside registers, and
the LUT index bytes are built directly from the spike booleans — the 8x8
bit transpose the unfused route needs (``lut_matmul.plane_indices``) is
free here because the bits haven't been packed along time yet.

Exactness: the LIF step is ``tflif.lif_charge_fire`` (the same op sequence
as ``ref.tflif_ref``), the gather is ``spike_matmul.gather256`` folded in
ascending-chunk order (the same defined reduction tree as
``lut_matmul.lut_matmul``), and integer tables accumulate in int32 — so the
fused step is bit-exact against the unfused composition on every backend,
which is what lets the packed_pallas backend enable it by default.

Interpret mode (CPU tier-1) runs the same kernel body under the Pallas
interpreter. The whole (C, 256, N) table sits in VMEM for every grid step,
so the kernel only serves tables up to ``MAX_TABLE_BYTES``; the backend
runs the two-kernel path for larger ones (the published widths' fc2 table
is 64 MiB in int16 — the route planner already sends fc2 to the unpack
route there, so the fused step does not apply).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .device import resolve_interpret, vmem_limit
from .spike_matmul import gather256
from .tflif import TAU, V_TH, lif_charge_fire
from .lut_matmul import K_CHUNK, num_k_chunks
from ..core.spike import num_plane_groups

# largest fc2 table the fused kernel keeps VMEM-resident (double-buffered)
MAX_TABLE_BYTES = 4 << 20


def _kernel(x_ref, b_ref, vth_ref, tbl_ref, s_ref, o_ref, *, t_steps: int,
            tau: float, acc_dtype):
    """x_ref: (T, bm, K) fc1 accumulators; b_ref, vth_ref: (1, K); tbl_ref:
    (C, 256, N) fc2 chunk-partial-sum table (VMEM-resident); s_ref:
    (G, bm, K) uint8 packed spikes out; o_ref: (T, bm, N) f32 fc2
    accumulators out. K is pre-padded to C*8 by the wrapper."""
    bias = b_ref[...]
    v_th = vth_ref[...]
    groups = s_ref.shape[0]
    bm, k = x_ref.shape[1:]
    c = tbl_ref.shape[0]
    # LUT index bytes from the spike bits as one MXU dot: byte c's bit i is
    # the spike of input 8c+i, i.e. idx = spikes @ P with P[8c+i, c] = 2^i.
    # Every product and partial sum is an integer <= 255, exact in any
    # precision — and no (bm, C, 8) relayout, which Mosaic cannot do.
    row = lax.broadcasted_iota(jnp.int32, (k, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (k, c), 1)
    pack = jnp.where((row >> 3) == col, (1 << (row & 7)).astype(jnp.float32),
                     0.0)
    v = jnp.zeros((bm, k), jnp.float32)
    for g in range(groups):            # static unroll: T lives in VREGs
        packed = jnp.zeros((bm, k), jnp.int32)
        for j in range(min(8, t_steps - 8 * g)):
            v, s = lif_charge_fire(v, x_ref[8 * g + j], bias, v_th, tau=tau)
            packed = packed | (s.astype(jnp.int32) << j)
            idx = jnp.dot(s.astype(jnp.float32), pack,
                          preferred_element_type=jnp.float32
                          ).astype(jnp.int32)              # (bm, C)
            y = gather256(tbl_ref[0], idx[:, 0:1], acc_dtype)
            for chunk in range(1, c):  # the defined ascending-chunk fold
                y = y + gather256(tbl_ref[chunk], idx[:, chunk:chunk + 1],
                                  acc_dtype)
            o_ref[8 * g + j] = y.astype(jnp.float32)
        s_ref[g] = packed.astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("tau", "bm", "interpret"))
def tflif_lut_matmul(x, bias, table, *, v_th=V_TH, tau: float = TAU,
                     bm: int = 128, interpret: bool | None = None):
    """Fused TFLIF + pack + byte-LUT matmul over a linear pair.

    Args:
      x: (T, R, K) f32 pre-LIF accumulators of the producer layer (its
        BN-folded bias NOT yet added — it is applied inside the LIF charge,
        matching ``ops.tflif_pack``).
      bias: (K,) producer bias (or None); v_th: scalar or (K,) producer
        threshold (per-channel for the int8 scale fold).
      table: (C, 256, N) consumer ``build_lut`` table, C = ceil(K/8).

    Returns:
      ``(spikes, acc)``: spikes (G, R, K) uint8 packed plane groups (the
      producer's LIF output — the unfused route's inter-layer tensor, still
      emitted for any second consumer), and acc (T, R, N) f32 consumer
      pre-LIF accumulators (consumer bias NOT added — the caller's LIF
      applies it, as on every other route).
    """
    t_steps, r, k = x.shape
    c, _, n = table.shape
    assert c == num_k_chunks(k), (x.shape, table.shape)
    groups = num_plane_groups(t_steps)
    if bias is None:
        bias = jnp.zeros((k,), jnp.float32)
    bias = jnp.broadcast_to(jnp.asarray(bias, jnp.float32), (k,))
    v_th = jnp.broadcast_to(jnp.asarray(v_th, jnp.float32), (k,))
    bm_ = min(bm, r)
    pr, pk = (-r) % bm_, c * K_CHUNK - k
    if pr or pk:
        # padded K neurons see x=0, bias=0, v_th=1: v' = v/tau from v0=0
        # stays 0 < 1 forever, so their index bits are 0 and their gathers
        # hit the zero weight rows build_lut padded with — exact identity
        x = jnp.pad(x, ((0, 0), (0, pr), (0, pk)))
        bias = jnp.pad(bias, (0, pk))
        v_th = jnp.pad(v_th, (0, pk), constant_values=1.0)
    rp, kp = x.shape[1:]
    acc_dtype = (jnp.int32 if jnp.issubdtype(table.dtype, jnp.integer)
                 else jnp.float32)
    x_block_bytes = t_steps * bm_ * (kp * 4 + n * 4) + groups * bm_ * kp

    spikes, acc = pl.pallas_call(
        functools.partial(_kernel, t_steps=t_steps, tau=tau,
                          acc_dtype=acc_dtype),
        grid=(rp // bm_,),
        in_specs=[
            pl.BlockSpec((t_steps, bm_, kp), lambda i: (0, i, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
            pl.BlockSpec((c, 256, n), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((groups, bm_, kp), lambda i: (0, i, 0)),
            pl.BlockSpec((t_steps, bm_, n), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((groups, rp, kp), jnp.uint8),
            jax.ShapeDtypeStruct((t_steps, rp, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(2 * (table.size * table.dtype.itemsize
                                             + x_block_bytes))),
        name="tflif_lut_matmul",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.float32), bias[None], v_th[None], table)
    return spikes[:, :r, :k], acc[:, :r, :]
