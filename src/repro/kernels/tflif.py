"""TFLIF Pallas kernel: fused (BN-folded bias add) + LIF over T timesteps,
emitting bit-packed spikes.

The T axis stays in registers (statically unrolled), the bias (which already
carries the folded BN shift — "subtract the LIF threshold from the BN bias")
is added in the same pass, and the output is written as ``G = ceil(T/8)``
uint8 plane groups per neuron with bit j of group g holding the timestep
``8g+j`` spike: the paper's Output-SRAM packing, which is what keeps
inter-layer traffic at 1 bit/activation. The membrane potential is carried
across group boundaries inside the kernel — T > 8 costs extra output bytes,
never a second pass over the input.

The threshold is an (M,)-vector operand rather than a compile-time constant
so the int8-weight route can fold its per-channel dequantization scale into
the comparison (spike iff h >= v_th/s) without ever rescaling the integer
accumulators.

Elementwise (VPU) kernel; grid over flattened neurons, tiled (rows, 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .device import resolve_interpret
from ..core.spike import num_plane_groups

TAU = 2.0
V_TH = 1.0
LANES = 128


def lif_charge_fire(v, x_t, bias, v_th, *, tau: float):
    """One in-kernel LIF timestep: charge, compare, hard-reset.

    Returns ``(v_next, spike_bool)``. This is the single arithmetic
    definition both the standalone TFLIF kernel and the fused
    pack->TFLIF->matmul kernel (``kernels.fused``) execute — extracting it
    keeps the two bit-identical to each other and to ``ref.tflif_ref``
    (same op sequence: ``(x + bias) - v`` first, one divide by tau).
    """
    h = v + (x_t + bias - v) / tau
    s = h >= v_th
    return jnp.where(s, 0.0, h), s     # hard reset; v crosses group bounds


def _kernel(x_ref, b_ref, vth_ref, o_ref, *, t_steps: int, tau: float):
    """x_ref: (T, br, 128); b_ref, vth_ref: (br, 128); o_ref: (G, br, 128)
    uint8 packed. Neurons are laid out as (rows, lanes) tiles — Mosaic
    vectorizes 2-D blocks, and the bits are or-ed in int32 because it has
    no 8-bit vector arithmetic (the store narrows to one byte)."""
    bias = b_ref[...]
    v_th = vth_ref[...]
    groups = o_ref.shape[0]
    v = jnp.zeros_like(bias)
    for g in range(groups):            # static unroll: T lives in VREGs
        packed = jnp.zeros(bias.shape, jnp.int32)
        for j in range(min(8, t_steps - 8 * g)):
            v, s = lif_charge_fire(v, x_ref[8 * g + j], bias, v_th, tau=tau)
            packed = packed | (s.astype(jnp.int32) << j)
        o_ref[g] = packed.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("tau", "bm", "interpret"))
def tflif_fused(x, bias=None, *, tau: float = TAU, v_th=V_TH,
                bm: int = 8192, interpret: bool | None = None):
    """x: (T, M) f32 pre-activation accumulators (BN scale already folded into
    the producing matmul); bias: (M,) BN-folded bias; v_th: scalar or (M,)
    per-neuron firing threshold. Returns (G, M) uint8, G = ceil(T/8), with
    bit j of group g = spike at timestep 8g+j.

    The M neurons are viewed as (M/128, 128) rows of lanes; one grid step
    covers ``bm`` neurons (a multiple of 128 * 32, the uint8 tile)."""
    t_steps, m = x.shape
    groups = num_plane_groups(t_steps)
    if bias is None:
        bias = jnp.zeros((m,), jnp.float32)
    v_th = jnp.broadcast_to(jnp.asarray(v_th, jnp.float32), (m,))
    rows = -(-m // LANES)
    br = min(bm // LANES, rows)
    pad = (-rows) % br * LANES + (rows * LANES - m)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        bias = jnp.pad(bias, (0, pad))
        v_th = jnp.pad(v_th, (0, pad), constant_values=1.0)
    r = x.shape[1] // LANES
    y = pl.pallas_call(
        functools.partial(_kernel, t_steps=t_steps, tau=tau),
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((t_steps, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((groups, br, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((groups, r, LANES), jnp.uint8),
        name="tflif_fused",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.float32).reshape(t_steps, r, LANES),
      bias.astype(jnp.float32).reshape(r, LANES), v_th.reshape(r, LANES))
    return y.reshape(groups, r * LANES)[:, :m]
