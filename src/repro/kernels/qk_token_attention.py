"""QKTA Pallas kernel: QKFormer's Q-K token attention on packed spikes.

For each token and head, the mask neuron fires where any of Q's channels
in that head spiked (the OR form of its LIF; ``PackedBackend.qkta`` in
``repro.infer.backends`` says when that is exact), and the output keeps
K's spikes where its head's mask fired:

    X'[t, n, c] = (OR_{c' in head(c)} Q[t, n, c'])  AND  K[t, n, c]

Q, K and X' stay packed, one byte a neuron holding 8 timesteps. The OR
over a head's channels is taken bit plane by bit plane as a count: the
planes are unpacked in registers, folded into the rows of one MXU dot
against the (D, D) block-diagonal head indicator (each channel's column
sums its own head), and a count above zero sets that plane's bit. The
counts are small integers, exact in bfloat16 inputs with a float32
accumulator.

Shapes: q, k: (R, D) uint8 rows (all leading axes folded), out (R, D).
Grid: rows in blocks of ``bm``; the indicator stays resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .device import resolve_interpret


def head_indicator(d: int, heads: int, dtype=jnp.bfloat16):
    """(D, D): 1 where two channels share a head."""
    h = jnp.arange(d) // (d // heads)
    return (h[:, None] == h[None, :]).astype(dtype)


def _kernel(q_ref, k_ref, e_ref, o_ref, *, planes: int):
    """q_ref, k_ref, o_ref: (bm, D) uint8; e_ref: (D, D) head indicator."""
    # Mosaic has no 8-bit vector arithmetic: widen the bytes first
    q = q_ref[...].astype(jnp.int32)
    bm, d = q.shape
    shifts = lax.broadcasted_iota(jnp.int32, (planes, 1, 1), 0)
    bits = ((q[None, :, :] >> shifts) & 1).reshape(planes * bm, d)
    counts = jnp.dot(bits.astype(jnp.float32).astype(jnp.bfloat16),
                     e_ref[...], preferred_element_type=jnp.float32)
    mask = jnp.zeros((bm, d), jnp.int32)
    for j in range(planes):              # static unroll: repack the planes
        fired = counts[j * bm:(j + 1) * bm] > 0.5
        mask = mask | (fired.astype(jnp.int32) << j)
    o_ref[...] = (mask & k_ref[...].astype(jnp.int32)).astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("heads", "planes", "bm", "interpret"))
def qk_token_attention(q, k, *, heads: int, planes: int = 8, bm: int = 512,
                       interpret: bool | None = None):
    """q, k: (R, D) uint8 packed spikes -> (R, D) uint8 packed X'.
    ``planes`` is how many low bits of a byte can hold a spike (min(8, T));
    the higher bits are zero in q, so skipping them changes nothing."""
    r, d = q.shape
    assert k.shape == (r, d) and d % heads == 0, (q.shape, k.shape, heads)
    bm_ = min(bm, -(-r // 32) * 32)      # uint8 rows come in tiles of 32
    pad = (-r) % bm_
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        k = jnp.pad(k, ((0, pad), (0, 0)))
    rows = q.shape[0]
    y = pl.pallas_call(
        functools.partial(_kernel, planes=planes),
        grid=(rows // bm_,),
        in_specs=[pl.BlockSpec((bm_, d), lambda i: (i, 0)),
                  pl.BlockSpec((bm_, d), lambda i: (i, 0)),
                  pl.BlockSpec((d, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm_, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.uint8),
        name="qk_token_attention",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(q, k, head_indicator(d, heads))
    return y[:r]
