"""STDP Pallas kernel: tile-wise fused (Q Kt) V — softmax-free spiking attention.

VESTA's STDP consumes each column of V immediately after it is produced, never
holding the full V (or the N x N score matrix). The TPU tiling is identical in
spirit: the grid streams KV tiles; for each Q tile we compute
``scores = Q Kt_tile`` and immediately contract with ``V_tile`` into the
output accumulator. Because spiking attention has NO softmax, there is no
online-max/renormalization bookkeeping — this is FlashAttention minus softmax,
and it is exact.

Shapes: q, k, v: (BH, N, Dh) — leading batch*heads dim is grid dim 0.
Out: (BH, N, Dh) = (Q Kt) V * scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .device import resolve_interpret


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, *, nkv: int, scale: float):
    """q_ref: (1, bq, dh); k_ref/v_ref: (1, bkv, dh); o_ref: (1, bq, dh)."""
    kv_step = pl.program_id(2)

    @pl.when(kv_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(scores, v, preferred_element_type=jnp.float32)

    @pl.when(kv_step == nkv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bq", "bkv", "interpret"))
def stdp_attention(q, k, v, *, scale: float, bq: int = 128, bkv: int = 128,
                   interpret: bool | None = None):
    """q, k, v: (BH, N, Dh) spike-valued ({0,1}) or real tensors."""
    bh, n, dh = q.shape
    bq_, bkv_ = min(bq, n), min(bkv, n)
    pq = (-n) % bq_
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        # K/V padding rows contribute zero scores only if K pad rows are zero
        k = jnp.pad(k, ((0, 0), (0, pq), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pq), (0, 0)))
    npad = q.shape[1]
    grid = (bh, npad // bq_, npad // bkv_)
    y = pl.pallas_call(
        functools.partial(_kernel, nkv=grid[2], scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv_, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv_, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, npad, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq_, dh), jnp.float32)],
        name="stdp_attention",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return y[:, :n, :]
