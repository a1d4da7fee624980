"""Causal FlashAttention Pallas kernel (online softmax, KV-tile streaming).

Beyond-paper infrastructure: VESTA's STDP fuses (Q Kt)V tile-wise because
spiking attention has no softmax. The SAME streaming schedule plus online
max/sum bookkeeping gives exact softmax attention for the standard (non-
spiking) assigned architectures — the score matrix never touches HBM.

Shapes: q: (BH, Nq, Dh); k, v: (BH, Nkv, Dh); causal over absolute positions
(q position offset = Nkv - Nq, i.e. the usual decode/prefill convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .device import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, nkv_steps: int, scale: float, bq: int, bkv: int, q_offset: int,
            causal: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bkv)
    if causal:
        qpos = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nkv_steps - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "bq", "bkv",
                                             "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool | None = None):
    """q: (BH, Nq, Dh); k, v: (BH, Nkv, Dh) -> (BH, Nq, Dh)."""
    bh, nq, dh = q.shape
    nkv = k.shape[1]
    bq_, bkv_ = min(bq, nq), min(bkv, nkv)
    pq, pk = (-nq) % bq_, (-nkv) % bkv_
    q_offset = nkv - nq  # causal alignment (decode convention)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        # pad K with zeros; padded scores masked below via kpos >= nkv check
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    # mask K padding by folding it into the causal comparison: padded kpos are
    # >= nkv, and the largest legal qpos is nkv-1, so qpos >= kpos already
    # excludes them when causal=True. For non-causal, handle via explicit mask.
    if not causal and pk:
        raise NotImplementedError("non-causal with KV padding")
    nqp, nkvp = q.shape[1], k.shape[1]
    grid = (bh, nqp // bq_, nkvp // bkv_)
    y = pl.pallas_call(
        functools.partial(_kernel, nkv_steps=grid[2], scale=scale, bq=bq_,
                          bkv=bkv_, q_offset=q_offset, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv_, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv_, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nqp, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return y[:, :nq, :]
