"""Jit'd dispatch wrappers for the Pallas kernels.

On a TPU the Pallas kernels run compiled; elsewhere they run under the Pallas
interpreter for correctness (``kernels.device.resolve_interpret`` makes that
call, once, for every kernel), and the pure-XLA reference path is used
wherever wall-time matters off-TPU. ``use_pallas()`` picks the default; every
wrapper takes an explicit override.

Plane-group convention (the arbitrary-T packed representation): a T-timestep
binary activation is stored as ``G = ceil(T/8)`` uint8 *plane groups* with a
leading group axis — bit j of group g is the spike at timestep ``8g + j``,
and bits past T-1 in the last group are zero. ``G == 1`` still carries the
axis, so every packed tensor in the datapath is (G, ...) uint8. Packing /
unpacking lives in ``core.spike.pack_timesteps`` / ``unpack_timesteps``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .device import on_tpu
from . import lut_matmul as lut
from .lut_matmul import (  # noqa: F401  (re-export: the dispatch heuristic)
    RouteConstants, choose_pallas_route, choose_route)
from .spike_matmul import spike_matmul as _spike_matmul_pallas
from .fused import tflif_lut_matmul as _tflif_lut_pallas
from .tflif import tflif_fused as _tflif_pallas
from .stdp_attention import stdp_attention as _stdp_pallas
from .qk_token_attention import qk_token_attention as _qkta_pallas
from .flash_attention import flash_attention as _flash_pallas
from ..core.spike import bitplanes_u8, num_plane_groups, unpack_timesteps


def _resolve_route(route, table, *, m, k, n, g, t, weights_are_int,
                   constants=None, occupancy=None):
    """Route resolution for the packed CPU matmuls.

    ``None`` is the *safe* default: LUT only when the caller (the session
    planner) supplies a prebuilt table — so un-planned callers keep the
    single-dot unpack route that mirrors the float reference bit for bit;
    a calibrated ``occupancy`` alongside the table upgrades that default to
    the zero-chunk-skipping gather (bit-identical, see
    ``lut_matmul.lut_matmul_sparse``). "auto" applies ``choose_route``
    inline (``constants`` overrides the cost model — plans carry autotuned
    values); "lut"/"lut_sparse"/"unpack" force. The forced sparse route
    requires ``occupancy`` — the gather budget is a static compile-time
    value derived from it, not something to guess.
    """
    if route is None:
        if table is None:
            return "unpack"
        return "lut_sparse" if occupancy is not None else "lut"
    if route == "auto":
        return choose_route(m=m, k=k, n=n, g=g, t=t,
                            weights_are_int=weights_are_int,
                            constants=constants, occupancy=occupancy)
    if route not in ("lut", "lut_sparse", "unpack"):
        raise ValueError(f"unknown packed-matmul route {route!r}")
    if route == "lut_sparse" and occupancy is None:
        raise ValueError("route='lut_sparse' requires a calibrated "
                         "occupancy (the static gather budget comes from "
                         "it); measure with infer.backends.chunk_occupancy")
    return route


def _have_table(table) -> bool:
    """A real (C, 256, N) table vs None or a planner boolean flag. The
    flag case (``lut=True``, what ``build_tables=False`` annotates for
    backends that never gather) appears as a traced 0-d bool under jit —
    ``ndim == 3`` separates it from an actual table either way."""
    return table is not None and getattr(table, "ndim", 0) == 3


def _resolve_route_pallas(route, table, *, m, k, n, g, t, weights_are_int,
                          constants=None):
    """Route resolution for the Pallas branch: "lut" (the byte-LUT gather
    kernel over a VMEM-resident table) or "unpack" (the grouped
    unpack-in-register dot kernel).

    Mirrors ``_resolve_route``'s contract with two Pallas-specific rules:
    "auto" consults ``choose_pallas_route`` (its own cost model — one-hot
    MXU selects vs in-register plane dots have different constants than
    the CPU gather vs unpack-and-write), and a pinned "lut_sparse" runs
    the DENSE Pallas gather — there is no zero-chunk-skipping kernel, and
    the dense fold is bitwise identical to the sparse one by construction,
    so replaying a CPU-calibrated sparse plan on the Pallas backend is
    exact, just not sparse.
    """
    if route is None:
        return "lut" if _have_table(table) else "unpack"
    if route == "auto":
        return choose_pallas_route(m=m, k=k, n=n, g=g, t=t,
                                   weights_are_int=weights_are_int,
                                   constants=constants)
    if route not in ("lut", "lut_sparse", "unpack"):
        raise ValueError(f"unknown packed-matmul route {route!r}")
    return "lut" if route == "lut_sparse" else route


def use_pallas(override: bool | None = None) -> bool:
    if override is not None:
        return override
    return on_tpu()


def spike_matmul(x_packed, w, *, mode: str = "per_plane",
                 pallas: bool | None = None, **blocks):
    """Unified-PE matmul over packed binary planes.

    Args:
      x_packed: (M, K) uint8 — bit p of byte [m, k] is plane p's spike — or
        (G, M, K) uint8 plane groups (mode="per_plane" only).
      w: (K, N) weights, any float/int dtype (cast to f32 in the dot).
      mode: "per_plane" — each of the 8 bit planes gets its own output
        (WSSL/ZSC/STDP operands); "shift_sum" — planes combined with scales
        2^p before the dot, i.e. the byte is treated as a uint8 *value*
        (SSSC).
      pallas: force the Pallas kernel (True) or the jnp oracle (False);
        None auto-selects (Pallas on TPU).

    Returns:
      (8, M, N) f32 for mode="per_plane"; (G, 8, M, N) for grouped input;
      (M, N) f32 for mode="shift_sum".
    """
    if use_pallas(pallas):
        return _spike_matmul_pallas(x_packed, w, mode=mode, **blocks)
    return ref.spike_matmul_ref(x_packed, w, mode=mode)


def tflif_fused(x, bias=None, *, tau: float = 2.0, v_th=1.0,
                pallas: bool | None = None):
    """Fused bias-add + LIF over T timesteps, emitting packed spikes.

    Args:
      x: (T, M) f32 pre-activation accumulators (BN scale already folded into
        the producing matmul). Any T >= 1.
      bias: optional (M,) BN-folded bias, added inside the LIF charge.
      tau: LIF leak constant.
      v_th: firing threshold — scalar, or (M,) per-neuron vector (used by the
        int8 route to fold the per-channel weight scale into the comparison).
      pallas: backend override as in ``spike_matmul``.

    Returns:
      (G, M) uint8, G = ceil(T/8); bit j of group g = spike at timestep
      8g + j. Membrane state is carried across group boundaries.
    """
    if use_pallas(pallas):
        return _tflif_pallas(x, bias, tau=tau, v_th=v_th)
    return ref.tflif_ref(x, bias, tau=tau, v_th=v_th)


def stdp_attention(q, k, v, *, scale: float, pallas: bool | None = None,
                   **blocks):
    """Softmax-free spiking attention (Q K^T) V * scale.

    q, k, v: (BH, N, Dh) float {0,1} spike planes (one plane per grid row —
    callers fold T into BH). Returns (BH, N, Dh) f32 exact accumulators.
    """
    if use_pallas(pallas):
        return _stdp_pallas(q, k, v, scale=scale, **blocks)
    return ref.stdp_attention_ref(q, k, v, scale=scale)


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    pallas: bool | None = None, **blocks):
    """Standard softmax attention (the non-spiking LM stack's kernel).

    q: (BH, Nq, Dh); k, v: (BH, Nkv, Dh). Returns (BH, Nq, Dh) f32.
    """
    if use_pallas(pallas):
        return _flash_pallas(q, k, v, scale=scale, causal=causal, **blocks)
    return ref.flash_attention_ref(q, k, v, scale=scale, causal=causal)


# ---------------------------------------------------------------------------
# Batched packed-bit entry points — the inference datapath
# ---------------------------------------------------------------------------
# These are what ``repro.infer`` dispatches through: activations stay packed
# 8-per-uint8 between layers (temporal bits for WSSL/ZSC/STDP, value bits for
# SSSC) and only unpack inside the matmul. The CPU reference route mirrors
# ``core.unified`` operation-for-operation — same reshapes, same single dot,
# same reduction order — so it is bit-exact against the float training graph;
# the Pallas route trades that for the fused uint8 kernels.

def spike_linear(x_packed, w, bias=None, *, t: int,
                 pallas: bool | None = None, route: str | None = None,
                 table=None, route_constants=None, occupancy=None, **blocks):
    """Packed WSSL (weight-stationary spiking linear).

    Args:
      x_packed: (G, ..., K) uint8 temporal plane groups, G = ceil(t/8);
        bit j of group g = the timestep-(8g+j) spike of that neuron.
      w: (K, N) weights; bias: optional (N,) added to every timestep.
      t: number of live timesteps (bits past t-1 must be zero).
      pallas: backend override. The Pallas branch honors ``route`` through
        ``_resolve_route_pallas``: "lut" runs the VMEM-table gather kernel
        (``lut_matmul_pallas``), "unpack" the grouped in-register dot
        kernel, "auto" the ``choose_pallas_route`` cost model, and a
        pinned "lut_sparse" the dense gather (bitwise identical).
      route: route selection — None (LUT iff ``table`` given, sparse
        LUT iff additionally ``occupancy`` given, else the unpack oracle),
        "auto" (the ``choose_route`` heuristic), or a forced "lut" /
        "lut_sparse" / "unpack".
      table: prebuilt ``lut_matmul.build_lut(w)`` result, cached by the
        compile-time route planner so the 256-entry chunk sums are paid
        once per layer, not per batch.
      route_constants: ``RouteConstants`` override for the route="auto"
        cost model (plans carry autotuned values; None = defaults).
      occupancy: calibrated CHUNK occupancy of this layer's packed inputs
        (``infer.backends.chunk_occupancy`` — fraction of nonzero index
        bytes), a STATIC python float: the sparse route's per-row gather
        budget is fixed at trace time from it. Inputs denser than the
        calibration fall back to the dense gather inside the kernel.

    Returns:
      (t, ..., N) f32 per-timestep accumulators. On the CPU unpack route all
      t planes of all groups are folded into the row dim of ONE dot (exactly
      ``unified.wssl``, hence bit-exact vs the float reference); the LUT
      route gathers chunk partial sums byte-wise with no unpacked tensor
      (bit-exact vs ``lut.lut_matmul_planes``, the fold-order oracle the
      reference backend emulates for planned layers) and the sparse LUT
      route additionally skips zero index bytes (still bit-exact — the
      skipped ``table[c, 0, :]`` entry is the exact-zero identity). The
      Pallas LUT route replays the same defined gather fold in-kernel
      (bit-exact against the CPU LUT route and its oracle); the Pallas
      unpack route runs the grouped dot kernel, one weight fetch per group
      of 8 planes (bit-exact for integer weights, reduction-order-
      tolerant for float32 — pin "lut" routes for float bit-exactness).
    """
    g = x_packed.shape[0]
    assert g == num_plane_groups(t), (g, t)
    lead, k = x_packed.shape[1:-1], x_packed.shape[-1]
    m = 1
    for d in lead:
        m *= d
    n = w.shape[-1]
    if use_pallas(pallas):
        resolved = _resolve_route_pallas(
            route, table, m=m, k=k, n=n, g=g, t=t,
            weights_are_int=lut._is_int_kernel(w),
            constants=route_constants)
        x2 = x_packed.reshape(g, -1, k)
        if resolved == "lut":
            tbl = table if _have_table(table) else lut.build_lut(w)
            idx = lut.plane_indices(x2)[:t]                # (t, M, C)
            per = lut.lut_matmul_pallas(idx, tbl)
        else:
            per8 = _spike_matmul_pallas(x2, w, mode="per_plane", **blocks)
            per = per8.reshape(g * 8, m, n)[:t]            # (t, M, N)
        if bias is not None:
            per = per + bias.astype(per.dtype)
        return per.reshape((t, *lead, n))
    resolved = _resolve_route(
        route, table, m=m, k=k, n=n, g=g, t=t,
        weights_are_int=lut._is_int_kernel(w),
        constants=route_constants, occupancy=occupancy)
    if resolved in ("lut", "lut_sparse"):
        tbl = lut.build_lut(w) if table is None else table
        idx = lut.plane_indices(x_packed)[:t]              # (t, ..., C)
        if resolved == "lut_sparse":
            budget = lut.sparse_budget(tbl.shape[0], occupancy)
            per = lut.lut_matmul_sparse(idx, tbl, max_chunks=budget)
        else:
            per = lut.lut_matmul(idx, tbl)                 # (t, ..., N)
        if bias is not None:
            per = per + bias.astype(per.dtype)
        return per
    else:
        x2 = x_packed.reshape(g, -1, k)
        planes = unpack_timesteps(x2, t)                   # (t, M, K)
        per = (planes.reshape(t * m, k) @ w.astype(jnp.float32)
               ).reshape(t, m, n)
    if bias is not None:
        per = per + bias.astype(per.dtype)
    return per.reshape((t, *lead, n))


def sssc_linear(x_u8, w, bias=None, *, pallas: bool | None = None,
                route: str | None = None, table=None, route_constants=None,
                occupancy=None, **blocks):
    """Packed SSSC (shift-and-sum spiking conv, as a linear over 8 bit-planes).

    Args:
      x_u8: (..., K) uint8 *values* (the image is its own packing: bit p of a
        byte is value-plane p, combined with scale 2^p). Always exactly 8
        planes — SSSC never grows a plane-group axis.
      w: (K, N) weights; bias: optional (N,).
      route, table: CPU-route selection as in ``spike_linear`` — the value
        bytes are the LUT index source directly (an 8x8 bit transpose turns
        K value bytes into ceil(K/8) per-plane index bytes), and the 2^p
        plane combine uses the defined ``shift_sum_fold`` order.
      occupancy: calibrated chunk occupancy of the transposed value bytes
        (``infer.backends.value_chunk_occupancy``), static — enables the
        zero-chunk-skipping gather exactly as in ``spike_linear``.

    Returns:
      (..., N) f32 accumulators, ``y = sum_p 2^p (plane_p . W)`` — identical
      to an 8-bit conv. The Pallas route collapses the 8 planes into one dot
      (shift_sum mode).
    """
    lead, k = x_u8.shape[:-1], x_u8.shape[-1]
    x2 = x_u8.reshape(-1, k)
    m = x2.shape[0]
    n = w.shape[-1]
    if use_pallas(pallas):
        resolved = _resolve_route_pallas(
            route, table, m=m, k=k, n=n, g=1, t=8,
            weights_are_int=lut._is_int_kernel(w),
            constants=route_constants)
        if resolved == "lut":
            tbl = table if _have_table(table) else lut.build_lut(w)
            idx = lut.plane_indices(x2[None])              # (8, M, C)
            per = lut.lut_matmul_pallas(idx, tbl)
            y = lut.shift_sum_fold(per)                    # (M, N)
        else:
            y = _spike_matmul_pallas(x2, w, mode="shift_sum", **blocks)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y.reshape((*lead, n))
    resolved = _resolve_route(
        route, table, m=m, k=k, n=n, g=1, t=8,
        weights_are_int=lut._is_int_kernel(w),
        constants=route_constants, occupancy=occupancy)
    if resolved in ("lut", "lut_sparse"):
        tbl = lut.build_lut(w) if table is None else table
        idx = lut.plane_indices(x_u8[None])                # (8, ..., C)
        if resolved == "lut_sparse":
            budget = lut.sparse_budget(tbl.shape[0], occupancy)
            per = lut.lut_matmul_sparse(idx, tbl, max_chunks=budget)
        else:
            per = lut.lut_matmul(idx, tbl)
        y = lut.shift_sum_fold(per)                        # (..., N)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y
    else:
        planes = bitplanes_u8(x2)                          # (8, M, K)
        per = (planes.reshape(8 * m, k) @ w.astype(jnp.float32)
               ).reshape(8, m, w.shape[-1])
        scales = (2.0 ** jnp.arange(8, dtype=per.dtype)).reshape(8, 1, 1)
        y = (per * scales).sum(axis=0)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y.reshape((*lead, w.shape[-1]))


def tflif_pack(acc, bias=None, *, t: int | None = None, tau: float = 2.0,
               v_th=1.0, pallas: bool | None = None):
    """Batched TFLIF: per-timestep accumulators -> packed plane groups.

    Args:
      acc: (T, ...) f32 accumulators, any T >= 1. The whole T axis is fused;
        membrane state crosses the 8-timestep group boundaries inside the
        kernel.
      bias: optional BN-folded shift, broadcastable to acc.shape[1:], added
        inside the same pass.
      v_th: scalar threshold, or an array broadcastable to acc.shape[1:] —
        per-channel thresholds carry the int8 weight-scale fold
        (spike iff h >= v_th/s without rescaling the accumulator).
      t: process only the first t timesteps of acc (defaults to all of
        them); honored identically on every branch.

    Returns:
      (G, ...) uint8 plane groups, G = ceil(T/8); bit j of group g = spike at
      timestep 8g + j.
    """
    if t is not None and t != acc.shape[0]:
        acc = acc[:t]                  # honor the override on every branch
    t = acc.shape[0]
    lead = acc.shape[1:]
    if not use_pallas(pallas):
        # CPU oracle runs natively N-D: in-graph flattens force XLA CPU's
        # fusion emitter into ~10x-slower reshape-chasing loop nests, and
        # broadcast shape never changes per-element results.
        return ref.tflif_ref(acc, bias, tau=tau, v_th=v_th)
    x2 = acc.reshape(t, -1)
    if bias is not None:
        bias = jnp.broadcast_to(bias, lead).reshape(-1)
    if not isinstance(v_th, (int, float)):
        v_th = jnp.broadcast_to(v_th, lead).reshape(-1)
    packed = tflif_fused(x2, bias, tau=tau, v_th=v_th, pallas=pallas)
    return packed.reshape((packed.shape[0], *lead))


def tflif_lut(acc, bias=None, *, table, v_th=1.0, t: int | None = None,
              tau: float = 2.0, pallas: bool | None = None):
    """Fused LIF -> pack -> byte-LUT matmul over a producer/consumer pair
    (the MLP fc1 -> fc2 step).

    Args:
      acc: (T, ..., K) f32 producer pre-LIF accumulators (producer bias
        NOT added — it goes through ``bias`` into the LIF charge, exactly
        as ``tflif_pack``). The trailing axis is the producer's channel
        dim = the consumer's contraction dim.
      bias: producer bias, None / scalar / (K,); v_th: producer threshold,
        scalar or (K,) (the int8 scale fold).
      table: (C, 256, N) consumer ``build_lut`` table — a REAL table, the
        fused step is a gather by definition.
      t: live timesteps (defaults to acc.shape[0]).

    Returns:
      ``(spikes, acc2)``: spikes (G, ..., K) uint8 packed producer output
      (what the unfused route would have written between the layers) and
      acc2 (t, ..., N) f32 consumer pre-LIF accumulators (consumer bias
      not added). The Pallas branch runs the single fused kernel
      (``kernels.fused.tflif_lut_matmul``); the CPU branch composes the
      same math from ``tflif_pack`` + ``plane_indices`` + ``lut_matmul``
      — both bit-exact against each other, so the fused step never
      changes logits, only traffic.
    """
    if not _have_table(table):
        raise ValueError("tflif_lut requires a real (C, 256, N) table — "
                         "the fused step is a gather by definition; build "
                         "one with lut_matmul.build_lut")
    if t is not None and t != acc.shape[0]:
        acc = acc[:t]
    t = acc.shape[0]
    lead, k = acc.shape[1:-1], acc.shape[-1]
    n = table.shape[-1]
    if use_pallas(pallas):
        x2 = acc.reshape(t, -1, k)
        b = None if bias is None else jnp.broadcast_to(
            jnp.asarray(bias, jnp.float32), (k,))
        vth = jnp.broadcast_to(jnp.asarray(v_th, jnp.float32), (k,))
        spikes, acc2 = _tflif_lut_pallas(x2, b, table, v_th=vth, tau=tau)
        return (spikes.reshape(spikes.shape[0], *lead, k),
                acc2.reshape(t, *lead, n))
    spikes = tflif_pack(acc, bias, tau=tau, v_th=v_th, pallas=pallas)
    idx = lut.plane_indices(spikes)[:t]                    # (t, ..., C)
    return spikes, lut.lut_matmul(idx, table)


STDP_LUT_MIN_TOKENS = 128  # below this, score-table build cost can't amortize


def stdp_attention_packed(q_packed, k_packed, v_packed, *, t: int,
                          scale: float, pallas: bool | None = None,
                          route: str | None = None, **blocks):
    """Packed STDP: softmax-free attention over temporal plane groups.

    Args:
      q_packed, k_packed, v_packed: (G, ..., N, Dh) uint8 temporal plane
        groups (G = ceil(t/8)). Timesteps attend independently — spike
        attention has no cross-T term — so all t planes fold into the
        batch-heads grid dim of the tile-fused kernel.
      t: live timesteps; scale: output scale (power of two in Spikformer, so
        results stay exact).
      route: CPU-route selection. The LUT route computes the score matmul
        Q K^T by byte-gather — Q is never unpacked; K (the "weight" side)
        is, to build per-(t, head) tables, so this only pays off when the
        token count N amortizes the 256-entry build ("auto": N >=
        STDP_LUT_MIN_TOKENS). Binary q/k/v make every accumulator an exact
        integer, so all routes agree bit for bit regardless of order.

    Returns:
      (t, ..., N, Dh) f32 attention accumulators.
    """
    lead = q_packed.shape[1:-2]
    n, dh = q_packed.shape[-2:]
    g = q_packed.shape[0]

    if not use_pallas(pallas):
        if route == "auto":
            # score tables are per-(t, batch*head) and rebuilt every call (K
            # is an activation): require both enough tokens to amortize the
            # 256-entry build AND a bounded transient footprint, mirroring
            # MAX_TABLE_BYTES on the linear layers
            bh_all = 1
            for d in lead:
                bh_all *= d
            tables_bytes = t * bh_all * lut.num_k_chunks(dh) * 256 * n * 4
            route = ("lut" if n >= STDP_LUT_MIN_TOKENS
                     and tables_bytes <= lut.MAX_TABLE_BYTES else "unpack")
        if route == "lut":
            bh = 1
            for d in lead:
                bh *= d
            idx_q = lut.plane_indices(
                q_packed.reshape(g, bh * n, dh))[:t].reshape(t, bh, n, -1)
            k_pl = unpack_timesteps(k_packed.reshape(g, bh, n, dh), t)
            v_pl = unpack_timesteps(v_packed.reshape(g, bh, n, dh), t)
            tables = jax.vmap(jax.vmap(lut.build_lut))(
                k_pl.transpose(0, 1, 3, 2))                # (t,BH,C,256,N)
            s = jax.vmap(jax.vmap(lut.lut_matmul))(idx_q, tables)
            out = jnp.einsum("tbnm,tbmd->tbnd", s, v_pl) * scale
            return out.reshape((t, *lead, n, dh))
        if route not in (None, "unpack"):
            raise ValueError(f"unknown packed-stdp route {route!r}")

    def unfold(z):
        planes = unpack_timesteps(z.reshape(z.shape[0], -1, n, z.shape[-1]),
                                  t)                       # (t, BH', N, Dh)
        return planes.reshape(-1, n, z.shape[-1])          # (t*BH, N, Dh)

    out = stdp_attention(unfold(q_packed), unfold(k_packed), unfold(v_packed),
                         scale=scale, pallas=pallas, **blocks)
    return out.reshape((t, *lead, n, dh))


def qk_token_attention(q_packed, k_packed, *, heads: int, t: int,
                       pallas: bool | None = None):
    """Packed Q-K token attention (QKFormer's QKTA in its OR form).

    Args:
      q_packed, k_packed: (G, ..., D) uint8 temporal plane groups of Q and
        K; D splits into ``heads`` heads of contiguous channels.
      t: live timesteps (bits past t-1 are zero).

    Returns:
      (G, ..., D) uint8 X': K's spikes where the OR of Q's spikes over the
      channels of the same head, at the same token and timestep, is set.
      Every bit is exact on both branches; the Pallas branch is the
      ``qk_token_attention`` kernel over all leading axes as rows.
    """
    d = q_packed.shape[-1]
    if use_pallas(pallas):
        y = _qkta_pallas(q_packed.reshape(-1, d), k_packed.reshape(-1, d),
                         heads=heads, planes=min(8, t))
        return y.reshape(q_packed.shape)
    return ref.qk_token_attention_ref(q_packed, k_packed, heads=heads)
