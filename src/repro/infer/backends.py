"""Execution backends for the BN-folded Spikformer inference graph.

``core.spikformer.forward_folded`` drives the layer sequence; a backend
decides how activations are represented and which kernels execute each of the
four unified dataflows:

  FloatBackend  — spikes are {0,1} float32 tensors with an explicit leading T
                  axis, every op runs through ``core.unified`` (the training
                  reference). Activation shapes: (T, B, H, W, C) / (T, B, N, D).
  PackedBackend — spikes are packed uint8 *plane groups*: a leading axis of
                  G = ceil(T/8) bytes per neuron, bit j of group g = timestep
                  8g+j, dispatched through the batched packed entry points in
                  ``kernels.ops`` (Pallas on TPU, the mirrored-reshape CPU
                  oracle elsewhere). Activation shapes: (G, B, H, W, C) /
                  (G, B, N, D) uint8 — 8x (x 32/T) less inter-layer traffic,
                  the paper's Small-Input/Output-SRAM packing, for ANY T.

Every ``*_lif`` method takes an optional per-output-channel ``scale`` leaf
(present when the folded tree was quantized by ``infer.quant``): the kernel
is then int8 and the scale is folded into the LIF bias/threshold instead of
the accumulator (see ``infer.quant`` for the math). FloatBackend applies the
identical scale-folded ops to the dequantized-integer float graph, making it
the bit-exact *emulation oracle* for the packed int8 route.

Each matmul method also takes an optional ``lut`` leaf — the byte-LUT table
the session planner cached for that layer (``kernels.lut_matmul``). When
present, PackedBackend runs the unpack-free gather route and FloatBackend
runs the *fold-order emulation* of the same reduction tree
(``lut_matmul_planes``) instead of its single dot: float32 sums are not
reorderable, so the reference follows the route plan exactly as it already
follows the int8 threshold fold. Both sessions of a parity pair plan the
same routes from the same static shapes, which keeps end-to-end logits
bit-identical.

The CPU route of PackedBackend performs operation-for-operation the same
float32 arithmetic as FloatBackend (same reshapes, same dots or the same
gather/fold tree, same reduction orders), so their logits are bit-identical
— spikes are binary, there is no tolerance to hide behind, and the parity
tests assert exact equality. The Pallas LUT route keeps the same contract:
its gather kernel replays lut_matmul's defined ascending-chunk fold with
one-hot-matmul row selects (exact — 255 of 256 products are exact zeros),
so table-planned sessions are bit-identical across ALL of {reference,
packed CPU, packed Pallas}; only the Pallas unpack-dot route on float32
weights relaxes to reduction-order tolerance (pin "lut" routes there).
"""
from __future__ import annotations

import functools
import math

import jax.numpy as jnp
from jax import lax

from . import registry
from ..core import unified
from ..core.lif import TAU, V_TH, tflif
from ..core.spike import (bitplanes_u8, packed_occupancy, rate_decode,
                          space_to_depth)
from ..kernels import fused, ops
from ..kernels import lut_matmul as lut


# ---------------------------------------------------------------------------
# Packed-popcount occupancy readouts. These live next to PackedBackend.rate
# (the popcount classification readout) because they are the same trick
# pointed at telemetry: sparsity statistics read straight off the packed
# bytes, no unpacking. All three return plain python floats — they are
# calibration/telemetry utilities, not jittable graph ops.
# ---------------------------------------------------------------------------

def spike_occupancy(x_packed, t: int) -> float:
    """Firing rate of a packed spike tensor: fraction of set bits over the
    ``t`` live planes. One implementation — ``core.spike.packed_occupancy``
    — shared with the event front end's per-window readout, so the number
    a DVS window reports at ingestion is the number serving calibrates
    with."""
    return packed_occupancy(x_packed, t)


def chunk_occupancy(x_packed, t: int) -> float:
    """CHUNK occupancy of a packed spike tensor: the fraction of nonzero
    per-plane chunk-index bytes — exactly the quantity the zero-chunk-
    skipping gather scales with (a zero byte = one skippable 8-row gather),
    and what ``choose_route``/``sparse_budget`` take as ``occupancy``."""
    idx = lut.plane_indices(x_packed)[:t]
    return float(jnp.mean((idx != 0).astype(jnp.float32)))


def value_chunk_occupancy(x_u8) -> float:
    """Chunk occupancy of uint8 *value* bytes (the SSSC operand): the
    8 bit-planes of the values are the LUT index source directly."""
    return chunk_occupancy(x_u8[None], 8)


def _to_spatial(x):
    """(lead, lead, N, D) tokens -> (lead, lead, side, side, D), N = side^2."""
    *lead, n, d = x.shape
    side = math.isqrt(n)
    return x.reshape(*lead, side, side, d)


class FloatBackend:
    """Reference backend: float spike trains through ``core.unified``."""

    name = "reference"
    # route planning reads this: the reference only needs the "lut" leaf as
    # a *flag* to switch to the fold-order emulation — caching the (C,256,N)
    # tables into its tree would be dead weight
    wants_lut_tables = False

    @staticmethod
    def _acc_and_vth(op, x, kernel, bias, scale):
        """Pre-LIF accumulator and firing threshold for ``op(x, k, b)``.
        int8 layers (``scale`` given) fold the per-channel scale into the
        bias/threshold — the float emulation of exactly the packed int8
        math."""
        if scale is None:
            return op(x, kernel, bias), V_TH
        acc = op(x, kernel.astype(jnp.float32), None) + (bias / scale)
        return acc, V_TH / scale

    # -- fold-order emulations of the byte-LUT route (plan says "lut") ------
    # Same signatures as the ``core.unified`` ops they stand in for; the
    # arithmetic replays lut_matmul's defined reduction tree on float planes.

    @staticmethod
    def _wssl_emu(spikes, kernel, bias=None):
        t, lead, d = spikes.shape[0], spikes.shape[1:-1], spikes.shape[-1]
        planes = spikes.reshape(t, -1, d).astype(jnp.float32)
        y = lut.lut_matmul_planes(planes, kernel)       # (t, M, N)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y.reshape((t, *lead, kernel.shape[-1]))

    @classmethod
    def _zsc_emu(cls, spikes, kernel, bias=None):
        return cls._wssl_emu(space_to_depth(spikes, 2),
                             kernel.reshape(-1, kernel.shape[-1]), bias)

    @classmethod
    def _sssc_emu(cls, image_u8, kernel, bias=None):
        return cls._value_emu(space_to_depth(image_u8, 2), kernel, bias)

    @staticmethod
    def _value_emu(x, kernel, bias=None):
        lead = x.shape[:-1]
        planes = bitplanes_u8(x).reshape(8, -1, x.shape[-1])
        per = lut.lut_matmul_planes(planes, kernel)     # (8, M, N)
        y = lut.shift_sum_fold(per)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y.reshape((*lead, kernel.shape[-1]))

    # ``occupancy`` (the sparse-route calibration) is accepted and IGNORED:
    # the zero-chunk-skipping gather only drops exact-zero identity entries
    # from the fold, so its bit-exact float emulation is the same
    # ``lut_matmul_planes`` replay the dense LUT route already uses.

    def value_lif(self, x_u8, kernel, bias, *, t: int, scale=None,
                  lut=None, occupancy=None):
        """SSSC over uint8 values already laid out as rows (..., K)."""
        op = unified.shift_sum_linear if lut is None else self._value_emu
        y, vth = self._acc_and_vth(op, x_u8, kernel, bias, scale)
        y = jnp.broadcast_to(y[None], (t, *y.shape))    # image constant in T
        return tflif(y, v_th=vth)

    def sssc_lif(self, images_u8, kernel, bias, **kw):
        return self.value_lif(space_to_depth(images_u8, 2), kernel, bias,
                              **kw)

    def image_conv_lif(self, images_u8, kernel, bias, **kw):
        """3x3 stride-1 conv on the 8-bit image (SSSC, once for all T)."""
        return self.value_lif(unified.im2col(images_u8), kernel, bias, **kw)

    def conv_lif(self, x, kernel, bias, **kw):
        """3x3 stride-1 zero-padded conv over spikes."""
        return self.wssl_lif(unified.im2col(x), kernel, bias, **kw)

    def zsc_lif(self, x, kernel, bias, *, t: int, scale=None, lut=None,
                occupancy=None):
        op = unified.zsc if lut is None else self._zsc_emu
        y, vth = self._acc_and_vth(op, x, kernel, bias, scale)
        return tflif(y, v_th=vth)

    def wssl_lif(self, x, kernel, bias, *, t: int, scale=None, lut=None,
                 occupancy=None):
        op = unified.wssl if lut is None else self._wssl_emu
        y, vth = self._acc_and_vth(op, x, kernel, bias, scale)
        return tflif(y, v_th=vth)

    def stdp_lif(self, q, k, v, *, heads: int, scale: float, t: int):
        tt, b, n, d = q.shape
        dh = d // heads

        def to_heads(z):
            return z.reshape(tt, b, n, heads, dh).transpose(0, 1, 3, 2, 4)

        att = unified.stdp(to_heads(q), to_heads(k), to_heads(v), scale=scale)
        att = tflif(att)                                # (T, B, H, N, dh)
        return att.transpose(0, 1, 3, 2, 4).reshape(tt, b, n, d)

    def qkta(self, q, k, *, heads: int, t: int, v_th: float):
        """Q-K token attention with the LIF run literally over T."""
        return unified.qkta(q, k, heads=heads, v_th=v_th)

    def max_pool(self, x):
        return unified.max_pool(x)

    def residual(self, new, res, mode: str):
        if mode == "iand":
            return (1.0 - new) * res
        return new + res

    def to_tokens(self, x):
        tt, b, h, w, c = x.shape
        return x.reshape(tt, b, h * w, c)

    def to_spatial(self, x):
        return _to_spatial(x)

    def rate(self, x, *, t: int):
        return rate_decode(x, axis=0).mean(axis=1)      # (B, D)


class PackedBackend:
    """Hardware-shaped backend: packed uint8 plane groups through
    ``kernels.ops``.

    ``pallas=None`` auto-selects (Pallas on TPU, CPU oracle otherwise);
    pass True/False to force either route.
    """

    name = "packed"

    # Route planning reads this: BOTH branches now consume the (C,256,N)
    # tables — the CPU gather route directly, the Pallas branch through the
    # VMEM-resident byte-LUT gather kernel (``lut_matmul_pallas``) and the
    # fused pack->TFLIF->matmul kernel. A session planned without tables
    # still runs: the Pallas route falls back to the grouped unpack-dot
    # kernel (bit-exact only for integer weights).
    wants_lut_tables = True

    def __init__(self, *, pallas: bool | None = None,
                 fuse_mlp: bool = True):
        self.pallas = pallas
        # fuse the MLP fc1 -> LIF -> fc2 step into one Pallas kernel when
        # possible (see ``mlp_pair_lif``); only consulted on the Pallas
        # branch — the CPU oracle always runs the two-layer composition
        self.fuse_mlp = fuse_mlp

    def _lif(self, acc, bias, scale):
        """acc (T, ...) -> (G, ...) packed; int8 layers fold their
        per-channel scale into the bias/threshold, never the accumulator."""
        if scale is None:
            return ops.tflif_pack(acc, bias, pallas=self.pallas)
        return ops.tflif_pack(acc, bias / scale, v_th=V_TH / scale,
                              pallas=self.pallas)

    @staticmethod
    def _w(kernel, scale):
        """How an int8 kernel enters the packed matmul (single spot)."""
        return kernel if scale is None else kernel.astype(jnp.float32)

    # ``occupancy`` is the plan's static per-layer chunk-occupancy
    # calibration (present only for "lut_sparse"-routed layers); the ops
    # layer derives the zero-chunk-skipping gather budget from it.

    def value_lif(self, x_u8, kernel, bias, *, t: int, scale=None,
                  lut=None, occupancy=None):
        """SSSC over uint8 values already laid out as rows (..., K)."""
        acc = ops.sssc_linear(x_u8, self._w(kernel, scale), None,
                              pallas=self.pallas, table=lut,
                              occupancy=occupancy)
        acc = jnp.broadcast_to(acc[None], (t, *acc.shape))
        return self._lif(acc, bias, scale)              # (G, ..., F) u8

    def sssc_lif(self, images_u8, kernel, bias, **kw):
        return self.value_lif(space_to_depth(images_u8, 2), kernel, bias,
                              **kw)

    def image_conv_lif(self, images_u8, kernel, bias, **kw):
        """3x3 stride-1 conv on the 8-bit image: im2col of the pixel bytes,
        then SSSC (once for all T)."""
        return self.value_lif(unified.im2col(images_u8), kernel, bias, **kw)

    def conv_lif(self, x, kernel, bias, **kw):
        """3x3 stride-1 zero-padded conv over spikes: im2col of the packed
        bytes, then the spike linear."""
        return self.wssl_lif(unified.im2col(x), kernel, bias, **kw)

    def zsc_lif(self, x, kernel, bias, *, t: int, scale=None, lut=None,
                occupancy=None):
        acc = ops.spike_linear(space_to_depth(x, 2), self._w(kernel, scale),
                               None, t=t, pallas=self.pallas, table=lut,
                               occupancy=occupancy)
        return self._lif(acc, bias, scale)

    def wssl_lif(self, x, kernel, bias, *, t: int, scale=None, lut=None,
                 occupancy=None):
        acc = ops.spike_linear(x, self._w(kernel, scale), None, t=t,
                               pallas=self.pallas, table=lut,
                               occupancy=occupancy)
        return self._lif(acc, bias, scale)

    def mlp_pair_lif(self, x, fc1, fc2, *, t: int, occupancy=None):
        """Fused MLP pair: fc1 matmul -> (LIF + pack + fc2 byte-LUT gather
        in ONE Pallas kernel) -> fc2 LIF. The unpacked fc1 spike tensor
        never reaches HBM (``kernels.fused``); the emitted logits are
        bit-identical to the two-layer path, so ``forward_folded`` may take
        either.

        Returns None when the fused kernel does not apply — CPU-oracle
        sessions, ``fuse_mlp=False``, no (C,256,N) table planned for fc2,
        or a table too large to stay VMEM-resident
        (``kernels.fused.MAX_TABLE_BYTES``) — and the caller falls back to
        the unfused two-layer composition. The decision is static (shapes
        only), made at trace time.
        ``occupancy`` is fc1's input calibration, forwarded to its matmul.
        """
        if not (self.fuse_mlp and ops.use_pallas(self.pallas)):
            return None
        tbl2 = fc2.get("lut")
        if not ops._have_table(tbl2):
            return None
        if tbl2.size * tbl2.dtype.itemsize > fused.MAX_TABLE_BYTES:
            return None
        scale1 = fc1.get("scale")
        acc1 = ops.spike_linear(x, self._w(fc1["kernel"], scale1), None,
                                t=t, pallas=self.pallas,
                                table=fc1.get("lut"), occupancy=occupancy)
        # fc1's int8 scale folds into its LIF bias/threshold exactly as in
        # ``_lif`` — the fused kernel sees the same charge/compare operands
        b1 = fc1["bias"] if scale1 is None else fc1["bias"] / scale1
        v1 = V_TH if scale1 is None else V_TH / scale1
        _s1, acc2 = ops.tflif_lut(acc1, b1, table=tbl2, v_th=v1, t=t,
                                  pallas=self.pallas)
        return self._lif(acc2, fc2["bias"], fc2.get("scale"))

    def stdp_lif(self, q, k, v, *, heads: int, scale: float, t: int):
        g, b, n, d = q.shape
        dh = d // heads

        def to_heads(z):
            return z.reshape(g, b, n, heads, dh).transpose(0, 1, 3, 2, 4)

        # route="auto": the LUT score path engages at large token counts
        # (bit-identical either way — binary q/k/v keep every accumulator an
        # exact integer, so no reference-side emulation is needed)
        acc = ops.stdp_attention_packed(
            to_heads(q), to_heads(k), to_heads(v), t=t, scale=scale,
            pallas=self.pallas, route="auto")           # (t, B, H, N, dh)
        att = ops.tflif_pack(acc, pallas=self.pallas)   # (G, B, H, N, dh) u8
        return att.transpose(0, 1, 3, 2, 4).reshape(g, b, n, d)

    def qkta(self, q, k, *, heads: int, t: int, v_th: float):
        """Q-K token attention on packed bytes. With tau = 2 and a hard
        reset, the mask neuron's input is a whole count, and for
        0 < v_th <= 1/tau it fires exactly when that count is at least 1:
        the mask is the OR of the head's Q bytes, which carry every
        timestep at once (``ops.qk_token_attention``). For any other
        threshold the LIF keeps state across timesteps and that form
        would be wrong, so it is refused."""
        if not 0.0 < v_th <= 1.0 / TAU:
            raise ValueError(
                f"packed QKTA computes the mask as an OR, exact only for "
                f"0 < v_th <= 1/tau = {1.0 / TAU}; got v_th={v_th}")
        return ops.qk_token_attention(q, k, heads=heads, t=t,
                                      pallas=self.pallas)

    def max_pool(self, x):
        """Spiking 3/2/1 max-pool: the max of binary values is their OR,
        which on packed bytes pools every timestep at once."""
        return functools.reduce(jnp.bitwise_or, unified.pool_windows(x))

    def residual(self, new, res, mode: str):
        if mode != "iand":
            raise ValueError(
                "packed activations are strictly binary; residual mode "
                f"{mode!r} requires the float reference backend")
        # SEW IAND on packed bytes, all plane groups at once: (NOT new) AND
        # res. Bits >= T in the last group are 0 in `res`, so the
        # complement's high bits are masked off for free.
        return jnp.bitwise_and(jnp.bitwise_not(new), res)

    def to_tokens(self, x):
        g, b, h, w, c = x.shape
        return x.reshape(g, b, h * w, c)

    def to_spatial(self, x):
        return _to_spatial(x)

    def rate(self, x, *, t: int):
        # popcount readout: sum of bits per neuron without unpacking. The
        # count is an exact integer (any summation order), and the /t
        # mirrors rate_decode's mean division, so this matches the float
        # reference bit for bit.
        counts = lax.population_count(x).astype(jnp.int32).sum(axis=0)
        rate = counts.astype(jnp.float32) / jnp.float32(t)
        return rate.mean(axis=1)


class OccupancyRecorder(PackedBackend):
    """A ``PackedBackend`` that records the chunk occupancy of every linear
    layer's packed matmul operand, in forward call order.

    ``infer.compile.calibrate_layer_occupancy`` runs one UN-JITTED forward
    through this backend (each readout concretizes to a python float, which
    a trace cannot do) and zips ``trace`` with the layer paths in the same
    deterministic order ``forward_folded`` visits them. The measured
    quantity is exactly what ``choose_route``/``sparse_budget`` consume:
    the fraction of nonzero chunk-index bytes the gather would visit.
    """

    def __init__(self):
        super().__init__(pallas=False)
        self.trace: list[float] = []

    def value_lif(self, x_u8, kernel, bias, *, t: int, scale=None,
                  lut=None, occupancy=None):
        self.trace.append(value_chunk_occupancy(x_u8))
        return super().value_lif(x_u8, kernel, bias, t=t, scale=scale,
                                 lut=lut)

    def zsc_lif(self, x, kernel, bias, *, t: int, scale=None, lut=None,
                occupancy=None):
        self.trace.append(chunk_occupancy(space_to_depth(x, 2), t))
        return super().zsc_lif(x, kernel, bias, t=t, scale=scale, lut=lut)

    def wssl_lif(self, x, kernel, bias, *, t: int, scale=None, lut=None,
                 occupancy=None):
        self.trace.append(chunk_occupancy(x, t))
        return super().wssl_lif(x, kernel, bias, t=t, scale=scale, lut=lut)


# ---------------------------------------------------------------------------
# Registration: the built-in backends enter the registry here; ``get_backend``
# is now a registry lookup (kept importable from this module for callers of
# the pre-registry API).
# ---------------------------------------------------------------------------

# keyword-only factories: a misspelled option key must raise TypeError,
# not silently run the default route. Every factory accepts + ignores
# ``interpret`` — the registry's CPU-only "run the Pallas interpreter"
# gate (see ``registry.get_backend``), checked there; the kernels make the
# same call themselves (``kernels.device.resolve_interpret``).
registry.register_backend(
    "packed",
    lambda *, pallas=None, fuse_mlp=True, interpret=None:
        PackedBackend(pallas=pallas, fuse_mlp=fuse_mlp),
    weight_dtypes=("float32", "int8"),
    device_kinds=("cpu", "tpu"),
    wants_lut_tables=True,      # both branches gather from planned tables
    overwrite=True)             # survive importlib.reload of this module

registry.register_backend(
    "reference",
    lambda *, pallas=None, interpret=None: FloatBackend(),
    weight_dtypes=("float32", "int8"),
    device_kinds=("cpu", "gpu", "tpu"),
    wants_lut_tables=False,     # plan flags only, never (C,256,N) tables
    aliases=("float",),
    overwrite=True)

# The Pallas-pinned packed backend: the registration path the registry
# docstring promises, as a real registration. Same PackedBackend class,
# pallas=True forced — the compiled kernels on TPU, interpret mode on a CPU
# host (the registry's device gate makes that an explicit
# ``backend_options={'interpret': True}`` opt-in). Route planning DOES
# build (C,256,N) tables for it: the Pallas byte-LUT gather kernel and the
# fused MLP kernel consume them from VMEM.
def _packed_pallas_factory(*, pallas=True, fuse_mlp=True, interpret=None):
    if pallas is not True:
        # this registration *is* the Pallas pin; a pallas=False instance
        # here would belie every capability the spec declares — reject at
        # the door, don't quietly run the CPU route under the wrong name
        raise ValueError("packed_pallas pins pallas=True; for the CPU "
                         "route use backend='packed' (optionally with "
                         "backend_options={'pallas': False})")
    return PackedBackend(pallas=True, fuse_mlp=fuse_mlp)


registry.register_backend(
    "packed_pallas",
    _packed_pallas_factory,
    weight_dtypes=("float32", "int8"),
    device_kinds=("tpu",),
    wants_lut_tables=True,
    aliases=("pallas",),
    overwrite=True)             # survive importlib.reload of this module

get_backend = registry.get_backend
