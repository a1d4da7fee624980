"""Spike-form data handling: bit-packing and encodings.

VESTA's PE unit feeds 8 binary inputs against one shared 8-bit weight. The
TPU-native analogue is *storage*: spikes live packed 8-per-uint8 in HBM (the
"Small Input SRAM" / "Output SRAM" of the paper), and kernels unpack them in
VMEM. This is where the 8x activation-bandwidth saving comes from.

Plane semantics:
  * temporal packing  — the 8 bits of a byte are 8 consecutive timesteps:
    used by ZSC / WSSL / STDP. Each plane is an independent output. For
    T > 8 the packed tensor carries a leading *plane-group* axis of size
    G = ceil(T/8); group g holds timesteps 8g .. 8g+7.
  * bit-plane packing — the 8 bits are the binary expansion of a uint8 pixel:
    used by SSSC. Planes are summed with weights 2^k (always exactly 8
    planes, so never more than one group).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_POWERS = 2 ** jnp.arange(8, dtype=jnp.uint8)


def pack_bits(x, axis: int = -1):
    """Pack a binary {0,1} array along ``axis`` (size must be multiple of 8)
    into uint8. Output has that axis shrunk 8x."""
    x = jnp.moveaxis(x, axis, -1)
    assert x.shape[-1] % 8 == 0, f"pack axis {x.shape[-1]} not multiple of 8"
    x = x.reshape(*x.shape[:-1], x.shape[-1] // 8, 8).astype(jnp.uint8)
    packed = (x * _POWERS).sum(axis=-1, dtype=jnp.uint32).astype(jnp.uint8)
    return jnp.moveaxis(packed, -1, axis)


def unpack_bits(x, axis: int = -1, *, count: int = 8, dtype=jnp.float32):
    """Inverse of pack_bits: uint8 -> {0,1} planes; axis grows 8x (or ``count``
    bits per byte if count < 8)."""
    x = jnp.moveaxis(x, axis, -1)
    bits = (x[..., None] >> jnp.arange(count, dtype=jnp.uint8)) & jnp.uint8(1)
    bits = bits.reshape(*x.shape[:-1], x.shape[-1] * count).astype(dtype)
    return jnp.moveaxis(bits, -1, axis)


def num_plane_groups(t: int) -> int:
    """Number of uint8 plane groups needed for a T-timestep spike train."""
    assert t >= 1, t
    return -(-t // 8)


def pack_timesteps(spikes, *, time_axis: int = 0):
    """Temporal packing for the inference datapath: a (T, ...) binary spike
    train becomes ``G = ceil(T/8)`` bytes per neuron, returned with a leading
    *plane-group* axis: output (G, ...) uint8 where bit j of group g is the
    spike at timestep ``8*g + j`` (matching ``kernels.ref.tflif_ref`` output).
    Bits past T-1 in the last group are zero. The T axis is consumed; all
    other axes keep their layout."""
    t = spikes.shape[time_axis]
    g = num_plane_groups(t)
    x = jnp.moveaxis(spikes, time_axis, 0).astype(jnp.uint8)
    pad = g * 8 - t
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, *x.shape[1:]), jnp.uint8)], axis=0)
    x = x.reshape(g, 8, *x.shape[1:])
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(
        (1, 8) + (1,) * (x.ndim - 2))
    return jnp.bitwise_or.reduce(x << shifts, axis=1)


def popcount(x) -> int:
    """Number of set bits in a uint8 array, as an exact integer.

    Counts 8 bytes at a time with ``np.bitwise_count`` over a uint64 view
    of the largest 8-byte multiple, then adds the 0-7 tail bytes. A
    contiguous input (such as a leading-row slice of a batch) is not
    copied."""
    x = np.ascontiguousarray(x, np.uint8).reshape(-1)
    n = x.size - x.size % 8
    return (int(np.bitwise_count(x[:n].view(np.uint64)).sum())
            + int(np.bitwise_count(x[n:]).sum()))


def packed_occupancy(packed, t: int) -> float:
    """Mean firing rate of a ``(G, ...)`` packed spike tensor over its ``t``
    live timesteps: set bits / (t * neurons). Bits past ``t - 1`` in the
    last group are zero by the ``pack_timesteps`` invariant, so a plain
    popcount over every byte is exact — no unpack, no masking. Accepts
    numpy or jax input (the readout is a host-side float either way); this
    is the firing-rate number the serving occupancy EWMAs and the event
    front end's per-window readout share."""
    g = packed.shape[0]
    assert g == num_plane_groups(t), (g, t)
    x = np.asarray(packed, np.uint8)
    neurons = x.size // g if g else 0
    if not neurons:
        return 0.0
    return popcount(x) / (t * neurons)


def unpack_timesteps(packed, t: int, *, time_axis: int = 0,
                     dtype=jnp.float32):
    """Inverse of ``pack_timesteps``: (G, ...) uint8 plane groups -> (T, ...)
    binary planes inserted at ``time_axis`` (bit j of group g = timestep
    ``8*g + j``)."""
    g = packed.shape[0]
    assert g == num_plane_groups(t), (g, t)
    bits = (packed[:, None, ...] >> jnp.arange(8, dtype=jnp.uint8).reshape(
        (1, 8) + (1,) * (packed.ndim - 1))) & jnp.uint8(1)
    planes = bits.reshape(g * 8, *packed.shape[1:])[:t]
    return jnp.moveaxis(planes.astype(dtype), 0, time_axis)


def bitplanes_u8(x, *, dtype=jnp.float32):
    """uint8 tensor (...,) -> (8, ...) binary planes, LSB first (SSSC input)."""
    planes = (x[None, ...] >> jnp.arange(8, dtype=jnp.uint8).reshape(
        (8,) + (1,) * x.ndim)) & jnp.uint8(1)
    return planes.astype(dtype)


def structured_spikes(key, *, t: int, shape: tuple, rate: float,
                      chunk: int = 8, group_rate: float = 0.9):
    """Random packed spikes at overall firing rate ``rate`` with
    CHANNEL-STRUCTURED sparsity: an exact count of ``chunk``-aligned
    channel groups is active (shared across rows and timesteps) and only
    those fire, each active channel at ``group_rate``. Returns
    ``(G, *shape)`` uint8 plane groups via ``pack_timesteps``.

    Why not iid bits: at iid rate p, a K-chunk of 8 channels is all-zero
    with probability ``(1-p)^8`` (~6% at p=0.3) — nearly nothing for a
    zero-chunk skipper to skip. Trained SNNs are not iid: whole channels
    go quiet together while the surviving ones fire often (the layer-wise
    sparsity structure sparse-accelerator papers exploit), which
    concentrates the zeros into skippable chunks. Here the active-group
    fraction is ``rate / group_rate``, so the resulting CHUNK occupancy
    (what the sparse route's budget is sized from) tracks the firing rate
    ~1:1 instead of doubling it; the active-group count is exact, not a
    Bernoulli draw, so the occupancy a benchmark measures is the one it
    asked for.

    The last axis of ``shape`` is the channel axis and must be a multiple
    of ``chunk``; ``rate`` must not exceed ``group_rate``.
    """
    assert 0.0 <= rate <= group_rate <= 1.0, (rate, group_rate)
    *lead, channels = shape
    assert channels % chunk == 0, (channels, chunk)
    if rate == 0.0:
        return jnp.zeros((num_plane_groups(t), *shape), jnp.uint8)
    kg, kb = jax.random.split(key)
    groups = channels // chunk
    n_active = max(1, round(rate / group_rate * groups))
    active = jnp.zeros(groups, bool).at[
        jax.random.permutation(kg, groups)[:n_active]].set(True)
    active = jnp.repeat(active, chunk)            # (channels,) group mask
    # in-group rate chosen so the overall rate stays ``rate`` after masking
    bits = jax.random.bernoulli(kb, min(1.0, rate * groups / n_active),
                                (t, *lead, channels))
    return pack_timesteps((bits & active).astype(jnp.uint8))


def rate_decode(spikes, axis: int = 0):
    """Spike train -> rate (mean over timesteps); classification readout."""
    return spikes.astype(jnp.float32).mean(axis=axis)


def space_to_depth(x, block: int = 2):
    """(..., H, W, C) -> (..., H/b, W/b, b*b*C). This *is* the ZSC zig-zag
    placement: a 2x2/s2 convolution becomes a plain matmul over 4C features."""
    *lead, h, w, c = x.shape
    assert h % block == 0 and w % block == 0, (h, w, block)
    x = x.reshape(*lead, h // block, block, w // block, block, c)
    x = jnp.moveaxis(x, -4, -3)  # (..., H/b, W/b, b, b, C)
    return x.reshape(*lead, h // block, w // block, block * block * c)
