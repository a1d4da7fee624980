"""Unified-PE Pallas kernel: packed binary planes x shared 8-bit weights.

This is VESTA's PE module mapped to the TPU. A PE unit = one 8-bit weight
shared by 8 binary inputs; here a *byte* of the packed activation tensor holds
those 8 binary planes, and one VMEM-resident weight tile serves all of them
(weight-stationary). Two reduction modes select the dataflow:

  mode="per_plane"  (WSSL / ZSC / STDP operands):
      Y[p] = S_p @ W  for p = 0..7        -> out (8, M, N)
      The 8 planes are *folded into the row dimension* of a single MXU dot —
      the TPU analogue of "all timesteps computed simultaneously".

  mode="shift_sum"  (SSSC):
      Y = sum_p 2^p * (S_p @ W)           -> out (M, N)
      The scaled combine happens at unpack time (sum_p 2^p S_p == the uint8
      value), so the MXU sees ONE dot instead of eight — a TPU-native
      improvement over the paper's 8-pass shift-and-sum, with identical math.

Memory win vs dense activations: the HBM->VMEM stream of S is 1 bit/plane
(uint8 carries 8 planes) instead of 8-32 bits — the same 8x traffic reduction
the paper gets from its Small-Input/Output SRAMs.

Grid: (M/bm, N/bn, K/bk), K innermost; f32 accumulator tile in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .device import resolve_interpret, vmem_limit


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, mode: str, nk: int,
            k_dim: int = 2):
    """x_ref: (bm, bk) uint8 packed (or (1, bm, bk) in the grouped grid);
    w_ref: (bk, bn); o_ref: (8,bm,bn) | (bm,bn) | (1,8,bm,bn) grouped."""
    k_step = pl.program_id(k_dim)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if x.ndim == 3:                     # grouped grid: squeeze the g block dim
        x = x[0]
    # Mosaic has no 8-bit vector arithmetic: widen the bytes before any
    # shift or float cast (the HBM->VMEM stream stays 1 byte per 8 planes)
    x = x.astype(jnp.int32)
    w = w_ref[...].astype(jnp.float32)
    bm, bk = x.shape
    if mode == "per_plane":
        # (bm, bk) bytes -> (8, bm, bk) bits -> (8*bm, bk) rows -> one MXU dot
        shifts = lax.broadcasted_iota(jnp.int32, (8, 1, 1), 0)
        bits = (x[None, :, :] >> shifts) & 1
        planes = bits.reshape(8 * bm, bk).astype(jnp.float32)
        part = jnp.dot(planes, w, preferred_element_type=jnp.float32)
        acc_ref[...] += part.reshape(8, bm, w.shape[-1])
    else:  # shift_sum: the byte IS sum_p 2^p S_p — combine before the dot
        val = x.astype(jnp.float32)
        acc_ref[...] += jnp.dot(val, w, preferred_element_type=jnp.float32)

    @pl.when(k_step == nk - 1)
    def _done():
        acc = acc_ref[...].astype(o_ref.dtype)
        o_ref[...] = acc if o_ref.ndim == acc.ndim else acc[None]


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk", "interpret"))
def spike_matmul(x_packed, w, *, mode: str = "per_plane",
                 bm: int = 128, bn: int = 128, bk: int = 256,
                 interpret: bool | None = None):
    """x_packed: (M, K) uint8 (bit p of [m,k] = plane p's spike) or, for
    mode="per_plane" only, (G, M, K) plane groups; w: (K, N).

    Returns (8, M, N) for mode="per_plane" [(G, 8, M, N) grouped], (M, N) for
    mode="shift_sum". Shapes are padded to block multiples internally.

    Grouped route: the plane-group axis becomes the outermost grid dimension,
    so each (bk, bn) weight tile streamed into VMEM serves all 8 planes of a
    group before the grid advances — the weight-stationary property is per
    group of 8, exactly the VESTA PE contract.
    """
    assert mode in ("per_plane", "shift_sum"), mode
    if x_packed.ndim == 3:
        assert mode == "per_plane", "plane groups are temporal: per_plane only"
        return _spike_matmul_grouped(x_packed, w, bm=bm, bn=bn, bk=bk,
                                     interpret=interpret)
    m, k = x_packed.shape
    k2, n = w.shape
    assert k == k2, (x_packed.shape, w.shape)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm_, (-n) % bn_, (-k) % bk_
    if pm or pk:
        x_packed = jnp.pad(x_packed, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    mp, kp = x_packed.shape
    np_ = w.shape[1]
    grid = (mp // bm_, np_ // bn_, kp // bk_)

    if mode == "per_plane":
        out_shape = jax.ShapeDtypeStruct((8, mp, np_), jnp.float32)
        out_spec = pl.BlockSpec((8, bm_, bn_), lambda i, j, kk: (0, i, j))
        acc = pltpu.VMEM((8, bm_, bn_), jnp.float32)
    else:
        out_shape = jax.ShapeDtypeStruct((mp, np_), jnp.float32)
        out_spec = pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j))
        acc = pltpu.VMEM((bm_, bn_), jnp.float32)

    y = pl.pallas_call(
        functools.partial(_kernel, mode=mode, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[acc],
        name="spike_matmul",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(x_packed, w)

    if mode == "per_plane":
        return y[:, :m, :n]
    return y[:m, :n]


def gather256(tbl_c, idx_col, acc_dtype):
    """Gather one LUT chunk inside a kernel: ``tbl_c`` (256, bn) partial
    sums, ``idx_col`` (bm, 1) int32 index bytes -> (bm, bn) gathered rows.

    Implemented as a one-hot matmul rather than a dynamic gather — the MXU
    has no gather unit, but a (bm, 256) one-hot against the VMEM-resident
    table IS the multiplexer select of VESTA's PE, and it is *exact in any
    reduction order*: 255 of the 256 products per output element are exact
    zeros (0 * v and 1 * v are both exact in IEEE), so the sum equals the
    selected table entry bit for bit regardless of how the hardware
    associates it (up to the sign of a zero, which ``==`` ignores).

    The dot runs in float32 at ``Precision.HIGHEST`` — the MXU has no
    int32 matmul, and HIGHEST splits each f32 table entry into bf16 pieces
    that sum back exactly, so the one-hot select stays exact on the TPU.
    Integer tables (entries bounded by 8 * 127) come back as exact
    integer-valued floats and accumulate in int32, as the CPU gather does.
    """
    iota = lax.broadcasted_iota(jnp.int32, (idx_col.shape[0], 256), 1)
    onehot = (idx_col == iota).astype(jnp.float32)
    y = lax.dot_general(onehot, tbl_c.astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    return y.astype(acc_dtype)


def _lut_kernel(idx_ref, tbl_ref, o_ref, acc_ref, *, nc: int, bc: int):
    """idx_ref: (1, bm, bc) uint8 per-plane index bytes; tbl_ref:
    (bc, 256, bn) chunk-partial-sum table tile in VMEM; o_ref: (1, bm, bn)
    f32; acc_ref: (bm, bn) f32/int32 scratch. Chunk tiles are visited
    ascending (innermost grid dim), and within a tile the fold is a static
    ascending python loop — together they replay ``lut_matmul``'s defined
    ascending-chunk reduction tree exactly."""
    c_step = pl.program_id(3)

    @pl.when(c_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0].astype(jnp.int32)  # (bm, bc); no 8-bit vector math
    acc = acc_ref[...]
    for cc in range(bc):                # static unroll: the defined fold
        acc = acc + gather256(tbl_ref[cc], idx[:, cc:cc + 1], acc.dtype)
    acc_ref[...] = acc

    @pl.when(c_step == nc - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bc", "interpret"))
def lut_gather_matmul(idx, table, *, bm: int = 128, bn: int = 128,
                      bc: int = 128, interpret: bool | None = None):
    """Pallas byte-LUT matmul: (P, M, C) uint8 per-plane index bytes x
    (C, 256, N) chunk-partial-sum table -> (P, M, N) f32 accumulators.

    The grid (P, M/bm, N/bn, C/bc) extends ``_spike_matmul_grouped``'s
    plane-group structure: the plane axis is outermost so one (bc, 256, bn)
    table tile streamed into VMEM serves every plane before the grid
    advances — the table is the stationary operand, exactly the paper's
    weight-stationary PE with the 8-row chunk partial sums precomputed.
    Reduction follows ``lut_matmul``'s defined ascending-chunk fold (chunk
    tiles ascend in the innermost grid dim, a static ascending unroll
    inside each tile), with int32 accumulation for int16 tables, so the
    result is bit-exact against the CPU gather route and its
    ``lut_matmul_planes`` float oracle.

    The chunk axis is the index block's lane dim, so on a TPU a chunk tile
    is either every chunk (C <= bc) or a multiple of 128 chunks — the
    default ``bc`` is one lane width; smaller tiles are for interpret-mode
    tiling tests. The double-buffered table tile sets the VMEM limit the
    kernel asks for.

    Padding: M pads with zero index bytes (they gather the exact-zero
    ``table[c, 0, :]`` entry), N pads the table with zero columns, C pads
    the table with all-zero chunks — all are exact-identity adds, sliced
    off on return.
    """
    p, m, c = idx.shape
    c2, _, n = table.shape
    assert c == c2, (idx.shape, table.shape)
    bm_, bn_, bc_ = min(bm, m), min(bn, n), min(bc, c)
    pm, pn, pc = (-m) % bm_, (-n) % bn_, (-c) % bc_
    if pm or pc:
        idx = jnp.pad(idx, ((0, 0), (0, pm), (0, pc)))
    if pc or pn:
        table = jnp.pad(table, ((0, pc), (0, 0), (0, pn)))
    mp, cp = idx.shape[1:]
    np_ = table.shape[-1]
    grid = (p, mp // bm_, np_ // bn_, cp // bc_)
    acc_dtype = (jnp.int32 if jnp.issubdtype(table.dtype, jnp.integer)
                 else jnp.float32)
    tile_bytes = bc_ * 256 * bn_ * table.dtype.itemsize

    y = pl.pallas_call(
        functools.partial(_lut_kernel, nc=grid[3], bc=bc_),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bc_), lambda pp, i, j, cc: (pp, i, cc)),
            pl.BlockSpec((bc_, 256, bn_), lambda pp, i, j, cc: (cc, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_),
                               lambda pp, i, j, cc: (pp, i, j)),
        out_shape=jax.ShapeDtypeStruct((p, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(2 * tile_bytes)),
        name="lut_gather_matmul",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(idx, table)
    return y[:, :m, :n]


def _spike_matmul_grouped(x_packed, w, *, bm: int, bn: int, bk: int,
                          interpret: bool | None):
    """(G, M, K) uint8 plane groups x (K, N) -> (G, 8, M, N) per-plane dots.

    Grid (G, M/bm, N/bn, K/bk): for each group the inner three dims replay the
    2D per_plane schedule, reusing the same (8, bm, bn) f32 accumulator tile.
    """
    g, m, k = x_packed.shape
    k2, n = w.shape
    assert k == k2, (x_packed.shape, w.shape)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm_, (-n) % bn_, (-k) % bk_
    if pm or pk:
        x_packed = jnp.pad(x_packed, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    mp, kp = x_packed.shape[1:]
    np_ = w.shape[1]
    grid = (g, mp // bm_, np_ // bn_, kp // bk_)

    y = pl.pallas_call(
        functools.partial(_kernel, mode="per_plane", nk=grid[3], k_dim=3),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((bk_, bn_), lambda gg, i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((1, 8, bm_, bn_),
                               lambda gg, i, j, kk: (gg, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, 8, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, bm_, bn_), jnp.float32)],
        name="spike_matmul",  # the kernel family in a device trace
        interpret=resolve_interpret(interpret),
    )(x_packed, w)
    return y[:, :, :m, :n]
