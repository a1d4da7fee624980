"""Unpack-free byte-LUT matmul: the CPU analogue of VESTA's multiplexer PE.

A binary spike turns multiply into *select* — VESTA's PE is a multiplexer,
not a multiplier. The software analogue on a byte-packed datapath: one uint8
of packed spikes *selects* a precomputed partial sum over its 8-row weight
chunk. Per chunk ``c`` of 8 weight rows, ``table[c, b, :]`` holds the partial
sum of rows whose bit is set in byte ``b``; the matmul then reduces to
gather-and-accumulate over the packed bytes — the ``(T, M, K)`` unpacked
plane tensor is never materialized, and the arithmetic drops from
``T*M*K*N`` multiply-adds to ``T*M*(K/8)*N`` gathered adds.

Bit layout plumbing: the inter-layer packed representation is *time*-packed
(bit j of byte ``[g, m, k]`` = timestep ``8g+j`` of neuron ``k`` — see
``core.spike``), while the LUT selects over 8 consecutive *K positions*. The
bridge is an 8x8 bit-matrix transpose (``plane_indices``), done wordwise on
two uint32 lanes (Hacker's Delight 7-3) — ~20 elementwise ops per 8 bytes,
several times cheaper than unpacking those 64 bits to float.

Exactness contract (the part that keeps the parity suite single-sourced):
float32 sums are not reorderable, and XLA's ``dot`` reduction order is both
unspecified and shape-dependent, so the LUT route does NOT try to match the
single-dot unpack oracle bitwise. Instead the route *defines* its reduction
tree — ascending-bit multiply-add folds inside a chunk, ascending-chunk adds
across chunks — built exclusively from elementwise IEEE ops whose per-element
results are shape-independent. ``lut_matmul_planes`` replays the identical
op sequence on unpacked {0,1} float planes; it is the bit-exact oracle for
this route (and what ``infer.backends.FloatBackend`` executes for LUT-planned
layers, the same emulation role it already plays for int8's threshold fold).
For integer weights (the int8 route) every partial sum is an exact small
integer, so all routes agree bitwise regardless of order; tables are then
held in int16 — half the gather bandwidth, still exact (|sum of 8| <= 1016,
chunk accumulation in int32).
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
from jax import lax

K_CHUNK = 8  # weight rows selected by one byte — the PE fan-in of the paper


def num_k_chunks(k: int) -> int:
    """Number of 8-row weight chunks (= LUT gather steps) for K input rows."""
    assert k >= 1, k
    return -(-k // K_CHUNK)


def table_bytes(k: int, n: int, weights_are_int: bool) -> int:
    """Size of the cached LUT for a (K, N) kernel — the memory side of the
    memory/compute trade-off the dispatch heuristic weighs."""
    return num_k_chunks(k) * 256 * n * (2 if weights_are_int else 4)


def _is_int_kernel(w) -> bool:
    return jnp.issubdtype(w.dtype, jnp.integer)


# ---------------------------------------------------------------------------
# 8x8 bit-matrix transpose (time-packed bytes -> K-packed index bytes)
# ---------------------------------------------------------------------------

def bit_transpose8(b):
    """Transpose an 8x8 bit matrix held as 8 bytes, elementwise over leading
    axes: input ``b`` (..., 8) uint8 with rows i = bytes; output (..., 8)
    uint8 where ``out[..., j]`` bit i == ``b[..., i]`` bit j.

    Wordwise Hacker's Delight 7-3 on two little-endian uint32 lanes; the
    byte<->word marshalling is a free bitcast, and the lane swap absorbs the
    big-endian byte order the original algorithm assumes.
    """
    w = lax.bitcast_convert_type(
        b.reshape(*b.shape[:-1], 2, 4), jnp.uint32)         # (..., 2) LE words
    x, y = w[..., 1], w[..., 0]
    t = (x ^ (x >> 7)) & jnp.uint32(0x00AA00AA)
    x = x ^ t ^ (t << 7)
    t = (y ^ (y >> 7)) & jnp.uint32(0x00AA00AA)
    y = y ^ t ^ (t << 7)
    t = (x ^ (x >> 14)) & jnp.uint32(0x0000CCCC)
    x = x ^ t ^ (t << 14)
    t = (y ^ (y >> 14)) & jnp.uint32(0x0000CCCC)
    y = y ^ t ^ (t << 14)
    t = (x & jnp.uint32(0xF0F0F0F0)) | ((y >> 4) & jnp.uint32(0x0F0F0F0F))
    y = ((x << 4) & jnp.uint32(0xF0F0F0F0)) | (y & jnp.uint32(0x0F0F0F0F))
    x = t
    out = jnp.stack([y, x], axis=-1)
    return lax.bitcast_convert_type(out, jnp.uint8).reshape(b.shape)


def _pad_k(x, k: int, value=0):
    """Pad the trailing (K) axis up to a multiple of 8."""
    pad = num_k_chunks(k) * K_CHUNK - k
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths, constant_values=value)
    return x


def plane_indices(x_packed):
    """Time-packed plane groups -> per-plane LUT index bytes.

    Args:
      x_packed: (G, ..., K) uint8, bit j of [g, ..., k] = plane ``8g+j`` of
        input k (temporal planes for WSSL/ZSC, value bit-planes for SSSC
        with G == 1). Any number of row axes — the transpose runs natively
        on the caller's layout (no in-graph flatten; see ``ref.tflif_ref``).

    Returns:
      (G*8, ..., C) uint8, C = ceil(K/8): bit i of [p, ..., c] = plane p of
      input ``8c+i`` — the byte that selects chunk c's LUT entry for that
      row. Planes past the live count are all-zero bytes (the packing
      invariant keeps dead bits zero); callers slice ``[:t]``.
    """
    g, k = x_packed.shape[0], x_packed.shape[-1]
    lead = x_packed.shape[1:-1]
    c = num_k_chunks(k)
    x = _pad_k(x_packed, k).reshape(g, *lead, c, K_CHUNK)
    idx = bit_transpose8(x)                                 # [..., j] bit i
    return jnp.moveaxis(idx, -1, 1).reshape(g * K_CHUNK, *lead, c)


# ---------------------------------------------------------------------------
# Table build and gather-accumulate (the defined reduction tree)
# ---------------------------------------------------------------------------

def build_lut(w):
    """Precompute the 256 chunk partial sums: (K, N) -> (C, 256, N) table.

    ``table[c, b, :]`` = ascending-bit fold of ``bit_i(b) * w[8c+i, :]`` —
    elementwise multiply-adds only, so every entry equals the corresponding
    ``lut_matmul_planes`` partial bit for bit. Integer kernels produce an
    int16 table (exact, half the gather bandwidth); float kernels float32.
    """
    k, n = w.shape
    c = num_k_chunks(k)
    if _is_int_kernel(w):
        wc = _pad_k(w.astype(jnp.int16).T, k).T.reshape(c, K_CHUNK, n)
        bits = ((jnp.arange(256, dtype=jnp.int16)[:, None]
                 >> jnp.arange(K_CHUNK, dtype=jnp.int16)) & 1)
        tbl = jnp.zeros((c, 256, n), jnp.int16)
    else:
        wc = _pad_k(w.astype(jnp.float32).T, k).T.reshape(c, K_CHUNK, n)
        bits = ((jnp.arange(256)[:, None] >> jnp.arange(K_CHUNK)) & 1
                ).astype(jnp.float32)
        tbl = jnp.zeros((c, 256, n), jnp.float32)
    for i in range(K_CHUNK):
        tbl = tbl + bits[None, :, i, None] * wc[:, None, i, :]
    return tbl


def lut_matmul(idx, table, *, block_n: int | None = None):
    """Gather-and-accumulate: (..., C) index bytes x (C, 256, N) table ->
    (..., N) f32 accumulators (any number of row axes).

    Reduction is the defined ascending-chunk sequential fold. ``block_n``
    tiles the output columns to bound the (R, M, N)-sized gather
    intermediates (the K tiling is the chunk fold itself); tiling never
    changes per-element op order, so exactness is unaffected.
    """
    c, _, n = table.shape
    assert idx.shape[-1] == c, (idx.shape, table.shape)
    if block_n is not None and n > block_n:
        outs = [lut_matmul(idx, table[..., s:s + block_n])
                for s in range(0, n, block_n)]
        return jnp.concatenate(outs, axis=-1)
    acc_int = jnp.issubdtype(table.dtype, jnp.integer)
    gathered = jnp.take(table[0], idx[..., 0], axis=0)
    y = gathered.astype(jnp.int32) if acc_int else gathered
    for cc in range(1, c):
        g = jnp.take(table[cc], idx[..., cc], axis=0)
        y = y + (g.astype(jnp.int32) if acc_int else g)
    return y.astype(jnp.float32)


def sparse_budget(c: int, occupancy: float) -> int:
    """Static per-row gather budget for the zero-chunk-skipping route.

    ``occupancy`` is the calibrated *chunk* occupancy — the fraction of
    nonzero chunk-index bytes the layer's packed inputs carry (what
    ``infer.backends.chunk_occupancy`` measures) — so the expected nonzero
    chunks per row is ``occupancy * c``. One extra chunk of slack absorbs
    calibration jitter; rows that still exceed the budget fall back to the
    dense gather inside ``lut_matmul_sparse`` (exact, just not faster).
    """
    if not 0.0 <= occupancy <= 1.0:
        raise ValueError(f"occupancy must be in [0, 1], got {occupancy!r}")
    return min(c, max(1, math.ceil(occupancy * c) + 1))


def lut_matmul_sparse(idx, table, *, max_chunks: int,
                      block_n: int | None = None):
    """Zero-chunk-skipping gather: like ``lut_matmul`` but each row gathers
    only its first ``max_chunks`` nonzero index bytes.

    Per (plane, row), the nonzero chunk indices are compacted to the front
    via a cumsum rank (each nonzero byte's position among its row's
    nonzeros) matched against the output slots — ascending chunk order is
    inherited from the cumsum, so the fold visits the surviving chunks in
    the SAME order as the dense route. (``lax.top_k`` would compact too,
    but is ~10x slower than these elementwise ops on the CPU backend.)
    The skipped positions would have gathered ``table[c, 0, :]`` — built as
    an ascending-bit fold of ``0 * w`` it is exactly +0.0 (int16 tables: 0)
    — and ``x + (+0.0) == x`` for every accumulator value this route can
    produce, so dropping them is a bitwise identity. Slots past a row's
    nonzero count match nothing, leaving a flattened index of 0 =
    ``table[0, 0, :]``: the same zero entry. When ANY row holds more than
    ``max_chunks`` nonzero bytes the whole call falls back to the dense
    gather (``lax.cond``) — miscalibrated occupancy costs speed, never
    correctness.
    """
    c, _, n = table.shape
    assert idx.shape[-1] == c, (idx.shape, table.shape)
    assert max_chunks >= 1, max_chunks
    if max_chunks >= c:
        return lut_matmul(idx, table, block_n=block_n)
    if block_n is not None and n > block_n:
        outs = [lut_matmul_sparse(idx, table[..., s:s + block_n],
                                  max_chunks=max_chunks)
                for s in range(0, n, block_n)]
        return jnp.concatenate(outs, axis=-1)
    nz = idx != 0
    pos = jnp.cumsum(nz.astype(jnp.int32), axis=-1) - 1    # rank among nz
    slots = jnp.arange(max_chunks, dtype=jnp.int32)
    match = (pos[..., None, :] == slots[:, None]) & nz[..., None, :]
    # flattened (chunk, byte) gather index; unmatched slots sum to 0
    val = (jnp.arange(c, dtype=jnp.int32) * 256 + idx.astype(jnp.int32))
    gidx = jnp.where(match, val[..., None, :], 0).sum(-1)  # (..., B)
    nnz_max = jnp.max(pos[..., -1]) + 1
    acc_int = jnp.issubdtype(table.dtype, jnp.integer)
    flat = table.reshape(c * 256, n)

    def gather_sparse(_):
        g0 = jnp.take(flat, gidx[..., 0], axis=0)
        y = g0.astype(jnp.int32) if acc_int else g0
        for j in range(1, max_chunks):
            gj = jnp.take(flat, gidx[..., j], axis=0)
            y = y + (gj.astype(jnp.int32) if acc_int else gj)
        return y.astype(jnp.float32)

    def gather_dense(_):
        return lut_matmul(idx, table)

    return lax.cond(nnz_max <= max_chunks, gather_sparse, gather_dense, None)


def lut_matmul_pallas(idx, table, *, bm: int = 128, bn: int = 128,
                      bc: int = 128, interpret: bool | None = None):
    """Pallas byte-LUT matmul: (..., C) index bytes x (C, 256, N) table ->
    (..., N) f32, same contract as ``lut_matmul`` but executed by the
    grouped-grid Pallas kernel (``spike_matmul.lut_gather_matmul``) with
    the table VMEM-resident. Bit-exact against ``lut_matmul`` — the kernel
    replays the identical defined ascending-chunk fold with the identical
    accumulator dtypes. The first input axis is treated as the plane axis
    (the outermost grid dim); remaining lead axes fold into the row dim.
    """
    from .spike_matmul import lut_gather_matmul
    c = table.shape[0]
    assert idx.shape[-1] == c, (idx.shape, table.shape)
    lead = idx.shape[:-1]
    if idx.ndim == 2:
        idx3 = idx[None]                               # (1, M, C)
    else:
        idx3 = idx.reshape(idx.shape[0], -1, c)        # (P, M, C)
    y = lut_gather_matmul(idx3, table, bm=bm, bn=bn, bc=bc,
                          interpret=interpret)
    return y.reshape(*lead, table.shape[-1])


def lut_matmul_planes(planes, w):
    """The route's bit-exact oracle on unpacked planes: (R, M, K) {0,1}
    float32 x (K, N) -> (R, M, N) f32 via the IDENTICAL reduction tree as
    ``build_lut`` + ``lut_matmul`` (ascending-bit multiply-add fold per
    chunk, ascending-chunk adds). Elementwise IEEE ops only — no ``dot`` —
    so results are independent of R/M batching and match the packed gather
    route bit for bit. This is what ``FloatBackend`` runs for LUT-planned
    layers.
    """
    r, m, k = planes.shape
    n = w.shape[-1]
    c = num_k_chunks(k)
    wf = _pad_k(w.astype(jnp.float32).T, k).T.reshape(c, K_CHUNK, n)
    pc = _pad_k(planes, k).reshape(r, m, c, K_CHUNK)
    part = jnp.zeros((r, m, c, n), jnp.float32)
    for i in range(K_CHUNK):
        part = part + pc[..., i, None] * wf[None, None, :, i, :]
    y = part[:, :, 0, :]
    for cc in range(1, c):
        y = y + part[:, :, cc, :]
    return y


def shift_sum_fold(per_plane):
    """SSSC bit-plane combine with a defined order: (8, ..., N) per-plane
    accumulators -> (..., N), ``y = fold_p y + per[p] * 2^p`` ascending.
    Power-of-two scaling is exact; both the packed LUT route and its float
    emulation share this fold (XLA's ``sum(axis=0)`` reduce order is
    unspecified, so neither route may use it)."""
    y = per_plane[0]
    for p in range(1, 8):
        y = y + per_plane[p] * jnp.float32(2.0 ** p)
    return y


# ---------------------------------------------------------------------------
# Dispatch heuristic
# ---------------------------------------------------------------------------

MAX_TABLE_BYTES = 1 << 24  # 16 MiB per-layer table cap (memory trade-off)


@dataclasses.dataclass(frozen=True)
class RouteConstants:
    """Cost-model constants for ``choose_route``, in units of one dot FMA.

    The defaults were fit on the CPU microbenchmarks that motivated the LUT
    route (see docs/architecture.md): a gathered table row costs ~4x a dot
    FMA per element but covers 8 weight rows; the bit transpose replaces the
    4-bytes-per-bit unpack with ~2.5 byte-ops per packed byte. They are a
    property of the *host*, not the model — ``scripts/autotune_routes.py``
    refits them from timings and an ``ExecutionPlan`` carries them as data,
    so a committed plan pins the dispatch decisions it was tuned for.

    The two ``pallas_*`` constants (``choose_pallas_route``) are a TPU v5e
    fit, in units of one Pallas unpack-dot FMA as that model counts them
    (t live planes x M x K x N). They come from device traces of
    Spikformer-8-512 with int8 weights at published widths on one v5e,
    where 35 layers took the gather and 17 the dot: at T=4, bucket 32,
    ``lut_gather_matmul`` spent 521.7 ms on 30.0 G selected elements
    (17.4 ps each) and ``spike_matmul`` 17.3 ms on 434 G FMAs (0.040 ps
    each), a ratio of 436; at T=16, bucket 8, 18.1 ps against 0.021 ps,
    873. The lower ratio is the default. The gather loses wherever
    ``pallas_gather_cost`` exceeds ~8x ``pallas_dot_cost`` (one selected
    element stands for 8 K-rows), so every shape of that network takes
    the dot at every batch size. The index-build term reuses the CPU
    ``transpose_cost``, far below the ~1270 ps a byte the same traces
    show for it, which only flatters the gather.
    """
    gather_cost: float = 4.0     # per gathered table element
    transpose_cost: float = 2.5  # per packed input byte
    unpack_cost: float = 8.0     # per unpacked plane element (u8->f32 write)
    int_gather_discount: float = 0.5   # int16 tables halve gather bandwidth
    cache_bytes: int = 1 << 21   # table size where gathers stop hitting L2
    cache_penalty: float = 3.0   # gather-cost multiplier past cache_bytes
    compact_cost: float = 40.0   # sparse route: per (index byte x slot)
                                 # compaction element (cumsum + one-hot
                                 # select; N-independent, int32-bound)
    pallas_gather_cost: float = 436.0  # pallas route: per gathered table
                                       # element (one-hot MXU select row)
    pallas_dot_cost: float = 1.0       # pallas route: per unpack-dot FMA
                                       # (8 planes folded into one MXU dot)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "RouteConstants":
        known = {f.name for f in dataclasses.fields(cls)}
        bad = set(d) - known
        if bad:
            raise ValueError(f"unknown route-constant keys {sorted(bad)}; "
                             f"expected a subset of {sorted(known)}")
        return cls(**d)


DEFAULT_ROUTE_CONSTANTS = RouteConstants()


def choose_route(*, m: int, k: int, n: int, g: int, t: int,
                 weights_are_int: bool = False,
                 max_table_bytes: int = MAX_TABLE_BYTES,
                 constants: RouteConstants | None = None,
                 occupancy: float | None = None) -> str:
    """Pick "lut", "lut_sparse" or "unpack" for a packed matmul of (t live
    planes, M rows, K inputs, N outputs, G plane groups) on the CPU route.

    The LUT route wins when its gather traffic (t*M*C*N table elements)
    undercuts the dot's t*M*K*N FMAs plus the t*M*K unpack writes it
    deletes; it loses when the table outgrows cache — int16 tables halve
    that pressure — or the per-layer table cap. The fallback is always the
    unpack route, which stays the bit-exact mirror of the float reference.
    ``constants`` overrides the host cost model (autotuned plans pass the
    fitted values; ``None`` keeps the committed defaults).

    ``occupancy`` is a measured/calibrated CHUNK occupancy (fraction of
    nonzero chunk-index bytes — ``infer.backends.chunk_occupancy``); when
    given, the zero-chunk-skipping gather competes too: its traffic scales
    with the *nonzero* chunks per row (``sparse_budget(c, occupancy)``
    gathers instead of c) plus an N-independent compaction term over the
    t*M*C index bytes times the slot count. ``None`` — no calibration —
    never picks the sparse route: sparsity claims must be measured, not
    assumed.
    """
    cc = DEFAULT_ROUTE_CONSTANTS if constants is None else constants
    c = num_k_chunks(k)
    tbl = table_bytes(k, n, weights_are_int)
    if tbl > max_table_bytes:
        return "unpack"
    gather_scale = cc.gather_cost * (cc.int_gather_discount
                                     if weights_are_int else 1.0)
    # cache pressure: once the table spills L2, gathered rows stop hitting
    cache_penalty = 1.0 if tbl <= cc.cache_bytes else cc.cache_penalty
    lut_cost = (t * m * c * n * gather_scale * cache_penalty
                + g * m * k * cc.transpose_cost)
    unpack_cost = t * m * k * (n + cc.unpack_cost)
    if occupancy is not None:
        budget = sparse_budget(c, occupancy)
        if budget < c:
            sparse_cost = (t * m * budget * n * gather_scale * cache_penalty
                           + g * m * k * cc.transpose_cost
                           + t * m * c * budget * cc.compact_cost)
            if sparse_cost < lut_cost and sparse_cost < unpack_cost:
                return "lut_sparse"
    return "lut" if lut_cost < unpack_cost else "unpack"


def choose_pallas_route(*, m: int, k: int, n: int, g: int, t: int,
                        weights_are_int: bool = False,
                        max_table_bytes: int = MAX_TABLE_BYTES,
                        constants: RouteConstants | None = None,
                        occupancy: float | None = None) -> str:
    """Pick "lut" or "unpack" for the Pallas backend's packed matmul.

    The Pallas kernel pair differs from the CPU routes in kind, so the
    cost model does too: the LUT route's gather is a (bm, 256) one-hot MXU
    select per chunk (``spike_matmul.gather256``) against a VMEM-resident
    table — t*M*C*N selected elements plus the G*M*K bit transpose that
    builds the index bytes — while the unpack route folds all 8 planes of
    a group into the row dim of one MXU dot (t*M*K*N FMAs, no unpack
    writes: the bits expand in-register inside the kernel). The constants
    (``pallas_gather_cost`` / ``pallas_dot_cost``) are device properties.
    The defaults are the TPU v5e fit from device traces (``RouteConstants``
    says which): a selected element costs ~436 dot FMAs there, so every
    layer whose table fits the cap still takes the dot — the gather wins
    only under constants that price it below ~8 FMAs.
    ``scripts/autotune_routes.py --pallas`` refits them on another chip.

    ``occupancy`` is accepted for signature parity with ``choose_route``
    and ignored: the dense Pallas gather has no zero-chunk skipping (a
    pinned "lut_sparse" route runs the dense Pallas gather, which is
    bitwise identical). There is no sparse candidate to weigh.
    """
    cc = DEFAULT_ROUTE_CONSTANTS if constants is None else constants
    c = num_k_chunks(k)
    if table_bytes(k, n, weights_are_int) > max_table_bytes:
        return "unpack"
    lut_cost = (t * m * c * n * cc.pallas_gather_cost
                + g * m * k * cc.transpose_cost)
    dot_cost = t * m * k * n * cc.pallas_dot_cost
    return "lut" if lut_cost < dot_cost else "unpack"
