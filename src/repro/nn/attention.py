"""Attention for the assigned LM architectures.

Features: GQA, RoPE / partial RoPE / M-RoPE, QK-norm, QKV bias, sliding
windows (+ per-layer traced global flag for Hymba), KV caches (linear and
ring-buffer), cross-attention (Whisper), and **chunked causal attention** —
the pure-XLA memory-efficient path used in dry-runs, where the score matrix
peak is O(B*H*chunk*S) instead of O(B*H*S^2). (On real TPUs the Pallas
``kernels.flash_attention`` kernel implements the same schedule in VMEM; the
chunked form is what we .lower()/.compile() on the CPU container.)

Conventions: x is (B, S, D); caches are (B, KV, S_cache, Dh); all softmax
math in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import get_abstract_mesh

from .module import KeyStream
from .layers import linear_init, linear, apply_rope, apply_mrope, rmsnorm_init, rmsnorm
from ..sharding.hints import shard_hint

NEG_INF = -1e30


def attn_init(key, cfg, dtype=jnp.float32):
    ks = KeyStream(key)
    dh = cfg.head_dim
    p = {
        "wq": linear_init(ks(), cfg.d_model, cfg.n_heads * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wk": linear_init(ks(), cfg.d_model, cfg.n_kv_heads * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wv": linear_init(ks(), cfg.d_model, cfg.n_kv_heads * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wo": linear_init(ks(), cfg.n_heads * dh, cfg.d_model, bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(dh, dtype)
        p["k_norm"] = rmsnorm_init(dh, dtype)
    return p


def _project_qkv(p, x, cfg, *, compute_dtype):
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = linear(p["wq"], x, compute_dtype=compute_dtype).reshape(b, s, cfg.n_heads, dh)
    k = linear(p["wk"], x, compute_dtype=compute_dtype).reshape(b, s, cfg.n_kv_heads, dh)
    v = linear(p["wv"], x, compute_dtype=compute_dtype).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    return q, k, v


def _rope(q, k, cfg, positions, mrope_positions=None):
    if cfg.mrope_sections is not None and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, theta=cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, theta=cfg.rope_theta)
    elif cfg.use_rope:
        q = apply_rope(q, positions, theta=cfg.rope_theta, rotary_frac=cfg.rotary_frac)
        k = apply_rope(k, positions, theta=cfg.rope_theta, rotary_frac=cfg.rotary_frac)
    return q, k


def _decode_grouped(q, k, v, *, scale, causal, q_positions, k_positions,
                    window, is_global):
    """One-token attention without expanding KV to q heads.

    q: (B, Hq, 1, Dh); k, v: (B, KV, S, Dh). Scores are (B, KV, g, S) with
    the KV-seq dim sharded over the model axis (distributed softmax)."""
    b, hq, _, dh = q.shape
    kvh = k.shape[1]
    g = hq // kvh
    am = get_abstract_mesh()
    seq_ok = (not am.empty and "model" in am.axis_names
              and k.shape[2] % am.shape["model"] == 0)
    if seq_ok:
        k = shard_hint(k, "dp", None, "model", None)
        v = shard_hint(v, "dp", None, "model", None)
    qg = q.reshape(b, kvh, g, dh)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * scale  # (B,KV,g,S)
    if seq_ok:
        s = shard_hint(s, "dp", None, None, "model")
    qp = q_positions[:, None, None, :]                 # (B,1,1,1)
    kp = k_positions[:, None, None, :]                 # (B,1,1,S)
    mask = kp >= 0
    if causal:
        mask = jnp.logical_and(mask, qp >= kp)
    if window is not None:
        w_ok = (qp - kp) < window
        if is_global is not None:
            w_ok = jnp.logical_or(w_ok, is_global)
        mask = jnp.logical_and(mask, w_ok)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, dh).astype(q.dtype)


def chunked_attention(q, k, v, *, scale: float, causal: bool = True,
                      q_positions=None, k_positions=None,
                      window=None, is_global=None, chunk: int = 512):
    """Memory-efficient attention.

    q: (B, Hq, Sq, Dh); k, v: (B, KV, Skv, Dh). GQA via Hq = KV * group.
    q_positions: (Sq,) or per-row (B, Sq) absolute query positions;
    k_positions: (Skv,) or per-row (B, Skv) key positions (ring buffers and
    continuous batching, where every row sits at a different offset).
    window: optional int — sliding-window width; is_global: traced bool scalar
    that disables the window (Hymba's per-layer full-attention flag).
    """
    b, hq, sq, dh = q.shape
    kvh = k.shape[1]
    g = hq // kvh
    if q_positions is None:
        q_positions = jnp.arange(sq) + (k.shape[2] - sq)
    if k_positions is None:
        k_positions = jnp.arange(k.shape[2])
    # normalize positions to per-row (B, ·)
    q_positions = jnp.broadcast_to(jnp.atleast_2d(q_positions), (b, sq))
    k_positions = jnp.broadcast_to(jnp.atleast_2d(k_positions),
                                   (b, k.shape[2]))

    if sq == 1:
        # decode fast path: GROUPED attention — never materialize the GQA
        # repeat (8x the cache traffic for qwen1.5-110b's g=8; §Perf B4),
        # keep KV sequence-sharded, softmax distributed over the KV shards.
        return _decode_grouped(q, k, v, scale=scale, causal=causal,
                               q_positions=q_positions,
                               k_positions=k_positions, window=window,
                               is_global=is_global)

    # GQA: expand KV to the full head count. The merged head axis (divisible
    # by the TP degree for the big archs) is what the "model" mesh axis
    # shards. When heads DON'T divide the axis, keep KV SEQUENCE-sharded —
    # the old unconditional head hint silently replicated S, which forced a
    # 15 GB fp32 all-gather of the whole KV cache per layer per decode step
    # on arctic-480b (529 GB/chip/step; §Perf B2).
    am0 = get_abstract_mesh()
    tp = am0.shape["model"] if (not am0.empty and "model" in am0.axis_names) \
        else 1
    if g > 1:
        if sq == 1:
            # decode: S is the only big dim — NEVER reshard the cache to a
            # head-major layout for one query token (stablelm-12b decode
            # regressed 1.1->4.0 s memory when we did; §Perf B2b follow-up)
            kv_dims = ("dp", None, "model", None)
        elif hq % max(tp, 1) == 0:
            kv_dims = ("dp", "model", None, None)
        else:
            # train/prefill with non-divisible heads: scores contract the
            # FULL kv-seq per chip (q-seq carries the TP sharding), so a
            # seq-sharded KV would be re-gathered every layer — replicate
            kv_dims = ("dp", None, None, None)
        k = shard_hint(jnp.repeat(k, g, axis=1), *kv_dims)
        v = shard_hint(jnp.repeat(v, g, axis=1), *kv_dims)

    # When heads don't divide the TP axis, shard q-SEQUENCE over it instead,
    # and drop the chunk loop: per-chip score memory is already cut TP-fold
    # by the seq sharding, and a while loop would re-gather K/V from its
    # carry every iteration (+570 GB of all-gather measured; §Perf C1/C2).
    am = get_abstract_mesh()
    # (measured both ways for hymba's windowed unrolled layers: keeping the
    # chunk loop bounds peak at 32.4 GB but costs 2x the bound (40.2 s vs
    # 19.6 s); both exceed 16 GB, so we take the better bound and list the
    # residency remedies in §Perf extras)
    seq_tp = (not am.empty and "model" in am.axis_names
              and hq % am.shape["model"] != 0
              and sq % am.shape["model"] == 0 and sq > 1)
    if seq_tp:
        chunk = sq
    # decode (sq == 1): KV sequence stays sharded over the model axis
    kv_seq_tp = (not am.empty and "model" in am.axis_names and sq == 1
                 and k.shape[2] % am.shape["model"] == 0)
    if kv_seq_tp:
        kf_dims = ("dp", None, "model", None)
        k = shard_hint(k, *kf_dims)
        v = shard_hint(v, *kf_dims)

    nchunks = max(1, sq // chunk)
    assert sq % nchunks == 0, (sq, chunk)
    cq = sq // nchunks
    qc_all = q.reshape(b, hq, nchunks, cq, dh)
    qpos_c = jnp.moveaxis(q_positions.reshape(b, nchunks, cq), 1, 0)

    # keep K/V in their native dtype (bf16 in production) and request fp32
    # ACCUMULATION via preferred_element_type — explicit astype(f32) copies
    # of the whole KV cache were hoisted out of the layer loop by XLA and
    # doubled decode peak memory (§Perf B3). Tests pass f32 inputs and are
    # bit-identical through this path.
    kf = k
    vf = v

    @jax.checkpoint  # recompute scores per chunk in backward: without this,
    # the map stacks (nchunks, B, H, cq, Skv) fp32 score residuals — the
    # exact O(S^2) blow-up this chunking exists to avoid.
    def one_chunk(args):
        qc, qpos = args                                  # (B,H,cq,dh), (B,cq)
        if seq_tp:
            qc = shard_hint(qc, "dp", None, "model", None)
        s = jnp.einsum("bhcd,bhsd->bhcs", qc, kf,
                       preferred_element_type=jnp.float32) * scale
        if seq_tp:
            s = shard_hint(s, "dp", None, "model", None)
        elif kv_seq_tp:
            # decode with seq-sharded KV: keep the scores KEY-sharded; the
            # softmax reductions become tiny cross-shard ARs instead of a
            # full KV gather (distributed softmax; §Perf B2)
            s = shard_hint(s, "dp", None, None, "model")
        qp = qpos[:, None, :, None]                      # (B,1,cq,1)
        kp = k_positions[:, None, None, :]               # (B,1,1,Skv)
        mask = jnp.ones((b, 1, cq, k.shape[2]), bool)
        if causal:
            mask = qp >= kp
        if window is not None:
            w_ok = (qp - kp) < window
            if is_global is not None:
                w_ok = jnp.logical_or(w_ok, is_global)
            mask = jnp.logical_and(mask, w_ok)
        # invalid key slots are marked with negative positions
        mask = jnp.logical_and(mask, kp >= 0)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)          # fp32 softmax
        return jnp.einsum("bhcs,bhsd->bhcd", p.astype(vf.dtype), vf,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    out = jax.lax.map(one_chunk, (jnp.moveaxis(qc_all, 2, 0), qpos_c))
    out = jnp.moveaxis(out, 0, 2)                        # (B,H,nc,cq,dh)
    return out.reshape(b, hq, sq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, kv_heads: int, length: int, head_dim: int,
                  dtype=jnp.bfloat16):
    """Linear KV cache. `positions` is PER ROW (B, length): the absolute
    position stored in each slot (-1 = empty). Per-row tracking is what lets
    one fused decode step serve a continuous-batching pool where every
    sequence sits at a different offset; it also uniformizes linear and
    ring-buffer caches."""
    return {
        "k": jnp.zeros((batch, kv_heads, length, head_dim), dtype),
        "v": jnp.zeros((batch, kv_heads, length, head_dim), dtype),
        "positions": jnp.full((batch, length), -1, jnp.int32),
    }


def cache_update(cache, k_new, v_new, pos, *, ring: bool = False):
    """Insert (B, KV, S_new, Dh) at absolute position ``pos`` — a traced
    int32 scalar (all rows aligned) or an (B,) vector (continuous batching).

    ring=True wraps slot indices mod cache length (sliding-window cache).

    Aligned rows (scalar pos) use ``dynamic_update_slice``: the SPMD
    partitioner keeps a DUS on the cache's own sharding, whereas the
    per-row scatter forces an involuntary reshard that replicates the whole
    cache through collectives every decode step (§Perf iteration 1)."""
    b = cache["k"].shape[0]
    length = cache["k"].shape[2]
    s_new = k_new.shape[2]
    if ring and s_new > length:
        # prefill longer than the window: only the last `length` tokens matter
        k_new = k_new[:, :, -length:]
        v_new = v_new[:, :, -length:]
        pos = pos + (s_new - length)
        s_new = length
    pos = jnp.asarray(pos, jnp.int32)

    if pos.ndim == 0 and (not ring or s_new == 1):
        # one contiguous window (ring with s_new==1 wraps to a single slot)
        start = jnp.mod(pos, length) if ring else pos
        abs_row = pos + jnp.arange(s_new, dtype=jnp.int32)       # (s_new,)
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), start, axis=2)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), start, axis=2)
        positions = jax.lax.dynamic_update_slice_in_dim(
            cache["positions"],
            jnp.broadcast_to(abs_row, (b, s_new)), start, axis=1)
        return {"k": k, "v": v, "positions": positions}

    # heterogeneous rows (continuous batching) or wrapping ring prefill:
    # per-row scatter
    pos = jnp.broadcast_to(pos, (b,))
    abs_pos = pos[:, None] + jnp.arange(s_new, dtype=jnp.int32)  # (B, s_new)
    slots = jnp.mod(abs_pos, length) if ring else abs_pos

    def put_row(buf, new, sl):                # (KV,S,dh), (KV,s,dh), (s,)
        return buf.at[:, sl, :].set(new.astype(buf.dtype))

    k = jax.vmap(put_row)(cache["k"], k_new, slots)
    v = jax.vmap(put_row)(cache["v"], v_new, slots)
    positions = jax.vmap(lambda p, sl, ap: p.at[sl].set(ap))(
        cache["positions"], slots, abs_pos)
    return {"k": k, "v": v, "positions": positions}


def attend_cache(q, cache, *, scale: float, q_positions, window=None,
                 is_global=None, chunk: int = 512):
    """Attention of q (B, Hq, Sq, Dh) against a (possibly ring) cache."""
    return chunked_attention(
        q, cache["k"], cache["v"], scale=scale, causal=True,
        q_positions=q_positions, k_positions=cache["positions"],
        window=window, is_global=is_global, chunk=chunk)


# ---------------------------------------------------------------------------
# the full attention block
# ---------------------------------------------------------------------------

def attn_apply(p, x, cfg, *, positions, cache=None, cache_pos=None,
               mrope_positions=None, window=None, is_global=None,
               cross_kv=None, causal=None, compute_dtype=jnp.bfloat16,
               chunk: int = 512):
    """Returns (out, new_cache). Modes:
      - train/prefill: cache=None -> self-attention over x (causal).
      - prefill w/ cache: cache given, cache_pos=0 -> fills cache, attends.
      - decode: x is (B, 1, D), cache_pos = current position.
      - cross: cross_kv = {"k","v"} precomputed (non-causal; Whisper).
    """
    b, s, _ = x.shape
    dh = cfg.head_dim
    scale = dh ** -0.5
    causal = cfg.causal if causal is None else causal
    q, k, v = _project_qkv(p, x, cfg, compute_dtype=compute_dtype)

    if cross_kv is not None:
        q = q.transpose(0, 2, 1, 3)
        out = chunked_attention(q, cross_kv["k"], cross_kv["v"], scale=scale,
                                causal=False, chunk=chunk)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return linear(p["wo"], out, compute_dtype=compute_dtype), cache

    q, k = _rope(q, k, cfg, positions, mrope_positions)
    # TP layout for attention: heads over the model axis when they divide
    # it; otherwise SEQUENCE over the model axis (q only). Without the
    # fallback XLA shards q-seq just 2-way for e.g. smollm's 15 heads on a
    # 16-way axis => 8x redundant score compute + replicated score memory
    # (§Perf iteration C1).
    am = get_abstract_mesh()
    heads_divide = (not am.empty and "model" in am.axis_names
                    and cfg.n_heads % am.shape["model"] == 0)
    if s == 1:
        # decode: one query token — keep q replicated across the model axis;
        # the KV cache stays sequence-sharded (distributed softmax)
        q = shard_hint(q.transpose(0, 2, 1, 3), "dp", None, None, None)
    elif heads_divide:
        q = shard_hint(q.transpose(0, 2, 1, 3), "dp", "model", None, None)
    else:
        q = shard_hint(q.transpose(0, 2, 1, 3), "dp", None, "model", None)
    k = shard_hint(k.transpose(0, 2, 1, 3), "dp", None, None, None)
    v = shard_hint(v.transpose(0, 2, 1, 3), "dp", None, None, None)

    if cache is not None:
        # ring buffer when the cache is only as long as the sliding window
        ring = window is not None and cache["k"].shape[2] <= window
        cache = cache_update(cache, k, v, cache_pos, ring=ring)
        cp = jnp.asarray(cache_pos, jnp.int32)
        qpos = (cp[:, None] if cp.ndim == 1 else cp) \
            + jnp.arange(s, dtype=jnp.int32)
        out = attend_cache(q, cache, scale=scale, q_positions=qpos,
                           window=window, is_global=is_global, chunk=chunk)
    else:
        out = chunked_attention(q, k, v, scale=scale, causal=causal,
                                q_positions=positions[0] if positions.ndim > 1 else positions,
                                window=window, is_global=is_global, chunk=chunk)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return linear(p["wo"], out, compute_dtype=compute_dtype), cache
