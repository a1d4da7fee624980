"""Plain reference of Spikformer V2-IAND inference with int-quantized weights.

Zhou et al., "Spikformer: When Spiking Neural Network Meets Transformer"
(ICLR 2023), with the IAND residual and the BN-folded, per-channel
symmetrically quantized weights that VESTA executes. Straightforward
``jax.numpy`` in float32 at ``Precision.HIGHEST``: spikes are {0, 1} floats
with an explicit leading T axis, every matmul is one einsum, attention is
(Q K^T) V with no softmax. It imports nothing of the program under test and
takes nothing that the program made: it folds and quantizes the raw
weights itself.

Quantization (``bits`` = 8 for the configuration, 4 for the control):
``w_q = clip(round(w / s), -q, q)`` with ``s = max|w| / q`` per output
channel and ``q = 2**(bits-1) - 1``. The scale is folded into the LIF
instead of the accumulator: a neuron charges with ``acc + b/s`` and fires at
``h >= 1/s``. LIF: ``h = v + (x - v) / tau`` with tau = 2, hard reset to 0.

Departures from the published training graph: none in the forward pass;
batch norm is folded (inference), the readout is the firing rate over T
averaged over tokens, then the linear head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
TAU = 2.0
BN_EPS = 1e-5
BN_BIAS = 1.0   # every batch-norm bias, so that no layer falls silent


def _conv_shapes(sizes: dict):
    cin = sizes["in_channels"]
    for i, cout in enumerate(sizes["scs_channels"]):
        yield f"conv{i}", cin, cout
        cin = cout


def init_params(sizes: dict, seed: int):
    """Raw (pre-fold) weights from ``seed``, in the tree the program's
    ``compile`` takes. Kernels are normal with std 1/sqrt(fan_in), batch
    norms are identity but for their bias, which is ``BN_BIAS``: at bias 0
    spikes die out in the stem, every image gets the same logits, and any
    comparison would pass vacuously."""
    d, hidden = sizes["dim"], sizes["dim"] * sizes["mlp_ratio"]

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.full((c,), BN_BIAS),
                "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

    def make(key):
        keys = iter(jax.random.split(key, 4 + 6 * sizes["depth"] + 1))

        def normal(shape, fan_in):
            return jax.random.normal(next(keys), shape) / np.sqrt(fan_in)

        p = {"scs": {}, "blocks": {}}
        for name, cin, cout in _conv_shapes(sizes):
            p["scs"][name] = {"kernel": normal((2, 2, cin, cout), 4 * cin),
                              "bn": bn(cout)}
        for b in range(sizes["depth"]):
            ssa = {}
            for w in ("wq", "wk", "wv", "wo"):
                ssa[w] = {"kernel": normal((d, d), d)}
                ssa[w + "_bn"] = bn(d)
            p["blocks"][f"b{b}"] = {"ssa": ssa, "mlp": {
                "fc1": {"kernel": normal((d, hidden), d)},
                "fc1_bn": bn(hidden),
                "fc2": {"kernel": normal((hidden, d), hidden)},
                "fc2_bn": bn(d)}}
        p["head"] = {"kernel": normal((d, sizes["num_classes"]), d),
                     "bias": jnp.zeros((sizes["num_classes"],))}
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _fold_quant(kernel, bnp, bits: int):
    """(K, N) kernel and its batch norm -> (w_q, b/s, 1/s), op by op."""
    inv = jax.lax.rsqrt(bnp["var"] + BN_EPS)
    g = bnp["scale"] * inv
    b = bnp["bias"] - bnp["mean"] * g
    w = kernel * g
    q = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(w), axis=0)
    s = jnp.where(amax > 0, amax / q, jnp.float32(1.0))
    w_q = jnp.clip(jnp.round(w / s), -q, q)
    return {"w": w_q, "b": b, "s": s}


def fold_quantize(params, sizes: dict, bits: int):
    """Every layer's quantized weight, its bias and its scale. Run eagerly,
    one operation at a time: the integer weights must not depend on how a
    compiler fuses the division and the rounding."""
    out = {"scs": {}, "blocks": {}, "head": params["head"]}
    for name, _, _ in _conv_shapes(sizes):
        c = params["scs"][name]
        kern = c["kernel"] * (1.0 / 255.0) if name == "conv0" else c["kernel"]
        out["scs"][name] = _fold_quant(kern.reshape(-1, kern.shape[-1]),
                                       c["bn"], bits)
    for bname, blk in params["blocks"].items():
        ssa, mlp = blk["ssa"], blk["mlp"]
        out["blocks"][bname] = {
            **{w: _fold_quant(ssa[w]["kernel"], ssa[w + "_bn"], bits)
               for w in ("wq", "wk", "wv", "wo")},
            **{f: _fold_quant(mlp[f]["kernel"], mlp[f + "_bn"], bits)
               for f in ("fc1", "fc2")}}
    return out


def _lif(acc_t, bias, v_th):
    """acc_t: a list of T accumulators -> (T, ...) spikes."""
    v = jnp.zeros_like(acc_t[0])
    out = []
    for x in acc_t:
        h = v + (x + bias - v) / TAU
        s = h >= v_th
        v = jnp.where(s, 0.0, h)
        out.append(s.astype(jnp.float32))
    return jnp.stack(out)


def _linear_lif(x, layer):
    """x: (T, ..., K) spikes -> (T, ..., N) spikes."""
    acc = jnp.einsum("...k,kn->...n", x, layer["w"], precision=HIGHEST)
    return _lif(list(acc), layer["b"] / layer["s"], 1.0 / layer["s"])


def _space_to_depth(x):
    *lead, h, w, c = x.shape
    x = x.reshape(*lead, h // 2, 2, w // 2, 2, c)
    x = jnp.moveaxis(x, -4, -3)
    return x.reshape(*lead, h // 2, w // 2, 4 * c)


def forward(q, images_u8, sizes: dict):
    """q: ``fold_quantize`` output; images_u8: (B, H, W, C) uint8 ->
    (B, classes) float32 logits."""
    t, heads = sizes["timesteps"], sizes["heads"]
    x0 = _space_to_depth(images_u8.astype(jnp.float32))
    c0 = q["scs"]["conv0"]
    acc0 = jnp.einsum("...k,kn->...n", x0, c0["w"], precision=HIGHEST)
    x = _lif([acc0] * t, c0["b"] / c0["s"], 1.0 / c0["s"])  # image constant in T
    for i in range(1, len(sizes["scs_channels"])):
        x = _linear_lif(_space_to_depth(x), q["scs"][f"conv{i}"])
    tt, b, side_h, side_w, d = x.shape
    n = side_h * side_w
    x = x.reshape(tt, b, n, d)
    dh = d // heads
    for i in range(sizes["depth"]):
        blk = q["blocks"][f"b{i}"]
        qs, ks, vs = (_linear_lif(x, blk[name]).reshape(tt, b, n, heads, dh)
                      for name in ("wq", "wk", "wv"))
        scores = jnp.einsum("tbnhd,tbmhd->tbhnm", qs, ks, precision=HIGHEST)
        att = jnp.einsum("tbhnm,tbmhd->tbnhd", scores, vs,
                         precision=HIGHEST) * sizes["attn_scale"]
        att = _lif(list(att), 0.0, 1.0).reshape(tt, b, n, d)
        x = (1.0 - _linear_lif(att, blk["wo"])) * x
        s1 = _linear_lif(x, blk["fc1"])
        x = (1.0 - _linear_lif(s1, blk["fc2"])) * x
    rate = (x.sum(axis=0) / t).mean(axis=1)                  # (B, D)
    head = q["head"]
    return jnp.dot(rate, head["kernel"], precision=HIGHEST) + head["bias"]


def logits(params, sizes: dict, images_u8: np.ndarray, *, bits: int = 8,
           block: int = 8) -> np.ndarray:
    """Reference logits of ``images_u8``, ``block`` images per call so
    that the largest configuration fits beside nothing else."""
    q = fold_quantize(params, sizes, bits)
    fwd = jax.jit(functools.partial(forward, sizes=sizes))
    n = len(images_u8)
    out = []
    for i in range(0, n, block):
        chunk = images_u8[i:i + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, 0)])
        out.append(np.asarray(fwd(q, jnp.asarray(chunk)))[:block - pad])
    return np.concatenate(out)
