"""Plain reference of QKFormer HST-10-768 inference (IAND) with
int-quantized weights.

Zhou et al., "QKFormer: Hierarchical Spiking Transformer using Q-K
Attention" (NeurIPS 2024, arXiv:2403.16552): three stages at widths 192,
384 and 768 over 56x56, 28x28 and 14x14 tokens, each opened by a patch
embedding of overlapping 3x3 spiking convs, 3/2/1 max-pools and a strided
1x1 shortcut; Q-K token attention (QKTA) in stages 1 and 2 (1 and 2
blocks), Spikformer's SSA in stage 3 (7 blocks); an MLP of ratio 4 in every
block. Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``:
spikes are {0, 1} floats with an explicit leading T axis, every conv is
``lax.conv_general_dilated``, every pool ``lax.reduce_window``, every
linear one einsum, and the QKTA mask neuron a LIF run step by step over T.
It imports nothing of the program under test and takes nothing that the
program made: it folds and quantizes the raw weights itself.

Quantization (``bits`` = 8 for the configuration, 4 for the control) and
the LIF (tau = 2, hard reset to 0) are as in ``bench/reference/
spikformer.py``: ``w_q = clip(round(w / s), -q, q)`` per output channel,
and the scale folded into the LIF (charge ``acc + b/s``, fire at
``h >= v_th/s``).

Departures from the published network, as the configuration's
``assumed`` lists them: every spike addition is an IAND, ``(NOT new) AND
res``, so that every activation stays binary; batch norm is folded
(inference); the readout is the firing rate over T averaged over tokens,
then the linear head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
TAU = 2.0
BN_EPS = 1e-5
BN_BIAS = 1.0   # every batch-norm bias but the QKTA ``wq`` layers'
QKTA_V_TH = 0.5  # the QKTA mask neuron's threshold


def half_side(side: int) -> int:
    """Output side of a 3/2/1 max-pool and of a 1x1/s2 conv."""
    return -(-side // 2)


def stage_kind(sizes: dict, stage: int) -> str:
    """QKTA in every stage but the last, which runs SSA."""
    return "ssa" if stage == len(sizes["embed_dims"]) else "qkta"


def layer_list(sizes: dict):
    """``(path, kernel shape)`` of every conv and linear, in call order;
    stage ``s``'s blocks are ``stage{s}/b{i}``."""
    out, cin, dims = [], sizes["in_channels"], sizes["embed_dims"]
    for s, (d, depth) in enumerate(zip(dims, sizes["depths"]), start=1):
        kind = stage_kind(sizes, s)
        p = f"stage{s}/embed"
        if s == 1:
            stem = d // 2
            out += [(f"{p}/conv0", (3, 3, cin, stem)),
                    (f"{p}/conv1", (3, 3, stem, d)),
                    (f"{p}/conv2", (3, 3, d, d)),
                    (f"{p}/short", (1, 1, stem, d))]
        else:
            out += [(f"{p}/conv0", (3, 3, cin, d)),
                    (f"{p}/conv1", (3, 3, d, d)),
                    (f"{p}/short", (1, 1, cin, d))]
        hidden = d * sizes["mlp_ratio"]
        attn = (("qkta", ("wq", "wk", "proj")) if kind == "qkta"
                else ("ssa", ("wq", "wk", "wv", "wo")))
        for b in range(depth):
            out += [(f"stage{s}/b{b}/{attn[0]}/{w}", (d, d)) for w in attn[1]]
            out += [(f"stage{s}/b{b}/mlp/fc1", (d, hidden)),
                    (f"stage{s}/b{b}/mlp/fc2", (hidden, d))]
        cin = d
    return out


def _set(tree, path, value):
    *keys, last = path.split("/")
    for key in keys:
        tree = tree.setdefault(key, {})
    tree[last] = value


def _get(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def init_params(sizes: dict, seed: int):
    """Raw (pre-fold) weights from ``seed``, in the tree the program's
    ``compile`` takes: ``{kernel, bn}`` at each layer's path, and the head.
    Kernels are normal with std 1/sqrt(fan_in); batch norms are identity
    but for their bias: ``BN_BIAS``, so that no layer falls silent, and
    ``sizes["qkta_q_bias"]`` in the QKTA ``wq`` layers, so that a head's
    mask (the OR of its Q channels) is neither always set nor never."""
    layers = layer_list(sizes)

    def bn(c, bias):
        return {"scale": jnp.ones((c,)), "bias": jnp.full((c,), bias),
                "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

    def make(key):
        keys = iter(jax.random.split(key, len(layers) + 1))
        p = {}
        for path, shape in layers:
            fan_in = int(np.prod(shape[:-1]))
            bias = (sizes["qkta_q_bias"] if path.endswith("qkta/wq")
                    else BN_BIAS)
            _set(p, path, {"kernel": jax.random.normal(next(keys), shape)
                           / np.sqrt(fan_in), "bn": bn(shape[-1], bias)})
        d = sizes["embed_dims"][-1]
        p["head"] = {"kernel": jax.random.normal(
            next(keys), (d, sizes["num_classes"])) / np.sqrt(d),
            "bias": jnp.zeros((sizes["num_classes"],))}
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _fold_quant(kernel, bnp, bits: int):
    """(..., N) kernel and its batch norm -> (w_q of the kernel's shape,
    b/s, 1/s), op by op."""
    inv = lax.rsqrt(bnp["var"] + BN_EPS)
    g = bnp["scale"] * inv
    b = bnp["bias"] - bnp["mean"] * g
    w = kernel * g
    q = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(w.reshape(-1, w.shape[-1])), axis=0)
    s = jnp.where(amax > 0, amax / q, jnp.float32(1.0))
    w_q = jnp.clip(jnp.round(w / s), -q, q)
    return {"w": w_q, "b": b, "s": s}


def fold_quantize(params, sizes: dict, bits: int):
    """Every layer's quantized weight, its bias and its scale. Run eagerly,
    one operation at a time: the integer weights must not depend on how a
    compiler fuses the division and the rounding."""
    out = {"head": params["head"]}
    for path, _ in layer_list(sizes):
        layer = _get(params, path)
        kern = layer["kernel"]
        if path == "stage1/embed/conv0":
            kern = kern * (1.0 / 255.0)     # the pixel scale
        _set(out, path, _fold_quant(kern, layer["bn"], bits))
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


def _conv(x, w, stride: int):
    """(N, H, W, C) by an HWIO kernel, zero padding that keeps the size for
    a 3x3 and none for a 1x1."""
    pad = "SAME" if w.shape[0] == 3 else "VALID"
    return lax.conv_general_dilated(
        x, w, (stride, stride), pad, dimension_numbers=("NHWC", "HWIO",
                                                        "NHWC"),
        precision=HIGHEST)


def _conv_lif(x, layer, stride: int = 1):
    """x: (T, B, H, W, C) spikes -> (T, B, H', W', F) spikes."""
    tt, b = x.shape[:2]
    acc = _conv(x.reshape(tt * b, *x.shape[2:]), layer["w"], stride)
    acc = acc.reshape(tt, b, *acc.shape[1:])
    return _lif(list(acc), layer["b"] / layer["s"], 1.0 / layer["s"])


def _pool(x):
    """3/2/1 max-pool over (T, B, H, W, C)."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3, 1),
                             (1, 1, 2, 2, 1),
                             ((0, 0), (0, 0), (1, 1), (1, 1), (0, 0)))


def _linear_lif(x, layer):
    acc = jnp.einsum("...k,kn->...n", x, layer["w"], precision=HIGHEST)
    return _lif(list(acc), layer["b"] / layer["s"], 1.0 / layer["s"])


def forward(q, images_u8, sizes: dict):
    """q: ``fold_quantize`` output; images_u8: (B, H, W, C) uint8 ->
    ((B, classes) float32 logits, [the share of (t, image, token, head)
    at which each QKTA block's mask fired])."""
    t, heads = sizes["timesteps"], sizes["heads"]
    densities = []
    x = None
    for s, depth in enumerate(sizes["depths"], start=1):
        kind = stage_kind(sizes, s)
        e = q[f"stage{s}"]["embed"]
        if s == 1:
            c = e["conv0"]
            acc0 = _conv(images_u8.astype(jnp.float32), c["w"], 1)
            c0 = _pool(_lif([acc0] * t, c["b"] / c["s"], 1.0 / c["s"]))
            new = _conv_lif(_pool(_conv_lif(c0, e["conv1"])), e["conv2"])
            src = c0
        else:
            tt, b, n, d = x.shape
            side = int(round(np.sqrt(n)))
            src = x.reshape(tt, b, side, side, d)
            new = _conv_lif(_pool(_conv_lif(src, e["conv0"])), e["conv1"])
        x = (1.0 - new) * _conv_lif(src, e["short"], stride=2)
        tt, b, hh, ww, d = x.shape
        n, dh = hh * ww, d // heads
        x = x.reshape(tt, b, n, d)
        for i in range(depth):
            blk = q[f"stage{s}"][f"b{i}"]
            if kind == "qkta":
                a = blk["qkta"]
                qs = _linear_lif(x, a["wq"]).reshape(tt, b, n, heads, dh)
                ks = _linear_lif(x, a["wk"]).reshape(tt, b, n, heads, dh)
                mask = _lif(list(qs.sum(axis=-1)), 0.0,
                            QKTA_V_TH)                      # (T, B, N, H)
                densities.append(mask.mean())
                att = (mask[..., None] * ks).reshape(tt, b, n, d)
                x = (1.0 - _linear_lif(att, a["proj"])) * x
            else:
                a = blk["ssa"]
                qs, ks, vs = (_linear_lif(x, a[w]).reshape(tt, b, n, heads,
                                                          dh)
                              for w in ("wq", "wk", "wv"))
                scores = jnp.einsum("tbnhd,tbmhd->tbhnm", qs, ks,
                                    precision=HIGHEST)
                att = jnp.einsum("tbhnm,tbmhd->tbnhd", scores, vs,
                                 precision=HIGHEST) * sizes["attn_scale"]
                att = _lif(list(att), 0.0, 1.0).reshape(tt, b, n, d)
                x = (1.0 - _linear_lif(att, a["wo"])) * x
            mlp = blk["mlp"]
            x = (1.0 - _linear_lif(_linear_lif(x, mlp["fc1"]),
                                   mlp["fc2"])) * x
    rate = (x.sum(axis=0) / t).mean(axis=1)                  # (B, D)
    head = q["head"]
    logits = jnp.dot(rate, head["kernel"], precision=HIGHEST) + head["bias"]
    return logits, densities


def _blocks(params, sizes, images_u8, bits, block):
    """``forward`` over ``block`` images a call (the last block padded by
    repeats): yields each block's (logits, densities, real rows)."""
    q = fold_quantize(params, sizes, bits)
    fwd = jax.jit(functools.partial(forward, sizes=sizes))
    for i in range(0, len(images_u8), block):
        chunk = images_u8[i:i + block]
        real = len(chunk)
        if real < block:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:],
                                                     block - real, 0)])
        logits, dens = fwd(q, jnp.asarray(chunk))
        yield np.asarray(logits)[:real], [float(x) for x in dens], real


def logits(params, sizes: dict, images_u8: np.ndarray, *, bits: int = 8,
           block: int = 8) -> np.ndarray:
    """Reference logits of ``images_u8``, ``block`` images per call so
    that the configuration fits beside nothing else."""
    return np.concatenate([lg for lg, _, _ in
                           _blocks(params, sizes, images_u8, bits, block)])


def mask_density(params, sizes: dict, images_u8: np.ndarray, *,
                 bits: int = 8, block: int = 8) -> list:
    """The share of (t, image, token, head) at which each QKTA block's mask
    fired, over ``images_u8`` (each block's share weighted by its rows;
    the padded repeats of the last block count in it)."""
    total, rows = None, 0
    for _, dens, real in _blocks(params, sizes, images_u8, bits, block):
        dens = np.asarray(dens) * real
        total = dens if total is None else total + dens
        rows += real
    return [float(x) for x in total / rows]
