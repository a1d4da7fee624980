"""Spikformer V2-8-512-IAND — the model VESTA executes (paper Fig. 1).

Structure:
  SCS  — Spiking Convolutional Stem: 4 conv layers, 2x2 kernel, stride 2
         (224 -> 14; channels 3 -> 64 -> 128 -> 256 -> 512). Layer 0 input is
         an 8-bit image => SSSC; layers 1..3 have spike inputs => ZSC.
  8 x Spikformer encoder blocks: SSA + MLP(512 -> 2048 -> 512), every linear
         followed by BN + LIF (=> TFLIF in hardware), IAND spike residuals.
  Head — rate decode over T=4 timesteps, mean over tokens, Linear -> 1000.

All activations between layers are binary spikes (the IAND variant's "pure
binary inter-layer propagation"), which is the property the whole VESTA
datapath relies on.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..nn.module import KeyStream, param_count
from ..nn.layers import linear_init, linear
from .lif import bn_init, bn_train_apply, bn_apply, tflif, fold_bn
from .spike import rate_decode
from .unified import LayerSpec, sssc, zsc, wssl
from .ssa import ssa_init, ssa_apply


@dataclasses.dataclass(frozen=True)
class SpikformerConfig:
    img_size: int = 224
    in_channels: int = 3
    timesteps: int = 4
    dim: int = 512
    depth: int = 8
    heads: int = 8
    mlp_ratio: int = 4
    num_classes: int = 1000
    scs_channels: tuple = (64, 128, 256, 512)
    residual: str = "iand"          # "iand" (SEW IAND, keeps binary) or "add"
    attn_scale: float = 0.125

    @property
    def tokens(self) -> int:
        side = self.img_size // (2 ** len(self.scs_channels))
        return side * side

    def scaled(self, *, img_size=32, dim=64, depth=2, heads=2, classes=10,
               timesteps=None):
        """Reduced config for CPU smoke tests. ``timesteps`` overrides T
        (any T >= 1 — the packed datapath uses ceil(T/8) plane groups)."""
        return dataclasses.replace(
            self, img_size=img_size, dim=dim, depth=depth, heads=heads,
            num_classes=classes, scs_channels=(8, 16, 32, dim),
            timesteps=self.timesteps if timesteps is None else timesteps)


def init(key, cfg: SpikformerConfig, dtype=jnp.float32):
    ks = KeyStream(key)
    p = {"scs": {}, "blocks": {}, "head": linear_init(
        ks(), cfg.dim, cfg.num_classes, bias=True, dtype=dtype)}
    cin = cfg.in_channels
    for i, cout in enumerate(cfg.scs_channels):
        p["scs"][f"conv{i}"] = {
            "kernel": jax.random.normal(ks(), (2, 2, cin, cout), dtype)
            * (1.0 / jnp.sqrt(4.0 * cin)),
            "bn": bn_init(cout, dtype),
        }
        cin = cout
    hidden = cfg.dim * cfg.mlp_ratio
    for i in range(cfg.depth):
        p["blocks"][f"b{i}"] = {
            "ssa": ssa_init(ks(), cfg.dim, cfg.heads, dtype),
            "mlp": {
                "fc1": linear_init(ks(), cfg.dim, hidden, bias=False, dtype=dtype),
                "fc1_bn": bn_init(hidden, dtype),
                "fc2": linear_init(ks(), hidden, cfg.dim, bias=False, dtype=dtype),
                "fc2_bn": bn_init(cfg.dim, dtype),
            },
        }
    return p


def _combine(new, res, mode: str):
    if mode == "iand":
        # SEW IAND: (NOT new) AND res — keeps activations strictly binary.
        return (1.0 - new) * res
    return new + res


def _bn_lif(pbn, y, axes, *, train: bool):
    if train:
        y, stats = bn_train_apply(pbn, y, axes=axes)
    else:
        y, stats = bn_apply(pbn, y), None
    return tflif(y), stats


def apply(params, images_u8, cfg: SpikformerConfig, *, train: bool = False):
    """images_u8: (B, H, W, C) uint8. Returns (logits, bn_stat_updates)."""
    t = cfg.timesteps
    stats = {"scs": {}, "blocks": {}}

    # --- SCS stem ---------------------------------------------------------
    # Layer 0: SSSC on the 8-bit image; identical accumulator for every
    # timestep (the image does not change across T), so compute once.
    c0 = params["scs"]["conv0"]
    y = sssc(images_u8, c0["kernel"] * (1.0 / 255.0))   # (B,H/2,W/2,C0), fp
    y = jnp.broadcast_to(y[None], (t, *y.shape))
    x, st = _bn_lif(c0["bn"], y, axes=(0, 1, 2, 3), train=train)
    stats["scs"]["conv0"] = st
    # Layers 1..3: ZSC on spike inputs.
    for i in range(1, len(cfg.scs_channels)):
        ci = params["scs"][f"conv{i}"]
        y = zsc(x, ci["kernel"])                        # (T,B,H/2,W/2,Ci)
        x, st = _bn_lif(ci["bn"], y, axes=(0, 1, 2, 3), train=train)
        stats["scs"][f"conv{i}"] = st

    # --- tokens -----------------------------------------------------------
    tt, b, h, w, c = x.shape
    x = x.reshape(tt, b, h * w, c)                      # (T,B,N,D) spikes

    # --- encoder blocks ----------------------------------------------------
    for i in range(cfg.depth):
        blk = params["blocks"][f"b{i}"]
        bstats = {}
        attn, st = ssa_apply(blk["ssa"], x, heads=cfg.heads,
                             scale=cfg.attn_scale, train=train)
        bstats["ssa"] = st
        x = _combine(attn, x, cfg.residual)
        mlp = blk["mlp"]
        y = wssl(x, mlp["fc1"]["kernel"])               # MLP1 (512 -> 2048)
        s1, st = _bn_lif(mlp["fc1_bn"], y, axes=(0, 1, 2), train=train)
        bstats["fc1_bn"] = st
        y = wssl(s1, mlp["fc2"]["kernel"])              # MLP2 (2048 -> 512)
        s2, st = _bn_lif(mlp["fc2_bn"], y, axes=(0, 1, 2), train=train)
        bstats["fc2_bn"] = st
        x = _combine(s2, x, cfg.residual)
        stats["blocks"][f"b{i}"] = bstats

    # --- head ---------------------------------------------------------------
    rate = rate_decode(x, axis=0).mean(axis=1)          # (B, D)
    logits = linear(params["head"], rate)
    return logits, stats


def merge_bn_stats(params, stats):
    """Write the EMA'd BN running stats produced by a training step back into
    the param tree (stats has the same topology with {mean,var} leaves)."""
    out = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy

    def rec(p, s):
        for k, v in s.items():
            if v is None:
                continue
            if isinstance(v, dict) and "mean" in v and "var" in v:
                tgt = p[k] if k in p else None
                if tgt is None:
                    continue
                tgt["mean"], tgt["var"] = v["mean"], v["var"]
            elif isinstance(v, dict):
                child = p.get(k, p)
                rec(child if isinstance(child, dict) else p, v)

    # stats paths: scs/convI -> params['scs'][convI]['bn']; blocks/bI/{ssa/*_bn, fcJ_bn}
    for name, st in stats.get("scs", {}).items():
        if st is not None:
            out["scs"][name]["bn"] = {**out["scs"][name]["bn"], **st}
    for bname, bstats in stats.get("blocks", {}).items():
        blk = out["blocks"][bname]
        ssa_st = bstats.get("ssa") or {}
        for wn, st in ssa_st.items():
            if st is not None:
                blk["ssa"][wn] = {**blk["ssa"][wn], **st}
        for fc in ("fc1_bn", "fc2_bn"):
            st = bstats.get(fc)
            if st is not None:
                blk["mlp"][fc] = {**blk["mlp"][fc], **st}
    return out


def fold_inference_params(params, cfg: SpikformerConfig):
    """Fold every BN into its preceding conv/linear (the TFLIF merge): the
    inference graph then contains only matmuls + LIF comparisons, exactly the
    layer set VESTA executes. Returns a new tree of {kernel, bias} pairs."""
    out = {"scs": {}, "blocks": {}, "head": params["head"]}
    for i in range(len(cfg.scs_channels)):
        c = params["scs"][f"conv{i}"]
        kern = c["kernel"] if i > 0 else c["kernel"] * (1.0 / 255.0)
        k2 = kern.reshape(-1, kern.shape[-1])
        kf, bf = fold_bn(k2, None, c["bn"])
        out["scs"][f"conv{i}"] = {"kernel": kf, "bias": bf}
    for bi, blk in params["blocks"].items():
        fb = {"ssa": {}, "mlp": {}}
        for wn in ("wq", "wk", "wv", "wo"):
            kf, bf = fold_bn(blk["ssa"][wn]["kernel"], None, blk["ssa"][wn + "_bn"])
            fb["ssa"][wn] = {"kernel": kf, "bias": bf}
        for fc in ("fc1", "fc2"):
            kf, bf = fold_bn(blk["mlp"][fc]["kernel"], None, blk["mlp"][fc + "_bn"])
            fb["mlp"][fc] = {"kernel": kf, "bias": bf}
        out["blocks"][bi] = fb
    return out


def layer_specs(cfg: SpikformerConfig) -> list:
    """Every layer of one ``forward_folded`` pass, in call order: the stem's
    convs, then each block's q/k/v projections, its STDP attention, the
    output projection and the two MLP layers. The one layer list:
    ``forward_folded`` selects each layer's weights and names its
    ``jax.named_scope`` by its path, and the compile passes
    (``repro.infer.compile``) take each matmul's rows, K and N from it."""
    side, cin = cfg.img_size, cfg.in_channels
    specs = []
    for i, cout in enumerate(cfg.scs_channels):
        side //= 2
        specs.append(LayerSpec(f"scs/conv{i}", "sssc" if i == 0 else "zsc",
                               side * side, 4 * cin, cout))
        cin = cout
    n, d, hidden = cfg.tokens, cfg.dim, cfg.dim * cfg.mlp_ratio
    for i in range(cfg.depth):
        specs += block_specs(f"blocks/b{i}", n, d, hidden)
    return specs


def block_specs(prefix: str, n: int, d: int, hidden: int) -> list:
    """The layers of one encoder block (``encoder_block``) at ``prefix``
    over ``n`` tokens of width ``d``."""
    return ([LayerSpec(f"{prefix}/ssa/{w}", "wssl", n, d, d)
             for w in ("wq", "wk", "wv")]
            + [LayerSpec(f"{prefix}/ssa/stdp", "stdp", n),
               LayerSpec(f"{prefix}/ssa/wo", "wssl", n, d, d),
               LayerSpec(f"{prefix}/mlp/fc1", "wssl", n, d, hidden),
               LayerSpec(f"{prefix}/mlp/fc2", "wssl", n, hidden, d)])


def layer_paths(cfg: SpikformerConfig) -> list:
    """The paths of ``layer_specs``, in call order."""
    return [s.path for s in layer_specs(cfg)]


def tree_at(tree, path: str):
    """The node of a nested dict at a ``/``-separated layer path."""
    for key in path.split("/"):
        tree = tree[key]
    return tree


class LayerRunner:
    """Runs the layers of a folded tree through an execution backend: each
    by its path, under a ``jax.named_scope`` of that path.

    ``layer_occupancy`` maps layer paths to STATIC calibrated
    chunk-occupancy floats for layers the plan routed "lut_sparse"; the
    kwarg is forwarded to a backend method only for layers that carry a
    value, so backends without the ``occupancy`` parameter keep working
    under dense plans."""

    def __init__(self, folded, backend, t: int, layer_occupancy=None):
        self.folded, self.backend, self.t = folded, backend, t
        self.occ = layer_occupancy or {}

    def extra(self, path):
        o = self.occ.get(path)
        return {} if o is None else {"occupancy": o}

    def at(self, path):
        return tree_at(self.folded, path)

    def linear(self, op, z, path, **kw):
        layer = self.at(path)
        with jax.named_scope(path):
            return op(z, layer["kernel"], layer["bias"], t=self.t,
                      scale=layer.get("scale"), lut=layer.get("lut"),
                      **self.extra(path), **kw)

    def head(self, x):
        with jax.named_scope("head"):
            rate = self.backend.rate(x, t=self.t)        # (B, D)
            head = self.folded["head"]
            logits = rate @ head["kernel"].astype(rate.dtype)
            if "bias" in head:
                logits = logits + head["bias"].astype(logits.dtype)
        return logits


def encoder_block(run: LayerRunner, x, prefix: str, *, heads: int,
                  scale: float, residual: str):
    """One Spikformer encoder block at ``prefix`` (``blocks/b3``): q/k/v
    projections, STDP attention, the output projection and the MLP, each
    combined with the block input by ``residual``."""
    backend, t = run.backend, run.t
    q, k, v = (run.linear(backend.wssl_lif, x, f"{prefix}/ssa/{w}")
               for w in ("wq", "wk", "wv"))
    with jax.named_scope(f"{prefix}/ssa/stdp"):
        att = backend.stdp_lif(q, k, v, heads=heads, scale=scale, t=t)
    att = run.linear(backend.wssl_lif, att, f"{prefix}/ssa/wo")
    x = backend.residual(att, x, residual)
    fc1, fc2 = f"{prefix}/mlp/fc1", f"{prefix}/mlp/fc2"
    # backends exposing ``mlp_pair_lif`` may fuse the fc1 -> LIF -> fc2
    # step into one kernel (packed spikes never unpacked in HBM); a None
    # return means "not applicable here" and the two-layer composition
    # below is the universal fallback — both are bit-exact against each
    # other, so the choice never changes logits
    s2 = None
    pair = getattr(backend, "mlp_pair_lif", None)
    if pair is not None:
        with jax.named_scope(f"{prefix}/mlp"):
            s2 = pair(x, run.at(fc1), run.at(fc2), t=t, **run.extra(fc1))
    if s2 is None:
        s2 = run.linear(backend.wssl_lif,
                        run.linear(backend.wssl_lif, x, fc1), fc2)
    return backend.residual(s2, x, residual)


def forward_folded(folded, images_u8, cfg: SpikformerConfig, *, backend,
                   layer_occupancy=None):
    """The inference forward over BN-folded params through a pluggable
    execution backend — the graph VESTA executes: matmuls + LIF comparisons
    only, with every activation between layers a binary spike train.

    ``backend`` implements the dataflow ops over an opaque activation type;
    the implementations live in ``repro.infer.backends`` (float {0,1} spike
    trains for the differentiable reference, packed uint8 plane groups for
    the hardware-shaped path). ``folded`` may be the float tree from
    ``fold_inference_params`` or its int8 quantization
    (``infer.quant.quantize_folded``) — layers carrying a ``scale`` leaf are
    dispatched with it — and may additionally carry per-layer ``lut`` leaves
    (the route-planning pass's cached byte-LUT tables,
    ``infer.compile.plan_route_tables``):
    the packed backend then runs the unpack-free gather route and the float
    backend its fold-order emulation, keeping the pair bit-exact.

    ``layer_occupancy`` maps layer paths ("scs/conv0", "blocks/b0/ssa/wq",
    ...) to STATIC calibrated chunk-occupancy floats for layers the plan
    routed "lut_sparse" (``LayerRunner``). It is closed over, never part of
    the traced tree — the sparse gather budget must be a compile-time
    constant. Returns (B, num_classes) logits.

    Each layer runs under a ``jax.named_scope`` of its path (the fused MLP
    pair under ``blocks/b{i}/mlp``), the token reshape under ``tokens`` and
    the readout under ``head``, so the compiled program's op metadata says
    which layer each device op belongs to.
    """
    run = LayerRunner(folded, backend, cfg.timesteps, layer_occupancy)
    x = run.linear(backend.sssc_lif, images_u8, "scs/conv0")
    for i in range(1, len(cfg.scs_channels)):
        x = run.linear(backend.zsc_lif, x, f"scs/conv{i}")
    with jax.named_scope("tokens"):
        x = backend.to_tokens(x)
    for i in range(cfg.depth):
        x = encoder_block(run, x, f"blocks/b{i}", heads=cfg.heads,
                          scale=cfg.attn_scale, residual=cfg.residual)
    return run.head(x)


def loss_fn(params, batch, cfg: SpikformerConfig, *, train: bool = True):
    """Cross-entropy over classes; returns (loss, (accuracy, stats))."""
    logits, stats = apply(params, batch["image"], cfg, train=train)
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return nll, (acc, stats)
