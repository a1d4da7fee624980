"""QKFormer HST-10-768 — a hierarchical spiking transformer with Q-K token
attention (Zhou et al., "QKFormer: Hierarchical Spiking Transformer using
Q-K Attention", NeurIPS 2024, arXiv:2403.16552), as VESTA's datapath runs
it: BN folded into the weights, every activation between layers a binary
spike train, and every spike addition of the source an IAND residual,
``(NOT new) AND res``, as in Spikformer V2-IAND.

Structure (published widths 192 / 384 / 768, depths 1 / 2 / 7, 8 heads):

  stage 1  SPEDS-1 patch embedding 224 -> 56: conv0 3x3 on the 8-bit image
           (SSSC) 3 -> 96, LIF, max-pool 3/2/1; conv1 3x3 96 -> 192, LIF,
           max-pool; conv2 3x3 192 -> 192, LIF; shortcut 1x1/s2 96 -> 192
           from conv0's pooled output, LIF; out = IAND(conv2, shortcut).
           Then 1 QKFormer block at D=192 over 3136 tokens.
  stage 2  SPEDS 56 -> 28, 192 -> 384 (conv0 3x3, LIF, max-pool; conv1 3x3,
           LIF; shortcut 1x1/s2, LIF; IAND), then 2 QKFormer blocks.
  stage 3  SPEDS 28 -> 14, 384 -> 768, then 7 Spikformer blocks (SSA).
  head     rate over T, mean over tokens, float linear -> 1000.

A QKFormer block is QKTA and an MLP: Q = LIF(x Wq), K = LIF(x Wk); per
head h the mask A[t, n, h] = LIF_{v_th}(sum of Q over h's channels);
X' = A[h(c)] * K; x = IAND(LIF(X' Wproj), x); then fc1 (D -> 4D) and fc2,
each LIF, combined with IAND.

``layer_specs`` is the one layer list: ``forward_folded`` runs the layers
in its order under ``jax.named_scope`` s of its paths, ``fold_inference_
params`` folds the weights it names, and the compile passes read each
matmul's rows, K and N from it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..nn.module import KeyStream
from ..nn.layers import linear_init
from .lif import bn_init, fold_bn
from .spikformer import LayerRunner, block_specs, encoder_block, tree_at
from .unified import LayerSpec


QKTA_V_TH = 0.5     # the QKTA mask neuron's threshold


@dataclasses.dataclass(frozen=True)
class QKFormerConfig:
    """Stages 1 .. n-1 run QKFormer blocks, the last stage Spikformer's."""
    img_size: int = 224
    in_channels: int = 3
    timesteps: int = 4
    embed_dims: tuple = (192, 384, 768)
    depths: tuple = (1, 2, 7)
    heads: int = 8
    mlp_ratio: int = 4
    num_classes: int = 1000
    attn_scale: float = 0.125                    # the SSA stage


def _half(side: int) -> int:
    """Output side of a 3/2/1 max-pool, and of a 1x1/s2 conv."""
    return -(-side // 2)


def _ssa(cfg: QKFormerConfig, stage: int) -> bool:
    return stage == len(cfg.embed_dims)


def layer_specs(cfg: QKFormerConfig) -> list:
    """Every layer of one ``forward_folded`` pass, in call order."""
    specs = []
    side, cin = cfg.img_size, cfg.in_channels
    stem = cfg.embed_dims[0] // 2
    for s, (d, depth) in enumerate(zip(cfg.embed_dims, cfg.depths),
                                   start=1):
        p = f"stage{s}/embed"
        if s == 1:
            specs.append(LayerSpec(f"{p}/conv0", "sssc", side * side,
                                   9 * cin, stem))
            side = _half(side)
            specs.append(LayerSpec(f"{p}/conv1", "conv", side * side,
                                   9 * stem, d))
            side = _half(side)
            specs += [LayerSpec(f"{p}/conv2", "conv", side * side, 9 * d, d),
                      LayerSpec(f"{p}/short", "wssl", side * side, stem, d)]
        else:
            specs.append(LayerSpec(f"{p}/conv0", "conv", side * side,
                                   9 * cin, d))
            side = _half(side)
            specs += [LayerSpec(f"{p}/conv1", "conv", side * side, 9 * d, d),
                      LayerSpec(f"{p}/short", "wssl", side * side, cin, d)]
        n, hidden = side * side, d * cfg.mlp_ratio
        for b in range(depth):
            prefix = f"stage{s}/b{b}"
            if _ssa(cfg, s):
                specs += block_specs(prefix, n, d, hidden)
            else:
                specs += [LayerSpec(f"{prefix}/qkta/wq", "wssl", n, d, d),
                          LayerSpec(f"{prefix}/qkta/wk", "wssl", n, d, d),
                          LayerSpec(f"{prefix}/qkta/mask", "qkta", n, d, d),
                          LayerSpec(f"{prefix}/qkta/proj", "wssl", n, d, d),
                          LayerSpec(f"{prefix}/mlp/fc1", "wssl", n, d, hidden),
                          LayerSpec(f"{prefix}/mlp/fc2", "wssl", n, hidden,
                                    d)]
        cin = d
    return specs


def layer_paths(cfg: QKFormerConfig) -> list:
    return [s.path for s in layer_specs(cfg)]


def _kernel_shape(spec: LayerSpec) -> tuple:
    if spec.op in ("sssc", "conv"):
        return (3, 3, spec.k // 9, spec.n)
    if spec.path.endswith("/short"):
        return (1, 1, spec.k, spec.n)
    return (spec.k, spec.n)


def _set(tree: dict, path: str, value) -> None:
    *keys, last = path.split("/")
    for key in keys:
        tree = tree.setdefault(key, {})
    tree[last] = value


def init(key, cfg: QKFormerConfig, *, bn_bias: float = 0.0,
         q_bias: float | None = None, dtype=jnp.float32):
    """Raw (pre-fold) weights: every matmul layer is ``{kernel, bn}`` at its
    path, kernels normal with std 1/sqrt(fan_in); batch norms identity but
    for their bias, ``bn_bias`` (``q_bias`` in the QKTA ``wq`` layers, if
    given)."""
    ks = KeyStream(key)
    p = {"head": linear_init(ks(), cfg.embed_dims[-1], cfg.num_classes,
                             bias=True, dtype=dtype)}
    for spec in layer_specs(cfg):
        if not spec.matmul:
            continue
        shape = _kernel_shape(spec)
        bn = bn_init(spec.n, dtype)
        bias = (q_bias if q_bias is not None and spec.path.endswith("qkta/wq")
                else bn_bias)
        bn["bias"] = jnp.full((spec.n,), bias, dtype)
        _set(p, spec.path, {
            "kernel": jax.random.normal(ks(), shape, dtype)
            / jnp.sqrt(jnp.asarray(spec.k, dtype)), "bn": bn})
    return p


def fold_inference_params(params, cfg: QKFormerConfig):
    """Fold every BN into its matmul's (K, N) kernel: a tree of
    ``{kernel, bias}`` at the layer list's paths. The image conv's kernel
    takes the 1/255 of the pixel scale, as Spikformer's stem does."""
    out = {"head": params["head"]}
    for spec in layer_specs(cfg):
        if not spec.matmul:
            continue
        layer = tree_at(params, spec.path)
        kern = layer["kernel"]
        if spec.op == "sssc":
            kern = kern * (1.0 / 255.0)
        kf, bf = fold_bn(kern.reshape(-1, kern.shape[-1]), None, layer["bn"])
        _set(out, spec.path, {"kernel": kf, "bias": bf})
    return out


def _qkformer_block(run: LayerRunner, x, prefix: str, cfg: QKFormerConfig):
    backend = run.backend
    q = run.linear(backend.wssl_lif, x, f"{prefix}/qkta/wq")
    k = run.linear(backend.wssl_lif, x, f"{prefix}/qkta/wk")
    with jax.named_scope(f"{prefix}/qkta/mask"):
        xq = backend.qkta(q, k, heads=cfg.heads, t=run.t, v_th=QKTA_V_TH)
    att = run.linear(backend.wssl_lif, xq, f"{prefix}/qkta/proj")
    x = backend.residual(att, x, "iand")
    s2 = run.linear(backend.wssl_lif,
                    run.linear(backend.wssl_lif, x, f"{prefix}/mlp/fc1"),
                    f"{prefix}/mlp/fc2")
    return backend.residual(s2, x, "iand")


def forward_folded(folded, images_u8, cfg: QKFormerConfig, *, backend,
                   layer_occupancy=None):
    """The inference forward over BN-folded (possibly int8, possibly
    route-annotated) params through an execution backend, as
    ``core.spikformer.forward_folded``. Each layer runs under a
    ``jax.named_scope`` of its path (a max-pool under its conv's), the
    reshapes between stages under ``stage{s}/tokens`` and
    ``stage{s}/spatial``, the readout under ``head``. Returns
    (B, num_classes) logits."""
    run = LayerRunner(folded, backend, cfg.timesteps, layer_occupancy)

    def conv(op, z, path, pool=False):
        y = run.linear(op, z, path)
        if pool:
            with jax.named_scope(path):
                y = backend.max_pool(y)
        return y

    x = images_u8
    for s, depth in enumerate(cfg.depths, start=1):
        p = f"stage{s}/embed"
        if s == 1:
            c0 = conv(backend.image_conv_lif, x, f"{p}/conv0", pool=True)
            c1 = conv(backend.conv_lif, c0, f"{p}/conv1", pool=True)
            new = conv(backend.conv_lif, c1, f"{p}/conv2")
            src = c0
        else:
            with jax.named_scope(f"stage{s}/spatial"):
                x = backend.to_spatial(x)
            c0 = conv(backend.conv_lif, x, f"{p}/conv0", pool=True)
            new = conv(backend.conv_lif, c0, f"{p}/conv1")
            src = x
        with jax.named_scope(f"{p}/short"):
            src = src[..., ::2, ::2, :]
        x = backend.residual(new, run.linear(backend.wssl_lif, src,
                                             f"{p}/short"), "iand")
        with jax.named_scope(f"stage{s}/tokens"):
            x = backend.to_tokens(x)
        for b in range(depth):
            prefix = f"stage{s}/b{b}"
            if _ssa(cfg, s):
                x = encoder_block(run, x, prefix, heads=cfg.heads,
                                  scale=cfg.attn_scale, residual="iand")
            else:
                x = _qkformer_block(run, x, prefix, cfg)
    return run.head(x)
