"""The program under test for a QKFormer configuration: its compiled,
served model, and the shape counts of its layers.

The weights come from ``bench.reference.qkformer.init_params`` (made by
the benchmark from the seed, on the device, in one jitted call); the program
folds, quantizes and plans its routes from them in ``repro.infer.compile``.
"""
from __future__ import annotations

import time

from bench import work
from bench.reference import qkformer as reference

SIZE_KEYS = ("img_size", "in_channels", "timesteps", "embed_dims", "depths",
             "heads", "mlp_ratio", "num_classes", "attn_scale")
TUPLE_KEYS = ("embed_dims", "depths")


def sizes(config: dict) -> dict:
    return {k: config[k] for k in SIZE_KEYS}


def image_shape(config: dict) -> tuple:
    return (config["img_size"], config["img_size"], config["in_channels"])


def init_params(config: dict, seed: int):
    return reference.init_params(config, seed)


def build(params, config: dict, buckets) -> tuple:
    """Compile the packed model at ``buckets`` and warm every bucket.
    Returns ``(model, info)``; ``info`` holds the resolved routes and the
    seconds of each phase."""
    from repro.core.qkformer import QKFormerConfig
    from repro.infer import ExecutionPlan, compile

    cfg = QKFormerConfig(**{**sizes(config),
                            **{k: tuple(config[k]) for k in TUPLE_KEYS}})
    t0 = time.perf_counter()
    model = compile(params, cfg, ExecutionPlan(
        backend="packed", weight_dtype=config["weight_dtype"],
        batch_buckets=tuple(buckets)))
    t1 = time.perf_counter()
    model.warmup()
    t2 = time.perf_counter()
    routes: dict[str, list] = {}
    for path, route in sorted(model.plan.routes.items()):
        routes.setdefault(route, []).append(path)
    return model, {"routes": routes, "compile_passes_s": t1 - t0,
                   "warmup_s": t2 - t1}


def layers(config: dict, batch: int = 1) -> list:
    """Every layer of one forward pass over ``batch`` images, in call order,
    counted as ``bench/work.py`` counts Spikformer's: a k x k conv is a
    matmul of K = k*k*Cin (its im2col rows) at each output position; the
    image conv (SSSC) runs once for all T; a QKTA mask (family "qkta") has
    no MACs and moves its packed Q and K in and X' out."""
    t = config["timesteps"]
    g = -(-t // 8)
    heads = config["heads"]
    out = []

    def matmul(path, family, rows, k, n, once=False):
        m = batch * rows
        macs, in_bytes = (m * k * n, m * k) if once else (t * m * k * n,
                                                          g * m * k)
        out.append(work.Layer(path, family, macs,
                              in_bytes + k * n + g * m * n, True))

    side, cin = config["img_size"], config["in_channels"]
    for s, (d, depth) in enumerate(zip(config["embed_dims"],
                                       config["depths"]), start=1):
        kind = reference.stage_kind(config, s)
        p = f"stage{s}/embed"
        if s == 1:
            stem = d // 2
            matmul(f"{p}/conv0", "embed", side * side, 9 * cin, stem,
                   once=True)
            side = reference.half_side(side)
            matmul(f"{p}/conv1", "embed", side * side, 9 * stem, d)
            side = reference.half_side(side)
            matmul(f"{p}/conv2", "embed", side * side, 9 * d, d)
            matmul(f"{p}/short", "embed", side * side, stem, d)
        else:
            matmul(f"{p}/conv0", "embed", side * side, 9 * cin, d)
            side = reference.half_side(side)
            matmul(f"{p}/conv1", "embed", side * side, 9 * d, d)
            matmul(f"{p}/short", "embed", side * side, cin, d)
        n, hidden = side * side, d * config["mlp_ratio"]
        for b in range(depth):
            pb = f"stage{s}/b{b}"
            if kind == "qkta":
                for w in ("wq", "wk"):
                    matmul(f"{pb}/qkta/{w}", "qkvo", n, d, d)
                out.append(work.Layer(f"{pb}/qkta/mask", "qkta", 0,
                                      3 * g * batch * n * d, False))
                matmul(f"{pb}/qkta/proj", "qkvo", n, d, d)
            else:
                for w in ("wq", "wk", "wv"):
                    matmul(f"{pb}/ssa/{w}", "qkvo", n, d, d)
                out.append(work.Layer(
                    f"{pb}/ssa/stdp", "stdp",
                    2 * t * batch * heads * n * n * (d // heads), 0, False))
                matmul(f"{pb}/ssa/wo", "qkvo", n, d, d)
            matmul(f"{pb}/mlp/fc1", "mlp", n, d, hidden)
            matmul(f"{pb}/mlp/fc2", "mlp", n, hidden, d)
        cin = d
    out.append(work.Layer("head", "head",
                          batch * config["embed_dims"][-1]
                          * config["num_classes"], 0, False))
    return out


def reference_logits(params, config: dict, images, *, bits: int):
    return reference.logits(params, config, images, bits=bits)
