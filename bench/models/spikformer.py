"""The program under test for a Spikformer configuration: its compiled,
served model, and the shape counts of its layers.

The weights come from ``bench.reference.spikformer.init_params`` (made by
the benchmark from the seed, on the device, in one jitted call); the program
folds, quantizes and plans its routes from them in ``repro.infer.compile``.
"""
from __future__ import annotations

import time

from bench import work
from bench.reference import spikformer as reference

SIZE_KEYS = ("img_size", "in_channels", "timesteps", "dim", "depth", "heads",
             "mlp_ratio", "num_classes", "scs_channels", "residual",
             "attn_scale")


def sizes(config: dict) -> dict:
    return {k: config[k] for k in SIZE_KEYS}


def image_shape(config: dict) -> tuple:
    return (config["img_size"], config["img_size"], config["in_channels"])


def init_params(config: dict, seed: int):
    return reference.init_params(sizes(config), seed)


def build(params, config: dict, buckets) -> tuple:
    """Compile the packed model at ``buckets`` and warm every bucket.
    Returns ``(model, info)``; ``info`` holds the resolved routes and the
    seconds of each phase."""
    from repro.core.spikformer import SpikformerConfig
    from repro.infer import ExecutionPlan, compile

    cfg = SpikformerConfig(**{**sizes(config),
                              "scs_channels": tuple(config["scs_channels"])})
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


def layers(config: dict, batch: int = 1):
    return work.spikformer_layers(sizes(config), batch)


def reference_logits(params, config: dict, images, *, bits: int):
    return reference.logits(params, sizes(config), images, bits=bits)
