#!/usr/bin/env python3
"""Bring-up check: serve Spikformer-8-512 end to end on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four one-chip fleet replicas

One chip (the default): ``configs/spikformer_v2.py:CONFIG`` (224 px, dim
512, depth 8, 8 heads, T=4, 1000 classes) is compiled through
``repro.infer.compile`` with the default ``packed`` backend and int8
weights, at buckets (1, 8). ``AsyncServeRuntime`` answers 16 seeded
requests of 1-3 images; every one must complete, and each served label must
equal the float reference backend's (compiled under the same resolved plan)
on the same images. The packed logits of one seeded bucket-8 batch must
equal the reference's bit for bit. ``CONFIG_T16`` at int8 (two plane
groups) runs one bucket-8 batch against its reference the same way.

``--four-chips``: ``ServeFleet(replicas=4)`` over four devices against
``replicas=1`` on one seeded Poisson trace; the labels must be identical,
and each replica's folded weights must sit on its own device. Nothing else
runs.

Weights are random, made from ``--seed``; nothing is downloaded. Lines
before the last describe this run (compile and warm step seconds per
bucket, images/s, p99, logit differences) and are not benchmark numbers.
The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script prints no such line and
exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_REQUESTS = 16


class CheckFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def info(**fields) -> None:
    print(json.dumps(fields), flush=True)


def firing_params(key, cfg, *, bn_bias: float = 1.0):
    """Random weights from ``key``, with every batch-norm bias set to
    ``bn_bias``. Under the initial batch norm (scale 1, bias 0) spikes die
    out within the stem, every image gets the same logits, and a parity
    check would pass vacuously; the raised bias keeps every layer firing."""
    from repro.core.spikformer import init

    def walk(t):
        if isinstance(t, dict) and set(t) == {"scale", "bias", "mean", "var"}:
            return {**t, "bias": t["bias"] + bn_bias}
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        return t

    return walk(init(key, cfg))


def step_seconds(model) -> dict:
    """Host-clock seconds of one step on zeros, per bucket; the first call
    of a bucket includes its compile."""
    import jax
    out = {}
    for b in model.buckets:
        t0 = time.perf_counter()
        jax.block_until_ready(model.step(np.zeros(model.input_shape(b),
                                                  np.uint8)))
        out[b] = round(time.perf_counter() - t0, 3)
    return out


def compile_pair(params, cfg, plan):
    """The packed model under ``plan`` and the float reference under the
    packed model's resolved plan (same routes: they are part of the
    math)."""
    from repro.infer import compile
    model = compile(params, cfg, plan)
    ref = compile(params, cfg, dataclasses.replace(model.plan,
                                                   backend="reference"))
    return model, ref


def check_logits(name: str, model, ref, images) -> None:
    """Packed logits equal the reference's bit for bit, are finite, and
    are not the same for every image (a dead network would agree
    vacuously)."""
    got = np.asarray(model.logits(images))
    want = np.asarray(ref.logits(images))
    info(check=name, shape=list(got.shape),
         max_abs_logit_diff=float(np.max(np.abs(got - want))),
         labels=got.argmax(-1).tolist())
    check(got.shape == (len(images), model.cfg.num_classes),
          f"{name}: logits shape {got.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    check(bool(np.any(got != got[:1])), f"{name}: every image has the same "
          "logits")
    check(np.array_equal(got, want), f"{name}: packed logits differ from "
          "the reference")


def serve(client, trace, image_shape, *, seed: int):
    """Replay ``trace`` open-loop through ``client``; returns the loadgen
    result, the client's stats, and {arrival: (images, labels)}."""
    from repro.serve.loadgen import image_maker, run_open_loop
    make = image_maker(image_shape, seed=seed)
    sent, handles = {}, {}

    def make_images(k, n):
        sent[k] = make(k, n)
        return sent[k]

    res = run_open_loop(client, trace, make_images, slo_ms=1000.0,
                        result_timeout_s=600.0,
                        on_accept=lambda k, h: handles.__setitem__(k, h))
    stats = client.stats()
    served = {k: (imgs, getattr(handles.get(k), "labels", None))
              for k, imgs in sent.items()}
    return res, stats, served


def check_served(name: str, res: dict, stats: dict, n: int) -> None:
    info(check=name, requests=n, images=res["images_completed"],
         images_per_s=res["completed_fps"], p99_s=res["latency_p99_s"],
         dropped=res["requests_dropped"], rejected=res["requests_rejected"],
         failed=stats["requests_failed"])
    check(res["requests_accepted"] == n, f"{name}: accepted "
          f"{res['requests_accepted']} of {n} requests")
    for key in ("requests_dropped", "requests_rejected"):
        check(res[key] == 0, f"{name}: {res[key]} {key}")
    for key in ("requests_failed", "requests_rejected"):
        check(stats[key] == 0, f"{name}: {stats[key]} {key}")
    check(stats["requests"] == n, f"{name}: {stats['requests']} of {n} "
          "requests completed")


def serve_config(cfg, *, seed: int, n_requests: int = N_REQUESTS) -> None:
    """``cfg`` at int8, buckets (1, 8): one seeded batch against the
    reference, then ``n_requests`` requests through ``AsyncServeRuntime``."""
    import jax
    from repro.infer import ExecutionPlan
    from repro.serve import AsyncServeRuntime, ServePolicy
    from repro.serve.loadgen import Arrival

    params = firing_params(jax.random.PRNGKey(seed), cfg)
    model, ref = compile_pair(params, cfg, ExecutionPlan(
        backend="packed", weight_dtype="int8", batch_buckets=(1, 8)))
    info(phase="compile", timesteps=cfg.timesteps,
         routes=sorted(set(model.plan.routes.values())),
         compile_s=step_seconds(model), step_s=step_seconds(model),
         reference_compile_s=step_seconds(ref))

    rng = np.random.default_rng(seed)
    check_logits(f"T={cfg.timesteps} int8 logits", model, ref,
                 rng.integers(0, 256, model.input_shape(8), dtype=np.uint8))

    trace = [Arrival(t_s=0.05 * k, n_images=int(n)) for k, n in
             enumerate(rng.integers(1, 4, n_requests))]
    with AsyncServeRuntime(model, policy=ServePolicy(max_wait_ms=10.0)) as rt:
        res, stats, served = serve(rt, trace, model.input_shape()[1:],
                                   seed=seed + 1)
    check_served("AsyncServeRuntime", res, stats, n_requests)
    for k, (imgs, labels) in sorted(served.items()):
        want = np.asarray(ref.classify(imgs)).tolist()
        check(labels == want, f"request {k}: served labels {labels} != "
              f"reference {want}")


def parity_config(cfg, *, seed: int) -> None:
    """``cfg`` at int8: one seeded bucket-8 batch against the reference."""
    import jax
    from repro.infer import ExecutionPlan

    params = firing_params(jax.random.PRNGKey(seed), cfg)
    model, ref = compile_pair(params, cfg, ExecutionPlan(
        backend="packed", weight_dtype="int8", batch_buckets=(8,)))
    info(phase="compile", timesteps=cfg.timesteps,
         routes=sorted(set(model.plan.routes.values())),
         compile_s=step_seconds(model), step_s=step_seconds(model))
    rng = np.random.default_rng(seed)
    check_logits(f"T={cfg.timesteps} int8 logits", model, ref,
                 rng.integers(0, 256, model.input_shape(8), dtype=np.uint8))


def one_chip(cfg, cfg16, *, seed: int, n_requests: int = N_REQUESTS) -> None:
    # one function per config: the T=4 models are freed before the T=16
    # reference, the largest program, takes its device memory
    serve_config(cfg, seed=seed, n_requests=n_requests)
    parity_config(cfg16, seed=seed)


def four_chips(cfg, *, seed: int) -> None:
    import jax
    from repro.infer import ExecutionPlan, compile
    from repro.serve import ServeFleet, ServePolicy
    from repro.serve.loadgen import poisson_trace

    replicas = 4
    check(len(jax.devices()) >= replicas, f"{len(jax.devices())} devices "
          f"for {replicas} replicas")
    params = firing_params(jax.random.PRNGKey(seed), cfg)
    model = compile(params, cfg, ExecutionPlan(
        backend="packed", weight_dtype="int8", batch_buckets=(8,)))
    trace = poisson_trace(rps=20.0, duration_s=2.0, seed=seed,
                          images_per_request=(1, 3))
    labels = {}
    for n in (1, replicas):
        fleet = ServeFleet(model, replicas=n,
                           policy=ServePolicy(max_wait_ms=10.0))
        homes = [{d for leaf in jax.tree.leaves(r.model.folded)
                  for d in leaf.devices()} for r in fleet.replicas]
        t0 = time.perf_counter()
        fleet.start()
        warm_s = round(time.perf_counter() - t0, 3)
        with fleet:
            res, stats, served = serve(fleet, trace, model.input_shape()[1:],
                                       seed=seed + 1)
        info(phase="fleet", replicas=n, warmup_s=warm_s,
             devices=[sorted(str(d) for d in h) for h in homes])
        check_served(f"ServeFleet replicas={n}", res, stats, len(trace))
        check(all(len(h) == 1 for h in homes), "a replica's weights span "
              "several devices")
        check(len({next(iter(h)) for h in homes}) == n, "replicas share a "
              "device")
        labels[n] = [served[k][1] for k in sorted(served)]
    check(labels[1] == labels[replicas], f"labels through {replicas} "
          "replicas differ from one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica fleet comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.configs.spikformer_v2 import CONFIG, CONFIG_T16

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    info(device_kind=dev.device_kind, device_count=len(devices))
    try:
        if args.four_chips:
            four_chips(CONFIG, seed=args.seed)
        else:
            one_chip(CONFIG, CONFIG_T16, seed=args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
