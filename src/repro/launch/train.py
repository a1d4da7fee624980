"""Training driver.

Two modes:
  * REAL RUN (default) — trains the requested arch (optionally ``--reduce``d
    so it fits this CPU container) on synthetic/file data with the full
    production loop: sharded jit step, async checkpointing, restart
    supervision, loss guard, straggler bookkeeping, metrics log.
  * DRY RUN (``--dry-run``) — delegates to launch/dryrun.py semantics for the
    production mesh (lower+compile only). Use dryrun.py directly for the
    full 40-cell sweep.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduce \
      --steps 50 --global-batch 8 --seq 256 --ckpt-dir /tmp/ck --ckpt-every 20
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
      --reduce --steps 10 --compression int8
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from ..configs.base import get_config
from ..data.pipeline import DataConfig, DataPipeline
from ..checkpoint.checkpointer import Checkpointer
from ..runtime.fault_tolerance import (LossGuard, RestartPolicy,
                                       StragglerDetector, TrainSupervisor,
                                       NodeFailure)
from ..optim import adamw
from ..nn import transformer as T
from ..sharding import rules
from . import steps
from .mesh import make_cpu_mesh


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true",
                    help="scale the arch down to a CPU-runnable size")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatch", type=int, default=0,
                    help="0 = no accumulation")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default="synthetic_lm")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="test hook: raise NodeFailure at this step once")
    return ap


@dataclasses.dataclass
class TrainState:
    params: dict
    opt_state: dict
    data: DataPipeline
    step: int


def main(argv=None):
    args = build_parser().parse_args(argv)
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = cfg.reduced()

    mesh = make_cpu_mesh()
    dcfg = DataConfig(seq=args.seq, global_batch=args.global_batch,
                      vocab=cfg.padded_vocab, seed=args.seed,
                      kind=args.data, path=args.data_path)
    ts = steps.TrainSettings(
        microbatch=args.microbatch or args.global_batch,
        compression=args.compression,
        opt=adamw.OptConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                            decay_steps=max(args.steps, 2 * args.warmup)))

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    guard = LossGuard()
    straggler = StragglerDetector(n_nodes=1)
    metrics_log: list[dict] = []
    injected = {"done": False}

    batch_shapes = {
        "tokens": jax.ShapeDtypeStruct((args.global_batch, args.seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((args.global_batch, args.seq), jnp.int32),
    }
    if cfg.family == "vlm":
        batch_shapes["image_embeds"] = jax.ShapeDtypeStruct(
            (args.global_batch, cfg.img_tokens, cfg.d_model), jnp.bfloat16)
        batch_shapes["mrope_positions"] = jax.ShapeDtypeStruct(
            (3, args.global_batch, args.seq), jnp.int32)
    if cfg.family == "encdec":
        batch_shapes["frames"] = jax.ShapeDtypeStruct(
            (args.global_batch, cfg.n_frames, cfg.d_model), jnp.bfloat16)

    with jax.set_mesh(mesh):
        step_fn, (p_sh, o_sh, _), in_sh = steps.jit_train_step(
            cfg, mesh, ts, batch_shapes)

        def augment(batch):
            """Add the stub modality inputs the synthetic LM stream lacks."""
            b, s = batch["tokens"].shape
            if cfg.family == "vlm":
                batch["image_embeds"] = jnp.zeros(
                    (b, cfg.img_tokens, cfg.d_model), jnp.bfloat16)
                pos = jnp.broadcast_to(jnp.arange(s), (b, s))
                batch["mrope_positions"] = jnp.broadcast_to(
                    pos[None], (3, b, s)).astype(jnp.int32)
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (b, cfg.n_frames, cfg.d_model), jnp.bfloat16)
            return batch

        def make_state(restore):
            if restore is not None and ckpt is not None \
                    and ckpt.latest_step() is not None:
                skel_p = steps.abstract_params(cfg)
                skel_o = steps.abstract_opt_state(cfg, skel_p, ts)
                tree, extra = ckpt.restore(
                    skeleton={"params": skel_p, "opt": skel_o},
                    shardings={"params": rules.param_shardings(mesh, skel_p),
                               "opt": rules.opt_state_shardings(mesh, skel_o)})
                data = DataPipeline.restore(dcfg, extra["data"])
                print(f"[restore] step {extra['step']} from {ckpt.dir}")
                return TrainState(tree["params"], tree["opt"], data,
                                  int(extra["step"]))
            params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
            params = jax.tree_util.tree_map(
                jax.device_put, params, rules.param_shardings(
                    mesh, jax.eval_shape(lambda: params)))
            opt_state = adamw.init(params, ts.opt)
            if ts.compression != "none":
                from ..optim.compression import ef_init
                opt_state["ef"] = ef_init(params)
            return TrainState(params, opt_state, DataPipeline(dcfg), 0)

        def run_segment(state: TrainState):
            params, opt_state, data = state.params, state.opt_state, state.data
            for step in range(state.step, args.steps):
                if step == args.inject_failure_at and not injected["done"]:
                    injected["done"] = True
                    data.close()
                    raise NodeFailure(f"injected at step {step}")
                t0 = time.time()
                batch = augment(next(data))
                params, opt_state, m = step_fn(params, opt_state, batch)
                loss = float(m["loss"])
                dt = time.time() - t0
                straggler.update([dt])
                if not guard.check(loss):
                    data.close()
                    raise NodeFailure(f"loss diverged: {loss} at step {step}")
                if step % args.log_every == 0 or step == args.steps - 1:
                    rec = {"step": step, "loss": round(loss, 4),
                           "grad_norm": round(float(m["grad_norm"]), 4),
                           "lr": float(m["lr"]), "step_s": round(dt, 3)}
                    metrics_log.append(rec)
                    print(json.dumps(rec), flush=True)
                if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                    ckpt.save(step + 1,
                              {"params": params, "opt": opt_state},
                              extra={"step": step + 1,
                                     "data": data.state_dict()})
            if ckpt is not None:
                ckpt.save(args.steps, {"params": params, "opt": opt_state},
                          extra={"step": args.steps,
                                 "data": data.state_dict()}, block=True)
            data.close()
            return None

        sup = TrainSupervisor(RestartPolicy(backoff_s=0.01), make_state,
                              run_segment)
        result = sup.run()
        print(json.dumps({"result": result}), flush=True)

    if args.metrics_out:
        pathlib.Path(args.metrics_out).write_text(json.dumps(metrics_log))
    return metrics_log


if __name__ == "__main__":
    main()
