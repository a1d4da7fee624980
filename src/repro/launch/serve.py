"""Serving driver: batched prefill + continuous-batching decode.

The serving loop maintains a fixed pool of `slots` (the decode batch); each
slot holds one request's KV/SSM cache rows. Requests arrive in a queue,
prefill runs per-request (chunked attention => O(S·chunk) peak), the
resulting cache row is spliced into the pool, and one fused `serve_step`
advances EVERY active slot by one token per iteration — the standard
continuous-batching schedule (vLLM-style), expressed with a static-shape
cache pool so the step stays jit-compiled.

This container runs reduced configs end-to-end on CPU; the decode_32k /
long_500k production shapes are exercised by launch/dryrun.py on the
512-chip mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduce \
      --slots 4 --requests 8 --max-new 32
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from collections import deque

import jax
import jax.numpy as jnp

from ..configs.base import get_config
from ..nn import transformer as T
from . import steps
from .mesh import make_cpu_mesh


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    t_arrival: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0


class Engine:
    """Continuous-batching engine over a static slot pool."""

    def __init__(self, cfg, *, slots: int, cache_len: int, seed: int = 0,
                 compute_dtype=None, cache_dtype=jnp.bfloat16):
        self.cfg = cfg
        self.slots = slots
        self.cache_len = cache_len
        self.compute_dtype = compute_dtype or jnp.dtype(cfg.compute_dtype)
        self.cache_dtype = cache_dtype
        self.params = T.init_model(jax.random.PRNGKey(seed), cfg)
        self.pool = T.init_cache(cfg, slots, cache_len, dtype=cache_dtype)
        self.active: dict[int, Request] = {}           # slot -> request
        self.positions = jnp.zeros((slots,), jnp.int32)
        self.queue: deque[Request] = deque()
        self.done: list[Request] = []

        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_impl)

    # -- jit bodies -----------------------------------------------------------
    def _prefill_impl(self, params, tokens):
        """tokens: (1, S) -> (next_token, cache_row)."""
        cache = T.init_cache(self.cfg, 1, self.cache_len,
                             dtype=self.cache_dtype)
        batch = {"tokens": tokens, "cache_pos": jnp.int32(0)}
        logits, cache, _ = T.model_apply(
            params, batch, self.cfg, mode="prefill", cache=cache,
            compute_dtype=self.compute_dtype)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    def _decode_impl(self, params, pool, tokens, positions):
        """tokens: (slots, 1); positions: (slots,) per-slot cache_pos.

        ONE fused step advances every slot: the cache tracks per-row
        positions, so heterogeneous offsets need no per-slot dispatch."""
        batch = {"tokens": tokens, "cache_pos": positions}
        logits, pool, _ = T.model_apply(
            params, batch, self.cfg, mode="decode", cache=pool,
            compute_dtype=self.compute_dtype)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), pool

    # -- pool management ------------------------------------------------------
    def _splice(self, slot: int, row_cache):
        """Copy a 1-row prefill cache into pool slot `slot`.

        The batch axis position is determined by the cache layout, NOT by
        shape matching (ambiguous when n_layers == slots): scan-stacked
        caches are (L, B, ...) => axis 1; per-layer list caches are
        (B, ...) => axis 0."""
        axis = 1 if self.cfg.scan_layers else 0

        def put(pool_leaf, row_leaf):
            if axis == 0:
                return pool_leaf.at[slot].set(row_leaf[0])
            return pool_leaf.at[:, slot].set(row_leaf[:, 0])

        self.pool = jax.tree_util.tree_map(put, self.pool, row_cache)

    def submit(self, req: Request):
        req.t_arrival = time.time()
        self.queue.append(req)

    def _admit(self):
        free = [s for s in range(self.slots) if s not in self.active]
        while free and self.queue:
            slot = free.pop(0)
            req = self.queue.popleft()
            toks = jnp.asarray(req.prompt, jnp.int32)[None]
            next_tok, row = self._prefill(self.params, toks)
            req.out.append(int(next_tok[0]))
            req.t_first = time.time()
            self._splice(slot, row)
            self.positions = self.positions.at[slot].set(len(req.prompt))
            self.active[slot] = req

    def step(self) -> int:
        """One engine iteration; returns number of active slots."""
        self._admit()
        if not self.active:
            return 0
        tokens = jnp.zeros((self.slots, 1), jnp.int32)
        for slot, req in self.active.items():
            tokens = tokens.at[slot, 0].set(req.out[-1])
        toks, self.pool = self._decode(self.params, self.pool, tokens,
                                       self.positions)
        self.positions = self.positions + 1
        finished = []
        for slot, req in self.active.items():
            req.out.append(int(toks[slot]))
            if len(req.out) >= req.max_new:
                req.t_done = time.time()
                finished.append(slot)
        for slot in finished:
            self.done.append(self.active.pop(slot))
        return len(self.active)

    def run(self):
        while self.queue or self.active:
            self.step()
        return self.done


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = cfg.reduced()

    mesh = make_cpu_mesh()
    with jax.set_mesh(mesh):
        eng = Engine(cfg, slots=args.slots, cache_len=args.cache_len,
                     seed=args.seed)
        rng = jax.random.PRNGKey(args.seed + 1)
        t0 = time.time()
        for i in range(args.requests):
            rng, k = jax.random.split(rng)
            prompt = jax.random.randint(
                k, (args.prompt_len,), 0, cfg.vocab).tolist()
            eng.submit(Request(rid=i, prompt=prompt, max_new=args.max_new))
        done = eng.run()
        wall = time.time() - t0

    total_tokens = sum(len(r.out) for r in done)
    ttfts = [r.t_first - r.t_arrival for r in done]
    summary = {
        "requests": len(done),
        "total_new_tokens": total_tokens,
        "wall_s": round(wall, 3),
        "tok_per_s": round(total_tokens / wall, 2),
        "mean_ttft_s": round(sum(ttfts) / len(ttfts), 4),
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
