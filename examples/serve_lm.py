"""Serve a small LM with continuous batching: 12 requests of mixed prompt
lengths stream through a 4-slot pool; one fused decode step advances every
active sequence per iteration.

  PYTHONPATH=src python examples/serve_lm.py
"""
import json
import time

import jax

from repro.configs.base import get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine, Request


def main():
    cfg = get_config("smollm-360m").reduced(
        n_layers=4, d_model=256, vocab=2048)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        eng = Engine(cfg, slots=4, cache_len=256, seed=0)
        rng = jax.random.PRNGKey(1)
        t0 = time.time()
        for i in range(12):
            rng, k = jax.random.split(rng)
            plen = int(8 + 24 * jax.random.uniform(k))
            prompt = jax.random.randint(k, (plen,), 0, cfg.vocab).tolist()
            eng.submit(Request(rid=i, prompt=prompt, max_new=24))
        it = 0
        while eng.queue or eng.active:
            n_active = eng.step()
            it += 1
            if it % 10 == 0:
                print(f"iter {it}: active={n_active} queued={len(eng.queue)} "
                      f"done={len(eng.done)}", flush=True)
        wall = time.time() - t0

    toks = sum(len(r.out) for r in eng.done)
    print(json.dumps({
        "requests": len(eng.done),
        "new_tokens": toks,
        "wall_s": round(wall, 2),
        "tok_per_s": round(toks / wall, 1),
        "mean_ttft_s": round(sum(r.t_first - r.t_arrival
                                 for r in eng.done) / len(eng.done), 3),
    }))


if __name__ == "__main__":
    main()
