"""End-to-end integration: the train driver learns + resumes exactly; the
serving engine matches sequential generation; hlo analysis is calibrated."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_module(mod, *args, timeout=560):
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.run(
        [sys.executable, "-m", mod, *args], capture_output=True, text=True,
        env=env, timeout=timeout,
        cwd=os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# training driver
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_loss_decreases(tmp_path):
    out = run_module(
        "repro.launch.train", "--arch", "smollm-360m", "--reduce",
        "--steps", "40", "--global-batch", "8", "--seq", "128",
        "--lr", "1e-3", "--log-every", "5",
        "--metrics-out", str(tmp_path / "m.json"))
    assert out.returncode == 0, out.stderr[-2000:]
    metrics = json.loads((tmp_path / "m.json").read_text())
    first, last = metrics[0]["loss"], metrics[-1]["loss"]
    assert last < first - 0.2, (first, last)


@pytest.mark.slow
def test_train_failure_injection_resumes(tmp_path):
    """A NodeFailure at step 15 restores from the step-10 checkpoint and
    completes; the final metrics line reports restarts=1."""
    out = run_module(
        "repro.launch.train", "--arch", "smollm-360m", "--reduce",
        "--steps", "25", "--global-batch", "4", "--seq", "64",
        "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "10",
        "--inject-failure-at", "15")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    assert lines[-1]["result"] == {"restarts": 1, "completed": True}


@pytest.mark.slow
def test_moe_arch_trains(tmp_path):
    out = run_module(
        "repro.launch.train", "--arch", "qwen3-moe-30b-a3b", "--reduce",
        "--steps", "6", "--global-batch", "4", "--seq", "64",
        "--compression", "int8",
        "--metrics-out", str(tmp_path / "m.json"))
    assert out.returncode == 0, out.stderr[-2000:]
    metrics = json.loads((tmp_path / "m.json").read_text())
    assert all(np.isfinite(m["loss"]) for m in metrics)


# ---------------------------------------------------------------------------
# serving engine == sequential reference
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_matches_sequential_generation():
    from repro.configs.base import get_config
    from repro.launch.serve import Engine, Request
    from repro.nn import transformer as T

    cfg = get_config("smollm-360m").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        # fp32 end-to-end: greedy argmax on an UNTRAINED model is otherwise
        # numerically unstable (logit gaps < bf16 eps flip between batchings)
        eng = Engine(cfg, slots=2, cache_len=64, seed=0,
                     compute_dtype=jnp.float32, cache_dtype=jnp.float32)
        prompts = [[5, 9, 2, 14, 3], [7, 7, 1, 30, 11, 2]]
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr, max_new=6))
        done = sorted(eng.run(), key=lambda r: r.rid)

        # sequential reference: greedy argmax with a fresh cache per prompt
        for req, prompt in zip(done, prompts):
            cache = T.init_cache(cfg, 1, 64, dtype=jnp.float32)
            toks = jnp.asarray(prompt, jnp.int32)[None]
            logits, cache, _ = T.model_apply(
                eng.params, {"tokens": toks, "cache_pos": jnp.int32(0)},
                cfg, mode="prefill", cache=cache,
                compute_dtype=jnp.float32)
            seq = [int(jnp.argmax(logits[0, -1]))]
            pos = len(prompt)
            for _ in range(5):
                logits, cache, _ = T.model_apply(
                    eng.params,
                    {"tokens": jnp.asarray([[seq[-1]]], jnp.int32),
                     "cache_pos": jnp.int32(pos)},
                    cfg, mode="decode", cache=cache,
                    compute_dtype=jnp.float32)
                seq.append(int(jnp.argmax(logits[0, -1])))
                pos += 1
            assert req.out == seq, (req.rid, req.out, seq)


# ---------------------------------------------------------------------------
# hlo analysis calibration
# ---------------------------------------------------------------------------

def test_hlo_flops_scan_known():
    M = K = N = 128
    TRIPS = 7

    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=TRIPS)
        return y

    from repro.launch.hlo_analysis import analyze
    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((K, N), jnp.float32),
                               jax.ShapeDtypeStruct((M, K), jnp.float32))
    text = lowered.compile().as_text()
    cost = analyze(text)
    expect = TRIPS * 2 * M * K * N
    assert expect * 0.95 < cost.flops < expect * 1.2


def test_hlo_collective_bytes_psum():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.hlo_analysis import analyze

    mesh = make_mesh((1,), ("x",))
    n = 4096

    def f(x):
        return jax.lax.psum(x, "x")

    sf = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())
    text = jax.jit(sf).lower(
        jax.ShapeDtypeStruct((n,), jnp.float32)).compile().as_text()
    cost = analyze(text)
    # single-device all-reduce may be optimized away; accept 0 or 2x payload
    assert cost.coll_bytes["all-reduce"] in (0.0, 2.0 * 4 * n)


def test_hlo_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        z, _ = jax.lax.scan(outer, x, None, length=5)
        return z

    from repro.launch.hlo_analysis import analyze
    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
    cost = analyze(text)
    expect = 15 * 2 * 64 ** 3
    assert expect * 0.95 < cost.flops < expect * 1.3
