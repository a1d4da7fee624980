"""Multi-device numerical equivalence: the sharded train/serve steps on an
8-device (2x4) CPU mesh must match single-device execution. Runs in a
subprocess because the device count must be set before jax initializes."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, %r)
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import get_config
    from repro.nn import transformer as T
    from repro.launch import steps
    from repro.optim import adamw
    from repro.launch.mesh import make_mesh

    cfg = get_config("smollm-360m").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, vocab=512)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    B, S = 8, 32
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    ts = steps.TrainSettings(microbatch=4)
    opt = adamw.init(params, ts.opt)

    # single device reference
    plain = jax.jit(steps.make_train_step(cfg, ts))
    p_ref, o_ref, m_ref = plain(params, opt, batch)

    # sharded on 2x4
    mesh = make_mesh((2, 4), ("data", "model"))
    bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
    with jax.set_mesh(mesh):
        sharded, _, in_sh = steps.jit_train_step(cfg, mesh, ts, bs)
        # shard + donate COPIES (x.copy() — device_put alone may alias the
        # origin buffer for replicated leaves, and donation deletes it)
        p_cp = jax.tree.map(lambda x, s: jax.device_put(x.copy(), s),
                            params, in_sh[0])
        o_cp = jax.tree.map(lambda x, s: jax.device_put(x.copy(), s),
                            opt, in_sh[1])
        p_sh, o_sh, m_sh = sharded(p_cp, o_cp, batch)

    np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]),
                               rtol=2e-4)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-3, atol=3e-3)
    print("TRAIN_OK")

    # decode parity: sharded serve step vs single-device decode
    cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
    toks = batch["tokens"][:, :1]
    dec_batch = {"tokens": toks, "cache_pos": jnp.int32(0)}
    ref_logits, _, _ = T.model_apply(params, dec_batch, cfg, mode="decode",
                                     cache=cache, compute_dtype=jnp.float32)
    with jax.set_mesh(mesh):
        cache_sh = jax.eval_shape(lambda: T.init_cache(cfg, B, S,
                                                       dtype=jnp.float32))
        fn, _, in_sh2 = steps.jit_serve_step(
            cfg, mesh, cache_sh,
            {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "cache_pos": jax.ShapeDtypeStruct((), jnp.int32)})
        p_put = jax.tree.map(lambda x, s: jax.device_put(x.copy(), s),
                             params, in_sh2[0])
        c_put = jax.tree.map(lambda x, s: jax.device_put(x, s),
                             T.init_cache(cfg, B, S, dtype=jnp.float32),
                             in_sh2[1])
        tok_sh, _ = fn(p_put, c_put, dec_batch)
    ref_tok = jnp.argmax(ref_logits[:, -1], -1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(ref_tok), np.asarray(tok_sh))
    print("DECODE_OK")
""" % SRC)


@pytest.mark.slow
def test_sharded_equals_single_device():
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TRAIN_OK" in out.stdout and "DECODE_OK" in out.stdout
