"""Optimizer + gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.optim.compression import ef_init, ef_compress, compressed_psum_int8


def test_schedule_warmup_peak_decay():
    cfg = adamw.OptConfig(peak_lr=1e-3, warmup_steps=10, decay_steps=100,
                          min_lr_frac=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(s))) for s in
           (0, 5, 10, 50, 100, 200)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 5e-4) < 1e-8          # mid warmup
    assert abs(lrs[2] - 1e-3) < 1e-8          # peak
    assert lrs[3] < lrs[2]
    assert abs(lrs[4] - 1e-4) < 1e-7          # floor
    assert abs(lrs[5] - 1e-4) < 1e-7


def test_adamw_converges_quadratic():
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros((3, 1))}   # 2-D so weight decay path runs
    cfg = adamw.OptConfig(peak_lr=0.1, warmup_steps=5, decay_steps=200,
                          weight_decay=0.0)
    state = adamw.init(params, cfg)

    def loss(p):
        return jnp.sum((p["w"][:, 0] - target) ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw.update(g, state, params, cfg)
    assert float(loss(params)) < 1e-2


def test_clip_norm_applied():
    params = {"w": jnp.zeros((2, 2))}
    cfg = adamw.OptConfig(clip_norm=1.0, peak_lr=1.0, warmup_steps=0,
                          decay_steps=10)
    state = adamw.init(params, cfg)
    g = {"w": jnp.full((2, 2), 100.0)}
    _, _, m = adamw.update(g, state, params, cfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_bf16_moments():
    params = {"w": jnp.zeros((4, 4))}
    cfg = adamw.OptConfig(state_dtype=jnp.bfloat16)
    state = adamw.init(params, cfg)
    assert state["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.ones((4, 4))}
    _, s2, _ = adamw.update(g, state, params, cfg)
    assert s2["m"]["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_compression_error_feedback_unbiased():
    """Error feedback: repeated compression of a CONSTANT gradient delivers
    the true mean in the long run (sum of deq -> n*g)."""
    g = {"w": jnp.array([[0.3, -0.7], [0.001, 1.2]])}
    ef = ef_init(g)
    total = jnp.zeros_like(g["w"])
    for _ in range(50):
        deq, ef = ef_compress(g, ef, method="int8")
        total = total + deq["w"]
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g["w"]),
                               rtol=1e-2, atol=1e-3)


def test_topk_keeps_largest():
    g = {"w": jnp.array([[10.0, 0.1], [0.2, -20.0]])}
    ef = ef_init(g)
    deq, ef2 = ef_compress(g, ef, method="topk", topk_frac=0.5)
    arr = np.asarray(deq["w"])
    assert arr[0, 0] == 10.0 and arr[1, 1] == -20.0
    assert arr[0, 1] == 0.0 and arr[1, 0] == 0.0
    # dropped mass retained in the error buffer
    np.testing.assert_allclose(np.asarray(ef2["w"]),
                               [[0.0, 0.1], [0.2, 0.0]], atol=1e-6)


def test_compressed_psum_matches_mean():
    """shard_map int8 all-reduce == fp32 mean within quantization error."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    mesh = make_mesh((1,), ("x",))
    x = jnp.array([[1.0, -2.0, 3.0, 0.5]])

    f = shard_map(lambda v: compressed_psum_int8(v[0], "x")[None],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    got = f(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=2e-2,
                               atol=2e-2)


def test_int8_training_still_converges():
    """End-to-end: quadratic fit with int8-compressed grads + EF converges."""
    target = jnp.array([0.5, -1.5])
    params = {"w": jnp.zeros((2, 1))}
    cfg = adamw.OptConfig(peak_lr=0.05, warmup_steps=0, decay_steps=300,
                          weight_decay=0.0)
    state = adamw.init(params, cfg)
    ef = ef_init(params)

    def loss(p):
        return jnp.sum((p["w"][:, 0] - target) ** 2)

    for _ in range(300):
        g = jax.grad(loss)(params)
        g, ef = ef_compress(g, ef, method="int8")
        params, state, _ = adamw.update(g, state, params, cfg)
    assert float(loss(params)) < 1e-2
