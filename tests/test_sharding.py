"""Sharding rules: spec validity, coverage, divisibility fallbacks, and a
real sharded-vs-single-device equivalence run on a CPU mesh."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.nn import transformer as T
from repro.sharding import rules
from repro.sharding.hints import shard_hint
from repro.launch import steps
from repro.launch.mesh import make_mesh


def fake_mesh(data=4, model=2, pod=None):
    """An abstract mesh over fake devices (no allocation) for rule tests."""
    if pod:
        return AbstractMesh((pod, data, model), ("pod", "data", "model"))
    return AbstractMesh((data, model), ("data", "model"))


# AbstractMesh lacks .devices; spec_for only uses .shape/.axis_names, so this
# adapter works for rule-level tests.
class MeshShim:
    def __init__(self, am):
        self.shape = dict(am.shape)
        self.axis_names = am.axis_names


def test_spec_divisibility_fallback():
    mesh = MeshShim(fake_mesh(data=4, model=2))
    # 2nd dim 10 not divisible by model=2? it is; use 7 => must drop axis
    spec = rules.spec_for("x/wq/kernel", (12, 7), mesh)
    assert spec == P("data", None)
    spec = rules.spec_for("x/wq/kernel", (12, 8), mesh)
    assert spec == P("data", "model")


def test_multi_pod_dp_group():
    mesh = MeshShim(fake_mesh(data=4, model=2, pod=2))
    spec = rules.spec_for("a/mlp/up/kernel", (16, 8), mesh)
    assert spec == P(("pod", "data"), "model")


def test_stacked_layer_leading_dims_padded():
    mesh = MeshShim(fake_mesh())
    spec = rules.spec_for("layers/attn/wq/kernel", (8, 16, 8), mesh)
    assert spec == P(None, "data", "model")


def test_moe_expert_sharding():
    mesh = MeshShim(fake_mesh())
    spec = rules.spec_for("layers/moe/w_gate", (2, 8, 16, 8), mesh)
    assert spec == P(None, "data", None, "model")    # E over data = EP


def test_every_param_leaf_gets_a_spec():
    """No leaf may error; 2-D+ leaves of each arch should mostly shard."""
    mesh = MeshShim(fake_mesh())
    for arch in ("smollm-360m", "qwen3-moe-30b-a3b", "mamba2-130m",
                 "hymba-1.5b", "whisper-large-v3"):
        cfg = get_config(arch).reduced()
        shapes = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
        from repro.nn.module import map_with_path
        specs = []
        map_with_path(lambda p, l: specs.append(
            rules.spec_for(p, l.shape, mesh)) or l, shapes)
        assert all(isinstance(s, P) for s in specs)


def test_shard_hint_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shard_hint(x, "dp", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharded_train_step_matches_unsharded():
    """jit with explicit shardings on a 1-device mesh == plain execution
    (numerical path identity for the full train step)."""
    cfg = get_config("smollm-360m").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    batch_shapes = {
        "tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
        "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32),
    }
    ts = steps.TrainSettings(microbatch=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    from repro.optim import adamw
    opt = adamw.init(params, ts.opt)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab)}

    plain = steps.make_train_step(cfg, ts)
    p2, o2, m2 = jax.jit(plain)(params, opt, batch)

    with jax.set_mesh(mesh):
        # donate_argnums consumes params/opt — run the plain step first
        step_sharded, _, _ = steps.jit_train_step(cfg, mesh, ts, batch_shapes)
        p1, o1, m1 = step_sharded(params, opt, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)


def test_batch_and_cache_shardings_build():
    cfg = get_config("hymba-1.5b")
    mesh_real = make_mesh((1, 1), ("data", "model"))
    cache_shapes = jax.eval_shape(lambda: T.init_cache(cfg, 4, 4096))
    c_sh = rules.cache_shardings(mesh_real, cache_shapes)
    for leaf in jax.tree_util.tree_leaves(
            c_sh, is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert isinstance(leaf, NamedSharding)


# ---------------------------------------------------------------------------
# the public import surface and the serving fleet's placement axis
# ---------------------------------------------------------------------------

def test_public_import_surface():
    """``repro.sharding`` is a real public API: everything the serving
    fleet (and training) consumes is importable from the package root and
    declared in __all__."""
    import repro.sharding as sharding
    for name in ("rules", "hints", "dp_axes", "spec_for",
                 "param_shardings", "opt_state_shardings",
                 "batch_shardings", "cache_shardings", "serving_mesh",
                 "replica_devices", "shard_hint"):
        assert name in sharding.__all__, name
        assert getattr(sharding, name) is not None
    # the package re-export is the module symbol, not a copy
    assert sharding.replica_devices is rules.replica_devices
    assert sharding.spec_for is rules.spec_for


def test_serving_mesh_and_replica_devices():
    mesh = rules.serving_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == len(jax.devices())
    with pytest.raises(ValueError, match="at least one device"):
        rules.serving_mesh(devices=[])
    with pytest.raises(ValueError, match="n >= 1"):
        rules.replica_devices(0)
    devs = rules.replica_devices(3)
    assert len(devs) == 3
    if len(jax.devices()) <= 1:
        # single-device host: thread-backed fleet, no pointless device_put
        assert devs == [None, None, None]
    else:
        # replicas round-robin the data axis
        flat = list(np.asarray(mesh.devices).flat)
        assert devs == [flat[i % len(flat)] for i in range(3)]
