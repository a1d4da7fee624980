"""QKFormer on the packed datapath: its new ops against plain references
(the QKTA kernel against the literal mask LIF, the im2col conv against
``lax.conv_general_dilated``, the OR max-pool against
``lax.reduce_window``), a tiny network bit-exact between the packed and
the float backends, its layer list against the folded tree and the
published weight count, and the route plan at published widths."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import qkformer, unified
from repro.core.qkformer import QKFormerConfig
from repro.core.spike import pack_timesteps, unpack_timesteps
from repro.infer import (ExecutionPlan, FloatBackend, PackedBackend, compile,
                         linear_layer_paths, plan_route_tables,
                         quantize_folded)
from repro.kernels import ops
from repro.serve import AsyncServeRuntime

TINY = QKFormerConfig(img_size=32, embed_dims=(48, 96, 192),
                      depths=(1, 2, 1), heads=2, num_classes=10)
HEADS = 8


def spikes(key, shape, rate):
    return jax.random.bernoulli(key, rate, shape).astype(jnp.float32)


@pytest.mark.parametrize("pallas", [True, False], ids=["kernel", "cpu"])
@pytest.mark.parametrize("dh", [24, 48, 96])
@pytest.mark.parametrize("t", [4, 16])
def test_qk_token_attention_matches_the_literal_mask_lif(t, dh, pallas):
    """The packed OR form, on the Pallas kernel (interpreted here) and on
    the CPU route, against the float QKTA whose mask neuron is a LIF run
    step by step over T (membrane carried across plane groups at T=16)."""
    d = HEADS * dh
    kq, kk = jax.random.split(jax.random.PRNGKey(dh + t))
    q = spikes(kq, (t, 2, 37, d), 1.5 / dh)         # masks both on and off
    k = spikes(kk, (t, 2, 37, d), 0.4)
    want = unified.qkta(q, k, heads=HEADS, v_th=0.5)
    got = ops.qk_token_attention(pack_timesteps(q), pack_timesteps(k),
                                 heads=HEADS, t=t, pallas=pallas)
    np.testing.assert_array_equal(np.asarray(unpack_timesteps(got, t)),
                                  np.asarray(want))
    mask = np.asarray(unified.qkta(q, jnp.ones_like(k), heads=HEADS,
                                   v_th=0.5))
    assert 0.1 < mask.mean() < 0.9


@pytest.mark.parametrize("v_th", [0.75, 1.0, 0.0])
def test_packed_qkta_refuses_a_threshold_its_or_form_cannot_hold(v_th):
    q = jnp.zeros((1, 1, 4, 16), jnp.uint8)
    with pytest.raises(ValueError, match="v_th"):
        PackedBackend(pallas=False).qkta(q, q, heads=2, t=4, v_th=v_th)


@pytest.mark.parametrize("kind, shape", [
    ("image", (2, 9, 9, 3)),
    ("spikes", (4, 2, 8, 8, 5)),
    ("spikes", (16, 1, 7, 7, 6)),
])
def test_im2col_conv_matches_lax_conv(kind, shape):
    """The 3x3 stride-1 conv as im2col rows times the (9C, F) kernel, on
    floats, and im2col of packed bytes being the packing of im2col."""
    kx, kw = jax.random.split(jax.random.PRNGKey(len(shape)))
    if kind == "image":
        x = jax.random.randint(kx, shape, 0, 256).astype(jnp.float32)
    else:
        x = spikes(kx, shape, 0.3)
    c, f = shape[-1], 4
    w = jax.random.randint(kw, (3, 3, c, f), -127, 128).astype(jnp.float32)
    rows = x.reshape(-1, *shape[-3:])
    want = lax.conv_general_dilated(
        rows, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST).reshape(*shape[:-1], f)
    got = unified.im2col(x) @ w.reshape(-1, f)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if kind == "spikes":
        t = shape[0]
        packed = unified.im2col(pack_timesteps(x))
        np.testing.assert_array_equal(
            np.asarray(unpack_timesteps(packed, t)),
            np.asarray(unified.im2col(x)))


@pytest.mark.parametrize("t, side", [(4, 8), (4, 7), (16, 10)])
def test_or_max_pool_matches_reduce_window(t, side):
    x = spikes(jax.random.PRNGKey(side), (t, 2, side, side, 3), 0.2)
    want = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3, 1),
                             (1, 1, 2, 2, 1),
                             ((0, 0), (0, 0), (1, 1), (1, 1), (0, 0)))
    packed = PackedBackend(pallas=False).max_pool(pack_timesteps(x))
    np.testing.assert_array_equal(np.asarray(unpack_timesteps(packed, t)),
                                  np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(FloatBackend().max_pool(x)), np.asarray(want))


@functools.cache
def tiny_params():
    return qkformer.init(jax.random.PRNGKey(0), TINY, bn_bias=1.0,
                         q_bias=0.8)


def tiny_images(n=4):
    return jax.random.randint(jax.random.PRNGKey(1), (n, 32, 32, 3), 0, 256,
                              jnp.uint8)


def plan(backend, weight_dtype, **kw):
    opts = {"interpret": True} if backend == "packed_pallas" else {}
    return ExecutionPlan(backend=backend, weight_dtype=weight_dtype,
                         batch_buckets=(4,), backend_options=opts, **kw)


@pytest.mark.parametrize("backend, weight_dtype", [
    ("packed", "float32"), ("packed", "int8"), ("packed_pallas", "int8")])
def test_tiny_qkformer_packed_is_bit_exact_to_the_float_backend(
        backend, weight_dtype):
    """Every op of the network on packed bytes (the CPU route, and the
    Pallas kernels interpreted) gives the float reference backend's logits
    bit for bit under the same routes, and they differ between images.
    (The Pallas unpack-dot is exact for integer weights only.)"""
    img = tiny_images()
    got = compile(tiny_params(), TINY, plan(backend, weight_dtype))
    ref = compile(tiny_params(), TINY, plan("reference", weight_dtype,
                                            routes=got.plan.routes))
    want = np.asarray(ref.logits(img))
    np.testing.assert_array_equal(np.asarray(got.logits(img)), want)
    assert np.abs(want[0] - want[1:]).max() > 0


def test_tiny_qkformer_serves_through_the_async_runtime():
    model = compile(tiny_params(), TINY, plan("packed", "int8"))
    img = np.asarray(tiny_images(6))
    want = np.asarray(model.classify(img))
    with AsyncServeRuntime(model) as rt:
        reqs = [rt.submit(img[i:i + 3]) for i in (0, 3)]
        got = np.concatenate([r.result(timeout=120) for r in reqs])
    np.testing.assert_array_equal(got, want)


def test_layer_list_names_every_folded_layer_and_scope():
    """The folded tree holds exactly the layer list's matmuls, each kernel
    (K, N) as listed, and the jitted forward names every layer's scope."""
    from repro.infer.quant import folded_layers

    folded = qkformer.fold_inference_params(tiny_params(), TINY)
    specs = {s.path: s for s in qkformer.layer_specs(TINY) if s.matmul}
    got = {p: tuple(layer["kernel"].shape)
           for p, layer in folded_layers(folded)}
    assert got == {p: (s.k, s.n) for p, s in specs.items()}
    assert linear_layer_paths(TINY) == list(specs)
    model = compile(tiny_params(), TINY, plan("packed", "int8"))
    text = model._fwd.lower(
        model.folded, jnp.zeros(model.input_shape(4), jnp.uint8)
    ).as_text(debug_info=True)
    for path in qkformer.layer_paths(TINY) + ["head"]:
        assert f"/{path}/" in text, path
    for path in ("stage1/embed/conv0", "stage1/b0/qkta/mask",
                 "stage2/b1/mlp/fc1", "stage3/b0/ssa/stdp"):
        assert path in qkformer.layer_paths(TINY)


def test_weight_count_is_the_published_one():
    """64.96 M parameters (arXiv:2403.16552, HST-10-768): the convs,
    linears and head, within 1% (batch norms fold away)."""
    params = jax.eval_shape(lambda: qkformer.init(jax.random.PRNGKey(0),
                                                  QKFormerConfig()))
    kernels = sum(int(np.prod(x.shape)) for path, x in
                  jax.tree_util.tree_flatten_with_path(params)[0]
                  if "'bn'" not in jax.tree_util.keystr(path))
    assert kernels == 64_804_360
    assert abs(kernels / 64.96e6 - 1) < 0.01


@pytest.mark.parametrize("batch_size", [1, 8, 16, 32])
def test_published_widths_route_every_layer_to_the_dot(batch_size):
    """Under the v5e-fitted Pallas constants every QKFormer linear and conv,
    the 27-row image conv and the 6912-row stage-3 conv among them, takes
    the unpack-dot and no table is built."""
    cfg = QKFormerConfig()
    tree = jax.eval_shape(lambda: quantize_folded(
        qkformer.fold_inference_params(qkformer.init(jax.random.PRNGKey(0),
                                                     cfg), cfg)))
    tree, routes = plan_route_tables(tree, cfg, batch_size=batch_size,
                                     build_tables=False, pallas=True)
    assert sorted(routes) == sorted(linear_layer_paths(cfg))
    assert len(routes) == 67 and set(routes.values()) == {"unpack"}


def test_a_tree_of_another_network_is_refused():
    from repro.core.spikformer import SpikformerConfig
    folded = qkformer.fold_inference_params(tiny_params(), TINY)
    with pytest.raises(ValueError, match="layer list"):
        plan_route_tables(folded, SpikformerConfig().scaled(), batch_size=2)
    with pytest.raises(TypeError, match="no network"):
        compile(tiny_params(), object())
