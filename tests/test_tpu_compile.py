"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each kernel is lowered with ``interpret=False`` at the published
Spikformer-8-512 widths (bucket 8 at 224 px: M = 8 x 196 token rows, dim
512, hidden 2048) and compiled by Mosaic for a *described* v5e chip, so
what the chip's compiler refuses fails here, without a chip. The topology
is described inside a fixture (never at import: only one process may load
the TPU library), and the persistent compilation cache is off around these
compiles (an entry compiled for a described chip cannot be read back here).

QKFormer HST-10-768's shapes are compiled too: its QKTA kernel at the
token counts and widths of stages 1 and 2, and the 3x3 convs whose
im2col rows (27, 864) fill no lane tile, at bucket 16.

The file also pins the single interpret decision
(``kernels.device.resolve_interpret``) and the planner rules at the
published widths: the fused MLP kernel never runs, and the v5e-fitted
Pallas cost model sends every linear layer to the unpack-dot.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.spikformer_v2 import CONFIG, CONFIG_T16
from repro.core.spikformer import fold_inference_params, init
from repro.infer import get_backend, plan_route_tables, quantize_folded
from repro.kernels import device, fused
from repro.kernels.fused import tflif_lut_matmul
from repro.kernels.qk_token_attention import qk_token_attention
from repro.kernels.lut_matmul import RouteConstants, table_bytes
from repro.kernels.spike_matmul import lut_gather_matmul, spike_matmul
from repro.kernels.stdp_attention import stdp_attention
from repro.kernels.tflif import tflif_fused

M = 8 * CONFIG.tokens                       # bucket-8 token rows
DIM = CONFIG.dim
HIDDEN = CONFIG.dim * CONFIG.mlp_ratio
STEM_ROWS = 8 * (CONFIG.img_size // 2) ** 2     # conv0 output pixels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def compile_for_chip(sharding, fn, *specs):
    """Lower ``fn`` over (shape, dtype) specs on the described chip and
    compile it; the result must contain a Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("groups,k,n", [
    (1, DIM, HIDDEN),          # T=4 fc1
    (1, HIDDEN, DIM),          # T=4 fc2
    (2, DIM, HIDDEN),          # T=16: two plane groups
    (1, 4 * CONFIG.scs_channels[2], DIM),   # conv3: 2x2 patches of 256 ch
])
def test_spike_matmul_per_plane_compiles(one_chip, groups, k, n):
    compile_for_chip(
        one_chip,
        lambda x, w: spike_matmul(x, w, mode="per_plane", interpret=False),
        ((groups, M, k), jnp.uint8), ((k, n), jnp.float32))


def test_spike_matmul_shift_sum_compiles_at_stem(one_chip):
    k = 2 * 2 * CONFIG.in_channels
    compile_for_chip(
        one_chip,
        lambda x, w: spike_matmul(x, w, mode="shift_sum", interpret=False),
        ((STEM_ROWS, k), jnp.uint8), ((k, CONFIG.scs_channels[0]),
                                      jnp.float32))


@pytest.mark.parametrize("mode,x,n", [
    ("shift_sum", (16 * 224 * 224, 27), 96),      # QKFormer image conv
    ("per_plane", (1, 16 * 112 * 112, 864), 192),  # SPEDS-1 conv1
])
def test_spike_matmul_compiles_at_qkformer_convs(one_chip, mode, x, n):
    compile_for_chip(
        one_chip,
        lambda x, w: spike_matmul(x, w, mode=mode, interpret=False),
        (x, jnp.uint8), ((x[-1], n), jnp.float32))


@pytest.mark.parametrize("rows,d,planes", [
    (16 * 3136, 192, 4),        # stage 1, T=4
    (16 * 784, 384, 4),         # stage 2, T=4
    (2 * 784, 384, 8),          # a full plane group
])
def test_qk_token_attention_compiles(one_chip, rows, d, planes):
    compile_for_chip(
        one_chip,
        lambda q, k: qk_token_attention(q, k, heads=8, planes=planes,
                                        interpret=False),
        ((rows, d), jnp.uint8), ((rows, d), jnp.uint8))


@pytest.mark.parametrize("planes,rows,chunks,n,dtype", [
    (4, M, DIM // 8, DIM, jnp.int16),            # q/k/v/wo, int8 weights
    (16, M, DIM // 8, DIM, jnp.int16),           # q/k/v/wo at T=16
    (4, M, DIM // 8, DIM, jnp.float32),          # q/k/v/wo, f32 weights
    (8, STEM_ROWS, 2, 64, jnp.int16),            # conv0 (SSSC value planes)
    (4, 8 * 56 * 56, 32, 128, jnp.int16),        # conv1
    (4, 8 * 28 * 28, 64, 256, jnp.int16),        # conv2
])
def test_lut_gather_matmul_compiles(one_chip, planes, rows, chunks, n, dtype):
    compile_for_chip(
        one_chip, lambda i, t: lut_gather_matmul(i, t, interpret=False),
        ((planes, rows, chunks), jnp.uint8), ((chunks, 256, n), dtype))


@pytest.mark.parametrize("t,neurons", [
    (4, M * HIDDEN),                             # fc1 output, T=4
    (16, M * DIM),                               # T=16, two groups
    (4, STEM_ROWS * CONFIG.scs_channels[0]),     # conv0 output
])
def test_tflif_fused_compiles(one_chip, t, neurons):
    compile_for_chip(
        one_chip,
        lambda x, b, v: tflif_fused(x, b, v_th=v, interpret=False),
        ((t, neurons), jnp.float32), ((neurons,), jnp.float32),
        ((neurons,), jnp.float32))


def test_stdp_attention_compiles(one_chip):
    bh = 4 * 8 * CONFIG.heads              # T x batch x heads
    dh = DIM // CONFIG.heads
    spec = ((bh, CONFIG.tokens, dh), jnp.float32)
    compile_for_chip(
        one_chip,
        lambda q, k, v: stdp_attention(q, k, v, scale=CONFIG.attn_scale,
                                       interpret=False),
        spec, spec, spec)


def test_fused_mlp_compiles_at_its_largest_table(one_chip):
    # the widest fc2 table the fused kernel serves: C * 256 * N int16 bytes
    # == MAX_TABLE_BYTES
    k, n = 256, 256
    assert table_bytes(k, n, True) == fused.MAX_TABLE_BYTES
    compile_for_chip(
        one_chip,
        lambda x, b, t, v: tflif_lut_matmul(x, b, t, v_th=v,
                                            interpret=False),
        ((4, M, k), jnp.float32), ((k,), jnp.float32),
        ((k // 8, 256, n), jnp.int16), ((k,), jnp.float32))


PUBLISHED = {"T4": CONFIG, "T16": CONFIG_T16}


@functools.cache
def _published_folded(name):
    """Shapes of the int8 folded tree of a published configuration."""
    cfg = PUBLISHED[name]
    return jax.eval_shape(lambda: quantize_folded(
        fold_inference_params(init(jax.random.PRNGKey(0), cfg), cfg)))


@pytest.mark.parametrize("name", PUBLISHED)
def test_published_widths_never_route_the_fused_mlp(name):
    """At the published widths fc2's byte-LUT table is past the planner's
    cap, so it gets no table, and the fused MLP kernel (which needs one,
    and keeps it VMEM-resident) never runs."""
    cfg = PUBLISHED[name]
    tree, routes = plan_route_tables(_published_folded(name), cfg,
                                     batch_size=8, build_tables=False,
                                     pallas=True)
    for i in range(cfg.depth):
        fc2 = tree["blocks"][f"b{i}"]["mlp"]["fc2"]
        assert routes[f"blocks/b{i}/mlp/fc2"] == "unpack"
        assert "lut" not in fc2
    assert table_bytes(HIDDEN, DIM, True) > fused.MAX_TABLE_BYTES


@pytest.mark.parametrize("batch_size", [1, 2, 4, 8, 32])
@pytest.mark.parametrize("name", PUBLISHED)
def test_published_widths_route_every_layer_to_the_dot(name, batch_size):
    """Under the v5e-fitted Pallas constants the one-hot gather costs far
    more than the 8 K-rows of dot it replaces, so at every bucket the
    benchmark serves, every linear layer of both published configurations
    takes the unpack-dot and no table is built."""
    cfg = PUBLISHED[name]
    tree, routes = plan_route_tables(_published_folded(name), cfg,
                                     batch_size=batch_size,
                                     build_tables=False, pallas=True)
    assert routes and set(routes.values()) == {"unpack"}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert not [p for p, _ in leaves if "'lut'" in jax.tree_util.keystr(p)]


@pytest.mark.parametrize("name", PUBLISHED)
def test_cheap_gather_constants_still_route_attention_to_the_lut(name):
    """The cost model still decides: priced at the pre-fit 2 FMAs a
    selected element, the int8 q/k/v/wo layers (16 MiB tables, at the cap)
    go back to the gather, while fc2 (past the cap) stays on the dot."""
    cfg = PUBLISHED[name]
    cheap = RouteConstants(pallas_gather_cost=2.0, pallas_dot_cost=1.0)
    _, routes = plan_route_tables(_published_folded(name), cfg,
                                  batch_size=8, build_tables=False,
                                  constants=cheap, pallas=True)
    for i in range(cfg.depth):
        for w in ("wq", "wk", "wv", "wo"):
            assert routes[f"blocks/b{i}/ssa/{w}"] == "lut"
        assert routes[f"blocks/b{i}/mlp/fc2"] == "unpack"


def test_interpret_is_decided_once(monkeypatch):
    monkeypatch.setattr(device, "on_tpu", lambda: False)
    assert device.resolve_interpret() is True
    assert device.resolve_interpret(False) is False   # AOT compiles here
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    assert device.resolve_interpret() is False
    assert device.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret"):
        device.resolve_interpret(True)


def test_registry_refuses_interpret_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret"):
        get_backend("packed_pallas", interpret=True)
    assert get_backend("packed_pallas").pallas is True
