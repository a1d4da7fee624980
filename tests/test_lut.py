"""Byte-LUT packed matmul: bit-exactness of the unpack-free route.

Contract under test (see kernels/lut_matmul.py):
  * int8 weights — every partial sum is an exact small integer, so the LUT
    route must equal the unpack route (and the float emulation) bit for bit.
  * float32 weights — float sums are not reorderable, so the LUT route is
    held bit-exact against its *fold-order oracle* ``lut_matmul_planes``
    (what FloatBackend executes for LUT-planned layers), and allclose
    against the single-dot unpack route.
  * STDP — binary q/k/v make every accumulator an exact integer: LUT ==
    unpack bitwise regardless of order.
  * tail bits — at awkward T (1, 9, 17) the planes past T-1 are all-zero
    bytes and must stay invisible to every route.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spike import (num_plane_groups, pack_timesteps,
                              unpack_timesteps, space_to_depth)
from repro.core.spikformer import SpikformerConfig, init
from repro.infer import (ExecutionPlan, FloatBackend, PackedBackend,
                         compile as infer_compile)
from repro.infer.compile import plan_route_tables
from repro.core.spikformer import fold_inference_params
from repro.infer.quant import quantize_layer
from repro.kernels import ops
from repro.kernels import lut_matmul as lut

AWKWARD_TS = [1, 9, 17]
# the Pallas cost model's constants before the v5e fit: a gathered element
# at 2 dot FMAs, cheap enough that the gather wins where its table fits
CHEAP_PALLAS_GATHER = lut.RouteConstants(pallas_gather_cost=2.0,
                                         pallas_dot_cost=1.0)


def exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def bern(key, shape, p=0.35):
    return (jax.random.uniform(key, shape) < p).astype(jnp.float32)


def int8_w(key, shape):
    return jax.random.randint(key, shape, -127, 128, jnp.int8)


# ---------------------------------------------------------------------------
# primitives: bit transpose, plane indices, table build
# ---------------------------------------------------------------------------

def test_bit_transpose8_matches_naive_and_is_involution():
    b = jax.random.randint(jax.random.PRNGKey(0), (5, 3, 8), 0, 256,
                           jnp.uint8)
    got = np.asarray(lut.bit_transpose8(b))
    bb = np.asarray(b)
    want = np.zeros_like(bb)
    for j in range(8):
        for i in range(8):
            want[..., j] |= (((bb[..., i] >> j) & 1) << i).astype(np.uint8)
    np.testing.assert_array_equal(got, want)
    exact(lut.bit_transpose8(lut.bit_transpose8(b)), b)


@pytest.mark.parametrize("t", AWKWARD_TS)
@pytest.mark.parametrize("k", [5, 8, 19])
def test_plane_indices_bit_layout_and_dead_planes(t, k):
    """idx[p, ..., c] bit i == spike at plane p of input 8c+i; planes past
    t-1 are all-zero bytes (the tail-bit invariant carried through the
    transpose)."""
    s = bern(jax.random.PRNGKey(1), (t, 3, k))
    packed = pack_timesteps(s)                  # (G, 3, k)
    idx = lut.plane_indices(packed)             # (G*8, 3, C)
    g, c = num_plane_groups(t), lut.num_k_chunks(k)
    assert idx.shape == (g * 8, 3, c) and idx.dtype == jnp.uint8
    sn = np.asarray(s, np.uint8)
    got = np.asarray(idx)
    for p in range(g * 8):
        for cc in range(c):
            for i in range(8):
                kk = 8 * cc + i
                want = sn[p, :, kk] if (p < t and kk < k) else 0
                np.testing.assert_array_equal((got[p, :, cc] >> i) & 1, want)
    assert not got[t:].any(), "dead planes must stay all-zero bytes"


def test_build_lut_entries_are_chunk_subset_sums_int8():
    w = int8_w(jax.random.PRNGKey(2), (19, 6))
    tbl = lut.build_lut(w)
    assert tbl.dtype == jnp.int16
    assert tbl.shape == (3, 256, 6)
    wn = np.asarray(w, np.int32)
    wn = np.concatenate([wn, np.zeros((5, 6), np.int32)])   # pad K -> 24
    for c in range(3):
        for b in (0, 1, 0x80, 0xA5, 0xFF):
            want = sum(((b >> i) & 1) * wn[8 * c + i] for i in range(8))
            np.testing.assert_array_equal(np.asarray(tbl)[c, b], want)


def test_lut_matmul_block_n_tiling_is_exact():
    key = jax.random.PRNGKey(3)
    idx = jax.random.randint(key, (4, 7, 5), 0, 256, jnp.uint8)
    w = jax.random.normal(key, (40, 33))
    tbl = lut.build_lut(w)
    exact(lut.lut_matmul(idx, tbl),
          lut.lut_matmul(idx, tbl, block_n=8))


# ---------------------------------------------------------------------------
# per-dataflow route parity at awkward T
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", AWKWARD_TS)
def test_wssl_lut_int8_bit_exact_vs_unpack(t):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    s = bern(ks[0], (t, 2, 6, 21))
    w = int8_w(ks[1], (21, 9))
    b = jax.random.normal(ks[2], (9,))
    p = pack_timesteps(s)
    exact(ops.spike_linear(p, w, b, t=t, route="lut"),
          ops.spike_linear(p, w, b, t=t, route="unpack"))


@pytest.mark.parametrize("t", AWKWARD_TS)
def test_wssl_lut_float32_bit_exact_vs_fold_oracle(t):
    """Float32: the LUT gather must replay lut_matmul_planes' reduction tree
    bit for bit (and track the single-dot unpack route to float tolerance —
    same subset sums, different association)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    s = bern(ks[0], (t, 2, 6, 21))
    w = jax.random.normal(ks[1], (21, 9))
    p = pack_timesteps(s)
    got = ops.spike_linear(p, w, None, t=t, route="lut")
    planes = s.reshape(t, 12, 21)
    want = lut.lut_matmul_planes(planes, w).reshape(t, 2, 6, 9)
    exact(got, want)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ops.spike_linear(p, w, None, t=t, route="unpack")),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", AWKWARD_TS)
def test_zsc_lut_int8_bit_exact_vs_unpack(t):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    s = bern(ks[0], (t, 2, 6, 6, 3))
    w = int8_w(ks[1], (12, 7))
    p = space_to_depth(pack_timesteps(s), 2)
    exact(ops.spike_linear(p, w, None, t=t, route="lut"),
          ops.spike_linear(p, w, None, t=t, route="unpack"))


def test_sssc_lut_int8_bit_exact_vs_unpack():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    img = jax.random.randint(ks[0], (2, 6, 6, 3), 0, 256, jnp.uint8)
    w = int8_w(ks[1], (12, 5))
    b = jax.random.normal(ks[2], (5,))
    x = space_to_depth(img, 2)
    exact(ops.sssc_linear(x, w, b, route="lut"),
          ops.sssc_linear(x, w, b, route="unpack"))


def test_sssc_lut_float32_bit_exact_vs_fold_oracle():
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    img = jax.random.randint(ks[0], (2, 6, 6, 3), 0, 256, jnp.uint8)
    w = jax.random.normal(ks[1], (12, 5))
    x = space_to_depth(img, 2)
    got = ops.sssc_linear(x, w, None, route="lut")
    want = FloatBackend._sssc_emu(img, w)
    exact(got, want)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ops.sssc_linear(x, w, None, route="unpack")),
        rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("t", AWKWARD_TS)
def test_stdp_lut_bit_exact_vs_unpack(t):
    """Binary q/k/v: every score and context value is an exact integer, so
    the LUT score path equals the einsum path bitwise at any T."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = [bern(kk, (t, 1, 2, 12, 16)) for kk in ks]
    qp, kp, vp = pack_timesteps(q), pack_timesteps(k), pack_timesteps(v)
    exact(ops.stdp_attention_packed(qp, kp, vp, t=t, scale=0.25,
                                    route="lut"),
          ops.stdp_attention_packed(qp, kp, vp, t=t, scale=0.25,
                                    route="unpack"))


@pytest.mark.parametrize("t", AWKWARD_TS)
def test_pack_roundtrip_and_tail_zero_awkward_t(t):
    """pack/unpack round-trip at T in {1, 9, 17} and the last-group zero-bit
    invariant the LUT transpose relies on."""
    s = bern(jax.random.PRNGKey(10), (t, 4, 9), 0.5)
    p = pack_timesteps(s)
    g = num_plane_groups(t)
    assert p.shape == (g, 4, 9)
    exact(unpack_timesteps(p, t), s)
    live_last = t - 8 * (g - 1)
    if live_last < 8:
        assert int(jnp.max(p[g - 1] >> live_last)) == 0


# ---------------------------------------------------------------------------
# int8 scale-folded LIF through the LUT route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [1, 9])
def test_wssl_lif_int8_lut_table_matches_float_emulation(t):
    """The planner's cached int16 table through the full matmul+LIF stage ==
    FloatBackend's scale-folded emulation, bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    s = bern(ks[0], (t, 2, 6, 16))
    q = quantize_layer({"kernel": jax.random.normal(ks[1], (16, 8)),
                        "bias": jax.random.normal(ks[2], (8,))})
    table = lut.build_lut(q["kernel"])
    got = PackedBackend().wssl_lif(pack_timesteps(s), q["kernel"], q["bias"],
                                   t=t, scale=q["scale"], lut=table)
    want = pack_timesteps(FloatBackend().wssl_lif(
        s, q["kernel"], q["bias"], t=t, scale=q["scale"], lut=table))
    exact(got, want)


@pytest.mark.parametrize("t", [4, 9])
def test_popcount_rate_matches_float_reference(t):
    s = bern(jax.random.PRNGKey(12), (t, 3, 5, 7), 0.5)
    exact(PackedBackend().rate(pack_timesteps(s), t=t),
          FloatBackend().rate(s, t=t))


# ---------------------------------------------------------------------------
# dispatch heuristic + planner
# ---------------------------------------------------------------------------

def test_choose_route_respects_table_cap():
    assert ops.choose_route(m=512, k=64, n=64, g=1, t=4,
                            max_table_bytes=1024) == "unpack"


def test_choose_route_picks_lut_at_bench_layer_shapes():
    # the encoder linears and conv stem of the benchmark config
    for m, k, n in [(32, 64, 256), (512, 32, 16), (2048, 12, 8)]:
        assert ops.choose_route(m=m, k=k, n=n, g=1, t=4) == "lut", (m, k, n)


def _compiled(params, cfg, *, backend="packed", batch_size=2,
              weight_dtype=None, route="auto", folded=False, pallas=None,
              jit=True, route_constants=lut.DEFAULT_ROUTE_CONSTANTS):
    """One-bucket compile() under the historical session argument names —
    keeps the parity tests reading like serving call sites."""
    options = {} if pallas is None else {"pallas": pallas}
    plan = ExecutionPlan(backend=backend, weight_dtype=weight_dtype,
                         batch_buckets=(int(batch_size),), route=route,
                         backend_options=options,
                         route_constants=route_constants)
    return infer_compile(params, cfg, plan, folded=folded, jit=jit)


def test_plan_routes_annotates_tables_and_paths():
    cfg = SpikformerConfig().scaled()
    params = init(jax.random.PRNGKey(0), cfg)
    folded = fold_inference_params(params, cfg)
    tree, plan = plan_route_tables(folded, cfg, batch_size=2)
    assert set(plan) >= {"scs/conv0", "blocks/b0/mlp/fc1"}
    for path, route in plan.items():
        parts = path.split("/")
        layer = tree
        for p in parts:
            layer = layer[p]
        if route == "lut":
            k, n = layer["kernel"].shape
            assert layer["lut"].shape == (lut.num_k_chunks(k), 256, n)
            assert layer["lut"].dtype == jnp.float32
        else:
            assert "lut" not in layer
    # the original tree is not mutated
    assert "lut" not in folded["scs"]["conv0"]


# ---------------------------------------------------------------------------
# end-to-end at awkward T: the acceptance property under the new route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,weight_dtype", [(1, "float32"), (9, "int8"),
                                            (17, "float32"), (9, "float32"),
                                            (17, "int8")])
def test_compiled_lut_planned_parity_awkward_t(t, weight_dtype):
    """Packed (LUT-planned) logits == reference logits bit for bit at
    T in {1, 9, 17} — the last-group zero-bit invariant under the new route,
    end to end through all four dataflows."""
    cfg = dataclasses.replace(SpikformerConfig().scaled(), timesteps=t)
    params = init(jax.random.PRNGKey(0), cfg)
    img = jax.random.randint(jax.random.PRNGKey(1), (2, 32, 32, 3), 0, 256,
                             jnp.uint8)
    packed = _compiled(params, cfg, backend="packed",
                       weight_dtype=weight_dtype)
    ref = _compiled(params, cfg, backend="reference",
                    weight_dtype=weight_dtype)
    assert any(r == "lut" for r in packed.plan.routes.values())
    exact(packed.logits(img), ref.logits(img))


def test_compiled_route_unpack_pins_oracle_route():
    """route='unpack' disables planning; for int8 weights the two routes are
    bit-identical end to end (exact integer accumulators), which pins the
    LUT route against the legacy oracle through the whole network."""
    cfg = SpikformerConfig().scaled()
    params = init(jax.random.PRNGKey(0), cfg)
    img = jax.random.randint(jax.random.PRNGKey(1), (2, 32, 32, 3), 0, 256,
                             jnp.uint8)
    auto = _compiled(params, cfg, backend="packed", weight_dtype="int8")
    pinned = _compiled(params, cfg, backend="packed", weight_dtype="int8",
                       route="unpack")
    assert pinned.plan.routes == {} and \
        any(r == "lut" for r in auto.plan.routes.values())
    exact(auto.logits(img), pinned.logits(img))


def test_compiled_rejects_unknown_route():
    cfg = SpikformerConfig().scaled()
    params = init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="route"):
        _compiled(params, cfg, route="fused")


def test_route_unpack_strips_stale_lut_annotations():
    """A pre-annotated folded tree through route='unpack' must actually run
    the unpack route — stale 'lut' leaves would silently keep the LUT route
    alive and break the documented pin."""
    cfg = SpikformerConfig().scaled()
    params = init(jax.random.PRNGKey(0), cfg)
    img = jax.random.randint(jax.random.PRNGKey(1), (2, 32, 32, 3), 0, 256,
                             jnp.uint8)
    auto = _compiled(params, cfg, backend="packed")
    pinned = _compiled(auto.folded, cfg, folded=True, backend="packed",
                       route="unpack")

    def lut_leaves(tree):
        found = []
        jax.tree_util.tree_map_with_path(
            lambda p, _: found.append(p) if "lut" in str(p) else None, tree)
        return found

    assert lut_leaves(auto.folded) and not lut_leaves(pinned.folded)
    fresh = _compiled(params, cfg, backend="packed", route="unpack")
    exact(pinned.logits(img), fresh.logits(img))


def test_reference_skips_and_pallas_builds_tables():
    """The table capability follows who gathers: the float reference never
    does (its LUT layers carry a cheap boolean plan flag), while a
    Pallas-pinned packed model DOES — its byte-LUT kernel gathers the
    (C,256,N) tables from VMEM, so planning must build them. The v5e
    defaults route every layer to the dot, so the Pallas side prices the
    gather cheap enough to pick it."""
    cfg = SpikformerConfig().scaled()
    params = init(jax.random.PRNGKey(0), cfg)
    ref = _compiled(params, cfg, backend="reference")
    pal = _compiled(params, cfg, backend="packed", pallas=True, jit=False,
                    route_constants=CHEAP_PALLAS_GATHER)

    def lut_layers(model):
        for path, route in model.plan.routes.items():
            if route == "lut":
                layer = model.folded
                for p in path.split("/"):
                    layer = layer[p]
                yield layer

    seen = 0
    for layer in lut_layers(ref):
        assert layer["lut"] is True            # flag, never a table
        seen += 1
    assert seen
    seen = 0
    for layer in lut_layers(pal):
        assert layer["lut"].ndim == 3          # a real gather table
        assert layer["lut"].shape[1] == 256
        seen += 1
    assert seen


def test_compare_bench_gate():
    import sys
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent
                           / "benchmarks"))
    import compare_bench

    def rec(points, exact_ok=True):
        return {"bit_exact": exact_ok,
                "sweep": [{"timesteps": t, "weight_dtype": wd,
                           "packed_speedup": s} for t, wd, s in points]}

    base = rec([(4, "float32", 1.0), (16, "int8", 2.0)])
    # healthy: geomean of (0.9, 1.1) ~ 1.0
    assert compare_bench.compare(
        rec([(4, "float32", 0.9), (16, "int8", 2.2)]), base,
        min_ratio=0.4) == []
    # cliff: every point halves -> geomean 0.25 < 0.4
    assert compare_bench.compare(
        rec([(4, "float32", 0.25), (16, "int8", 0.5)]), base,
        min_ratio=0.4)
    # bit-exactness is a hard gate
    assert compare_bench.compare(
        rec([(4, "float32", 1.0)], exact_ok=False), base, min_ratio=0.4)
    # zero overlapping points must fail loudly, not pass silently
    assert compare_bench.compare(
        rec([(8, "float32", 1.0)]), base, min_ratio=0.4)
