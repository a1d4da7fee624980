"""Spike sparsity end to end: the zero-chunk-skipping gather, the
occupancy-aware route choice, calibration, and the serving telemetry that
feeds measured occupancy back into scheduling.

Contract under test (see kernels/lut_matmul.py and infer/compile.py):

  * ``lut_matmul_sparse`` is bit-identical to the dense ``lut_matmul`` for
    EVERY input and EVERY budget — when a row's nonzero chunks exceed the
    budget the kernel falls back to the dense gather inside a ``lax.cond``,
    so a stale calibration costs throughput, never correctness. Empty
    budget slots gather ``table[0, 0, :]`` = the all-zero chunk's subset
    sum = exact zero, the same identity the dense fold adds.
  * ``choose_route`` never returns "lut_sparse" without a calibrated
    occupancy: sparsity claims must be measured, not assumed.
  * ``ExecutionPlan.layer_occupancy`` round-trips through JSON and replays
    pinned "lut_sparse" routes bit-exactly.
  * The engine/runtime measure per-step batch occupancy and the scheduler
    conditions its SLO service estimate on it.

Plus the serving-correctness regressions fixed alongside: multi-chunk SLO
budgeting, submit(rid=) conflicts, and microsecond latency reporting.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spike import pack_timesteps, structured_spikes
from repro.core.spikformer import SpikformerConfig, init
from repro.infer import (ExecutionPlan, MicroBatchEngine, OccupancyRecorder,
                         batch_occupancy, calibrate_layer_occupancy,
                         chunk_occupancy, compile as infer_compile,
                         linear_layer_paths, value_chunk_occupancy)
from repro.infer.compile import plan_chunks
from repro.infer.engine import (Request, StepAccounting, assemble_batch,
                                latency_summary)
from repro.kernels import lut_matmul as lut
from repro.kernels import ops
from repro.kernels.lut_matmul import RouteConstants
from repro.serve import (AsyncServeRuntime, ContinuousBatchingScheduler,
                         ServePolicy)

AWKWARD_TS = [1, 9, 17]


def exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def bern(key, shape, p=0.35):
    return (jax.random.uniform(key, shape) < p).astype(jnp.float32)


def int8_w(key, shape):
    return jax.random.randint(key, shape, -127, 128, jnp.int8)


@pytest.fixture(scope="module")
def small():
    cfg = SpikformerConfig().scaled(img_size=16, dim=32, depth=1)
    params = init(jax.random.PRNGKey(0), cfg)
    imgs = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (6, 16, 16, 3), 0, 256, "uint8"))
    return cfg, params, imgs


# ---------------------------------------------------------------------------
# sparse_budget: the static trace-time gather budget
# ---------------------------------------------------------------------------

def test_sparse_budget_units_and_bounds():
    # occupancy is a FRACTION of nonzero chunk-index bytes, budget a CHUNK
    # count: ceil(occ*c) plus one slack chunk for calibration jitter
    assert lut.sparse_budget(32, 0.0) == 1
    assert lut.sparse_budget(32, 0.1) == 5          # ceil(3.2) + 1
    assert lut.sparse_budget(32, 1.0) == 32         # never exceeds c
    assert lut.sparse_budget(4, 0.9) == 4
    assert lut.sparse_budget(1, 0.5) == 1
    prev = 0
    for occ in np.linspace(0.0, 1.0, 21):
        b = lut.sparse_budget(32, float(occ))
        assert 1 <= b <= 32 and b >= prev           # monotone in occupancy
        prev = b


# ---------------------------------------------------------------------------
# lut_matmul_sparse: bit-exact at every budget, for every input
# ---------------------------------------------------------------------------

def sparse_idx(key, t, m, k, rate=0.15):
    """Chunk-index planes from channel-structured spikes (some chunks all
    zero, some dense — the distribution the sparse route exists for)."""
    x = structured_spikes(key, t=t, shape=(m, k), rate=rate)
    return lut.plane_indices(x)[:t]


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_lut_matmul_sparse_every_budget_bit_exact(dtype):
    key = jax.random.PRNGKey(0)
    t, m, k = 8, 16, 64
    idx = sparse_idx(key, t, m, k)
    if dtype == "int8":
        w = int8_w(jax.random.fold_in(key, 1), (k, 9))
    else:
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, 9))
    tbl = lut.build_lut(w)
    want = lut.lut_matmul(idx, tbl)
    c = tbl.shape[0]
    for budget in range(1, c + 1):
        exact(lut.lut_matmul_sparse(idx, tbl, max_chunks=budget), want)


def test_lut_matmul_sparse_all_zero_planes():
    # the degenerate best case: every slot gathers the zero identity
    t, m, k = 8, 5, 40
    idx = jnp.zeros((t, m, lut.num_k_chunks(k)), jnp.uint8)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, 7))
    tbl = lut.build_lut(w)
    got = lut.lut_matmul_sparse(idx, tbl, max_chunks=2)
    exact(got, jnp.zeros((t, m, 7), jnp.float32))
    exact(got, lut.lut_matmul(idx, tbl))


def test_lut_matmul_sparse_single_spike_planes():
    # exactly one nonzero chunk per row: budget 1 must already be exact
    t, m, c = 4, 6, 8
    k = 8 * c
    rows = jax.random.randint(jax.random.PRNGKey(3), (t, m), 0, c)
    vals = jax.random.randint(jax.random.PRNGKey(4), (t, m), 1, 256,
                              jnp.uint8)
    idx = jnp.zeros((t, m, c), jnp.uint8).at[
        jnp.arange(t)[:, None], jnp.arange(m)[None, :], rows].set(vals)
    w = jax.random.normal(jax.random.PRNGKey(5), (k, 11))
    tbl = lut.build_lut(w)
    exact(lut.lut_matmul_sparse(idx, tbl, max_chunks=1),
          lut.lut_matmul(idx, tbl))


@pytest.mark.parametrize("t", AWKWARD_TS)
def test_spike_linear_sparse_tail_k_awkward_t(t):
    """K=21 (tail chunk live on 5 of 8 lanes) through the op-level route,
    int8 weights: sparse == dense LUT == unpack, bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    s = bern(ks[0], (t, 2, 6, 21), p=0.1)
    w = int8_w(ks[1], (21, 9))
    b = jax.random.normal(ks[2], (9,))
    p = pack_timesteps(s)
    occ = chunk_occupancy(p, t)
    got = ops.spike_linear(p, w, b, t=t, route="lut_sparse", occupancy=occ)
    exact(got, ops.spike_linear(p, w, b, t=t, route="lut"))
    exact(got, ops.spike_linear(p, w, b, t=t, route="unpack"))


def test_spike_linear_sparse_float32_matches_fold_oracle():
    t, m, k, n = 8, 12, 64, 9
    key = jax.random.PRNGKey(7)
    x = structured_spikes(key, t=t, shape=(m, k), rate=0.15)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    occ = chunk_occupancy(x, t)
    got = ops.spike_linear(x, w, None, t=t, route="lut_sparse",
                           occupancy=occ)
    exact(got, ops.spike_linear(x, w, None, t=t, route="lut"))
    from repro.core.spike import unpack_timesteps
    planes = unpack_timesteps(x, t)
    exact(got, lut.lut_matmul_planes(planes, w))


def test_sssc_linear_sparse_route_parity():
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    x = jax.random.randint(ks[0], (5, 24), 0, 4, jnp.uint8)  # dark pixels
    w = int8_w(ks[1], (24, 7))
    occ = value_chunk_occupancy(x)
    exact(ops.sssc_linear(x, w, None, route="lut_sparse", occupancy=occ),
          ops.sssc_linear(x, w, None, route="lut"))


def test_lut_matmul_sparse_block_n_tiling_is_exact():
    idx = sparse_idx(jax.random.PRNGKey(9), 8, 7, 40)
    w = jax.random.normal(jax.random.PRNGKey(10), (40, 33))
    tbl = lut.build_lut(w)
    exact(lut.lut_matmul_sparse(idx, tbl, max_chunks=2, block_n=8),
          lut.lut_matmul_sparse(idx, tbl, max_chunks=2))


def test_sparse_path_actually_executes():
    """Guard against the sparse route silently degenerating into the dense
    gather: under budget the lowering must carry the runtime nnz check
    (a ``cond``), and at full budget it must NOT (plain dense gather)."""
    idx = sparse_idx(jax.random.PRNGKey(11), 8, 4, 32)
    tbl = lut.build_lut(jax.random.normal(jax.random.PRNGKey(12), (32, 5)))
    sparse = str(jax.make_jaxpr(
        lambda i: lut.lut_matmul_sparse(i, tbl, max_chunks=2))(idx))
    dense = str(jax.make_jaxpr(
        lambda i: lut.lut_matmul_sparse(i, tbl,
                                        max_chunks=tbl.shape[0]))(idx))
    assert "cond" in sparse
    assert "cond" not in dense


# ---------------------------------------------------------------------------
# choose_route: occupancy-aware dispatch
# ---------------------------------------------------------------------------

def test_choose_route_requires_measured_occupancy():
    shape = dict(m=512, k=256, n=256, g=1, t=8)
    # no calibration -> sparsity is never assumed
    assert lut.choose_route(**shape) != "lut_sparse"
    # calibrated low occupancy on a cache-spilling shape: sparse wins
    assert lut.choose_route(**shape, occupancy=0.05) == "lut_sparse"
    # near-dense traffic leaves no budget headroom -> same as uncalibrated
    assert lut.choose_route(**shape, occupancy=0.95) == \
        lut.choose_route(**shape)


def test_choose_route_sparse_loses_when_compaction_dominates():
    # tiny N: the N-independent compaction term swamps the gather saving
    shape = dict(m=64, k=32, n=16, g=1, t=8)
    assert lut.choose_route(**shape, occupancy=0.4) != "lut_sparse"


def test_ops_resolve_route_guards():
    x = structured_spikes(jax.random.PRNGKey(13), t=8, shape=(4, 32),
                          rate=0.1)
    w = jax.random.normal(jax.random.PRNGKey(14), (32, 5))
    with pytest.raises(ValueError, match="occupancy"):
        ops.spike_linear(x, w, None, t=8, route="lut_sparse")


# ---------------------------------------------------------------------------
# ExecutionPlan: layer_occupancy as data
# ---------------------------------------------------------------------------

def test_plan_layer_occupancy_json_roundtrip_and_validation():
    occ = {"scs/conv0": 0.12, "blocks/b0/mlp/fc1": 0.4}
    p = ExecutionPlan(batch_buckets=(2,), layer_occupancy=occ)
    q = ExecutionPlan.from_json(p.to_json())
    assert q.layer_occupancy == occ
    assert q == p
    with pytest.raises(ValueError, match="occupancy"):
        ExecutionPlan(layer_occupancy={"scs/conv0": 1.5})
    with pytest.raises(ValueError, match="occupancy"):
        ExecutionPlan(layer_occupancy={"scs/conv0": -0.1})


def test_calibrate_layer_occupancy_covers_every_linear(small):
    cfg, params, imgs = small
    occ = calibrate_layer_occupancy(params, cfg, imgs[:2])
    assert sorted(occ) == sorted(linear_layer_paths(cfg))
    assert all(0.0 <= v <= 1.0 for v in occ.values())
    # the recorder trace it is built from has one sample per linear
    rec = OccupancyRecorder()
    assert rec.trace == []


def sparse_plan(paths, *, weight_dtype="int8"):
    """A plan that routes every calibrated layer sparse: low calibrated
    occupancy + constants that make the compaction free and the unpack
    route prohibitive, so the cost model picks "lut_sparse" wherever a
    budget exists. Correctness never depends on these being realistic."""
    return ExecutionPlan(
        batch_buckets=(2,), weight_dtype=weight_dtype,
        route_constants=RouteConstants(compact_cost=1e-6, unpack_cost=1e6),
        layer_occupancy={p: 0.05 for p in paths})


def test_compile_sparse_plan_end_to_end_bit_exact(small):
    """The acceptance property: a compiled model whose layers route through
    the zero-chunk-skipping gather classifies bit-identically to the dense
    plan — on ordinary (not especially sparse) images, where per-row nnz
    routinely overflows the budget and the cond fallback must carry it."""
    cfg, params, imgs = small
    sp = sparse_plan(linear_layer_paths(cfg))
    m_sparse = infer_compile(params, cfg, sp)
    assert "lut_sparse" in m_sparse.plan.routes.values()
    m_dense = infer_compile(params, cfg, ExecutionPlan(
        batch_buckets=(2,), weight_dtype="int8",
        route_constants=RouteConstants(unpack_cost=1e6)))
    assert "lut_sparse" not in m_dense.plan.routes.values()
    exact(m_sparse.classify(imgs), m_dense.classify(imgs))
    # and against the float-oracle emulation backend: the repo-wide
    # packed == reference bit-identity must survive sparse routing
    m_ref = infer_compile(params, cfg, ExecutionPlan(
        batch_buckets=(2,), weight_dtype="int8", backend="reference"))
    exact(m_sparse.classify(imgs), m_ref.classify(imgs))


def test_pinned_lut_sparse_replays_from_json(small):
    cfg, params, imgs = small
    m1 = infer_compile(params, cfg, sparse_plan(linear_layer_paths(cfg)))
    replay = ExecutionPlan.from_json(m1.plan.to_json())
    assert replay.routes == m1.plan.routes
    m2 = infer_compile(params, cfg, replay)
    exact(m1.classify(imgs), m2.classify(imgs))


def test_pinned_lut_sparse_without_occupancy_fails_loud(small):
    cfg, params, _ = small
    m1 = infer_compile(params, cfg, sparse_plan(linear_layer_paths(cfg)))
    stripped = dataclasses.replace(m1.plan, layer_occupancy=None)
    with pytest.raises(ValueError, match="occupancy"):
        infer_compile(params, cfg, stripped)


# ---------------------------------------------------------------------------
# structured_spikes: the sparsity the benchmarks measure is the one asked for
# ---------------------------------------------------------------------------

def test_structured_spikes_rate_and_chunk_occupancy():
    t, shape = 8, (64, 256)
    for rate in (0.1, 0.3):
        x = structured_spikes(jax.random.PRNGKey(15), t=t, shape=shape,
                              rate=rate)
        fired = float(jnp.mean(jnp.unpackbits(np.asarray(x).reshape(-1))))
        assert fired == pytest.approx(rate, abs=0.05)
        # chunk occupancy tracks the firing rate ~1:1 (the point of the
        # channel-structured distribution), not ~2x like iid spikes
        occ = chunk_occupancy(x, t)
        assert occ == pytest.approx(rate / 0.9, abs=0.08)
    z = structured_spikes(jax.random.PRNGKey(16), t=t, shape=shape,
                          rate=0.0)
    assert not np.asarray(z).any()
    with pytest.raises(AssertionError):
        structured_spikes(jax.random.PRNGKey(17), t=t, shape=(4, 12),
                          rate=0.1)   # channels not a multiple of 8


# ---------------------------------------------------------------------------
# serving telemetry: occupancy through accounting, stats and the scheduler
# ---------------------------------------------------------------------------

def test_step_accounting_occupancy_rows_weighted():
    acct = StepAccounting()
    assert acct.occupancy is None                    # absence, not 0.0
    acct.record_step(rows=2, bucket=2, busy_s=0.0, wall_s=0.0)
    assert acct.occupancy is None                    # unmeasured step
    acct.record_step(rows=2, bucket=2, busy_s=0.0, wall_s=0.0,
                     occupancy=0.5)
    acct.record_step(rows=6, bucket=8, busy_s=0.0, wall_s=0.0,
                     occupancy=0.25)
    assert acct.occupancy == pytest.approx((0.5 * 2 + 0.25 * 6) / 8)


def test_batch_occupancy_counts_set_bits():
    assert batch_occupancy(np.zeros((2, 2, 2, 1), np.uint8)) == 0.0
    assert batch_occupancy(np.full((1, 1, 1, 1), 255, np.uint8)) == 1.0
    assert batch_occupancy(np.zeros((0, 2, 2, 1), np.uint8)) == 0.0


def _unpacked_occupancy(a):
    """The readout as it was computed before the word popcount."""
    a = np.asarray(a, np.uint8)
    return float(np.unpackbits(a.reshape(-1)).mean()) if a.size else 0.0


def _occupancy_case(name):
    rng = np.random.default_rng(1717)
    if name == "multiple_of_8":
        return rng.integers(0, 256, (4, 6, 8, 3), dtype=np.uint8)
    if name == "tail":
        return rng.integers(0, 256, (3, 5, 7, 1), dtype=np.uint8)
    if name == "strided_view":
        return rng.integers(0, 256, (4, 9, 7, 3), dtype=np.uint8)[:, ::2]
    if name == "padded_prefix":
        imgs = [rng.integers(0, 256, (6, 5, 3), dtype=np.uint8)
                for _ in range(3)]
        batch, pad = assemble_batch(imgs, 8)
        assert pad == 5
        return batch[:3]
    if name == "sparse":
        bits = rng.random((2, 11, 13, 3)) < 0.03
        return np.where(bits, rng.integers(1, 256, bits.shape), 0).astype(
            np.uint8)
    if name == "all_ones":
        return np.full((5, 7, 3, 1), 255, np.uint8)
    if name == "empty":
        return np.zeros((0, 4, 4, 3), np.uint8)
    raise AssertionError(name)


@pytest.mark.parametrize("case", ["multiple_of_8", "tail", "strided_view",
                                  "padded_prefix", "sparse", "all_ones",
                                  "empty"])
def test_batch_occupancy_matches_unpacked_mean_exactly(case):
    # the word popcount divides the same exact bit count by the same bit
    # total as the unpack-and-mean it replaced, so the float is identical
    a = _occupancy_case(case)
    assert batch_occupancy(a) == _unpacked_occupancy(a)


def test_engine_and_runtime_stats_report_occupancy(small):
    cfg, params, imgs = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)))
    eng = MicroBatchEngine(model)
    assert eng.stats()["occupancy"] is None          # nothing measured yet
    eng.submit(imgs[:2])
    eng.run()
    occ = eng.stats()["occupancy"]
    assert occ == pytest.approx(batch_occupancy(imgs[:2]), abs=1e-4)
    with AsyncServeRuntime(model,
                           policy=ServePolicy(max_wait_ms=2.0)) as rt:
        rt.submit(imgs[:2]).result(timeout=30)
        assert rt.stats()["occupancy"] is not None


def test_scheduler_conditions_estimate_on_occupancy():
    s = ContinuousBatchingScheduler(
        (2, 8), ServePolicy(sparse_occupancy=0.35))
    s.observe_step(2, 0.03, occupancy=0.8)           # dense sample
    s.observe_step(2, 0.01, occupancy=0.1)           # sparse sample
    assert s.service_estimate(2, occupancy=0.1) == pytest.approx(0.01)
    assert s.service_estimate(2, occupancy=0.9) == pytest.approx(0.03)
    # no explicit occupancy: the running EWMA (dense-leaning here) decides
    assert s.service_estimate(2) == pytest.approx(0.03)
    # split disabled: one EWMA regardless of occupancy
    s2 = ContinuousBatchingScheduler(
        (2, 8), ServePolicy(sparse_occupancy=None))
    s2.observe_step(2, 0.03, occupancy=0.8)
    s2.observe_step(2, 0.01, occupancy=0.1)
    assert s2.service_estimate(2, occupancy=0.1) == \
        s2.service_estimate(2, occupancy=0.9)


def test_serve_policy_validates_sparse_occupancy():
    with pytest.raises(ValueError, match="sparse_occupancy"):
        ServePolicy(sparse_occupancy=0.0)
    with pytest.raises(ValueError, match="sparse_occupancy"):
        ServePolicy(sparse_occupancy=1.5)
    assert ServePolicy(sparse_occupancy=None).sparse_occupancy is None


# ---------------------------------------------------------------------------
# serving-correctness regressions (each failed before the fix)
# ---------------------------------------------------------------------------

def test_decide_slo_budgets_the_whole_split():
    """SLO pressure must reserve service time for EVERY chunk of the
    pad-minimizing split, not just the first: the oldest request's last
    image may land in the final chunk. Before the fix this scenario kept
    the window open ('wait') because one 4 ms step fit the budget."""
    s = ContinuousBatchingScheduler(
        (2, 8), ServePolicy(max_wait_ms=10.0, slo_ms=20.0))
    s.observe_step(2, 0.004)
    chunks = plan_chunks(6, s.buckets)
    assert len(chunks) > 1                           # scenario sanity
    d = s.decide(backlog=6, oldest_submit_s=0.0, now_s=0.009)
    assert (d.action, d.reason) == ("dispatch", "SLO pressure")
    # inside the full-split deadline the window stays open
    d = s.decide(backlog=6, oldest_submit_s=0.0, now_s=0.007)
    assert d.action == "wait"


def test_submit_rid_conflict_is_rejected(small):
    """submit(Request, rid=) with a disagreeing rid used to silently keep
    the Request's own id — the caller polled an id that never completes."""
    cfg, params, imgs = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)))
    eng = MicroBatchEngine(model)
    req = Request(rid=5, images=imgs[:1])
    with pytest.raises(ValueError, match="conflicts"):
        eng.submit(req, rid=6)
    assert eng.submit(req, rid=5) is req             # agreeing rid is fine
    assert req.latency_s is None                     # in flight: no latency
    eng.run()
    assert req.latency_s is not None and req.latency_s >= 0.0


def test_latency_summary_keeps_microsecond_precision():
    """Sub-millisecond latencies used to be rounded to 4 decimals, which
    collapsed every serving step on a small model into 0.0001 or 0.0002."""
    out = latency_summary([0.0001234])
    assert out["latency_p50_s"] == 0.000123
    assert out["latency_mean_s"] == 0.000123
    empty = latency_summary([])
    assert empty["latency_p50_s"] is None
