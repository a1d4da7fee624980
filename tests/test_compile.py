"""The compile/serve split: backend registry, ExecutionPlan JSON
round-trip, the pass pipeline, multi-bucket engine parity, replica
placement (``replicate_model``), and the retirement of the old
InferenceSession shim (the surface is gone AND the package imports
warning-free).

The exactness standard is inherited from tests/test_infer.py: packed and
reference logits are bit-identical on CPU — including when requests reach
the compiled model through different batch buckets, and when the route
plan was deserialized from JSON or built from autotuned constants."""
import dataclasses
import json
import sys
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spikformer import (SpikformerConfig, init,
                                   fold_inference_params, layer_paths)
from repro.infer import (CompiledModel, ExecutionPlan, MicroBatchEngine,
                         Request, backend_spec, compile as infer_compile,
                         list_backends, quantize_weights, register_backend,
                         replicate_model, unregister_backend)
from repro.infer.compile import fold_bn, plan_route_tables
from repro.kernels.lut_matmul import RouteConstants
from repro.kernels import ops

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "scripts"))


# the Pallas cost model's constants before the v5e fit: cheap enough that
# the gather wins where its table fits (fc1 and fc2 of the scaled model
# too, which the fused MLP pair needs)
CHEAP_PALLAS_GATHER = RouteConstants(pallas_gather_cost=2.0,
                                     pallas_dot_cost=1.0)


def exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def small():
    cfg = SpikformerConfig().scaled()
    params = init(jax.random.PRNGKey(0), cfg)
    img = jax.random.randint(jax.random.PRNGKey(1), (5, 32, 32, 3), 0, 256,
                             jnp.uint8)
    return cfg, params, img


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

def test_builtin_backends_registered():
    assert set(list_backends()) >= {"packed", "reference"}
    spec = backend_spec("reference")
    assert spec.wants_lut_tables is False
    assert backend_spec("float").name == "reference"   # alias resolves


def test_register_backend_and_capability_filtering():
    register_backend("test_f32only", lambda **kw: object(),
                     weight_dtypes=("float32",), device_kinds=("tpu",))
    try:
        assert "test_f32only" in list_backends()
        assert "test_f32only" in list_backends(weight_dtype="float32")
        assert "test_f32only" not in list_backends(weight_dtype="int8")
        assert "test_f32only" not in list_backends(device_kind="cpu")
        assert "test_f32only" in list_backends(device_kind="tpu")
    finally:
        unregister_backend("test_f32only")
    assert "test_f32only" not in list_backends()


def test_register_backend_refuses_silent_shadowing():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("packed", lambda **kw: object())


def test_register_backend_overwrite_takes_over_alias():
    """Overwriting an alias must actually reroute it (and detach it from
    its old owner without removing the owner)."""
    sentinel = object()
    register_backend("float", lambda **kw: sentinel, overwrite=True)
    try:
        from repro.infer import get_backend
        assert get_backend("float") is sentinel
        assert backend_spec("reference").aliases == ()   # owner survives
    finally:
        unregister_backend("float")
        register_backend("reference",
                         backend_spec("reference").factory,
                         weight_dtypes=("float32", "int8"),
                         wants_lut_tables=False, aliases=("float",),
                         overwrite=True)
    assert backend_spec("float").name == "reference"     # restored


def test_packed_pallas_backend_registered_and_compiles(small):
    """The registration path the registry docstring promises, exercised
    end-to-end: "packed_pallas" (alias "pallas") resolves through
    ``compile()`` to a Pallas-pinned PackedBackend, declares TPU device
    kind (enforced: a CPU host needs the interpret escape hatch), and —
    capability-declared — gets REAL (C,256,N) gather tables built into
    its LUT-planned layers: the Pallas byte-LUT kernel consumes them from
    VMEM. The v5e defaults send every layer to the dot, so the plan prices
    the gather cheap enough for the cost model to pick it."""
    cfg, params, _ = small
    spec = backend_spec("packed_pallas")
    assert backend_spec("pallas").name == "packed_pallas"   # alias resolves
    assert spec.device_kinds == ("tpu",)
    assert spec.wants_lut_tables is True
    assert "packed_pallas" in list_backends(device_kind="tpu")
    assert "packed_pallas" not in list_backends(device_kind="cpu")

    model = infer_compile(params, cfg,
                          ExecutionPlan(backend="pallas", batch_buckets=(2,),
                                        backend_options={"interpret": True},
                                        route_constants=CHEAP_PALLAS_GATHER))
    assert model.backend.pallas is True
    assert model.plan.routes                   # planning ran
    luts = [p for p, r in model.plan.routes.items() if r == "lut"]
    assert luts                                # pallas cost model picks LUTs
    for path in luts:
        layer = model.folded
        for p in path.split("/"):
            layer = layer[p]
        assert layer["lut"].ndim == 3          # a real table, not a flag
        assert layer["lut"].shape[1] == 256
    # the pin is real: a pallas=False override is rejected at the door
    # (this registration IS the Pallas pin; "packed" is the CPU route)
    with pytest.raises(ValueError, match="pins pallas=True"):
        infer_compile(params, cfg,
                      ExecutionPlan(backend="pallas",
                                    backend_options={"pallas": False,
                                                     "interpret": True}))


def test_pallas_backend_device_gate_names_escape_hatch(small):
    """Asking for the TPU-only backend on this CPU host fails up front,
    naming the backend's device kinds, the available platforms, and the
    ``interpret`` escape hatch — not deep inside a kernel trace."""
    cfg, params, _ = small
    if jax.default_backend() == "tpu":
        pytest.skip("device gate only fires off-TPU")
    with pytest.raises(ValueError) as ei:
        infer_compile(params, cfg, ExecutionPlan(backend="packed_pallas"))
    msg = str(ei.value)
    assert "'packed_pallas'" in msg and "tpu" in msg
    assert jax.default_backend() in msg        # what this host has
    assert "interpret" in msg                  # and the way out


def test_unknown_backend_name_errors(small):
    cfg, params, _ = small
    with pytest.raises(ValueError, match="unknown inference backend"):
        infer_compile(params, cfg, ExecutionPlan(backend="no_such"))


def test_compile_rejects_unsupported_weight_dtype(small):
    cfg, params, _ = small
    register_backend("test_nof32", lambda **kw: object(),
                     weight_dtypes=("int8",))
    try:
        with pytest.raises(ValueError, match="does not support weight_dtype"):
            infer_compile(params, cfg,
                          ExecutionPlan(backend="test_nof32",
                                        weight_dtype="float32"))
    finally:
        unregister_backend("test_nof32")


# ---------------------------------------------------------------------------
# ExecutionPlan: validation + JSON round-trip
# ---------------------------------------------------------------------------

def test_plan_validates_fields():
    with pytest.raises(ValueError, match="route"):
        ExecutionPlan(route="fused")
    with pytest.raises(ValueError, match="weight_dtype"):
        ExecutionPlan(weight_dtype="int4")
    with pytest.raises(ValueError, match="batch_buckets"):
        ExecutionPlan(batch_buckets=())
    # buckets are sorted + deduped; plan_batch is the largest
    p = ExecutionPlan(batch_buckets=(8, 2, 8))
    assert p.batch_buckets == (2, 8) and p.plan_batch == 8


def test_plan_json_roundtrip_identity():
    p = ExecutionPlan(backend="packed", weight_dtype="int8",
                      batch_buckets=(2, 8), max_table_bytes=1 << 20,
                      route_constants=RouteConstants(gather_cost=3.25),
                      routes={"scs/conv0": "lut", "blocks/b0/mlp/fc1":
                              "unpack"})
    q = ExecutionPlan.from_json(p.to_json())
    assert q == p


def test_plan_json_fragment_fills_defaults():
    q = ExecutionPlan.from_json(json.dumps(
        {"route_constants": {"gather_cost": 2.0}}))
    assert q.route_constants.gather_cost == 2.0
    assert q.route_constants.transpose_cost == \
        RouteConstants().transpose_cost
    assert q.backend == "packed" and q.batch_buckets == (8,)


def test_plan_json_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown ExecutionPlan keys"):
        ExecutionPlan.from_json('{"batch_size": 8}')
    with pytest.raises(ValueError, match="route-constant keys"):
        ExecutionPlan.from_json('{"route_constants": {"gatherr": 1.0}}')


def test_compiled_plan_roundtrip_reproduces_route_plan(small):
    """The acceptance property: serialize the resolved plan, recompile from
    JSON, get the identical per-layer route plan AND identical logits."""
    cfg, params, img = small
    m1 = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2, 8)))
    assert m1.plan.routes                      # resolved, non-empty
    m2 = infer_compile(params, cfg, ExecutionPlan.from_json(m1.plan.to_json()))
    assert m2.plan.routes == m1.plan.routes
    exact(m1.logits(img), m2.logits(img))


def test_pallas_plan_json_roundtrip_replays_pinned_routes(small):
    """A pallas-compiled plan is a committable artifact: its JSON
    round-trips with the routes pinned, recompiling from it replays the
    same per-layer routes through the Pallas kernels with bit-identical
    logits — and the same plan stripped of its ``interpret`` escape hatch
    fails loudly on a host without the backend's device, instead of
    quietly serving through some other backend."""
    cfg, params, img = small
    cfg = dataclasses.replace(cfg, depth=1)
    params1 = init(jax.random.PRNGKey(0), cfg)
    m1 = infer_compile(params1, cfg,
                       ExecutionPlan(backend="packed_pallas",
                                     batch_buckets=(2,),
                                     backend_options={"interpret": True}))
    plan2 = ExecutionPlan.from_json(m1.plan.to_json())
    assert plan2.backend == "packed_pallas"
    assert plan2.routes == m1.plan.routes and plan2.routes
    m2 = infer_compile(params1, cfg, plan2)
    assert m2.plan.routes == m1.plan.routes    # replayed, not re-derived
    exact(m1.logits(img[:2]), m2.logits(img[:2]))
    if jax.default_backend() != "tpu":
        bare = dataclasses.replace(plan2, backend_options={})
        with pytest.raises(ValueError, match="interpret"):
            infer_compile(params1, cfg, bare)


def test_pinned_routes_reject_foreign_config(small):
    """A deserialized plan for a different architecture must fail loudly,
    not plan a fresh heuristic."""
    cfg, params, _ = small
    m1 = infer_compile(params, cfg)
    deep = dataclasses.replace(cfg, depth=3)
    params3 = init(jax.random.PRNGKey(0), deep)
    with pytest.raises(ValueError, match="no entry for layer"):
        infer_compile(params3, deep,
                      dataclasses.replace(m1.plan, batch_buckets=(8,)))


# ---------------------------------------------------------------------------
# pass pipeline in isolation
# ---------------------------------------------------------------------------

def test_quantize_weights_pass(small):
    cfg, params, _ = small
    tree = fold_bn(params, cfg)
    t8, d8 = quantize_weights(tree, "int8")
    assert d8 == "int8" and "scale" in t8["scs"]["conv0"]
    # None resolves from the tree
    _, dN = quantize_weights(t8, None)
    assert dN == "int8"
    _, dF = quantize_weights(tree, None)
    assert dF == "float32"
    with pytest.raises(ValueError, match="already int8-quantized"):
        quantize_weights(t8, "float32")


def test_plan_route_tables_pinned_replay(small):
    """plan_route_tables under pinned routes applies them verbatim —
    including a deliberately non-heuristic choice."""
    cfg, params, _ = small
    tree = fold_bn(params, cfg)
    _, auto = plan_route_tables(tree, cfg, batch_size=8)
    flipped = {p: ("unpack" if r == "lut" else r) for p, r in auto.items()}
    t2, replay = plan_route_tables(tree, cfg, batch_size=8, routes=flipped)
    assert replay == flipped
    assert all("lut" not in t2["scs"][n] for n in t2["scs"])


def test_route_constants_change_decisions():
    """The constants are real plan inputs: an absurd gather cost flips every
    borderline shape to unpack."""
    expensive = RouteConstants(gather_cost=1e9)
    for m, k, n in [(32, 64, 256), (512, 32, 16), (2048, 12, 8)]:
        assert ops.choose_route(m=m, k=k, n=n, g=1, t=4) == "lut"
        assert ops.choose_route(m=m, k=k, n=n, g=1, t=4,
                                constants=expensive) == "unpack"


# ---------------------------------------------------------------------------
# multi-bucket CompiledModel + engine parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,weight_dtype", [(4, "float32"), (4, "int8"),
                                            (16, "float32"), (16, "int8")])
def test_compile_packed_matches_reference_across_buckets(small, t,
                                                         weight_dtype):
    """The acceptance sweep through the new API: packed == reference
    bit-for-bit, with requests served through DIFFERENT buckets."""
    cfg, params, img = small
    cfg = dataclasses.replace(cfg, timesteps=t)
    plan = ExecutionPlan(weight_dtype=weight_dtype, batch_buckets=(2, 8))
    packed = infer_compile(params, cfg, plan, backend="packed")
    ref = infer_compile(params, cfg, plan, backend="reference")
    lp = packed.logits(img)                    # 5 rows -> 2+2+2-pad steps
    exact(lp, ref.logits(img))
    # bucket invariance: the same image through the 2-bucket and the
    # 8-bucket produces identical rows
    big = jnp.concatenate([img, img[:3]])      # 8 rows -> one 8-bucket step
    exact(packed.logits(big)[:5], lp)
    eng = MicroBatchEngine(packed)
    eng.submit(np.asarray(img[:2]))            # backlog 2 -> bucket 2
    eng.run()
    eng.submit(np.asarray(big))                # backlog 8 -> bucket 8
    done = sorted(eng.run(), key=lambda r: r.rid)
    assert {eng.pick_bucket(2), eng.pick_bucket(8)} == {2, 8}
    want = np.asarray(packed.classify(big)).tolist()
    assert [int(x) for x in done[0].labels] == want[:2]
    assert [int(x) for x in done[1].labels] == want


@pytest.mark.parametrize("backend,options,constants", [
    ("packed", {}, RouteConstants()),            # fc1 and fc2 apart
    ("packed_pallas", {"interpret": True},       # the fused MLP pair
     CHEAP_PALLAS_GATHER),
], ids=["packed", "pallas_fused_mlp"])
def test_jitted_forward_names_every_layer_scope(backend, options, constants):
    """The compiled forward runs each layer under a ``jax.named_scope`` of
    its path (``layer_paths``; the fused MLP pair under its block's
    ``mlp``), the token reshape under ``tokens`` and the readout under
    ``head``: the lowered program's locations carry every one."""
    cfg = SpikformerConfig().scaled(img_size=16, dim=32, depth=2)
    model = infer_compile(init(jax.random.PRNGKey(0), cfg), cfg,
                          ExecutionPlan(backend=backend,
                                        backend_options=options,
                                        batch_buckets=(2,),
                                        route_constants=constants))
    text = model._fwd.lower(
        model.folded, jnp.zeros(model.input_shape(2), jnp.uint8)
    ).as_text(debug_info=True)
    fused = backend == "packed_pallas"
    for path in layer_paths(cfg) + ["tokens", "head"]:
        if fused and "/mlp/" in path:
            assert f"/{path}/" not in text, path
            path = path.rsplit("/", 1)[0]
        assert f"/{path}/" in text, path


def test_compiled_step_rejects_non_bucket_batch(small):
    cfg, params, img = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2, 8)))
    with pytest.raises(ValueError, match="not a compiled bucket"):
        model.step(np.asarray(img)[:3])


def test_engine_pad_waste_accounting(small):
    """Multi-bucket dispatch cuts pad waste, and the engine reports it:
    3 images over buckets (2, 8) pad 3->8 single-bucket but 2+1->2+2
    multi-bucket."""
    cfg, params, img = small
    imgs = np.asarray(img)[:3]
    single = MicroBatchEngine(
        infer_compile(params, cfg, ExecutionPlan(batch_buckets=(8,))))
    multi = MicroBatchEngine(
        infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2, 8))))
    for eng in (single, multi):
        for i in range(3):                     # one image per request
            eng.submit(imgs[i:i + 1])
        eng.run()
    assert single.total_rows == 8 and single.padded_rows == 5
    assert multi.total_rows == 4 and multi.padded_rows == 1
    assert multi.pad_waste < single.pad_waste
    s = multi.stats()
    assert s["pad_waste"] == 0.25 and s["padded_rows"] == 1
    assert s["images"] == 3 and s["requests"] == 3
    assert s["latency_p95_s"] is not None


def test_engine_rejects_inflight_rid_and_completes_empty(small):
    cfg, params, img = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)))
    eng = MicroBatchEngine(model)
    imgs = np.asarray(img)
    eng.submit(Request(rid=0, images=imgs[:2]))
    with pytest.raises(ValueError, match="already in flight"):
        eng.submit(Request(rid=0, images=imgs[2:]))
    eng.run()
    eng.submit(Request(rid=0, images=imgs[:2]))   # completed rid reusable
    # a zero-image request completes immediately, with no queue entry
    empty = eng.submit(imgs[:0])
    assert empty in eng.done and empty.labels == []
    done = eng.run()
    assert eng.stats()["requests"] == len(done) == 3


def test_engine_mixed_requests_match_direct_classify(small):
    cfg, params, img = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2, 4)))
    eng = MicroBatchEngine(model)
    imgs = np.asarray(img)
    eng.submit(Request(rid=0, images=imgs[:3]))
    eng.submit(Request(rid=1, images=imgs[3:]))
    done = sorted(eng.run(), key=lambda r: r.rid)
    got = [lab for r in done for lab in r.labels]
    assert got == np.asarray(model.classify(imgs)).tolist()


# ---------------------------------------------------------------------------
# autotuned constants, end to end
# ---------------------------------------------------------------------------

def test_autotune_fit_and_plan_accepted_end_to_end(small):
    """fit_constants on synthetic timings (generated FROM a known cost
    model) recovers constants that reproduce its decisions, and the
    resulting ExecutionPlan compiles and serves bit-exactly."""
    from autotune_routes import fit_constants

    true = RouteConstants(gather_cost=6.0, transpose_cost=1.5,
                          unpack_cost=12.0)
    alpha = 1e-9                                # seconds per FMA
    samples = []
    for m, k, n, g in [(64, 32, 16, 1), (256, 64, 64, 1), (512, 32, 32, 1),
                       (1024, 64, 32, 2), (2048, 32, 16, 1),
                       (256, 128, 128, 1)]:
        t = 8 * g
        c = -(-k // 8)
        samples.append({
            "m": m, "k": k, "n": n, "g": g, "t": t, "c": c,
            "table_bytes": 32 * k * n,
            "unpack_s": alpha * t * m * k * (n + true.unpack_cost),
            "lut_s": alpha * (t * m * c * n * true.gather_cost
                              + g * m * k * true.transpose_cost),
        })
    fitted = fit_constants(samples)
    assert fitted.gather_cost == pytest.approx(true.gather_cost, rel=0.05)
    assert fitted.unpack_cost == pytest.approx(true.unpack_cost, rel=0.15)

    cfg, params, img = small
    plan = ExecutionPlan.from_json(json.dumps(
        {"route_constants": fitted.to_dict(), "batch_buckets": [2, 8]}))
    packed = infer_compile(params, cfg, plan, backend="packed")
    ref = infer_compile(params, cfg, plan, backend="reference")
    exact(packed.logits(img), ref.logits(img))


# ---------------------------------------------------------------------------
# the shim is gone: the old name is unimportable and nothing in the
# package warms up with a DeprecationWarning
# ---------------------------------------------------------------------------

def test_session_shim_removed():
    with pytest.raises(ImportError):
        from repro.infer import InferenceSession  # noqa: F401
    assert not (pathlib.Path(__file__).resolve().parent.parent
                / "src/repro/infer/session.py").exists()


def test_infer_package_compiles_without_deprecation_warnings(small):
    cfg, params, img = small
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", DeprecationWarning)
        model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)))
        model.classify(img)


# ---------------------------------------------------------------------------
# replica placement
# ---------------------------------------------------------------------------

def test_replicate_model_shares_plan_and_math(small):
    cfg, params, img = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)))
    twin = replicate_model(model)
    # thread-backed replica: same resolved plan and folded tree verbatim,
    # same jitted step (no recompile for a same-device copy)
    assert twin.plan is model.plan
    assert twin.folded is model.folded
    assert twin._fwd is model._fwd
    exact(twin.logits(img), model.logits(img))


def test_replicate_model_onto_device_recompiles_bit_exact(small):
    cfg, params, img = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)))
    dev = jax.devices()[0]
    placed = replicate_model(model, device=dev)
    assert placed.plan is model.plan
    assert placed._fwd is not model._fwd    # per-device executable
    exact(placed.logits(img), model.logits(img))


def test_replicate_model_preserves_jit_choice(small):
    """A jit=False template replicates to jit=False steps — a replica must
    behave like the model it replicates, on or off device."""
    cfg, params, img = small
    model = infer_compile(params, cfg, ExecutionPlan(batch_buckets=(2,)),
                          jit=False)
    assert model.jit is False
    twin = replicate_model(model)
    placed = replicate_model(model, device=jax.devices()[0])
    assert twin.jit is False and placed.jit is False
    exact(placed.logits(img[:2]), model.logits(img[:2]))
