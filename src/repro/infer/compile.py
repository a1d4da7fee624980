"""Compile/serve split: ``compile(params, cfg, plan) -> CompiledModel``.

Everything decided *before the first batch* lives in an ``ExecutionPlan`` —
backend, weight dtype, the static batch buckets the step is compiled for,
the byte-LUT table budget, and the ``choose_route`` cost constants (host
properties, autotunable). Compilation is then an explicit pass pipeline
over the folded tree:

    fold_bn  ->  quantize_weights  ->  plan_route_tables  ->  lower

each pass a named function, so tests and the autotuner can run them in
isolation. The result is a ``CompiledModel``: a jit-compiled fixed-shape
step per batch bucket plus the resolved plan (per-layer routes filled in),
which ``to_json``/``from_json`` turn into a committable artifact — serving
a model under a reviewed plan replays exactly the route decisions the plan
records, never a fresh heuristic call.

    from repro.infer import ExecutionPlan, compile
    plan = ExecutionPlan(backend="packed", weight_dtype="int8",
                         batch_buckets=(2, 8))
    model = compile(params, cfg, plan)
    logits = model.logits(images_u8)          # any N; bucketed + padded
    pathlib.Path("plan.json").write_text(model.plan.to_json())

The serving loop over a ``CompiledModel`` is ``repro.infer.engine``;
``replicate_model`` places copies of one for the multi-replica fleet.
"""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from . import backends as _backends  # noqa: F401  (registers built-ins)
from . import registry
from .quant import (WEIGHT_DTYPES, folded_layers, map_folded_layers,
                    quantize_folded)
from ..core import qkformer, spikformer
from ..core.qkformer import QKFormerConfig
from ..core.spikformer import SpikformerConfig
from ..kernels import lut_matmul
from ..kernels.lut_matmul import RouteConstants
from ..kernels.ops import choose_pallas_route, choose_route, use_pallas

ROUTES = ("auto", "unpack", "lut")

# each network's module: ``layer_specs``, ``fold_inference_params`` and
# ``forward_folded`` for its config type
_NETWORKS = {SpikformerConfig: spikformer, QKFormerConfig: qkformer}


def network(cfg):
    """The module that describes ``cfg``'s network."""
    try:
        return _NETWORKS[type(cfg)]
    except KeyError:
        raise TypeError(f"no network is registered for {type(cfg).__name__}; "
                        f"expected one of "
                        f"{sorted(c.__name__ for c in _NETWORKS)}") from None


def matmul_specs(cfg) -> dict:
    """path -> ``LayerSpec`` of every spiking matmul of ``cfg``'s network,
    in call order."""
    return {s.path: s for s in network(cfg).layer_specs(cfg) if s.matmul}


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything decided before the first batch, as one committable value.

    ``batch_buckets`` are the static shapes the step compiles for; the
    engine picks the smallest bucket covering its backlog, so low-occupancy
    traffic stops padding to the full batch. Route planning runs once at
    the LARGEST bucket and every bucket shares the annotated tree — the
    per-image math is row-independent, which is what keeps logits identical
    across buckets (the multi-bucket parity contract).

    ``routes`` is the resolved per-layer plan (path -> "lut" |
    "lut_sparse" | "unpack"). ``None`` means "decide at compile time via
    ``route_constants``"; a non-None mapping PINS the decisions — that is
    what a deserialized plan carries, so a committed plan is replayed, not
    re-derived.

    ``layer_occupancy`` maps layer paths to calibrated chunk-occupancy
    floats (fraction of nonzero chunk-index bytes at that layer's input,
    from ``calibrate_layer_occupancy``). It is what lets ``choose_route``
    consider the sparse gather route, and what sizes the static gather
    budget at lowering time — sparsity claims are measured and committed
    with the plan, never assumed.
    """
    backend: str = "packed"
    weight_dtype: str | None = None     # None: whatever the tree carries
    batch_buckets: tuple[int, ...] = (8,)
    max_table_bytes: int = lut_matmul.MAX_TABLE_BYTES
    route: str = "auto"                 # "auto" | "unpack" | "lut"
    route_constants: RouteConstants = dataclasses.field(
        default_factory=RouteConstants)
    routes: dict | None = None          # resolved: layer path -> route
    layer_occupancy: dict | None = None  # path -> calibrated chunk occupancy
    backend_options: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.route not in ROUTES:
            raise ValueError(f"unknown route {self.route!r}; "
                             f"expected one of {ROUTES}")
        if (self.weight_dtype is not None
                and self.weight_dtype not in WEIGHT_DTYPES):
            raise ValueError(f"unknown weight_dtype {self.weight_dtype!r}; "
                             f"expected one of {WEIGHT_DTYPES}")
        buckets = tuple(sorted({int(b) for b in self.batch_buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"batch_buckets must be >= 1, got "
                             f"{self.batch_buckets!r}")
        object.__setattr__(self, "batch_buckets", buckets)
        if isinstance(self.route_constants, dict):
            object.__setattr__(self, "route_constants",
                               RouteConstants.from_dict(self.route_constants))
        if self.layer_occupancy is not None:
            occ = {}
            for path, o in self.layer_occupancy.items():
                o = float(o)
                if not 0.0 <= o <= 1.0:
                    raise ValueError(
                        f"layer_occupancy[{path!r}] = {o!r}; occupancy is a "
                        "fraction of nonzero chunk bytes in [0, 1]")
                occ[str(path)] = o
            object.__setattr__(self, "layer_occupancy", occ)

    @property
    def plan_batch(self) -> int:
        """The bucket route planning keys its (M, K, N, G) shapes on."""
        return self.batch_buckets[-1]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["batch_buckets"] = list(self.batch_buckets)
        return d

    def to_json(self, *, indent: int | None = 1) -> str:
        if not isinstance(self.backend, str):
            raise TypeError("plans holding a backend *instance* are not "
                            "serializable; register it and use the name")
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "ExecutionPlan":
        known = {f.name for f in dataclasses.fields(cls)}
        bad = set(d) - known
        if bad:
            raise ValueError(f"unknown ExecutionPlan keys {sorted(bad)}; "
                             f"expected a subset of {sorted(known)}")
        d = dict(d)
        if "batch_buckets" in d:
            d["batch_buckets"] = tuple(d["batch_buckets"])
        return cls(**d)

    @classmethod
    def from_json(cls, text: str) -> "ExecutionPlan":
        """Accepts a full plan or any fragment of one (autotune emits just
        ``{"route_constants": ...}``); missing fields keep their defaults."""
        return cls.from_dict(json.loads(text))


# ---------------------------------------------------------------------------
# The pass pipeline. Each pass is a named function over the folded tree so
# tests and the autotuner can run them in isolation.
# ---------------------------------------------------------------------------

def fold_bn(params, cfg, *, folded: bool = False):
    """Pass 1 — BN folding: training params -> inference tree of
    {kernel, bias} layers (the network's ``fold_inference_params``).
    ``folded=True`` passes a pre-folded (possibly pre-quantized) tree
    through untouched."""
    return params if folded else network(cfg).fold_inference_params(
        params, cfg)


def quantize_weights(tree, weight_dtype: str | None):
    """Pass 2 — weight quantization. Returns ``(tree, resolved_dtype)``.

    ``None`` resolves to whatever the tree carries (int8 for a
    pre-quantized tree, float32 for a fresh fold); an explicit "float32"
    on an already-quantized tree fails loudly rather than silently running
    int8."""
    if weight_dtype is not None and weight_dtype not in WEIGHT_DTYPES:
        raise ValueError(f"unknown weight_dtype {weight_dtype!r}; "
                         f"expected one of {WEIGHT_DTYPES}")
    already_quantized = any("scale" in layer
                            for _, layer in folded_layers(tree))
    if weight_dtype == "float32" and already_quantized:
        raise ValueError(
            "weight_dtype='float32' requested but the folded tree is "
            "already int8-quantized; pass the float tree or drop the "
            "weight_dtype argument")
    if weight_dtype == "int8" and not already_quantized:
        tree = quantize_folded(tree)
    resolved = ("int8" if weight_dtype == "int8" or already_quantized
                else "float32")
    return tree, resolved


def plan_route_tables(folded, cfg, *, batch_size: int,
                      max_table_bytes: int = lut_matmul.MAX_TABLE_BYTES,
                      build_tables: bool = True,
                      constants: RouteConstants | None = None,
                      routes: dict | None = None,
                      layer_occupancy: dict | None = None,
                      force: str | None = None,
                      pallas: bool = False):
    """Pass 3 — per-layer matmul route planning: the byte-LUT's precompute.

    For every folded layer this takes the packed-route matmul shape
    (M, K, N, G) the compiled step will see at ``batch_size`` from the
    network's layer list (``matmul_specs``: M is ``batch_size`` times the
    layer's rows an image; a layer whose kernel is not (K, N) is refused)
    and decides between the unpack-free byte-LUT datapath and the
    unpack-then-dot oracle — via ``kernels.ops.choose_route`` under ``constants`` when
    ``routes`` is None, or by REPLAYING a pinned ``routes`` mapping (what a
    deserialized plan carries). Where the LUT wins, the (C, 256, N)
    chunk-partial-sum table is built ONCE and cached in the returned tree
    as a ``lut`` leaf, so the per-batch work is pure gather-and-accumulate.

    Both backends of a parity pair consume trees annotated by the same
    deterministic plan: the packed backend executes the gather route, the
    float reference the fold-order emulation — the planning decision, like
    the int8 threshold fold, is part of the math both sides agree on. The
    reference side never gathers, so ``build_tables=False`` (what
    ``compile()`` uses for backends whose capability says no tables)
    annotates LUT layers with a cheap boolean flag instead.

    ``layer_occupancy`` (path -> calibrated chunk occupancy) lets
    ``choose_route`` weigh the zero-chunk-skipping gather route; a layer
    with no calibrated value never routes "lut_sparse" — the sparse budget
    is sized from the measurement, so an unmeasured layer has nothing to
    size it with. The same rule holds for pinned plans: replaying a
    "lut_sparse" pin without the occupancy that produced it is an error,
    not a silent densification.

    ``pallas=True`` plans for the Pallas kernel branch: the heuristic is
    ``choose_pallas_route`` (the one-hot-gather vs in-register-dot cost
    model with its own constants) and its "lut" tables feed the VMEM
    gather kernel. ``force`` (what ``plan.route == "lut"`` sets) pins that
    route on EVERY layer instead of consulting the heuristic — the
    bit-exactness pin for float32 weights on the Pallas branch, where the
    unpack-dot kernel is reduction-order-tolerant. Pinned ``routes``
    always win over both (a committed plan replays verbatim).

    Returns ``(annotated_tree, plan)`` with ``plan`` mapping layer paths
    to routes.
    """
    t = cfg.timesteps
    g = -(-t // 8)
    specs = matmul_specs(cfg)
    plan = {}
    occ_map = layer_occupancy or {}
    choose = choose_pallas_route if pallas else choose_route

    def annotate(path, layer):
        wq = layer["kernel"]
        spec = specs.get(path)
        if spec is None or tuple(wq.shape) != (spec.k, spec.n):
            raise ValueError(
                f"folded layer {path!r} with kernel {tuple(wq.shape)} is not "
                f"a matmul of the layer list of {type(cfg).__name__} "
                f"({None if spec is None else (spec.k, spec.n)})")
        if routes is None:
            m, k, n = batch_size * spec.rows, spec.k, spec.n
            # SSSC: always 8 value planes, one group
            tt, gg = (8, 1) if spec.op == "sssc" else (t, g)
            is_int = jnp.issubdtype(wq.dtype, jnp.integer)
            route = force or choose(m=m, k=k, n=n, g=gg, t=tt,
                                    weights_are_int=is_int,
                                    max_table_bytes=max_table_bytes,
                                    constants=constants,
                                    occupancy=occ_map.get(path))
        else:
            try:
                route = routes[path]
            except KeyError:
                raise ValueError(
                    f"pinned route plan has no entry for layer {path!r} — "
                    "the plan was built for a different config") from None
            if route not in ("lut", "lut_sparse", "unpack"):
                raise ValueError(f"pinned route {route!r} for {path!r}; "
                                 "expected 'lut', 'lut_sparse' or 'unpack'")
        if route == "lut_sparse" and occ_map.get(path) is None:
            raise ValueError(
                f"route 'lut_sparse' for {path!r} requires a calibrated "
                "occupancy in the plan's layer_occupancy — the static "
                "gather budget is sized from it")
        plan[path] = route
        # drop any stale annotation first — re-planning an annotated tree
        # must not leave a previous plan's "lut" leaf on an unpack layer
        layer = {k2: v for k2, v in layer.items() if k2 != "lut"}
        if route in ("lut", "lut_sparse"):
            layer["lut"] = (lut_matmul.build_lut(wq) if build_tables
                            else True)
        return layer

    return map_folded_layers(folded, annotate), plan


def strip_lut_annotations(folded):
    """Remove every ``lut`` leaf from a folded tree (shallow copies only) —
    what ``route="unpack"`` uses to pin the mirrored-dot oracle route even
    on a tree a previous planner annotated."""
    return map_folded_layers(
        folded, lambda _, l: {k: v for k, v in l.items() if k != "lut"})


def linear_layer_paths(cfg) -> list:
    """Layer paths in FORWARD-CALL order — the order a single
    ``forward_folded`` pass hits each spiking linear, which is the order
    ``backends.OccupancyRecorder`` appends its trace in. (``map_folded_layers``
    walks the same paths but in tree order; calibration needs call order.)
    The network's layer list without the weightless attention layers."""
    return list(matmul_specs(cfg))


def calibrate_layer_occupancy(params, cfg, images_u8, *,
                              folded: bool = False,
                              weight_dtype: str | None = None) -> dict:
    """Measure per-layer chunk occupancy on a calibration batch.

    Runs ONE un-jitted forward through ``backends.OccupancyRecorder`` (a
    packed backend that notes, before each spiking linear, the fraction of
    nonzero chunk-index bytes in its input) and zips the trace with
    ``linear_layer_paths``. The result is the ``layer_occupancy`` mapping
    an ``ExecutionPlan`` commits — measured on real data, JSON-serializable,
    replayable.

    The calibration forward runs the plain dense routes (the recorder
    delegates without occupancy), so calibration never depends on the
    decisions it is about to inform.
    """
    tree = fold_bn(params, cfg, folded=folded)
    tree, _ = quantize_weights(tree, weight_dtype)
    recorder = _backends.OccupancyRecorder()
    fwd = lower(tree, cfg, recorder, jit=False)
    fwd(tree, jnp.asarray(images_u8, jnp.uint8))
    paths = linear_layer_paths(cfg)
    if len(recorder.trace) != len(paths):
        raise RuntimeError(
            f"occupancy trace has {len(recorder.trace)} entries but the "
            f"config has {len(paths)} spiking linears — recorder and "
            "forward_folded disagree about the layer sequence")
    return dict(zip(paths, recorder.trace))


def lower(folded, cfg, backend, *, jit: bool = True,
          layer_occupancy: dict | None = None):
    """Pass 4 — lowering: the annotated tree becomes one step callable
    (jitted unless ``jit=False``; each batch bucket compiles its own
    fixed-shape executable under it on first use / warmup).

    ``layer_occupancy`` (path -> static occupancy float, for layers routed
    "lut_sparse") is CLOSED OVER, not threaded through the traced tree —
    the sparse gather budget must be a trace-time constant, and the folded
    tree is a jit argument whose leaves become tracers."""
    forward = network(cfg).forward_folded

    def fwd(folded_tree, images):
        return forward(folded_tree, images, cfg, backend=backend,
                       layer_occupancy=layer_occupancy)

    return jax.jit(fwd) if jit else fwd


# ---------------------------------------------------------------------------
# compile() and its result
# ---------------------------------------------------------------------------

def plan_chunks(n: int, buckets) -> list:
    """Split ``n`` rows into bucket-shaped steps, minimizing padded rows and
    then step count: whole largest buckets peel off first, the remainder is
    solved exactly over the bucket set (3 rows over buckets (2, 8) run 2+2
    with one pad row, not 3 padded to 8 — but 7 rows run one 8-bucket, not
    four 2-buckets, because the pad is the same and one dispatch beats
    four). Returns ``[(rows, bucket), ...]``.

    Module-level (not just the ``CompiledModel`` method) because the serve
    scheduler makes its wait-vs-dispatch decisions over the SAME split the
    model will execute — one implementation, no drift.
    """
    buckets = tuple(sorted({int(b) for b in buckets}))
    if not buckets or buckets[0] < 1:
        raise ValueError(f"buckets must be >= 1, got {buckets!r}")
    chunks = []
    bmax = buckets[-1]
    while n >= bmax:
        chunks.append((bmax, bmax))
        n -= bmax
    if n == 0:
        return chunks
    # exact DP on the remainder (< largest bucket): lexicographic
    # (padded rows, steps) minimum, reconstructed front-first
    best = {0: (0, 0, None)}            # rows left -> (pad, steps, b)
    for r in range(1, n + 1):
        best[r] = min((best[r - min(b, r)][0] + b - min(b, r),
                       best[r - min(b, r)][1] + 1, b)
                      for b in buckets)
    while n:
        b = best[n][2]
        chunks.append((min(b, n), b))
        n -= min(b, n)
    return chunks


class CompiledModel:
    """A network (Spikformer, QKFormer) lowered under an ``ExecutionPlan``:
    one jit-compiled fixed-shape step per batch bucket over an annotated
    folded tree.

    ``plan`` is the RESOLVED plan — ``weight_dtype`` concretized and the
    per-layer ``routes`` filled in — so ``model.plan.to_json()`` is the
    committable artifact that replays this exact compilation.
    """

    def __init__(self, *, cfg, backend, folded, plan: ExecutionPlan, fwd,
                 jit: bool = True):
        self.cfg = cfg
        self.backend = backend
        self.folded = folded
        self.plan = plan
        self._fwd = fwd
        self.jit = jit       # how _fwd was lowered; replicate_model re-lowers
        self.buckets = plan.batch_buckets   # with the same choice

    # -- shapes -------------------------------------------------------------

    @property
    def batch_size(self) -> int:
        """The largest compiled bucket (the planning shape)."""
        return self.buckets[-1]

    @property
    def weight_dtype(self) -> str:
        return self.plan.weight_dtype

    def input_shape(self, bucket: int | None = None):
        c = self.cfg
        b = self.batch_size if bucket is None else bucket
        return (b, c.img_size, c.img_size, c.in_channels)

    def bucket_for(self, n: int) -> int:
        """Smallest compiled bucket covering ``n`` rows (the largest bucket
        when nothing covers it — the caller chunks)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def plan_chunks(self, n: int) -> list:
        """Split ``n`` rows into compiled-bucket steps via the module-level
        pad-minimizing ``plan_chunks`` over this model's bucket set."""
        return plan_chunks(n, self.buckets)

    # -- execution ----------------------------------------------------------

    def warmup(self):
        """Compile (and time) every bucket's fixed-shape step on zeros."""
        t0 = time.perf_counter()
        for b in self.buckets:
            jax.block_until_ready(
                self._fwd(self.folded, jnp.zeros(self.input_shape(b),
                                                 jnp.uint8)))
        return time.perf_counter() - t0

    def step(self, images_u8):
        """One compiled step: images MUST already be a whole bucket."""
        if images_u8.shape[0] not in self.buckets:
            raise ValueError(
                f"batch of {images_u8.shape[0]} is not a compiled bucket "
                f"{self.buckets}; pad to one (the engine does this)")
        return self._fwd(self.folded, jnp.asarray(images_u8, jnp.uint8))

    def logits(self, images_u8):
        """images_u8: (N, H, W, C) uint8, any N >= 1 -> (N, classes) f32.

        Bucketed dispatch via ``plan_chunks`` — pad rows are dropped
        before returning.
        """
        images_u8 = jnp.asarray(images_u8, jnp.uint8)
        outs, i = [], 0
        for rows, b in self.plan_chunks(images_u8.shape[0]):
            chunk = images_u8[i:i + rows]
            if b > rows:
                chunk = jnp.concatenate(
                    [chunk, jnp.zeros((b - rows, *chunk.shape[1:]),
                                      jnp.uint8)], axis=0)
            outs.append(self.step(chunk)[:rows])
            i += rows
        return jnp.concatenate(outs, axis=0)

    def classify(self, images_u8):
        """(N, H, W, C) uint8 -> (N,) int32 argmax class ids."""
        return jnp.argmax(self.logits(images_u8), axis=-1).astype(jnp.int32)

    def __call__(self, images_u8):
        return self.logits(images_u8)


def compile(params, cfg, plan: ExecutionPlan | None = None,
            *, folded: bool = False, jit: bool = True,
            **plan_overrides) -> CompiledModel:
    """Run the pass pipeline under ``plan`` and return a ``CompiledModel``.

    ``cfg`` is a ``SpikformerConfig`` or a ``QKFormerConfig``: every pass
    reads the network's shapes from its one layer list (``network``).
    ``params`` is a training tree (BN folded here) unless ``folded=True``,
    in which case it is already a ``fold_inference_params`` tree (possibly
    pre-quantized, possibly pre-annotated). ``plan_overrides`` are
    convenience ``dataclasses.replace`` fields on the plan::

        compile(params, cfg)                                # all defaults
        compile(params, cfg, backend="reference")
        compile(params, cfg, ExecutionPlan.from_json(text)) # replay

    ``jit=False`` lowers to the uncompiled step (debugging, error paths
    that must raise eagerly).
    """
    plan = ExecutionPlan() if plan is None else plan
    if plan_overrides:
        plan = dataclasses.replace(plan, **plan_overrides)

    backend = registry.get_backend(plan.backend, **plan.backend_options)
    spec = (registry.backend_spec(plan.backend)
            if isinstance(plan.backend, str) else None)

    def check_dtype(dtype):
        if spec is not None and dtype not in spec.weight_dtypes:
            raise ValueError(
                f"backend {spec.name!r} does not support weight_dtype "
                f"{dtype!r} (capabilities: {spec.weight_dtypes})")

    if plan.weight_dtype is not None:
        check_dtype(plan.weight_dtype)    # fail before paying to quantize
    tree = fold_bn(params, cfg, folded=folded)
    tree, weight_dtype = quantize_weights(tree, plan.weight_dtype)
    check_dtype(weight_dtype)             # dtype=None resolved from the tree

    if plan.route in ("auto", "lut"):
        # plan for the branch the backend will actually execute: a Pallas
        # backend (pinned, or auto-selected on TPU) routes via the Pallas
        # cost model and consumes real tables in its gather kernels
        is_pallas = use_pallas(getattr(backend, "pallas", False))
        tree, routes = plan_route_tables(
            tree, cfg, batch_size=plan.plan_batch,
            max_table_bytes=plan.max_table_bytes,
            build_tables=registry.wants_lut_tables(plan.backend, backend),
            constants=plan.route_constants, routes=plan.routes,
            layer_occupancy=plan.layer_occupancy,
            force="lut" if plan.route == "lut" else None,
            pallas=is_pallas)
    else:
        # the pin must hold even for a pre-annotated folded tree: stale
        # "lut" leaves would silently keep the LUT route alive
        tree = strip_lut_annotations(tree)
        routes = {}

    # static per-path occupancy, only for layers the plan routed sparse —
    # closed over at lowering, never a leaf of the traced tree
    occ_all = plan.layer_occupancy or {}
    sparse_occ = {p: occ_all[p]
                  for p, r in routes.items() if r == "lut_sparse"} or None

    resolved = dataclasses.replace(plan, weight_dtype=weight_dtype,
                                   routes=routes)
    return CompiledModel(cfg=cfg, backend=backend, folded=tree,
                         plan=resolved, jit=jit,
                         fwd=lower(tree, cfg, backend, jit=jit,
                                   layer_occupancy=sparse_occ))


def replicate_model(model: CompiledModel, *, device=None) -> CompiledModel:
    """A data-parallel serving copy of a compiled model — the fleet's
    per-replica plumbing.

    The RESOLVED ``ExecutionPlan`` is shared verbatim: replicas of one
    fleet run the same plan by construction (routes are already pinned in
    ``model.plan.routes``, so nothing can silently re-plan). With
    ``device=None`` the copy shares the folded tree AND the jitted step —
    jit executables are thread-safe, so thread-backed replicas on one
    device pay zero extra memory or compile time. With a ``device``, the
    folded tree is placed there and the plan re-lowers into a fresh step,
    so that replica's compute (weights committed to its device) runs
    data-parallel to the others."""
    if device is None:
        return CompiledModel(cfg=model.cfg, backend=model.backend,
                             folded=model.folded, plan=model.plan,
                             fwd=model._fwd, jit=model.jit)
    folded = jax.device_put(model.folded, device)
    occ_all = model.plan.layer_occupancy or {}
    sparse_occ = {p: occ_all[p]
                  for p, r in (model.plan.routes or {}).items()
                  if r == "lut_sparse"} or None
    return CompiledModel(cfg=model.cfg, backend=model.backend, folded=folded,
                         plan=model.plan, jit=model.jit,
                         fwd=lower(folded, model.cfg, model.backend,
                                   jit=model.jit,
                                   layer_occupancy=sparse_occ))
