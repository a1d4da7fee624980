"""Backend registry: inference backends as declared capabilities, not
string-matched branches.

``compile()`` resolves an ``ExecutionPlan.backend`` name through this
registry; a backend is a *registration* — name, factory, and the
capabilities the compile pipeline consults — so adding one (the forthcoming
Pallas/TPU backend, a sparse-event backend, ...) never edits core dispatch:

    from repro.infer.registry import register_backend

    register_backend("pallas_tpu", lambda **opts: PallasBackend(**opts),
                     weight_dtypes=("float32", "int8"),
                     device_kinds=("tpu",), wants_lut_tables=False)

Capabilities:

* ``weight_dtypes`` — which ``ExecutionPlan.weight_dtype`` values the
  backend's kernels execute; ``compile()`` rejects a plan outside the set.
* ``device_kinds`` — JAX platform names the backend is built for.
  ``get_backend`` enforces this against the current JAX platform: asking
  for a TPU-only backend on a CPU host fails up front with the available
  platforms named, instead of tracing kernels that cannot lower. Passing
  ``interpret=True`` in the options means exactly "run the Pallas kernels
  under the interpreter, on a CPU host" — how tier-1 exercises the
  ``packed_pallas`` kernels. On a TPU host it is refused: there the
  kernels always run compiled (``kernels.device.resolve_interpret``).
* ``wants_lut_tables`` — whether the route planner should build and cache
  the (C, 256, N) byte-LUT tables into this backend's folded tree, or only
  flag planned layers. ``None`` defers to the backend *instance* (the
  packed backend answers per ``pallas`` mode).

The built-in "packed" and "reference" backends register themselves when
``repro.infer.backends`` imports (any ``repro.infer`` import does).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One registered backend: how to build it and what it can do."""
    name: str
    factory: Callable[..., Any]
    weight_dtypes: tuple[str, ...] = ("float32", "int8")
    device_kinds: tuple[str, ...] = ("cpu", "gpu", "tpu")
    wants_lut_tables: bool | None = None   # None: ask the instance
    aliases: tuple[str, ...] = ()

    def make(self, **options):
        return self.factory(**options)


_REGISTRY: dict[str, BackendSpec] = {}
_ALIASES: dict[str, str] = {}


def register_backend(name: str, factory: Callable[..., Any], *,
                     weight_dtypes=("float32", "int8"),
                     device_kinds=("cpu", "gpu", "tpu"),
                     wants_lut_tables: bool | None = None,
                     aliases=(), overwrite: bool = False) -> BackendSpec:
    """Register ``factory(**options) -> backend`` under ``name``.

    ``overwrite=False`` (the default) refuses to shadow an existing
    registration — re-registering a name is almost always an import-order
    accident, and a silent swap would corrupt every plan naming it.
    """
    taken = {name, *aliases} & ({*_REGISTRY} | {*_ALIASES})
    if taken and not overwrite:
        raise ValueError(f"backend name(s) {sorted(taken)} already "
                         "registered; pass overwrite=True to replace")
    # an overwrite must actually take: every name the new spec claims is
    # evicted first — a directly-registered spec goes entirely (with its
    # aliases); a claimed *alias* is detached from its owner, which keeps
    # its primary name. Either way resolution can't silently keep routing
    # an old spec through a stale entry.
    for key in {name, *aliases}:
        old = _REGISTRY.pop(key, None)
        if old is not None:
            for a in old.aliases:
                _ALIASES.pop(a, None)
            continue
        owner = _ALIASES.pop(key, None)
        if owner is not None and owner in _REGISTRY:
            kept = _REGISTRY[owner]
            _REGISTRY[owner] = dataclasses.replace(
                kept, aliases=tuple(a for a in kept.aliases if a != key))
    spec = BackendSpec(name=name, factory=factory,
                       weight_dtypes=tuple(weight_dtypes),
                       device_kinds=tuple(device_kinds),
                       wants_lut_tables=wants_lut_tables,
                       aliases=tuple(aliases))
    _REGISTRY[name] = spec
    for a in aliases:
        _ALIASES[a] = name
    return spec


def unregister_backend(name: str) -> None:
    """Remove a registration, by name or alias (tests use this to clean
    up); removing via an alias drops the whole spec and its aliases."""
    spec = _REGISTRY.pop(_ALIASES.get(name, name), None)
    if spec is not None:
        for a in spec.aliases:
            _ALIASES.pop(a, None)


def backend_spec(name: str) -> BackendSpec:
    """Spec by name or alias; unknown names fail with the available set."""
    key = _ALIASES.get(name, name)
    spec = _REGISTRY.get(key)
    if spec is None:
        raise ValueError(f"unknown inference backend {name!r}; registered: "
                         f"{sorted(_REGISTRY)}")
    return spec


def list_backends(*, weight_dtype: str | None = None,
                  device_kind: str | None = None) -> list[str]:
    """Registered backend names, filtered by capability."""
    names = []
    for name, spec in sorted(_REGISTRY.items()):
        if weight_dtype is not None and weight_dtype not in spec.weight_dtypes:
            continue
        if device_kind is not None and device_kind not in spec.device_kinds:
            continue
        names.append(name)
    return names


def get_backend(name, **options):
    """Backend *instance* by registered name; instances pass through
    (callers may hand ``compile()`` a pre-built backend). ``options`` go to the factory — unknown keys are the
    factory's problem, by design.

    The spec's ``device_kinds`` is enforced here: a backend built for
    hardware this host does not have fails loudly, naming the platforms
    that ARE available and the ``interpret=True`` option that runs its
    kernels under the Pallas interpreter instead (the tier-1 testing
    mode). The option is an explicit opt-in so nobody mistakes interpreted
    timings for the real thing, and it is CPU-only: on a TPU host it is
    refused rather than ignored.
    """
    if not isinstance(name, str):
        return name
    spec = backend_spec(name)
    import jax
    platform = jax.default_backend()
    interpret = options.get("interpret")
    if interpret and platform == "tpu":
        raise ValueError(
            f"backend {spec.name!r}: interpret=True selects the Pallas "
            "interpreter, a CPU test mode; on a TPU the kernels run "
            "compiled — drop the option")
    if not interpret and platform not in spec.device_kinds:
        available = sorted({d.platform for d in jax.devices()})
        raise ValueError(
            f"backend {spec.name!r} targets device kind(s) "
            f"{sorted(spec.device_kinds)} but the current JAX platform "
            f"is {platform!r} (available: {available}); pass "
            "backend_options={'interpret': True} to run its Pallas "
            "kernels in interpret mode on this host (bit-exact, "
            "test-speed only)")
    return spec.make(**options)


def wants_lut_tables(name_or_instance, backend) -> bool:
    """Resolve the table capability: spec declaration first, else the
    instance's own ``wants_lut_tables`` attribute, else True."""
    if isinstance(name_or_instance, str):
        declared = backend_spec(name_or_instance).wants_lut_tables
        if declared is not None:
            return declared
    return bool(getattr(backend, "wants_lut_tables", True))
