"""Autotune the ``choose_route`` cost constants on this host.

The dispatch heuristic (``kernels/lut_matmul.py:choose_route``) compares a
cost model of the byte-LUT gather route against the unpack-then-dot route:

    lut_cost    = t*M*C*N * gather_cost * [cache_penalty]  +  G*M*K * transpose_cost
    unpack_cost = t*M*K * (N + unpack_cost)

in units of one dot FMA. The committed defaults were hand-fit to one
container's CPU; this script refits them FROM MEASUREMENT: it times both
routes of ``ops.spike_linear`` over a small (M, K, N, G) grid, solves the
model's coefficients by least squares (everything is linear in the
constants once normalized by the FMA unit), and emits the result as an
``ExecutionPlan`` JSON fragment — paste or ``--out`` it, then

    plan = ExecutionPlan.from_json(open("routes.json").read())
    model = compile(params, cfg, plan)

serves under the tuned dispatch. Only the *decisions* change; every route
stays bit-exact, so a bad fit costs throughput, never correctness.

``--pallas`` additionally times the Pallas kernel pair (VMEM byte-LUT
gather vs grouped unpack-dot) over a small grid and refits the
``choose_pallas_route`` constants (``pallas_gather_cost`` /
``pallas_dot_cost``) in the same FMA unit. On a CPU host those kernels
run under the Pallas interpreter — the samples are flagged and the fit
describes the interpreter, so refit on a TPU host before committing the
constants to a servable plan.

  PYTHONPATH=src python scripts/autotune_routes.py [--fast] [--pallas] \
      [--out routes.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spike
from repro.kernels import ops
from repro.kernels import lut_matmul as lut
from repro.kernels.lut_matmul import RouteConstants

# (m, k, n, g) grid: spans the repo's real layer shapes (conv stem rows x
# small K through encoder linears) without taking minutes. t = 8*g keeps
# every plane live.
GRID = [
    (64, 32, 16, 1), (64, 64, 64, 1), (256, 32, 64, 1), (256, 64, 16, 1),
    (512, 32, 32, 1), (512, 64, 64, 1), (1024, 12, 8, 1), (1024, 64, 32, 2),
    (2048, 32, 16, 1), (256, 128, 128, 1),
]
FAST_GRID = GRID[:5]

# Pallas grid: small shapes with varied chunk counts (C in {2..5}) and a
# multi-group point. Deliberately tiny — on a CPU host every point runs
# under the Pallas interpreter, whose cost still scales with the same
# traffic volumes the cost model uses, just with a huge unit.
PALLAS_GRID = [
    (32, 16, 8, 1), (32, 32, 16, 1), (64, 16, 16, 1),
    (64, 40, 8, 1), (48, 24, 24, 2),
]


def time_call(fn, *args, repeats: int = 3, inner: int = 4) -> float:
    """Best-of-``repeats`` wall time of ``inner`` back-to-back calls,
    compile excluded (one untimed call first)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def measure_point(m: int, k: int, n: int, g: int, *, repeats: int = 3,
                  seed: int = 0) -> dict:
    """Time unpack vs LUT for one (M, K, N, G) shape. Returns a sample."""
    t = 8 * g
    key = jax.random.PRNGKey(seed)
    x = jax.random.randint(key, (g, m, k), 0, 256, jnp.uint8)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    table = lut.build_lut(w)

    unpack = jax.jit(lambda xx: ops.spike_linear(xx, w, t=t, pallas=False,
                                                 route="unpack"))
    gather = jax.jit(lambda xx: ops.spike_linear(xx, w, t=t, pallas=False,
                                                 route="lut", table=table))
    return {
        "m": m, "k": k, "n": n, "g": g, "t": t,
        "c": lut.num_k_chunks(k),
        "table_bytes": lut.table_bytes(k, n, False),
        "unpack_s": time_call(unpack, x, repeats=repeats),
        "lut_s": time_call(gather, x, repeats=repeats),
    }


def measure_grid(grid=GRID, *, repeats: int = 3, seed: int = 0) -> list:
    samples = []
    for m, k, n, g in grid:
        s = measure_point(m, k, n, g, repeats=repeats, seed=seed)
        print(json.dumps(s))
        samples.append(s)
    return samples


def measure_sparse_point(m: int, k: int, n: int, g: int, rate: float, *,
                         repeats: int = 3, seed: int = 0) -> dict | None:
    """Time the dense LUT route against the zero-chunk-skipping route on
    channel-structured spikes at firing rate ``rate``. Returns None when
    the measured chunk occupancy leaves no budget headroom (sparse route
    would just be the dense gather)."""
    t = 8 * g
    key = jax.random.PRNGKey(seed + 1000)
    x = spike.structured_spikes(key, t=t, shape=(m, k), rate=rate)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    table = lut.build_lut(w)
    c = lut.num_k_chunks(k)
    occ = float(jnp.mean(lut.plane_indices(x)[:t] != 0))
    budget = lut.sparse_budget(c, occ)
    if budget >= c:
        return None
    dense = jax.jit(lambda xx: ops.spike_linear(xx, w, t=t, pallas=False,
                                                route="lut", table=table))
    sparse = jax.jit(lambda xx: ops.spike_linear(
        xx, w, t=t, pallas=False, route="lut_sparse", table=table,
        occupancy=occ))
    return {
        "m": m, "k": k, "n": n, "g": g, "t": t, "c": c,
        "rate": rate, "occupancy": round(occ, 4), "budget": budget,
        "table_bytes": lut.table_bytes(k, n, False),
        "lut_s": time_call(dense, x, repeats=repeats),
        "sparse_s": time_call(sparse, x, repeats=repeats),
    }


def measure_sparse_grid(grid=GRID, rates=(0.1, 0.2, 0.3), *,
                        repeats: int = 3, seed: int = 0) -> list:
    samples = []
    for m, k, n, g in grid:
        if k % 8:                      # structured spikes need whole chunks
            continue
        for rate in rates:
            s = measure_sparse_point(m, k, n, g, rate,
                                     repeats=repeats, seed=seed)
            if s is not None:
                print(json.dumps(s))
                samples.append(s)
    return samples


def measure_pallas_point(m: int, k: int, n: int, g: int, *,
                         repeats: int = 3, seed: int = 0) -> dict:
    """Time the Pallas byte-LUT gather kernel against the Pallas grouped
    unpack-dot kernel for one (M, K, N, G) shape. ``interpret`` flags
    whether the kernels ran under the Pallas interpreter (any non-TPU
    host) — such timings calibrate the interpreter, not an accelerator."""
    t = 8 * g
    key = jax.random.PRNGKey(seed + 2000)
    x = jax.random.randint(key, (g, m, k), 0, 256, jnp.uint8)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    table = lut.build_lut(w)
    gather = jax.jit(lambda xx: ops.spike_linear(xx, w, t=t, pallas=True,
                                                 route="lut", table=table))
    dot = jax.jit(lambda xx: ops.spike_linear(xx, w, t=t, pallas=True,
                                              route="unpack"))
    return {
        "m": m, "k": k, "n": n, "g": g, "t": t,
        "c": lut.num_k_chunks(k),
        "interpret": not ops.on_tpu(),
        "pallas_lut_s": time_call(gather, x, repeats=repeats),
        "pallas_dot_s": time_call(dot, x, repeats=repeats),
    }


def measure_pallas_grid(grid=PALLAS_GRID, *, repeats: int = 3,
                        seed: int = 0) -> list:
    samples = []
    for m, k, n, g in grid:
        s = measure_pallas_point(m, k, n, g, repeats=repeats, seed=seed)
        print(json.dumps(s))
        samples.append(s)
    return samples


def _lstsq(X, y):
    """Raw least-squares coefficients — callers validate signs themselves
    (a negative unit cost means the sample set cannot identify the model,
    and the right answer is the committed defaults, not a clamp)."""
    coef, *_ = np.linalg.lstsq(X, y, rcond=None)
    return coef


def fit_constants(samples: list, *,
                  base: RouteConstants = RouteConstants()) -> RouteConstants:
    """Fit (gather_cost, transpose_cost, unpack_cost) from measured route
    times; cache constants refit only when the grid spans the cache knee.

    unpack_s ~ alpha*(t*m*k*n) + alpha*unpack_cost*(t*m*k): a 2-coefficient
    linear fit gives the FMA unit ``alpha`` (seconds per FMA) and the
    unpack write cost in FMA units. lut_s ~ alpha*gather*(t*m*c*n) +
    alpha*transpose*(g*m*k) reuses that unit, so all constants land in the
    dimensionless form ``choose_route`` compares. Falls back to the
    committed defaults for anything the sample set cannot identify.
    """
    sm = [s for s in samples if s["unpack_s"] > 0 and s["lut_s"] > 0]
    if len(sm) < 3:
        return base

    fma = np.array([s["t"] * s["m"] * s["k"] * s["n"] for s in sm], float)
    wr = np.array([s["t"] * s["m"] * s["k"] for s in sm], float)
    uy = np.array([s["unpack_s"] for s in sm], float)
    a, b = _lstsq(np.stack([fma, wr], 1), uy)
    if not np.isfinite(a) or a <= 0:
        return base                     # FMA unit unidentifiable: keep defaults
    unpack_cost = float(b / a)

    small = [s for s in sm if s["table_bytes"] <= base.cache_bytes]
    large = [s for s in sm if s["table_bytes"] > base.cache_bytes]

    def fit_lut(subset):
        gath = np.array([s["t"] * s["m"] * s["c"] * s["n"] for s in subset],
                        float)
        tr = np.array([s["g"] * s["m"] * s["k"] for s in subset], float)
        ly = np.array([s["lut_s"] for s in subset], float)
        gc, tc = _lstsq(np.stack([gath, tr], 1), ly)
        return float(gc / a), float(tc / a)

    gather_cost, transpose_cost = fit_lut(small if len(small) >= 2 else sm)
    cache_penalty = base.cache_penalty
    if len(large) >= 2 and len(small) >= 2:
        g_large, _ = fit_lut(large)
        if gather_cost > 0:
            cache_penalty = float(np.clip(g_large / gather_cost, 1.0, 16.0))

    clip = lambda v, lo, hi, dflt: (float(np.clip(v, lo, hi))
                                    if np.isfinite(v) and v > 0 else dflt)
    return RouteConstants(
        gather_cost=clip(gather_cost, 0.1, 64.0, base.gather_cost),
        transpose_cost=clip(transpose_cost, 0.1, 64.0, base.transpose_cost),
        unpack_cost=clip(unpack_cost, 0.1, 256.0, base.unpack_cost),
        int_gather_discount=base.int_gather_discount,
        cache_bytes=base.cache_bytes,
        cache_penalty=cache_penalty,
    )


def fit_compact_cost(samples: list, sparse_samples: list, *,
                     base: RouteConstants) -> RouteConstants:
    """Fit the sparse route's per-(index byte x slot) compaction cost from
    measured sparse timings, reusing the dense/unpack fit for everything
    else.

    sparse_s ~ alpha * [t*m*budget*n*gather_cost*cache_penalty
                        + g*m*k*transpose_cost + t*m*c*budget*compact_cost]
    — every term but the last is pinned by ``base`` (the constants just
    fitted from the dense grid), so the residual over the compaction
    volume is a one-coefficient least squares. Falls back to ``base``
    whenever the samples cannot identify a positive cost.
    """
    sm = [s for s in samples if s["unpack_s"] > 0 and s["lut_s"] > 0]
    if len(sparse_samples) < 2 or len(sm) < 3:
        return base
    # re-derive the FMA unit (seconds per dot FMA) exactly as fit_constants
    fma = np.array([s["t"] * s["m"] * s["k"] * s["n"] for s in sm], float)
    wr = np.array([s["t"] * s["m"] * s["k"] for s in sm], float)
    uy = np.array([s["unpack_s"] for s in sm], float)
    alpha, _ = _lstsq(np.stack([fma, wr], 1), uy)
    if not np.isfinite(alpha) or alpha <= 0:
        return base
    resid, vol = [], []
    for s in sparse_samples:
        pen = (1.0 if s["table_bytes"] <= base.cache_bytes
               else base.cache_penalty)
        gather = (s["t"] * s["m"] * s["budget"] * s["n"]
                  * base.gather_cost * pen)
        transpose = s["g"] * s["m"] * s["k"] * base.transpose_cost
        resid.append(s["sparse_s"] / alpha - gather - transpose)
        vol.append(s["t"] * s["m"] * s["c"] * s["budget"])
    compact, = _lstsq(np.array(vol, float)[:, None], np.array(resid, float))
    if not np.isfinite(compact) or compact <= 0:
        return base
    return dataclasses.replace(
        base, compact_cost=float(np.clip(compact, 1.0, 256.0)))


def fit_pallas_constants(samples: list, pallas_samples: list, *,
                         base: RouteConstants) -> RouteConstants:
    """Fit (pallas_gather_cost, pallas_dot_cost) for ``choose_pallas_route``
    from measured Pallas kernel timings, expressed in the SAME FMA unit as
    the CPU fit (``alpha`` re-derived from the unpack samples, so the two
    cost models stay comparable in one RouteConstants). The bit-transpose
    term is pinned at ``base.transpose_cost``; each pallas constant is
    then a one-coefficient least squares over its traffic volume
    (t*M*C*N gathered elements, t*M*K*N dot FMAs). Falls back to ``base``
    whenever the samples cannot identify a positive cost."""
    sm = [s for s in samples if s["unpack_s"] > 0 and s["lut_s"] > 0]
    if len(pallas_samples) < 2 or len(sm) < 3:
        return base
    fma = np.array([s["t"] * s["m"] * s["k"] * s["n"] for s in sm], float)
    wr = np.array([s["t"] * s["m"] * s["k"] for s in sm], float)
    uy = np.array([s["unpack_s"] for s in sm], float)
    alpha, _ = _lstsq(np.stack([fma, wr], 1), uy)
    if not np.isfinite(alpha) or alpha <= 0:
        return base
    gvol = np.array([s["t"] * s["m"] * s["c"] * s["n"]
                     for s in pallas_samples], float)
    gres = np.array([s["pallas_lut_s"] / alpha
                     - s["g"] * s["m"] * s["k"] * base.transpose_cost
                     for s in pallas_samples], float)
    gc, = _lstsq(gvol[:, None], gres)
    dvol = np.array([s["t"] * s["m"] * s["k"] * s["n"]
                     for s in pallas_samples], float)
    dy = np.array([s["pallas_dot_s"] / alpha for s in pallas_samples], float)
    dc, = _lstsq(dvol[:, None], dy)
    # interpreter-fitted constants can be orders of magnitude above an
    # accelerator's; the cap only guards against a degenerate fit blowing
    # up the JSON, relative ordering is what the dispatch compares
    clip = lambda v, dflt: (float(np.clip(v, 0.05, 4096.0))
                            if np.isfinite(v) and v > 0 else dflt)
    return dataclasses.replace(
        base,
        pallas_gather_cost=clip(gc, base.pallas_gather_cost),
        pallas_dot_cost=clip(dc, base.pallas_dot_cost))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="half the grid, one repeat (CI/smoke)")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pallas", action="store_true",
                    help="also time the Pallas kernel pair (interpret mode "
                         "off-TPU) and refit pallas_gather_cost / "
                         "pallas_dot_cost for choose_pallas_route")
    ap.add_argument("--firing-rates", default=None,
                    help="comma-separated firing rates (e.g. 0.1,0.2,0.3): "
                         "also measure the zero-chunk-skipping route on "
                         "structured spikes and fit compact_cost")
    ap.add_argument("--out", default=None,
                    help="write the ExecutionPlan JSON fragment here "
                         "(stdout always gets it)")
    args = ap.parse_args(argv)

    grid = FAST_GRID if args.fast else GRID
    repeats = args.repeats or (1 if args.fast else 3)
    samples = measure_grid(grid, repeats=repeats, seed=args.seed)
    constants = fit_constants(samples)
    sparse_samples = []
    if args.firing_rates:
        rates = tuple(float(r) for r in args.firing_rates.split(","))
        sparse_samples = measure_sparse_grid(grid, rates, repeats=repeats,
                                             seed=args.seed)
        constants = fit_compact_cost(samples, sparse_samples, base=constants)
    pallas_samples = []
    if args.pallas:
        p_grid = PALLAS_GRID[:3] if args.fast else PALLAS_GRID
        pallas_samples = measure_pallas_grid(p_grid, repeats=repeats,
                                             seed=args.seed)
        constants = fit_pallas_constants(samples, pallas_samples,
                                         base=constants)

    # the committable artifact: a fragment ExecutionPlan.from_json accepts
    fragment = {"route_constants": constants.to_dict()}
    text = json.dumps(fragment, indent=1, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")

    # sanity: how often the tuned model agrees with measurement on the grid
    agree = sum(
        (ops.choose_route(m=s["m"], k=s["k"], n=s["n"], g=s["g"], t=s["t"],
                          constants=constants) == "lut")
        == (s["lut_s"] < s["unpack_s"]) for s in samples)
    summary = {"grid_points": len(samples),
               "tuned_agreement": f"{agree}/{len(samples)}"}
    if sparse_samples:
        sagree = sum(
            (ops.choose_route(m=s["m"], k=s["k"], n=s["n"], g=s["g"],
                              t=s["t"], constants=constants,
                              occupancy=s["occupancy"]) == "lut_sparse")
            == (s["sparse_s"] < s["lut_s"]) for s in sparse_samples)
        summary["sparse_points"] = len(sparse_samples)
        summary["sparse_agreement"] = f"{sagree}/{len(sparse_samples)}"
    if pallas_samples:
        pagree = sum(
            (ops.choose_pallas_route(m=s["m"], k=s["k"], n=s["n"], g=s["g"],
                                     t=s["t"], constants=constants) == "lut")
            == (s["pallas_lut_s"] < s["pallas_dot_s"])
            for s in pallas_samples)
        summary["pallas_points"] = len(pallas_samples)
        summary["pallas_agreement"] = f"{pagree}/{len(pallas_samples)}"
        summary["pallas_interpret"] = bool(pallas_samples[0]["interpret"])
        if summary["pallas_interpret"]:
            print("note: pallas samples ran under the Pallas interpreter — "
                  "the fitted pallas constants describe this host's "
                  "interpreter; refit on a TPU before serving them",
                  file=sys.stderr)
    print(json.dumps(summary))
    return constants


if __name__ == "__main__":
    main()
