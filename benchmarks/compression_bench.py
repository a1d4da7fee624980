"""Gradient-compression transport benchmark: HLO collective bytes of the
cross-pod reduction with fp32 vs int8(+scale) payloads, plus the numerics
cost (quantization error with/without error feedback).

The transport measurement lowers a shard_map over an N-device CPU mesh and
counts all-gather/all-reduce payload bytes with the same analyzer the
roofline uses — the wire saving is visible structurally, no TPU needed.
"""
from __future__ import annotations

import os

import numpy as np


def transport_bytes() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_mesh
    from repro.optim.compression import compressed_psum_int8

    n = 1 << 20  # 4 MB fp32 gradient shard
    mesh = make_mesh((jax.device_count(),), ("x",))

    def f_fp32(x):
        return jax.lax.pmean(x, "x")

    def f_int8(x):
        return compressed_psum_int8(x, "x")

    out = {}
    for name, f in (("fp32_pmean", f_fp32), ("int8_ef", f_int8)):
        sf = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        text = jax.jit(sf).lower(
            jax.ShapeDtypeStruct((n,), jnp.float32)).compile().as_text()
        c = analyze(text)
        out[f"{name}_collective_bytes"] = c.collective_total
    return out


def numerics() -> dict:
    import jax
    import jax.numpy as jnp
    from repro.optim.compression import ef_init, ef_compress

    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 0.01}
    out = {}
    # one-shot error
    deq, _ = ef_compress(g, ef_init(g), method="int8")
    out["int8_one_shot_rel_err"] = float(
        jnp.linalg.norm(deq["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    # accumulated with EF over 20 steps of the same grad
    ef = ef_init(g)
    tot = jnp.zeros_like(g["w"])
    for _ in range(20):
        deq, ef = ef_compress(g, ef, method="int8")
        tot += deq["w"]
    out["int8_ef_20step_rel_err"] = float(
        jnp.linalg.norm(tot / 20 - g["w"]) / jnp.linalg.norm(g["w"]))
    return out


def run() -> dict:
    rows = transport_bytes()
    rows.update(numerics())
    return rows


def main():
    for k, v in run().items():
        print(f"compression,{k},{v:.6g}")


if __name__ == "__main__":
    main()
