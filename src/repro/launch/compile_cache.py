"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``launch.serve_spikformer``,
``benchmarks/infer_bench.py``).

A cache hit needs the same directory every run — the path is part of the
cache's key — so it is never a temporary directory, a pid or a timestamp:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Call it before the first compile; JAX fixes the cache directory then.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one fixed place and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
