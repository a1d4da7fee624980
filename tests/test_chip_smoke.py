"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, and its
phases run end to end at the reduced config (the chip runs them at the
published widths). Plus the compile-cache placement its entry points share.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs.spikformer_v2 import REDUCED, REDUCED_T16
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_no_result(smoke, monkeypatch, capsys):
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_one_chip_phases_at_reduced_size(smoke, capsys):
    smoke.one_chip(REDUCED, REDUCED_T16, seed=0, n_requests=6)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    checks = {l["check"]: l for l in lines if "check" in l}
    assert checks["T=4 int8 logits"]["max_abs_logit_diff"] == 0.0
    assert checks["T=16 int8 logits"]["max_abs_logit_diff"] == 0.0
    served = checks["AsyncServeRuntime"]
    assert (served["dropped"], served["rejected"], served["failed"]) == (
        0, 0, 0)


def test_a_failed_request_fails_the_check(smoke):
    with pytest.raises(smoke.CheckFailed, match="requests_failed"):
        smoke.check_served("x", {"requests_accepted": 2,
                                 "requests_dropped": 0,
                                 "requests_rejected": 0,
                                 "images_completed": 1, "completed_fps": 1.0,
                                 "latency_p99_s": 0.1},
                           {"requests_failed": 1, "requests_rejected": 0,
                            "requests": 1}, 2)


def test_four_chip_fleet_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        import chip_smoke
        from repro.configs.spikformer_v2 import REDUCED
        chip_smoke.four_chips(REDUCED, seed=0)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    fleets = [json.loads(l) for l in out.stdout.splitlines()
              if '"phase": "fleet"' in l]
    assert [f["replicas"] for f in fleets] == [1, 4]
    assert len({d[0] for d in fleets[1]["devices"]}) == 4


def test_compile_cache_honours_the_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
