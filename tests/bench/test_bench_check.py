"""The comparison that decides ``correct``, on the CPU at a tiny size: a
sound run passes, a run whose timed path is broken underneath fails, and
the lower-precision control fails the committed limits."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import ROOT, run_tiny, tiny_bench

from bench import check
from bench.reference import spikformer as ref_model
from repro.infer.compile import CompiledModel

CONFIGS = ("spikformer_8_512_t4", "spikformer_8_512_t16")


def limits(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["limits"]


def test_sound_run_is_correct(tmp_path):
    result, checks = run_tiny(tmp_path)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(checks) == {"logit_gap"}
    assert list(result)[-1] == "checks"


def _broken(monkeypatch, corrupt):
    real = CompiledModel.step

    def step(self, images):
        return corrupt(real(self, images))

    monkeypatch.setattr(CompiledModel, "step", step)


def test_an_answer_altered_where_it_is_produced_fails(tmp_path, monkeypatch):
    _broken(monkeypatch, lambda out: out.at[0].set(jnp.roll(out[0], 1)))
    result, checks = run_tiny(tmp_path)
    assert not result["correct"], checks


def test_half_the_batch_left_out_fails(tmp_path, monkeypatch):
    def half(out):
        h = out.shape[0] // 2
        return jnp.concatenate([out[:h], out[:out.shape[0] - h]])

    _broken(monkeypatch, half)
    result, checks = run_tiny(tmp_path)
    assert not result["correct"], checks


@pytest.mark.parametrize("config", CONFIGS)
def test_control_fails_the_limits(tmp_path, config):
    """The reference at the next precision below the configuration's
    (int4 for int8 weights), put in the program's place."""
    bench, bench_dir = tiny_bench(tmp_path, config=config)
    cfg = json.loads((bench_dir / "configs" / "tiny.json").read_text())
    sizes = {k: cfg[k] for k in ("img_size", "in_channels", "timesteps",
                                 "dim", "depth", "heads", "mlp_ratio",
                                 "num_classes", "scs_channels", "residual",
                                 "attn_scale")}
    params = ref_model.init_params(sizes, 2 ** 31 + 99)
    images = np.random.default_rng(3).integers(
        0, 256, (16, 32, 32, 3), dtype=np.uint8)
    ref = ref_model.logits(params, sizes, images, bits=cfg["weight_bits"])
    low = ref_model.logits(params, sizes, images, bits=cfg["control_bits"])
    assert check.distinct(ref)
    numbers = check.compare(low, ref)
    assert not check.judge(numbers, limits(config)), numbers
    assert check.judge(check.compare(ref, ref), limits(config))


def test_compare_reads_gaps_in_reference_spreads():
    ref = np.array([[0.0, 1.0, 2.0], [2.0, 0.0, -2.0]])
    served = ref.copy()
    served[1] = [0.0, 0.0, 3.0]
    got = check.compare(served, ref)
    assert got == {"logit_gap": pytest.approx(5.0 / ref[1].std())}
    assert not check.distinct(np.ones((3, 4)))
