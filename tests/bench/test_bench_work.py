"""The work counter (``bench/work.py``) against the hand count from
Spikformer-8-512's shapes, and against the program's own layer list."""
import json

import pytest

from bench_tiny import ROOT

from bench import work
from bench.models import spikformer as mm


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, gmac", [("spikformer_8_512_t4", 22.232),
                                        ("spikformer_8_512_t16", 88.898)])
def test_macs_per_image_match_the_hand_count(name, gmac):
    layers = mm.layers(config(name))
    assert sum(x.macs for x in layers) / 1e9 == pytest.approx(gmac, abs=5e-4)


def test_t4_split_by_family():
    fam = work.macs_by_family(mm.layers(config("spikformer_8_512_t4")))
    total = sum(fam.values())
    share = {k: round(100 * v / total, 1) for k, v in fam.items()}
    assert share["mlp"] == 59.2
    assert share["qkvo"] == 29.6
    assert share["stdp"] == 5.7
    assert share["stem"] == 5.6
    assert fam["head"] == 512 * 1000


def test_exact_layer_counts():
    cfg = config("spikformer_8_512_t4")
    by_path = {x.path: x for x in mm.layers(cfg)}
    assert by_path["scs/conv0"].macs == 112 * 112 * 12 * 64        # once
    assert by_path["scs/conv1"].macs == 4 * 56 * 56 * 256 * 128
    assert by_path["blocks/b0/mlp/fc1"].macs == 4 * 196 * 512 * 2048
    assert by_path["blocks/b0/ssa/stdp"].macs == 2 * 4 * 8 * 196 * 196 * 64
    # packed input + int8 weights + packed output, one group at T=4
    assert by_path["blocks/b0/mlp/fc1"].bytes == (196 * 512 + 512 * 2048
                                                   + 196 * 2048)


def test_work_scales_with_batch():
    cfg = config("spikformer_8_512_t16")
    one, eight = mm.layers(cfg, 1), mm.layers(cfg, 8)
    assert [8 * x.macs for x in one] == [x.macs for x in eight]


def test_matmul_layers_are_the_programs_spiking_linears():
    """Every spiking linear the program runs is counted, whatever its
    route, and nothing else is a matmul layer."""
    from repro.core.spikformer import SpikformerConfig
    from repro.infer.compile import linear_layer_paths

    cfg = config("spikformer_8_512_t4")
    program = linear_layer_paths(SpikformerConfig(
        **{**mm.sizes(cfg), "scs_channels": tuple(cfg["scs_channels"])}))
    assert [x.path for x in mm.layers(cfg) if x.matmul] == program


class _View:
    """The part of a run that ``matmul_roofline`` reads."""

    def __init__(self, family, cfg):
        from bench.trace_reduce import Module, Reduced
        self.cell = type("C", (), {"config": cfg})
        self.peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())[
            "TPU v5 lite"]
        self.trace = Reduced(window_ns=(0, 2e9), devices=1, busy_ns=1e9,
                             family_ns={family: 1e9}, gaps=[], modules=[
                                 Module(0, 1e9, {family: 0.5e9}, 32),
                                 Module(1e9, 2e9, {family: 0.5e9}, 32)])

    def layers(self, batch):
        return mm.layers(self.cell.config, batch)

    def least_time_s(self, layers):
        return work.least_time_s(
            layers, ops_per_s=self.peaks["int8_ops_per_s"],
            bytes_per_s=self.peaks["hbm_bytes_per_s"])


def test_roofline_is_the_same_whichever_kernel_runs_the_matmuls():
    from bench import spec
    read = spec.metric_reader("matmul_roofline")
    cfg = config("spikformer_8_512_t4")
    got = {fam: read(_View(fam, cfg)) for fam in
           ("lut_gather_matmul", "spike_matmul", "tflif_lut_matmul")}
    assert len(set(got.values())) == 1
    least, compute_share = work.least_time_s(
        [x for x in mm.layers(cfg, 32) if x.matmul],
        ops_per_s=393e12, bytes_per_s=819e9)
    assert got["spike_matmul"] == pytest.approx(100 * 2 * least / 1.0)
    assert compute_share > 0.9          # these layers are compute-bound
    assert read(_View("xla:fusion", cfg)) is None   # no kernel, no reading
