"""The QKFormer cell's files: the work count against the hand count at
published widths and against the program's layer list, a tiny QKFormer
cell run end to end on the CPU, the comparison that decides ``correct``
failing a program without the QKTA mask and the int4 control, and
``qkta_roofline`` read from a synthetic reduced trace."""
import json

import numpy as np
import pytest

from bench_tiny import ROOT, committed

from bench import check, spec, work
from bench.models import qkformer as mm
from bench.reference import qkformer as ref_model

NAME = "qkformer_10_768_t4"
TINY = {"img_size": 32, "embed_dims": [48, 96, 192], "depths": [1, 2, 1],
        "heads": 2, "num_classes": 10, "check_images": 12}
CLOSED = {"arrivals": "closed", "clients": 2, "images_per_request": 2,
          "warmup_s": 0.2, "buckets": [4], "policy": {}}


def config(tiny=False):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{NAME}.json")
                     .read_text())
    return {**cfg, **TINY} if tiny else cfg


def test_macs_per_image_match_the_hand_count():
    layers = mm.layers(config())
    assert sum(x.macs for x in layers) / 1e9 == pytest.approx(94.036,
                                                              abs=5e-4)
    total = sum(x.macs for x in layers)

    def share(*prefixes):
        return round(100 * sum(x.macs for x in layers
                               if x.path.startswith(prefixes)) / total, 1)

    assert share("stage1/embed", "stage2/embed", "stage3/embed") == 40.7
    assert share("stage1/b", "stage2/b") == 16.2
    assert share("stage3/b") == 43.1


def test_exact_layer_counts():
    by_path = {x.path: x for x in mm.layers(config())}
    assert by_path["stage1/embed/conv0"].macs == 224 * 224 * 27 * 96  # once
    assert by_path["stage1/embed/conv1"].macs == 4 * 112 * 112 * 864 * 192
    assert by_path["stage1/embed/short"].macs == 4 * 56 * 56 * 96 * 192
    assert by_path["stage3/embed/conv1"].macs == 4 * 14 * 14 * 6912 * 768
    assert by_path["stage2/b1/mlp/fc1"].macs == 4 * 784 * 384 * 1536
    assert by_path["stage3/b6/ssa/stdp"].macs == 2 * 4 * 8 * 196 * 196 * 96
    mask = by_path["stage1/b0/qkta/mask"]
    assert (mask.family, mask.macs, mask.matmul) == ("qkta", 0, False)
    assert mask.bytes == 3 * 3136 * 192       # Q, K in and X' out, packed
    # packed im2col input + int8 weights + packed output, one group at T=4
    assert by_path["stage2/embed/conv0"].bytes == (
        3136 * 1728 + 1728 * 384 + 3136 * 384)


def test_work_scales_with_batch():
    one, sixteen = mm.layers(config(), 1), mm.layers(config(), 16)
    assert [16 * x.macs for x in one] == [x.macs for x in sixteen]
    assert [16 * x.bytes for x in one if x.family == "qkta"] == [
        x.bytes for x in sixteen if x.family == "qkta"]


def test_matmul_layers_are_the_programs_spike_matmuls():
    from repro.core.qkformer import QKFormerConfig
    from repro.infer.compile import linear_layer_paths

    cfg = config()
    program = linear_layer_paths(QKFormerConfig(**{
        **mm.sizes(cfg), **{k: tuple(cfg[k]) for k in mm.TUPLE_KEYS}}))
    assert [x.path for x in mm.layers(cfg) if x.matmul] == program
    assert len(program) == 67


def test_config_is_the_published_network():
    cfg, entry = config(), next(c for c in committed()["configs"]
                                if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"] == []
    assert (cfg["embed_dims"], cfg["depths"], cfg["heads"]) == (
        [192, 384, 768], [1, 2, 7], 8)
    shapes = ref_model.layer_list(cfg)
    weights = sum(int(np.prod(s)) for _, s in shapes)
    weights += 768 * 1000 + 1000
    assert abs(weights / 64.96e6 - 1) < 0.01


def tiny_cell(tmp_path):
    for sub in ("configs", "traffic", "arrivals", "metrics"):
        (tmp_path / sub).mkdir(parents=True, exist_ok=True)
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(config(True)))
    (tmp_path / "traffic" / "closed.json").write_text(json.dumps(CLOSED))
    for sub in ("arrivals", "metrics"):
        for f in (ROOT / "bench" / sub).glob("*.py"):
            (tmp_path / sub / f.name).write_text(f.read_text())
    bench = committed()
    bench["workloads"] = [{"name": "tiny.qk", "config": "tiny",
                           "traffic": "closed", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:        # the metrics of the QKFormer cell
            m["workloads"] = (["tiny.qk"] if "qkformer_t4.bulk"
                              in m["workloads"] else [])
    return spec.resolve("tiny.qk", bench, bench_dir=tmp_path)


def test_tiny_cell_runs_end_to_end_and_is_correct(tmp_path):
    from bench import run
    cell = tiny_cell(tmp_path)
    assert [m["name"] for m in cell.end_to_end] == ["img_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "step_mfu", "matmul_roofline", "device_idle_pct", "qkta_roofline"]
    result, checks = run.run_cell(cell, 2 ** 31 + 23, 0.6, False)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert checks["logit_gap"]["value"] < 0.15 / 5


@pytest.fixture(scope="module")
def tiny_sample():
    cfg = config(True)
    params = mm.init_params(cfg, 2 ** 31 + 99)
    images = np.random.default_rng(3).integers(
        0, 256, (12, 32, 32, 3), dtype=np.uint8)
    return cfg, params, images, mm.reference_logits(params, cfg, images,
                                                   bits=cfg["weight_bits"])


def test_mask_density_is_neither_full_nor_empty(tiny_sample):
    cfg, params, images, ref = tiny_sample
    assert check.distinct(ref)
    dens = ref_model.mask_density(params, cfg, images[:8])
    assert len(dens) == 3 and all(0.25 < d < 0.75 for d in dens), dens


@pytest.mark.parametrize("control", ["int4", "qkta_mask_removed"])
def test_control_fails_the_limits(tiny_sample, monkeypatch, control):
    """The reference with int4 weights in the program's place, and the
    program with its QKTA mask removed (X' = K), fail the committed
    limits; the sound program passes them."""
    from repro.infer.backends import PackedBackend

    cfg, params, images, ref = tiny_sample
    if control == "int4":
        low = mm.reference_logits(params, cfg, images,
                                  bits=cfg["control_bits"])
    else:
        sound = mm.build(params, cfg, [4])[0].logits(images)
        assert check.judge(check.compare(sound, ref), cfg["limits"])
        monkeypatch.setattr(PackedBackend, "qkta",
                            lambda self, q, k, **kw: k)
        low = mm.build(params, cfg, [4])[0].logits(images)
    numbers = check.compare(low, ref)
    assert not check.judge(numbers, cfg["limits"]), numbers


class _View:
    """The part of a run that ``qkta_roofline`` reads."""

    def __init__(self, family_ns):
        from bench.trace_reduce import Module, Reduced
        self.cell = type("C", (), {"config": config()})
        self.peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())[
            "TPU v5 lite"]
        self.trace = Reduced(
            window_ns=(0, 2e9), devices=1, busy_ns=1e9, family_ns={},
            gaps=[], modules=[Module(0, 1e9, family_ns, 16),
                              Module(1e9, 2e9, family_ns, 16),
                              Module(2e9, 2.1e9, family_ns, None)])

    def layers(self, batch):
        return mm.layers(self.cell.config, batch)

    def least_time_s(self, layers):
        return work.least_time_s(
            layers, ops_per_s=self.peaks["int8_ops_per_s"],
            bytes_per_s=self.peaks["hbm_bytes_per_s"])


@pytest.mark.parametrize("family", ["qk_token_attention",
                                    "xla:qk_token_attention"])
def test_qkta_roofline_reads_the_kernel_time(family):
    read = spec.metric_reader("qkta_roofline")
    got = read(_View({family: 1e6, "spike_matmul": 5e7}))
    bytes16 = sum(x.bytes for x in mm.layers(config(), 16)
                  if x.family == "qkta")
    assert bytes16 == 3 * 16 * (3136 * 192 + 2 * 784 * 384)
    # two traced steps at bucket 16, 1 ms of the kernel in each
    assert got == pytest.approx(100 * 2 * (bytes16 / 819e9) / 2e-3)
    assert read(_View({"spike_matmul": 5e7})) is None
