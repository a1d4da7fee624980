"""Data pipeline determinism/restart + checkpointer atomicity/elasticity."""
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import (DataConfig, DataPipeline, synthetic_lm_batch,
                                 image_batch, TokenFileSource)
from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_synthetic_deterministic():
    cfg = DataConfig(seq=32, global_batch=4, vocab=100, seed=7)
    a = synthetic_lm_batch(cfg, step=3)
    b = synthetic_lm_batch(cfg, step=3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = synthetic_lm_batch(cfg, step=4)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_labels_are_shifted_tokens():
    cfg = DataConfig(seq=32, global_batch=2, vocab=100)
    b = synthetic_lm_batch(cfg, 0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_host_sharding_disjoint_and_consistent():
    """2 hosts each produce half the global batch; together they equal the
    1-host global batch (elastic data semantics)."""
    g = DataConfig(seq=16, global_batch=4, vocab=50, seed=1)
    h0 = DataConfig(seq=16, global_batch=4, vocab=50, seed=1, host_id=0,
                    n_hosts=2)
    h1 = DataConfig(seq=16, global_batch=4, vocab=50, seed=1, host_id=1,
                    n_hosts=2)
    full = synthetic_lm_batch(g, 5)["tokens"]
    part0 = synthetic_lm_batch(h0, 5)["tokens"]
    part1 = synthetic_lm_batch(h1, 5)["tokens"]
    np.testing.assert_array_equal(np.concatenate([part0, part1]), full)


def test_pipeline_restart_exact():
    cfg = DataConfig(seq=16, global_batch=2, vocab=64, seed=3, prefetch=1)
    p = DataPipeline(cfg)
    seen = [next(p) for _ in range(5)]
    state = p.state_dict()
    nxt = next(p)
    p.close()

    q = DataPipeline.restore(cfg, state)
    resumed = next(q)
    q.close()
    np.testing.assert_array_equal(np.asarray(nxt["tokens"]),
                                  np.asarray(resumed["tokens"]))


def test_token_file_source(tmp_path):
    path = tmp_path / "toks.bin"
    np.arange(1000, dtype=np.uint32).tofile(path)
    cfg = DataConfig(seq=9, global_batch=2, kind="token_file", path=str(path))
    src = TokenFileSource(cfg)
    b = src.batch(0)
    assert b["tokens"].shape == (2, 9)
    np.testing.assert_array_equal(b["tokens"][0], np.arange(9))
    np.testing.assert_array_equal(b["labels"][0], np.arange(1, 10))


def test_image_batch_learnable_structure():
    cfg = DataConfig(global_batch=8, kind="images", image_size=16, n_classes=4)
    b = image_batch(cfg, 0)
    assert b["image"].shape == (8, 16, 16, 3) and b["image"].dtype == np.uint8
    assert set(np.unique(b["label"])) <= set(range(4))


# ---------------------------------------------------------------------------
# checkpointer
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"layer": {"w": jax.random.normal(k, (4, 4)),
                      "b": jnp.zeros((4,))},
            "step_count": jnp.int32(7)}


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(10, tree, extra={"data": {"step": 10, "seed": 0}}, block=True)
    assert ck.latest_step() == 10
    skel = jax.eval_shape(lambda: tree)
    got, extra = ck.restore(skeleton=skel)
    np.testing.assert_allclose(np.asarray(got["layer"]["w"]),
                               np.asarray(tree["layer"]["w"]))
    assert extra["data"]["step"] == 10


def test_atomicity_tmp_dirs_invisible(tmp_path):
    ck = Checkpointer(str(tmp_path))
    # a crashed half-write: tmp dir with files but no commit rename
    bad = tmp_path / "step_00000099.tmp"
    bad.mkdir()
    (bad / "x.npy").write_bytes(b"junk")
    # and a dir missing its manifest
    bad2 = tmp_path / "step_00000098"
    bad2.mkdir()
    assert ck.latest_step() is None
    ck.save(5, _tree(), block=True)
    assert ck.latest_step() == 5


def test_gc_keeps_newest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(), block=True)
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4]


def test_checksum_detects_corruption(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _tree(), block=True)
    # corrupt one leaf
    f = next((tmp_path / "step_00000001").glob("layer.w.npy"))
    arr = np.load(f)
    arr[0, 0] += 1.0
    np.save(f, arr)
    with pytest.raises(IOError, match="checksum"):
        ck.restore(skeleton=jax.eval_shape(_tree))


def test_async_save_does_not_block(tmp_path):
    ck = Checkpointer(str(tmp_path))
    big = {"w": jnp.zeros((2000, 2000))}
    t0 = time.time()
    ck.save(1, big)
    t_return = time.time() - t0
    ck.wait()
    assert t_return < 1.0
    assert ck.latest_step() == 1


def test_elastic_restore_onto_mesh(tmp_path):
    """Save unsharded, restore with explicit NamedShardings for a 1-device
    mesh (the elastic path: same call works for any target device count)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(2, tree, block=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), jax.eval_shape(lambda: tree))
    got, _ = ck.restore(skeleton=jax.eval_shape(lambda: tree), shardings=sh)
    assert got["layer"]["w"].sharding == NamedSharding(mesh, P())
