"""Checkpointing (atomic/async/restore) + data pipeline determinism and
straggler skip."""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt import (save_checkpoint, restore_checkpoint,
                        async_save_checkpoint, latest_step)
from repro.data.synthetic import SyntheticLMDataset
from repro.data.pipeline import DataPipeline


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
            "b": {"c": jnp.asarray(rng.integers(0, 5, (3,)), jnp.int32),
                  "d": jnp.asarray(rng.normal(size=()), jnp.float32)}}


def test_roundtrip_bitexact(tmp_path):
    t = _tree(0)
    save_checkpoint(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    r = restore_checkpoint(str(tmp_path), 7, t)
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_and_overwrite(tmp_path):
    t = _tree(1)
    th = async_save_checkpoint(str(tmp_path), 3, t)
    th.join()
    assert latest_step(str(tmp_path)) == 3
    t2 = _tree(2)
    save_checkpoint(str(tmp_path), 3, t2)       # overwrite commit
    r = restore_checkpoint(str(tmp_path), 3, t2)
    np.testing.assert_array_equal(np.asarray(r["a"]), np.asarray(t2["a"]))


def test_incomplete_checkpoint_ignored(tmp_path):
    t = _tree(3)
    save_checkpoint(str(tmp_path), 1, t)
    # simulate a crash mid-write: tmp dir without DONE
    os.makedirs(tmp_path / "step_00000002.tmp")
    assert latest_step(str(tmp_path)) == 1


def test_dataset_determinism():
    ds = SyntheticLMDataset(256, 32, seed=5)
    a = ds.batch(10, 4, host=2)
    b = ds.batch(10, 4, host=2)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.batch(10, 4, host=3)
    assert not np.array_equal(a["tokens"], c["tokens"])   # hosts differ
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_pipeline_prefetch_and_straggler_skip():
    ds = SyntheticLMDataset(64, 8, seed=0)
    calls = []

    def make(step):
        calls.append(step)
        if step == 2:
            time.sleep(0.8)           # simulated straggler
        return ds.batch(step, 2)

    pipe = DataPipeline(make, prefetch=1, skip_threshold=0.25)
    seen = [pipe.next()[0] for _ in range(4)]
    pipe.stop()
    assert seen == sorted(seen)       # order preserved
    assert seen[0] == 0


def test_elastic_restore_across_meshes(tmp_path):
    """Save replicated; restore sharded onto a different layout (1 device →
    trivially, but exercises the device_put path with NamedSharding)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 0, t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    sh = {"w": NamedSharding(mesh, P("model", None))}
    r = restore_checkpoint(str(tmp_path), 0, t, shardings=sh)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t["w"]))
    assert r["w"].sharding == sh["w"]
