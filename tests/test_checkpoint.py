"""Checkpoint manager: round-trips (incl. bfloat16), async writes,
retention, latest-discovery, elastic restore re-placement."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore, save


def tree():
    return {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nested": {"b": jnp.ones((2, 2), jnp.bfloat16) * 1.5,
                   "c": jnp.zeros((5,), jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path):
    t = tree()
    save(str(tmp_path / "ck"), t, step=7)
    restored, step = restore(str(tmp_path / "ck"), t)
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_bfloat16_dtype_preserved(tmp_path):
    t = {"w": jnp.full((4,), 0.375, jnp.bfloat16)}
    save(str(tmp_path / "ck"), t, step=0)
    restored, _ = restore(str(tmp_path / "ck"), t)
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                  np.full((4,), 0.375, np.float32))


def test_manager_async_retention_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = tree()
    for s in (10, 20, 30):
        mgr.save_async(t, s)
    mgr.wait()
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step-"))
    assert dirs == ["step-00000020", "step-00000030"]
    restored, step = mgr.restore_latest(t)
    assert step == 30


def test_restore_shape_mismatch_raises(tmp_path):
    t = tree()
    save(str(tmp_path / "ck"), t, step=0)
    bad = {**t, "a": jnp.zeros((4, 4))}
    with pytest.raises(ValueError):
        restore(str(tmp_path / "ck"), bad)


def test_elastic_restore_with_shardings(tmp_path):
    """Restore re-places leaves with the current mesh's shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    t = {"w": jnp.arange(8, dtype=jnp.float32)}
    save(str(tmp_path / "ck"), t, step=1)
    shardings = {"w": NamedSharding(mesh, P("data"))}
    restored, _ = restore(str(tmp_path / "ck"), t, shardings=shardings)
    assert restored["w"].sharding == shardings["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8))
