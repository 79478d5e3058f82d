"""Checkpoint manager: atomicity, async, keep-k GC, reshard-on-load."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointConfig, CheckpointManager


def _tree(seed=0):
    key = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(key, (8, 4)),
                       "b": jnp.arange(4, dtype=jnp.float32)},
            "opt": {"m": jnp.zeros((8, 4)), "step": jnp.asarray(3)}}


class TestRoundtrip:
    def test_save_restore(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        tree = _tree()
        mgr.save(10, tree, extra={"data_state": {"step": 10}})
        step, restored, extra = mgr.restore(target=tree)
        assert step == 10 and extra["data_state"]["step"] == 10
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_selected(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        for s in (1, 5, 3):
            mgr.save(s, _tree(s))
        assert mgr.latest_step() == 5

    def test_async_save_then_wait(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=True))
        tree = _tree()
        mgr.save(1, tree)
        mgr.wait()
        assert mgr.latest_step() == 1

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        mgr.save(1, _tree())
        bad = {"params": {"w": jnp.zeros((9, 4)), "b": jnp.zeros(4)},
               "opt": {"m": jnp.zeros((8, 4)), "step": jnp.asarray(0)}}
        with pytest.raises(ValueError):
            mgr.restore(target=bad)


class TestGC:
    def test_keep_last_k(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), keep_last=2,
                                                 async_save=False))
        for s in range(5):
            mgr.save(s, _tree(s))
        assert mgr.all_steps() == [3, 4]

    def test_stale_tmp_cleaned(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        stale = tmp_path / "ckpt_00000001.tmp.abc"
        stale.mkdir()
        mgr.save(2, _tree())
        assert not stale.exists()

    def test_crash_leaves_no_partial_checkpoint(self, tmp_path):
        """Atomicity: only fully-renamed dirs count as checkpoints."""
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        mgr.save(7, _tree())
        # simulate a crashed save: tmp dir with partial content
        partial = tmp_path / "ckpt_00000009.tmp.x"
        partial.mkdir()
        (partial / "arrays.npz").write_bytes(b"garbage")
        assert mgr.all_steps() == [7]
        assert mgr.latest_step() == 7


class TestReshard:
    def test_restore_with_new_sharding(self, tmp_path):
        """Elastic restart: restore onto a different device layout."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        tree = {"w": jnp.arange(16.0).reshape(8, 2)}
        mgr.save(1, tree)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("data",))
        shardings = {"w": NamedSharding(mesh, P("data", None))}
        step, restored, _ = mgr.restore(target=tree, shardings=shardings)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding == shardings["w"]

    def test_dtype_cast_on_restore(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        mgr.save(1, {"w": jnp.ones((4,), jnp.float32)})
        target = {"w": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
        _, restored, _ = mgr.restore(target=target)
        assert restored["w"].dtype == jnp.bfloat16
