"""Fault tolerance: atomic checkpointing, crash recovery, auto-resume."""
import json
import os
import shutil

import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 10, (3,)), jnp.int32)},
        "scalar": jnp.asarray(3, jnp.int32),
    }


def _eq(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    t = _tree()
    mgr.save(5, t)
    step, got = mgr.restore_latest(t)
    assert step == 5 and _eq(t, got)


def test_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]
    step, got = mgr.restore_latest(_tree())
    assert step == 4 and _eq(got, _tree(4))


def test_damaged_checkpoint_falls_back(tmp_path):
    """Simulated crash: newest checkpoint missing a leaf file → restore
    falls back to the previous intact one."""
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _tree(1))
    mgr.save(2, _tree(2))
    os.remove(os.path.join(str(tmp_path), "step_00000002", "leaf_00000.npy"))
    step, got = mgr.restore_latest(_tree())
    assert step == 1 and _eq(got, _tree(1))


def test_tmp_dir_never_visible(tmp_path):
    """A leftover .tmp directory (crash mid-write) is not restorable."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(1))
    # fake an in-flight write that crashed
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
    assert mgr.all_steps() == [1]


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    t = _tree(7)
    mgr.save(7, t, blocking=False)
    mgr.wait()
    step, got = mgr.restore_latest(t)
    assert step == 7 and _eq(t, got)
