"""Checkpointing: roundtrip, atomicity, retention, elastic resharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint, elastic


def _tree(key):
    ks = jax.random.split(key, 3)
    return {
        "params": {"w": jax.random.normal(ks[0], (8, 16)),
                   "b": jnp.zeros((16,))},
        "opt": {"m": {"w": jax.random.normal(ks[1], (8, 16)),
                      "b": jnp.zeros((16,))},
                "step": jnp.int32(7)},
    }


def test_save_restore_roundtrip(tmp_path):
    tree = _tree(jax.random.key(0))
    d = str(tmp_path / "ck")
    checkpoint.save(tree, d)
    out = checkpoint.restore(d, like=tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_rejects_shape_mismatch(tmp_path):
    tree = _tree(jax.random.key(0))
    d = str(tmp_path / "ck")
    checkpoint.save(tree, d)
    bad = jax.tree.map(lambda x: jnp.zeros((3,)) if x.ndim == 2 else x, tree)
    with pytest.raises(ValueError, match="shape"):
        checkpoint.restore(d, like=bad)


def test_manager_retention_and_latest(tmp_path):
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=2)
    tree = _tree(jax.random.key(1))
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, blocking=True)
    assert mgr.all_steps() == [3, 4]
    step, out = mgr.restore_latest(tree)
    assert step == 4 and out is not None


def test_manager_async_save(tmp_path):
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=1)
    mgr.save(1, _tree(jax.random.key(2)))
    mgr.wait()
    assert mgr.latest_step() == 1


def test_no_tmp_dirs_left_behind(tmp_path):
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=3)
    mgr.save(5, _tree(jax.random.key(3)), blocking=True)
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


def test_publish_metadata_roundtrip(tmp_path):
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=3)
    tree = _tree(jax.random.key(5))
    meta = {"step": 7, "config_hash": "abc123", "eval": {"loss": 1.25}}
    mgr.publish(7, tree, metadata=meta)
    assert mgr.metadata(7) == meta
    # ModelStore speaks versions over the same directory layout.
    store = checkpoint.ModelStore(str(tmp_path))
    assert store.versions() == [7]
    assert store.latest_version() == 7
    out = store.load_version(7, like=tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_never_deletes_retained_steps(tmp_path):
    """A live-served version is pinned by retain_fn even when ``keep``
    would age it out."""
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=1,
                                       retain_fn=lambda: {1})
    tree = _tree(jax.random.key(6))
    for s in (1, 2, 3):
        mgr.save(s, tree, blocking=True)
    assert mgr.all_steps() == [1, 3]      # 1 pinned, 2 collected


def test_gc_deletes_nothing_when_retain_fn_raises(tmp_path):
    def broken():
        raise ConnectionError("registry down")

    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=1,
                                       retain_fn=broken)
    tree = _tree(jax.random.key(7))
    for s in (1, 2):
        mgr.save(s, tree, blocking=True)
    assert mgr.all_steps() == [1, 2]      # fail safe: keep everything


def test_half_written_checkpoint_is_skipped(tmp_path):
    """A dir without a manifest (crash mid-write) is invisible to
    ``all_steps``/``restore_latest`` and unloadable as a version."""
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=5)
    tree = _tree(jax.random.key(8))
    mgr.save(1, tree, blocking=True)
    # Simulate a crash: step 2 has leaves but no manifest.
    half = tmp_path / "step_00000002"
    half.mkdir()
    (half / "leaf_00000.npy").write_bytes(b"garbage")
    assert not checkpoint.is_complete(str(half))
    assert mgr.all_steps() == [1]
    step, out = mgr.restore_latest(tree)
    assert step == 1 and out is not None
    store = checkpoint.ModelStore(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        store.load_version(2, like=tree)


def test_elastic_reshard_roundtrip(tmp_path):
    """Save under one mesh, restore under a different one (elastic)."""
    from repro.sharding.rules import param_sharding
    tree = {"blocks": {"0": {"mlp": {"w_up": {"kernel":
            jax.random.normal(jax.random.key(4), (2, 4, 8))}}}}}
    d = str(tmp_path / "ck")
    checkpoint.save(tree, d)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    out = elastic.restore_elastic(d, like=tree, new_mesh=mesh)
    leaf = out["blocks"]["0"]["mlp"]["w_up"]["kernel"]
    np.testing.assert_array_equal(
        np.asarray(leaf),
        np.asarray(tree["blocks"]["0"]["mlp"]["w_up"]["kernel"]))
    assert leaf.sharding.mesh.axis_names == ("data", "model")


def test_self_restoring_node_pattern(tmp_path):
    """Paper §6: a stateful node killed and restarted resumes from its
    checkpoint (scheduler restart + self-restore, no exact recovery)."""
    from repro import core as lp

    class Learner:
        def __init__(self, ckpt_dir):
            self._mgr = checkpoint.CheckpointManager(ckpt_dir, keep=2)
            self._state = {"step": jnp.int32(0)}
            step, restored = self._mgr.restore_latest(self._state)
            self._start = 0
            if restored is not None:
                self._state = restored
                self._start = int(restored["step"])

        def run(self):
            step = self._start
            for _ in range(3):
                step += 1
                self._state = {"step": jnp.int32(step)}
                self._mgr.save(step, self._state, blocking=True)
            if step < 6:
                raise RuntimeError("simulated node failure")
            lp.stop_program()

    p = lp.Program("self-restore")
    p.add_node(lp.PyNode(Learner, str(tmp_path)))
    launcher = lp.ThreadLauncher(
        restart_policy=lp.RestartPolicy(max_restarts=3, backoff_s=0.01))
    launcher.launch(p)
    assert launcher.wait(timeout=30)
    # Crashed once at step 3, restarted, resumed 4..6.
    mgr = checkpoint.CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == 6
    assert len([f for f in launcher.failures if not f.fatal]) == 1
