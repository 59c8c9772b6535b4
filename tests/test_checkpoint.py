"""Checkpointing: atomic roundtrip, keep-N rotation, async writer,
mesh-agnostic restore."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, restore_tree, save_tree


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)), "b": jnp.zeros((16,))},
        "opt": {"mu": jnp.ones((8, 16)), "step": jnp.asarray(7, jnp.int32)},
    }


def _assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a, b,
    )


def test_save_restore_roundtrip(tmp_path):
    state = _state()
    save_tree(str(tmp_path / "ck"), state, step=42)
    restored, step = restore_tree(str(tmp_path / "ck"))
    assert step == 42
    _assert_tree_equal(state, restored)


def test_atomic_no_partial_dirs(tmp_path):
    state = _state()
    save_tree(str(tmp_path / "ck"), state, step=1)
    leftovers = [p for p in os.listdir(tmp_path) if p.startswith(".tmp")]
    assert leftovers == []


def test_manager_keep_policy(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in range(5):
        mgr.save(s, _state(s), blocking=True)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_manager_async_overlap(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = _state()
    mgr.save(1, state)           # async
    # mutate the original AFTER save snapshotted it
    state["params"]["w"] = state["params"]["w"] * 0.0
    mgr.wait()
    restored, step = mgr.restore(1)
    assert step == 1
    assert np.abs(np.asarray(restored["params"]["w"])).max() > 0  # snapshot taken


def test_restore_with_shardings(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, _state(), blocking=True)
    from repro.mesh import make_mesh as _make_mesh
    mesh = _make_mesh((1,), ("data",))
    sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), _state()
    )
    restored, _ = mgr.restore(shardings=sh)
    assert restored["params"]["w"].sharding.mesh.shape["data"] == 1


def test_restore_empty(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree, step = mgr.restore()
    assert tree is None and step is None
