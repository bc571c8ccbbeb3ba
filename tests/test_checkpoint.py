"""Checkpoint: roundtrip, commit marker, async, latest, resharding restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (latest_step, restore_checkpoint,
                              save_checkpoint, wait_pending)


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (8, 16)),
            "b": {"c": jnp.arange(7), "d": (jnp.ones((3,)), jnp.zeros((2, 2)))}}


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t)
    got, step = restore_checkpoint(str(tmp_path))
    assert step == 3
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_multiple(tmp_path):
    for s in (1, 5, 12):
        save_checkpoint(str(tmp_path), s, _tree(s))
    assert latest_step(str(tmp_path)) == 12
    got, step = restore_checkpoint(str(tmp_path), 5)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(_tree(5)["a"]))


def test_async_save(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t, async_save=True)
    wait_pending()
    got, _ = restore_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(t["a"]))


def test_uncommitted_ignored(tmp_path):
    save_checkpoint(str(tmp_path), 2, _tree())
    d = os.path.join(str(tmp_path), "step_00000007")
    os.makedirs(d)  # no DONE marker
    assert latest_step(str(tmp_path)) == 2


def test_resharding_restore(tmp_path):
    from jax.sharding import PartitionSpec as P
    t = {"w": jnp.arange(32.0).reshape(8, 4)}
    save_checkpoint(str(tmp_path), 1, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    got, _ = restore_checkpoint(str(tmp_path), mesh=mesh,
                                specs={"w": P("data", None)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))
    assert got["w"].sharding.is_equivalent_to(
        jax.NamedSharding(mesh, P("data", None)), 2)
