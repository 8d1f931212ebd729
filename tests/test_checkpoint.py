"""Checkpointing: roundtrip, integrity, retention, async fence, latest."""

import os
import time

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import save, restore, latest_step, Checkpointer


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32),
                  "d": jnp.asarray(2.5)}}


def test_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t, extra={"note": "hi"})
    got, extra = restore(str(tmp_path), 7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert extra["note"] == "hi"


def test_latest_and_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    t = _tree()
    for s in (10, 20, 30):
        ck.save_async(s, t)
        ck.wait()
    assert latest_step(str(tmp_path)) == 30
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [20, 30]  # keep=2 garbage-collected step 10


def test_corruption_detected(tmp_path):
    t = _tree()
    d = save(str(tmp_path), 1, t)
    npz = os.path.join(d, "shard_p0.npz")
    raw = bytearray(open(npz, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(raw))
    with pytest.raises(IOError, match="corrupt"):
        restore(str(tmp_path), 1, t)


def test_tree_mismatch_detected(tmp_path):
    t = _tree()
    save(str(tmp_path), 2, t)
    other = {"x": jnp.zeros(3)}
    with pytest.raises(ValueError, match="mismatch"):
        restore(str(tmp_path), 2, other)


def test_async_then_restore(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    t = _tree()
    ck.save_async(5, t, extra={"arch": "x"})
    step, got, extra = ck.restore_latest(t)  # restore_latest waits implicitly?
    # restore may race the writer thread: wait explicitly then retry
    ck.wait()
    step, got, extra = ck.restore_latest(t)
    assert step == 5 and extra["arch"] == "x"


def test_elastic_restore_with_shardings(tmp_path):
    """Restore under (trivial single-device) shardings — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = _tree()
    save(str(tmp_path), 3, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(AxisType.Auto,) * 1)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    got, _ = restore(str(tmp_path), 3, t, shardings=sh)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
