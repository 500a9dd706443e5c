"""Checkpointing: atomic round-trip, corruption detection, retention,
elastic restore across device layouts."""
import json
import pathlib

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "params": {"w": jnp.asarray(rng.randn(8, 16), jnp.float32),
                   "b": jnp.asarray(rng.randn(16), jnp.bfloat16)},
        "opt": {"mu": {"w": jnp.asarray(rng.randn(8, 16), jnp.float32)}},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(7, tree, blocking=True)
    restored, step = ck.restore(tree)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_latest_and_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        ck.save(s, tree, blocking=True)
    assert ck.latest_step() == 4
    kept = sorted(p.name for p in pathlib.Path(tmp_path).glob("step_*"))
    assert len(kept) == 2 and kept[-1].endswith("4".zfill(12))


def test_corruption_detected(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _tree(), blocking=True)
    d = next(pathlib.Path(tmp_path).glob("step_*"))
    blob = (d / "arrays.npz").read_bytes()
    (d / "arrays.npz").write_bytes(b"CORR" + blob[4:])
    with pytest.raises(IOError):
        ck.restore(_tree())


def test_elastic_restore_resharding(tmp_path):
    """Save replicated, restore sharded onto the host mesh (different
    layout) — values identical."""
    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(3, tree, blocking=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    from jax.sharding import NamedSharding, PartitionSpec as P
    shardings = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, P(*([None] * a.ndim))), tree)
    restored, step = ck.restore(tree, shardings=shardings)
    np.testing.assert_array_equal(
        np.asarray(tree["params"]["w"]), np.asarray(restored["params"]["w"]))


def test_resume_from_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        Checkpointer(str(tmp_path)).restore(_tree())
