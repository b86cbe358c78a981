"""Checkpoint save/restore (``repro.ckpt.checkpoint``, the atomic
tmp + os.replace discipline the session journal rides on): round trip,
retention + atomicity, and restore onto a mesh with explicit shardings."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt


def _tree(rng):
    """A nested pytree of mixed-dtype arrays, shaped like selection state."""
    return {
        "sim": jnp.asarray(rng.normal(size=(12, 12)).astype(np.float32)),
        "greedy": {
            "order": jnp.asarray(rng.permutation(12)[:5].astype(np.int32)),
            "gains": jnp.asarray(rng.random(5).astype(np.float32)),
            "selected": jnp.asarray(rng.random(12) < 0.4),
        },
        "budgets": [jnp.int32(5), jnp.int32(3)],
    }


def test_checkpoint_roundtrip(tmp_path, rng):
    tree = _tree(rng)
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, tree, {"family": "FacilityLocation"})
    assert ckpt.latest_step(d) == 7
    restored, meta = ckpt.restore(d, tree)
    assert meta["step"] == 7 and meta["family"] == "FacilityLocation"
    assert jax.tree.structure(restored) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_atomicity(tmp_path, rng):
    tree = _tree(rng)
    d = str(tmp_path / "ck")
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(d, s, tree, keep_last=2)
    steps = sorted(os.listdir(d))
    assert steps == ["step_0000000004", "step_0000000005"]
    assert not any(p.endswith(".tmp") for p in os.listdir(d))


def test_checkpoint_elastic_restore_to_mesh(tmp_path, rng):
    """Restore onto a (different) mesh with explicit shardings — the elastic
    restart path after node loss."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = _tree(rng)
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, tree)
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    restored, meta = ckpt.restore(d, tree, shardings=shardings)
    assert meta["step"] == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert isinstance(b.sharding, NamedSharding)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
