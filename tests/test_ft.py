"""Checkpoints: bit-exact round trip, async write, latest step, missing keys."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import checkpoint as ckpt


def test_checkpoint_roundtrip_bit_exact(tmp_path):
    tree = dict(a=jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                nested=dict(b=jnp.asarray([1, 2, 3], jnp.int32),
                            c=jnp.asarray(2.5, jnp.bfloat16)))
    ckpt.save(tmp_path / "step_5", 5, tree, metadata=dict(note="x"))
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored, manifest = ckpt.restore(tmp_path / "step_5", abstract)
    assert manifest["step"] == 5 and manifest["metadata"]["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_checkpoint_async_and_latest(tmp_path):
    tree = dict(w=jnp.ones((8,)))
    t = ckpt.save(tmp_path / "step_1", 1, tree, async_write=True)
    t.join()
    ckpt.save(tmp_path / "step_3", 3, tree)
    assert ckpt.latest_step(tmp_path) == 3


def test_restore_missing_key_raises(tmp_path):
    ckpt.save(tmp_path / "step_1", 1, dict(a=jnp.ones(3)))
    with pytest.raises(ValueError, match="missing"):
        ckpt.restore(tmp_path / "step_1", dict(a=jax.ShapeDtypeStruct((3,), jnp.float32),
                                               b=jax.ShapeDtypeStruct((2,), jnp.float32)))
