"""Fault tolerance: checkpoint roundtrip, failure/resume, elastic reshard."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import checkpoint as ckpt

SRC = str(Path(__file__).parent.parent / "src")


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


def _run_train(args, timeout=560):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *args],
        capture_output=True, text=True, timeout=timeout,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"})


@pytest.mark.slow
def test_failure_and_resume_deterministic(tmp_path):
    """Crash at step 7, resume from ckpt@5, final loss == uninterrupted run."""
    common = ["--arch", "llama3.2-1b", "--smoke", "--steps", "12",
              "--batch", "4", "--seq", "32", "--ckpt-every", "5"]
    r_ref = _run_train(common + ["--ckpt-dir", str(tmp_path / "ref")])
    assert r_ref.returncode == 0, r_ref.stderr[-2000:]

    crash = _run_train(common + ["--ckpt-dir", str(tmp_path / "ft"),
                                 "--simulate-failure", "7"])
    assert crash.returncode == 17, "simulated failure must exit(17)"
    resume = _run_train(common + ["--ckpt-dir", str(tmp_path / "ft"), "--resume"])
    assert resume.returncode == 0, resume.stderr[-2000:]
    assert "[resume] restored step 5" in resume.stdout

    def final_loss(out):
        lines = [l for l in out.splitlines() if "final loss" in l]
        return float(lines[-1].split()[-1])

    # identical final loss: step-indexed pipeline + mesh-agnostic ckpt
    assert abs(final_loss(r_ref.stdout) - final_loss(resume.stdout)) < 1e-4


@pytest.mark.slow
def test_elastic_reshard_across_device_counts(tmp_path):
    """Save on 4 fake devices, restore + continue on 2 — mesh-agnostic ckpt."""
    code = r"""
import sys
sys.path.insert(0, "%s")
import jax, jax.numpy as jnp, numpy as np
from repro import configs as C
from repro.models.registry import get_model
from repro.distributed import sharding as sh
from repro.ft import checkpoint as ckpt

mode, path = sys.argv[1], sys.argv[2]
cfg = C.get_smoke("llama3.2-1b")
api = get_model(cfg)
ndev = len(jax.devices())
mesh = jax.make_mesh((1, ndev), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    pspecs = sh.param_specs(api.abstract_params(), mesh)
    if mode == "save":
        params = api.init(jax.random.key(0))
        params = jax.tree.map(lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)), params, pspecs)
        ckpt.save(path, 1, params)
        print("SAVED", ndev)
    else:
        abstract = api.abstract_params()
        params, _ = ckpt.restore(path, abstract, sh.named(pspecs, mesh))
        tot = sum(float(jnp.sum(jnp.abs(x).astype(jnp.float32))) for x in jax.tree.leaves(params))
        print("RESTORED", ndev, f"{tot:.4f}")
""" % SRC
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}
    r1 = subprocess.run([sys.executable, "-c", code, "save", str(tmp_path / "ck")],
                        capture_output=True, text=True, timeout=560,
                        env={**env, "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert "SAVED 4" in r1.stdout, r1.stderr[-2000:]
    r2 = subprocess.run([sys.executable, "-c", code, "load", str(tmp_path / "ck")],
                        capture_output=True, text=True, timeout=560,
                        env={**env, "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert "RESTORED 2" in r2.stdout, r2.stderr[-2000:]
    # checksum must match a same-process recomputation
    import jax
    from repro import configs as C
    from repro.models.registry import get_model
    api = get_model(C.get_smoke("llama3.2-1b"))
    params = api.init(jax.random.key(0))
    tot = sum(float(jnp.sum(jnp.abs(x).astype(jnp.float32)))
              for x in jax.tree.leaves(params))
    got = float(r2.stdout.split()[-1])
    assert abs(got - tot) / tot < 1e-5
