"""Distributed correctness on 8 fake devices (subprocess — the main pytest
process is pinned to 1 CPU device): DaM-sharded retrieval equivalence,
sharded decode equivalence, compressed psum, sharding rule sanity."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).parent.parent / "src")
ENV = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
       "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def _run(code: str, timeout=560):
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=ENV)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2500:])
    return r.stdout


@pytest.mark.slow
def test_sharded_retrieval_matches_single_device():
    out = _run(r"""
import sys; sys.path.insert(0, "%s")
import numpy as np, jax
from repro.data.synthetic import make_dataset
from repro.index import Index, IndexSpec, SearchParams

db = make_dataset("unit")
idx = Index.build(db, IndexSpec.for_db(db, m=8, dfloat_recall_target=None))
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = SearchParams(ef=32, k=10, use_dfloat=False)
sharded = idx.searcher("sharded", params, mesh=mesh)(db.queries[:16])
ref = idx.searcher("local", params)(db.queries[:16])
overlap = np.mean([len(set(a.tolist()) & set(b.tolist()))/10
                   for a, b in zip(sharded.ids, ref.ids)])
print("OVERLAP", overlap)
assert overlap >= 0.99, overlap
""" % SRC)
    assert "OVERLAP" in out


@pytest.mark.slow
def test_sharded_decode_matches_unsharded():
    out = _run(r"""
import sys; sys.path.insert(0, "%s")
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro import configs as C
from repro.models.registry import get_model
from repro.distributed import sharding as sh

cfg = dataclasses.replace(C.get_smoke("llama3.2-1b"), dtype=jnp.float32)
api = get_model(cfg)
params = api.init(jax.random.key(0))
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 8)), jnp.int32)

# unsharded reference
_, cache = api.prefill(params, dict(tokens=toks[:, :4]), 16)
ref_logits = None
for t in range(4, 8):
    ref_logits, cache = api.decode(params, cache, toks[:, t])

# sharded: seq-sharded KV over model axis
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    pspecs = sh.param_specs(api.abstract_params(), mesh)
    params_s = jax.tree.map(lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)),
                            params, pspecs)
    _, cache = api.prefill(params_s, dict(tokens=toks[:, :4]), 16)
    cspecs = sh.cache_specs(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache), mesh)
    cache = jax.tree.map(lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)), cache, cspecs)
    dec = jax.jit(api.decode)
    for t in range(4, 8):
        logits, cache = dec(params_s, cache, toks[:, t])
err = float(jnp.abs(logits - ref_logits).max() / (jnp.abs(ref_logits).max() + 1e-9))
print("ERR", err)
assert err < 2e-4, err
""" % SRC)
    assert "ERR" in out


@pytest.mark.slow
def test_compressed_psum_shard_map():
    out = _run(r"""
import sys; sys.path.insert(0, "%s")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.training.compress import GradCompressor

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
comp = GradCompressor(bits=8)
g_global = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)

def body(g):
    grads = dict(w=g[0])
    err = comp.init_error(grads)
    deq, err = comp.compressed_psum(grads, err, "data")
    return deq["w"][None], err["w"][None]

with jax.set_mesh(mesh):
    deq, err = jax.shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                             out_specs=(P("data", None), P("data", None)),
                             check_vma=False)(g_global)
true_mean = np.asarray(g_global).mean(0)
got = np.asarray(deq)[0]
rel = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
print("REL", rel)
assert rel < 0.02, rel   # int8 quantization error bound
# error feedback residual reconstructs the local value
recon = np.asarray(deq) * 0  # placeholder; residual check:
assert np.isfinite(np.asarray(err)).all()
""" % SRC)
    assert "REL" in out


def test_param_specs_cover_all_leaves():
    import jax
    from repro import configs as C
    from repro.distributed import sharding as shd
    from repro.models.registry import get_model
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in C.ARCHS:
        api = get_model(C.get_smoke(arch))
        abs_p = api.abstract_params()
        specs = shd.param_specs(abs_p, mesh)
        n1 = len(jax.tree.leaves(abs_p))
        n2 = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)))
        assert n1 == n2, arch
