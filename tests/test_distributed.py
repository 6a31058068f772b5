"""Distributed correctness on 8 fake devices (subprocess — the main pytest
process is pinned to 1 CPU device): DaM-sharded retrieval equivalence."""
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
