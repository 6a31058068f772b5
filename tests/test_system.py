"""End-to-end behaviour tests for the NasZip system."""
import numpy as np
import pytest

from repro.index import SearchParams


@pytest.mark.slow
def test_end_to_end_naszip_pipeline(unit_db, unit_index_dfloat):
    """Full paper pipeline: PCA -> beta -> graph -> Dfloat -> FEE search,
    recall at the paper's operating point (recall@10 >= 0.85 on the tiny
    test DB; the full-size stand-ins hit >= 0.9 in the benchmarks)."""
    idx = unit_index_dfloat
    res = idx.evaluate(unit_db, SearchParams(ef=64, k=10, trace=True))
    assert res["recall"] >= 0.78
    # compression actually engaged
    assert idx.dfloat_cfg.bursts_per_vector() <= 16
    assert res["dims_per_eval"] < unit_db.dim


def test_end_to_end_speedup_projection(unit_db, unit_index):
    """NasZip (all techniques) must beat the naive NDP baseline in the
    performance model — the paper's core claim, directionally."""
    from repro.core import graph as gmod
    from repro.core.dfloat import fp32_config
    from repro.ndpsim import SimFlags, simulate_ndp
    from repro.ndpsim.timing import NASZIP_2CH

    out = unit_index.search(unit_db.queries[:48],
                            SearchParams(ef=32, k=10, trace=True))
    out_nofee = unit_index.search(unit_db.queries[:48],
                                  SearchParams(ef=32, k=10, use_fee=False,
                                               trace=True))
    owner = gmod.map_owners(unit_db.n, NASZIP_2CH.n_subchannels, "shuffle")
    adj = unit_index.graph.base_adjacency
    full = simulate_ndp(out, owner, adj, NASZIP_2CH,
                        SimFlags(dam=True, lnc=True, prefetch=True),
                        unit_index.dfloat_cfg, 16)
    naive = simulate_ndp(out_nofee, owner, adj, NASZIP_2CH,
                         SimFlags(dam=False, lnc=False, prefetch=False),
                         fp32_config(unit_db.dim), 16)
    assert full.qps > 2.0 * naive.qps, (full.qps, naive.qps)


@pytest.mark.slow
def test_quickstart_example_runs():
    import os, subprocess, sys
    from pathlib import Path
    root = Path(__file__).parent.parent
    r = subprocess.run([sys.executable, str(root / "examples" / "quickstart.py"),
                        "--tiny"], capture_output=True, text=True, timeout=560,
                       env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
                            "HOME": os.environ.get("HOME", ""),
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1200:], r.stderr[-2000:])
    assert "recall@10" in r.stdout
