"""Owner-sharded search correctness.

Fast, in-process: the stale-threshold FEE admit property the overlap pipeline
relies on, and the ShardedMutableIndex ownership/routing invariants (pure
numpy — no devices needed).

Subprocess (8 fake XLA devices, same harness as tests/test_distributed.py):
bit-parity of the ``sharded`` backend against ``local`` — identical ids AND
dists — across metric (l2, ip), storage (f32, packed), shard counts, with
expand > 1 and with tombstoned rows; plus overlap-vs-sync agreement."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
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


# -- fast: stale-threshold FEE properties (in-process) ------------------------

def _fee_inputs(seed=0, c=96, d=64):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal(d).astype(np.float32)
    x = rng.standard_normal((c, d)).astype(np.float32)
    return q, x


def test_stale_exit_admits_superset():
    """Exiting against a stale (>=) threshold can only admit MORE lanes —
    the exactness argument of the overlap pipeline."""
    from repro.core.fee import FeeParams
    from repro.kernels import ops as kops

    q, x = _fee_inputs()
    fee = FeeParams.identity(x.shape[1] // 16)
    exact = ((x - q) ** 2).sum(-1)
    fresh = float(np.quantile(exact, 0.3))
    admit = float(np.quantile(exact, 0.6))
    _, a_fresh, _ = kops.fee_distance_stale(
        q, x, fresh, admit, fee.alpha, fee.beta, fee.margin, seg=16)
    for stale in (fresh * 1.5, fresh * 4.0, 3.0e38):
        _, a_stale, _ = kops.fee_distance_stale(
            q, x, stale, admit, fee.alpha, fee.beta, fee.margin, seg=16)
        a_f, a_s = np.asarray(a_fresh), np.asarray(a_stale)
        assert (a_s | ~a_f).all(), "stale exit dropped a fresh-admitted lane"
    # admitted lanes always carry the exact full distance below the bound
    d_s, a_s, _ = kops.fee_distance_stale(
        q, x, 3.0e38, admit, fee.alpha, fee.beta, fee.margin, seg=16)
    d_s, a_s = np.asarray(d_s), np.asarray(a_s)
    assert np.array_equal(a_s, exact < admit)
    np.testing.assert_allclose(d_s[a_s], exact[a_s], rtol=1e-5)


def test_stale_equal_thresholds_match_sync_path():
    """fee_distance_stale(thr, thr) == fee_distance + (dist < thr) filter —
    the synchronous hop and the overlap hop score identically when the
    threshold is fresh."""
    from repro.core.fee import FeeParams
    from repro.kernels import ops as kops

    q, x = _fee_inputs(seed=1)
    fee = FeeParams.identity(x.shape[1] // 16)
    exact = ((x - q) ** 2).sum(-1)
    thr = float(np.quantile(exact, 0.5))
    d0, rej, s0 = kops.fee_distance(q, x, thr, fee.alpha, fee.beta,
                                    fee.margin, seg=16)
    d1, adm, s1 = kops.fee_distance_stale(q, x, thr, thr, fee.alpha,
                                          fee.beta, fee.margin, seg=16)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    assert np.array_equal(np.asarray(adm),
                          ~np.asarray(rej) & (np.asarray(d0) < thr))


# -- fast: ShardedMutableIndex ownership/routing (in-process) -----------------

def _small_sharded(unit_db, n_shards=4):
    from repro.index import Index, IndexSpec
    from repro.streaming import ShardedMutableIndex

    idx = Index.build(unit_db, IndexSpec.for_db(unit_db, m=8,
                                                dfloat_recall_target=None))
    return ShardedMutableIndex(idx, n_shards)


def test_sharded_mutable_owner_stable_and_balanced(unit_db):
    sm = _small_sharded(unit_db)
    before = sm.owner_of(np.arange(sm.mutable.n)).copy()
    rng = np.random.default_rng(0)
    ids = sm.append(rng.standard_normal((80, unit_db.dim)).astype(np.float32))
    # existing rows never migrate; appended slots spread across shards
    assert np.array_equal(sm.owner_of(np.arange(len(before))), before)
    per = np.bincount(sm.owner_of(ids), minlength=4)
    assert per.min() >= len(ids) // 4 - 1, per
    load = sm.shard_load()
    assert load.max() - load.min() <= load.mean() * 0.2, load


def test_sharded_mutable_touched_words_single_shard(unit_db):
    sm = _small_sharded(unit_db)
    rng = np.random.default_rng(1)
    ids = sm.append(rng.standard_normal((16, unit_db.dim)).astype(np.float32))
    for i in ids.tolist():
        tw = sm.touched_words([i])
        # a visibility flip of one id dirties exactly one word of one shard
        assert len(tw) == 1
        (shard, words), = tw.items()
        assert shard == int(sm.owner_of([i])[0])
        assert len(words) == 1


# -- slow: bit-parity vs the local backend (subprocess, 8 fake devices) -------

_PARITY = r"""
import sys; sys.path.insert(0, "%s")
import numpy as np, jax
from repro.data.synthetic import make_dataset
from repro.index import Index, IndexSpec, SearchParams

db = make_dataset(%r)
idx = Index.build(db, IndexSpec.for_db(db, m=8, %s))
params = SearchParams(ef=48, k=10, expand=4, compact=1.0, %s)
ref = idx.searcher("local", params)(db.queries[:32])
for shape in %r:
    mesh = jax.make_mesh(shape, ("data", "model"))
    got = idx.searcher("sharded", params, mesh=mesh)(db.queries[:32])
    assert np.array_equal(got.ids, ref.ids), (shape, "ids diverged")
    assert np.array_equal(got.dists, ref.dists), (shape, "dists diverged")
    print("PARITY", shape)
"""


@pytest.mark.slow
def test_parity_l2_f32_multi_shard():
    out = _run(_PARITY % (SRC, "unit", "dfloat_recall_target=None",
                          "use_dfloat=False", ((1, 4), (2, 4), (1, 8))))
    assert out.count("PARITY") == 3


@pytest.mark.slow
def test_parity_ip_f32():
    out = _run(_PARITY % (SRC, "unit_ip", "dfloat_recall_target=None",
                          "use_dfloat=False", ((1, 4),)))
    assert "PARITY" in out


@pytest.mark.slow
def test_parity_l2_packed():
    out = _run(_PARITY % (SRC, "unit",
                          "dfloat_recall_target=0.80, ef_fit=32",
                          'use_dfloat=True, storage="packed"', ((1, 4),)))
    assert "PARITY" in out


@pytest.mark.slow
def test_parity_with_tombstones():
    out = _run(r"""
import sys; sys.path.insert(0, "%s")
import numpy as np, jax
from repro.data.synthetic import make_dataset
from repro.index import Index, IndexSpec, SearchParams
from repro.streaming import ShardedMutableIndex

db = make_dataset("unit")
idx = Index.build(db, IndexSpec.for_db(db, m=8, dfloat_recall_target=None))
sm = ShardedMutableIndex(idx, 4)
rng = np.random.default_rng(0)
sm.append(rng.standard_normal((64, db.dim)).astype(np.float32))
dead = rng.choice(db.n, 150, replace=False)
sm.delete(dead)
params = SearchParams(ef=48, k=10, expand=4, compact=1.0, use_dfloat=False)
snap = sm.freeze()
ref = snap.searcher("local", params)(db.queries[:32])
mesh = jax.make_mesh((2, 4), ("data", "model"))
got = sm.searcher(params, mesh=mesh)(db.queries[:32])
assert np.array_equal(got.ids, ref.ids), "ids diverged"
assert np.array_equal(got.dists, ref.dists), "dists diverged"
assert not np.isin(got.ids, dead).any(), "tombstoned id surfaced"
print("PARITY tombstones")
""" % SRC)
    assert "PARITY" in out


@pytest.mark.slow
def test_overlap_mode_matches_sync():
    out = _run(r"""
import sys; sys.path.insert(0, "%s")
import numpy as np, jax
from repro.data.synthetic import make_dataset
from repro.index import Index, IndexSpec, SearchParams

db = make_dataset("unit")
idx = Index.build(db, IndexSpec.for_db(db, m=8, dfloat_recall_target=None))
mesh = jax.make_mesh((2, 4), ("data", "model"))
params = SearchParams(ef=48, k=10, expand=4, compact=1.0, use_dfloat=False)
sync = idx.searcher("sharded", params, mesh=mesh)(db.queries[:32])
ov = idx.searcher("sharded", params, mesh=mesh, overlap=True)(db.queries[:32])
frac = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                for a, b in zip(ov.ids, sync.ids)])
print("OVERLAP", frac)
assert frac >= 0.99, frac
""" % SRC)
    assert "OVERLAP" in out
