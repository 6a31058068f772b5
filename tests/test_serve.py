"""Serving tier: bucket-padding correctness, mixed-traffic determinism,
SLO admission, hot-swap generation consistency, and delta-upload accounting.

The determinism property is exact: a request served through the batcher —
rounded up to its ef bucket, padded to a batch bucket, k-sliced out of the
shared k_max-wide program — must return ids AND dists bit-identical to a
one-by-one local search replayed through the same fixed-shape program,
regardless of lane position, padding, or what it was co-batched with.
(Across *different* program shapes XLA's gemm blocking changes the fp32
reduction order, so only ids are exact there and dists agree to ~1e-6;
within one program shape everything is bitwise.)
"""
import time

import numpy as np
import pytest

from repro.index import DeviceCache, SearchParams
from repro.serve import (AdmissionController, LatencyModel, RequestQueue,
                         ServeConfig, Server, run_load)
from repro.serve.batcher import params_for, run_bucketed
from repro.serve.request import Request
from repro.streaming import MutableIndex

K = 10
STORAGES = ("f32", "packed", "tiered")


def _direct(idx, q, cfg, ef, k, storage="f32", bucket=None):
    """One-by-one local search replayed through the exact serving program:
    same ef bucket, same k_max width, padded to the same batch bucket."""
    ids, dists, *_ = run_bucketed(idx, cfg, q, cfg.ef_bucket(ef),
                                  cfg.expand, storage, bucket=bucket)
    return ids[:, :k], dists[:, :k]


# ---------------------------------------------------------------------------
# config / queue / admission units
# ---------------------------------------------------------------------------
def test_config_validation():
    with pytest.raises(ValueError, match="smallest ef bucket"):
        ServeConfig(ef_buckets=(16, 32), k_max=20)
    with pytest.raises(ValueError, match="use_dfloat"):
        ServeConfig(storages=("packed",), use_dfloat=False)
    with pytest.raises(ValueError, match="sorted"):
        ServeConfig(ef_buckets=(64, 32))
    cfg = ServeConfig(ef_buckets=(16, 32, 64), k_max=10)
    assert cfg.ef_bucket(16) == 16        # exact hit
    assert cfg.ef_bucket(17) == 32        # rounds UP
    assert cfg.ef_bucket(999) == 64       # capped at the top bucket
    assert cfg.batch_bucket(3) == 4
    assert cfg.lower_bucket(16) is None
    assert cfg.lower_bucket(64) == 32


def _req(ef=32, k=5, deadline_ms=100.0, group="f32"):
    return Request(query=np.zeros(4, np.float32), k=k, ef=ef, expand=4,
                   storage=group, deadline_ms=deadline_ms)


def test_queue_sheds_when_full_and_groups_batches():
    q = RequestQueue(max_queue=2, shed_on_full=True)
    assert q.put(_req()) and q.put(_req())
    assert not q.put(_req())              # third is shed
    cfg = ServeConfig(ef_buckets=(16, 32), k_max=10)
    q2 = RequestQueue(max_queue=8)
    reqs = [_req(ef=16), _req(ef=32), _req(ef=16), _req(ef=32)]
    for r in reqs:
        q2.put(r)
    batch = q2.take_group(lambda r: r.group(cfg), max_n=8)
    # oldest-first, coalescing only its own group; order preserved
    assert [r.id for r in batch] == [reqs[0].id, reqs[2].id]
    rest = q2.take_group(lambda r: r.group(cfg), max_n=8)
    assert [r.id for r in rest] == [reqs[1].id, reqs[3].id]


def test_admission_timeout_and_degrade():
    cfg = ServeConfig(ef_buckets=(16, 32, 64), k_max=10, degrade=True,
                      max_queue=64)
    model = LatencyModel()
    adm = AdmissionController(cfg, model)

    dead = _req(deadline_ms=0.0)
    time.sleep(0.002)                     # let the deadline lapse
    live = _req(ef=64, deadline_ms=50.0)
    serve, timed_out, ef, degraded = adm.plan([dead, live], queue_len=0)
    assert [r.id for r in timed_out] == [dead.id]
    assert [r.id for r in serve] == [live.id] and ef == 64 and not degraded

    # a 64-bucket EMA way over budget degrades the batch to a faster bucket
    model.observe((64, 4, "f32"), 1, 10.0)   # 10 s >> 50 ms deadline
    model.observe((32, 4, "f32"), 1, 0.001)
    serve, _, ef, degraded = adm.plan([_req(ef=64, deadline_ms=50.0)], 0)
    assert serve and ef == 32 and degraded

    # queue pressure beyond degrade_depth forces the floor bucket
    serve, _, ef, degraded = adm.plan([_req(ef=64, deadline_ms=5000.0)],
                                      queue_len=cfg.degrade_depth)
    assert serve and ef == 16 and degraded


# ---------------------------------------------------------------------------
# bucket padding + determinism against direct searches
# ---------------------------------------------------------------------------
def test_bucket_padding_batch_of_1_vs_32(unit_db, unit_index):
    """A single query padded to a 32-wide bucket must return exactly its own
    results: no padded lane in the output, and the padding/co-batched lanes
    must not perturb the real lane (bitwise, at any lane position)."""
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(32,), k_max=K)
    q = unit_db.queries[:1]
    ids, dists, *_ = run_bucketed(unit_index, cfg, q, 32, cfg.expand, "f32")
    assert ids.shape == (1, K) and dists.shape == (1, K)

    # same program, 32 real queries: lane 0 must be bit-identical to the
    # padded single — padding cannot consume beam slots or shift results
    full = unit_db.queries[:32]
    ids_f, dists_f, *_ = run_bucketed(unit_index, cfg, full, 32,
                                      cfg.expand, "f32")
    np.testing.assert_array_equal(ids[0], ids_f[0])
    np.testing.assert_array_equal(dists[0], dists_f[0])

    # ... at any lane position
    perm = np.concatenate([unit_db.queries[1:18], q,
                           unit_db.queries[18:32]])
    ids_p, dists_p, *_ = run_bucketed(unit_index, cfg, perm, 32,
                                      cfg.expand, "f32")
    np.testing.assert_array_equal(ids[0], ids_p[17])
    np.testing.assert_array_equal(dists[0], dists_p[17])

    # against the unpadded batch-1 program: ids exact, dists to fp32 noise
    # (different program shape -> different gemm blocking)
    res = unit_index.searcher(
        "local", params_for(cfg, 32, cfg.expand, "f32"))(q)
    np.testing.assert_array_equal(ids, res.ids[:, :K])
    np.testing.assert_allclose(dists, res.dists[:, :K], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("storage,metric", [
    *(pytest.param(s, "l2", id=s) for s in STORAGES),
    *(pytest.param(s, "ip", id=f"{s}-ip") for s in STORAGES)])
def test_batched_mixed_traffic_bit_identical(unit_store, storage, metric):
    """Mixed k/ef traffic through the live batcher == one-by-one searches."""
    db, idx = unit_store(storage, metric)
    cfg = ServeConfig(ef_buckets=(16, 32), batch_buckets=(1, 4, 8), k_max=K,
                      storages=(storage,), use_dfloat=storage != "f32",
                      slo_ms=5000.0)
    with Server(idx, cfg) as srv:
        cases = [(db.queries[i], [16, 32, 48][i % 3], [3, 7, K][i % 3])
                 for i in range(24)]
        futs = [srv.submit(q, k=k, ef=ef) for q, ef, k in cases]
        resps = [f.result(timeout=60) for f in futs]
    for (q, ef, k), r in zip(cases, resps):
        assert r.status == "ok"
        assert r.ids.shape == (k,) and r.dists.shape == (k,)
        assert r.ef_served == cfg.ef_bucket(ef)   # rounded UP, never down
        # replay one-by-one through the program that served it: whatever the
        # request was co-batched with must not have changed a single bit
        ref_ids, ref_dists = _direct(idx, q[None], cfg, ef, k, storage,
                                     bucket=r.batch_bucket)
        np.testing.assert_array_equal(r.ids, ref_ids[0])
        np.testing.assert_array_equal(r.dists, ref_dists[0])


# ---------------------------------------------------------------------------
# hot swap: zero failures, consistent generations, delta uploads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("storage", STORAGES)
def test_hot_swap_mid_stream_consistent(unit_store, storage):
    db, idx = unit_store(storage)
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(1, 4), k_max=K,
                      storages=(storage,), use_dfloat=storage != "f32",
                      slo_ms=5000.0, swap_poll_s=0.05)
    mi = MutableIndex(idx, ef_build=32, sub_batch=64)
    rng = np.random.default_rng(0)

    def churn():
        mi.append(rng.standard_normal((4, db.dim)).astype(np.float32))
        mi.delete(rng.integers(0, db.n, 2))

    churn()             # compile the write path before the window,
    mi.freeze()         # and drain its repair
    # a churn installs up to two generations (after its append, and after
    # its delete and repair): with the first, three churns make at most 7,
    # so every generation a response names is still in the server's
    # 8-deep history
    window = iter(range(3))

    def churn_in_window():
        if next(window, None) is not None:
            churn()

    with Server(mi, cfg) as srv:
        resps = run_load(srv, db.queries, rps=60, duration_s=3.0,
                         ef=32, k=K, deadline_ms=5000.0, seed=1,
                         mutate_fn=churn_in_window, mutate_every_s=0.3)
        history = dict(srv.history)
        swap_summary = srv.metrics.summary().get("swaps", {})

    # zero request failures across every swap
    assert all(r.status == "ok" for r in resps)
    gens = {r.generation for r in resps}
    assert len(gens) > 1, "expected at least one mid-stream hot swap"
    # every response came from an actually-installed generation
    assert gens <= set(history)

    # a served response must be reproducible on its own generation's
    # snapshot — bit-identical, not merely plausible
    by_gen = {}
    for i, r in enumerate(resps):
        by_gen.setdefault(r.generation, (i, r))
    for gen, (i, r) in by_gen.items():
        snap = history[gen]
        q = db.queries[i % len(db.queries)][None]
        ref_ids, ref_dists = _direct(snap, q, cfg, 32, K, storage,
                                     bucket=r.batch_bucket)
        np.testing.assert_array_equal(r.ids, ref_ids[0])
        np.testing.assert_array_equal(r.dists, ref_dists[0])

    # swaps shipped deltas, not full payloads
    assert swap_summary.get("delta_installs", 0) >= 1
    assert swap_summary["max_delta_reupload_fraction"] < 0.25


def test_delta_upload_accounting(unit_db, unit_index):
    """Byte-exact: a generation swap ships only the appended tail + dirtied
    adjacency/tombstone, and splices to exactly what a cold upload builds."""
    import copy

    mi = MutableIndex(unit_index, ef_build=32, sub_batch=64)
    cache = DeviceCache(storage="f32", use_dfloat=False, donate=True)
    s0 = cache.install(mi.freeze())
    assert s0.mode == "full" and s0.h2d_bytes == s0.full_bytes

    rng = np.random.default_rng(2)
    mi.append(rng.standard_normal((8, unit_db.dim)).astype(np.float32))
    mi.delete(np.arange(4))
    snap = mi.freeze()
    s1 = cache.install(snap)
    assert s1.mode == "delta" and s1.donated
    assert s1.tail_rows == 8
    assert s1.dirty_tombstone_words >= 1
    assert s1.h2d_bytes < 0.1 * s1.full_bytes
    assert s1.reused_rows > 0

    fresh = DeviceCache(storage="f32", use_dfloat=False, donate=False)
    bare = copy.copy(snap)
    bare._device, bare._searchers = {}, {}
    fresh.install(bare)
    np.testing.assert_array_equal(np.asarray(cache._db),
                                  np.asarray(fresh._db))
    np.testing.assert_array_equal(np.asarray(cache._adj),
                                  np.asarray(fresh._adj))
    np.testing.assert_array_equal(np.asarray(cache._tomb),
                                  np.asarray(fresh._tomb))


def test_freeze_stamps_n_rows(unit_db, unit_index):
    mi = MutableIndex(unit_index)
    snap = mi.freeze()
    assert snap.n_rows == unit_db.n      # allocated prefix, not capacity
    assert snap.n >= snap.n_rows         # capacity array is larger
    mi.append(np.zeros((3, unit_db.dim), np.float32))
    assert mi.freeze().n_rows == unit_db.n + 3


# ---------------------------------------------------------------------------
# persistent compilation cache (warm start)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_compilation_cache_persists(tmp_path):
    """enable_compilation_cache must make jit executables land on disk, in
    $JAX_COMPILATION_CACHE_DIR, even when something compiled before it ran
    (fresh interpreter per phase)."""
    import subprocess
    import sys

    prog = """
import jax, jax.numpy as jnp                      # compile before enabling
jax.jit(lambda x: x + 1)(jnp.zeros(8)).block_until_ready()
from repro.serve import enable_compilation_cache
print(enable_compilation_cache())
jax.jit(lambda x: x * 3 - 1)(jnp.zeros(128)).block_until_ready()
"""
    d = tmp_path / "cc"
    out = subprocess.run([sys.executable, "-c", prog], check=True,
                         capture_output=True, text=True, timeout=300,
                         env=dict(_env(), JAX_COMPILATION_CACHE_DIR=str(d)))
    assert out.stdout.split()[-1] == str(d)
    entries = list(d.glob("*"))
    assert entries, "no compilation cache entries were persisted"


def _env():
    import os
    from pathlib import Path

    env = dict(os.environ)
    src = str(Path(__file__).parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env
