"""Packed-native storage: scoring straight from the Dfloat bitstream must be
bit-identical to scoring the derived f32 view, the manual-DMA kernels must
match their auto-pipelined baselines, and format-v1 artifacts must still load."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dfloat as dfl
from repro.index import Index, IndexSpec, SearchParams

PARAMS = SearchParams(ef=48, k=10, use_dfloat=True)


def _kernel_inputs(c=100, d=128, seg=16, seed=0, metric="l2"):
    rng = np.random.default_rng(seed)
    s = d // seg
    x = rng.standard_normal((c, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal(d), jnp.float32)
    alpha = jnp.asarray(1.0 + 1.0 / np.arange(1, s + 1), jnp.float32)
    beta = jnp.asarray(1.0 + 0.2 / np.arange(1, s + 1), jnp.float32)
    margin = jnp.zeros(s, jnp.float32)
    base = np.median(((x - np.asarray(q)) ** 2).sum(1)) if metric == "l2" \
        else -np.median(x @ np.asarray(q))
    return q, x, jnp.float32(base), alpha, beta, margin


# ---------------------------------------------------------------------------
# bitstream decode + packed scoring parity (jnp layer)
# ---------------------------------------------------------------------------


def test_unpack_rows_jnp_bit_exact():
    _, x, *_ = _kernel_inputs()
    cfg = dfl.make_config(128, [(21, 6, 64), (14, 5, 64)], x)
    packed = dfl.pack_db(x, cfg)
    want = dfl.unpack_db(packed, cfg)
    got = np.asarray(dfl.unpack_rows_jnp(jnp.asarray(packed), cfg))
    assert np.array_equal(got, want)
    # and the decode equals the mask-emulated view the search scores against
    assert np.array_equal(want, dfl.emulate_db(x, cfg))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_packed_ref_scoring_bit_equals_dbq(metric):
    from repro.kernels import ref as ref_ops

    q, x, thr, alpha, beta, margin = _kernel_inputs(metric=metric)
    cfg = dfl.make_config(128, [(18, 6, 80), (12, 4, 48)], x)
    packed = jnp.asarray(dfl.pack_db(x, cfg))
    dbq = jnp.asarray(dfl.emulate_db(x, cfg))
    want = ref_ops.fee_distance_ref(q, dbq, thr, alpha, beta, margin,
                                    seg=16, metric=metric)
    got = ref_ops.fee_distance_packed_ref(q, packed, thr, alpha, beta, margin,
                                          dfloat_cfg=cfg, seg=16, metric=metric)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# kernel variants: skip_dma == baseline, packed == f32-over-db_q
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_skipdma_kernel_equals_baseline(metric):
    from repro.kernels.fee_distance import (fee_distance_pallas,
                                            fee_distance_skipdma_pallas)

    q, x, thr, alpha, beta, margin = _kernel_inputs(c=129, metric=metric)
    xj = jnp.asarray(x)
    base = fee_distance_pallas(q, xj, thr, alpha, beta, margin,
                               seg=16, metric=metric, tile_c=64)
    skip = fee_distance_skipdma_pallas(q, xj, thr, alpha, beta, margin,
                                       seg=16, metric=metric, tile_c=64)
    for g, w in zip(skip, base):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# (candidates, width, tile_c, threshold): one 100-lane query over two
# tiles; five tiles, the last ragged; a row past one exit-check group where
# every lane exits at the first block, so every tile's later blocks (and, with
# skip_dma, their fetches) are skipped
PACKED_CASES = {"base": (100, 128, 64, None), "multi_tile": (300, 128, 64, None),
                "all_exit": (150, 256, 64, -1.0)}


@pytest.mark.parametrize("case,skip_dma,metric", [
    *(pytest.param(case, skip, "l2",
                   id=str(skip) if case == "base" else f"{case}-{skip}")
      for case in PACKED_CASES for skip in (False, True)),
    *(pytest.param(case, False, "ip",
                   id="ip" if case == "base" else f"{case}-ip")
      for case in PACKED_CASES)])
def test_packed_kernel_matches_ref(case, skip_dma, metric):
    from repro.kernels import ref as ref_ops
    from repro.kernels.fee_distance import fee_distance_packed_pallas

    c, d, tile_c, fixed_thr = PACKED_CASES[case]
    q, x, thr, alpha, beta, margin = _kernel_inputs(c=c, d=d, metric=metric)
    if fixed_thr is not None:
        # l2 scores are >= 0; an ip score can be any float, so its threshold
        # sits below every one
        thr = jnp.float32(fixed_thr if metric == "l2" else -3e38)
    cfg = dfl.make_config(d, [(21, 6, d // 2), (14, 5, d // 2)], x)
    packed = jnp.asarray(dfl.pack_db(x, cfg))
    want = ref_ops.fee_distance_packed_ref(q, packed, thr, alpha, beta, margin,
                                           dfloat_cfg=cfg, seg=16, metric=metric)
    got = fee_distance_packed_pallas(q, packed, thr, alpha, beta, margin,
                                     dfloat_cfg=cfg, seg=16, metric=metric,
                                     tile_c=tile_c, skip_dma=skip_dma)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5, atol=2e-4)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))
    if case == "all_exit":
        assert np.asarray(got[1]).all() and (np.asarray(got[2]) == 1).all()


# ---------------------------------------------------------------------------
# end-to-end search parity: storage="packed" vs storage="f32" over db_q
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixtures", ["l2", "ip"])
def test_packed_search_bit_identical(fixtures, unit_db, unit_ip_db,
                                     unit_index, unit_ip_index):
    db, idx = ((unit_db, unit_index) if fixtures == "l2"
               else (unit_ip_db, unit_ip_index))
    ref = idx.search(db.queries, PARAMS)
    got = idx.search(db.queries, dataclasses.replace(PARAMS, storage="packed"))
    np.testing.assert_array_equal(got.ids, ref.ids)
    np.testing.assert_array_equal(got.dists, ref.dists)


def test_packed_search_no_fee_bit_identical(unit_db, unit_index):
    p = dataclasses.replace(PARAMS, use_fee=False)
    ref = unit_index.search(unit_db.queries[:32], p)
    got = unit_index.search(unit_db.queries[:32],
                            dataclasses.replace(p, storage="packed"))
    np.testing.assert_array_equal(got.ids, ref.ids)


def test_packed_search_never_materializes_dbq(unit_db):
    idx = Index.build(unit_db, IndexSpec.for_db(unit_db, m=8,
                                                dfloat_recall_target=None))
    assert idx._db_q is None
    idx.search(unit_db.queries[:8], dataclasses.replace(PARAMS, storage="packed"))
    assert idx._db_q is None, "packed path must not derive the full f32 copy"
    # the f32 view is still available on demand
    assert idx.db_q.shape == idx.db_rot.shape


def test_sharded_packed_parity(unit_db, unit_index):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ref = unit_index.searcher("local", PARAMS)(unit_db.queries[:32])
    sh = unit_index.searcher("sharded",
                             dataclasses.replace(PARAMS, storage="packed"),
                             mesh=mesh)(unit_db.queries[:32])
    overlap = np.mean([len(set(a) & set(b)) / PARAMS.k
                       for a, b in zip(sh.ids.tolist(), ref.ids.tolist())])
    assert overlap >= 0.95


def test_ndpsim_packed_backend(unit_db, unit_index):
    res = unit_index.searcher(
        "ndpsim", dataclasses.replace(PARAMS, storage="packed"))(unit_db.queries[:8])
    assert res.sim is not None and res.sim.qps > 0


@pytest.mark.slow
def test_search_fee_backend_pallas_skip_dma(unit_db, unit_index):
    """The manual-DMA kernel path through the full search loop (interpret
    mode on CPU) must agree with the jnp oracle path."""
    base = dataclasses.replace(PARAMS, ef=16, fee_backend="jnp")
    ref = unit_index.search(unit_db.queries[:4], base)
    for storage in ("f32", "packed"):
        got = unit_index.search(
            unit_db.queries[:4],
            dataclasses.replace(base, fee_backend="pallas_skip_dma",
                                storage=storage))
        overlap = np.mean([len(set(a) & set(b)) / PARAMS.k
                           for a, b in zip(got.ids.tolist(), ref.ids.tolist())])
        assert overlap >= 0.9, storage


# ---------------------------------------------------------------------------
# knob validation + device cache
# ---------------------------------------------------------------------------


def test_storage_validation(unit_index):
    from repro.core.search import SearchConfig, make_searcher

    with pytest.raises(ValueError):
        SearchParams(storage="packed", use_dfloat=False)
    with pytest.raises(ValueError):
        SearchConfig(storage="warp-drive")
    with pytest.raises(ValueError):
        make_searcher(unit_index.db_packed, unit_index.graph.base_adjacency,
                      SearchConfig(storage="packed"))


def test_device_cache_uploads_packed(unit_index):
    a = unit_index.device_db(True, "packed")
    b = unit_index.device_db(True, "packed")
    assert a is b
    assert a.dtype == jnp.uint32
    assert a.shape == unit_index.db_packed.shape


# ---------------------------------------------------------------------------
# persistence: v2 drops db_q; v1 artifacts still load
# ---------------------------------------------------------------------------


def test_save_drops_dbq_payload(unit_index, tmp_path):
    path = unit_index.save(tmp_path / "v2.naszip")
    with np.load(path / "arrays.npz") as z:
        assert "db_q" not in z.files
        arrays = {k: z[k] for k in z.files}
    new_size = (path / "arrays.npz").stat().st_size
    # re-add the derived copy the old format persisted: the artifact must
    # shrink by (at least most of) that payload — gaussian f32 data is
    # essentially incompressible, so the compressed delta tracks nbytes
    np.savez_compressed(tmp_path / "v1_arrays.npz", db_q=unit_index.db_q,
                        **arrays)
    old_size = (tmp_path / "v1_arrays.npz").stat().st_size
    assert old_size - new_size >= 0.8 * unit_index.db_q.nbytes


def test_load_pre_refactor_v1_artifact(unit_db, unit_index, tmp_path):
    """A format-v1 directory (spec.json v1 + arrays.npz carrying db_q) must
    load and search identically to the index that wrote it."""
    path = unit_index.save(tmp_path / "old.naszip")
    spec = path / "spec.json"
    meta = json.loads(spec.read_text())
    assert meta["format_version"] == 3
    meta["format_version"] = 1
    spec.write_text(json.dumps(meta, indent=1))
    with np.load(path / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files}
    np.savez_compressed(path / "arrays.npz", db_q=unit_index.db_q, **arrays)

    loaded = Index.load(path)
    assert loaded._db_q is not None, "v1 db_q seeds the derived-view cache"
    np.testing.assert_array_equal(loaded.db_q, unit_index.db_q)
    ref = unit_index.search(unit_db.queries[:16], PARAMS)
    got = loaded.search(unit_db.queries[:16], PARAMS)
    np.testing.assert_array_equal(got.ids, ref.ids)
    pk = loaded.search(unit_db.queries[:16],
                       dataclasses.replace(PARAMS, storage="packed"))
    np.testing.assert_array_equal(pk.ids, ref.ids)
