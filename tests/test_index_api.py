"""Unified Index API: persistence round trip, typed params, backend parity,
and the removal of the legacy surface."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.index import Index, IndexSpec, SearchParams, SearchResult

PARAMS = SearchParams(ef=48, k=10, use_dfloat=False)
STORAGES = ("f32", "packed", "tiered")
# every storage under each metric, named as in the other test files
STORE_METRIC = [*(pytest.param(s, "l2", id=s) for s in STORAGES),
                *(pytest.param(s, "ip", id=f"{s}-ip") for s in STORAGES)]


def _params(storage):
    return dataclasses.replace(PARAMS, storage=storage,
                               use_dfloat=storage != "f32")


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.mean([len(set(x.tolist()) & set(y.tolist())) / a.shape[1]
                          for x, y in zip(a, b)]))


# ---------------------------------------------------------------------------
# typed params
# ---------------------------------------------------------------------------


def test_fee_params_is_a_pytree(unit_index):
    fp = unit_index.fee.params
    leaves, treedef = jax.tree_util.tree_flatten(fp)
    assert len(leaves) == 3
    fp2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(np.asarray(fp2.alpha), np.asarray(fp.alpha))
    # usable through jit like any other array bundle
    scaled = jax.jit(lambda p: jax.tree.map(lambda x: 2 * x, p))(fp)
    np.testing.assert_allclose(np.asarray(scaled.beta),
                               2 * np.asarray(fp.beta), rtol=1e-6)


def test_spec_json_round_trip(unit_db):
    spec = IndexSpec.for_db(unit_db, m=8, dfloat_recall_target=None)
    assert IndexSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError):
        Index.build(unit_db, dataclasses.replace(spec, metric="ip"))


def test_search_params_validation(unit_index):
    with pytest.raises(ValueError):
        unit_index.searcher("warp-drive")
    with pytest.raises(ValueError):
        unit_index.searcher("sharded", SearchParams(trace=True))


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage,metric", STORE_METRIC)
def test_save_load_round_trip(unit_store, storage, metric, tmp_path):
    db, idx = unit_store(storage, metric)
    params = _params(storage)
    path = idx.save(tmp_path / "idx.naszip")
    loaded = Index.load(path)

    assert loaded.spec == idx.spec
    assert loaded.dfloat_cfg == idx.dfloat_cfg
    for f in ("alpha", "beta", "margin", "var_k"):
        np.testing.assert_array_equal(getattr(loaded.fee, f),
                                      getattr(idx.fee, f))
    np.testing.assert_array_equal(loaded.db_packed, idx.db_packed)

    ref = idx.search(db.queries, params)
    got = loaded.search(db.queries, params)
    np.testing.assert_array_equal(got.ids, ref.ids)
    np.testing.assert_array_equal(got.dists, ref.dists)


def test_load_rejects_unknown_format(unit_index, tmp_path):
    path = unit_index.save(tmp_path / "idx.naszip")
    spec = path / "spec.json"
    spec.write_text(spec.read_text().replace('"format_version": 3',
                                             '"format_version": 99'))
    with pytest.raises(ValueError):
        Index.load(path)


# ---------------------------------------------------------------------------
# backend parity (one searcher() call, three substrates)
# ---------------------------------------------------------------------------


def _single_device_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("storage,metric", STORE_METRIC)
def test_local_sharded_parity(unit_store, storage, metric):
    db, idx = unit_store(storage, metric)
    params = _params(storage)
    ref = idx.searcher("local", params)(db.queries)
    sh = idx.searcher("sharded", params,
                      mesh=_single_device_mesh())(db.queries)
    assert _overlap(sh.ids, ref.ids) >= 0.95


def test_loaded_index_runs_all_backends(unit_db, unit_index, tmp_path):
    """Acceptance: build -> save -> load -> one searcher(backend=...) call per
    substrate, identical ids on the local round trip."""
    loaded = Index.load(unit_index.save(tmp_path / "idx.naszip"))
    ref = unit_index.search(unit_db.queries[:16], PARAMS)

    local = loaded.searcher("local", PARAMS)(unit_db.queries[:16])
    np.testing.assert_array_equal(local.ids, ref.ids)

    sharded = loaded.searcher("sharded", PARAMS,
                              mesh=_single_device_mesh())(unit_db.queries[:16])
    assert _overlap(sharded.ids, ref.ids) >= 0.9

    ndp = loaded.searcher("ndpsim", PARAMS)(unit_db.queries[:16])
    assert ndp.sim is not None and ndp.sim.qps > 0
    assert _overlap(ndp.ids, ref.ids) >= 0.9
    for r in (local, sharded, ndp):
        assert isinstance(r, SearchResult)
        assert r.ids.shape == (16, PARAMS.k)


def test_searcher_cache_reuses_compiled_fn(unit_index):
    a = unit_index.searcher("local", PARAMS)
    b = unit_index.searcher("local", PARAMS)
    assert a is b
    c = unit_index.searcher("local", dataclasses.replace(PARAMS, ef=49))
    assert c is not a


# ---------------------------------------------------------------------------
# legacy surface removed (deprecation window closed after PR 2)
# ---------------------------------------------------------------------------


def test_legacy_shims_are_gone():
    import repro.core as core
    from repro.core import fee as fee_mod
    from repro.core import search as search_mod

    assert not hasattr(core, "vdzip")
    assert not hasattr(search_mod, "run_search")
    assert not hasattr(fee_mod, "make_fee_params")
