"""Beam search behaviour: recall vs exact GT, FEE effects, trace invariants."""
import numpy as np
import pytest

from repro.core.search import SearchConfig
from repro.index import SearchParams


def test_exact_search_recall(unit_db, unit_index):
    res = unit_index.evaluate(unit_db, SearchParams(ef=64, k=10, use_fee=False,
                                                    use_dfloat=False))
    assert res["recall"] >= 0.92, res


def test_fee_preserves_recall_within_budget(unit_db, unit_index):
    base = unit_index.evaluate(unit_db, SearchParams(ef=64, k=10, use_fee=False,
                                                     use_dfloat=False, trace=True))
    fee = unit_index.evaluate(unit_db, SearchParams(ef=64, k=10, use_fee=True,
                                                    use_dfloat=False, trace=True))
    assert fee["recall"] >= base["recall"] - 0.03, (base, fee)
    assert fee["dims_per_eval"] <= base["dims_per_eval"] + 1e-6
    # claim: FEE reduces dims touched (paper Fig. 8: ~does more on steeper
    # spectra; the unit dataset is small, so just require strict reduction)
    assert fee["dims_per_eval"] < base["dims_per_eval"]


@pytest.mark.slow
def test_dfloat_search_recall(unit_db, unit_index_dfloat):
    res = unit_index_dfloat.evaluate(unit_db, SearchParams(ef=64, k=10))
    assert res["recall"] >= 0.85, res
    assert (unit_index_dfloat.dfloat_cfg.bursts_per_vector()
            <= 16), "compression should not exceed fp32 bursts (64d -> 16)"


@pytest.mark.parametrize("storage", ["f32", "packed", "tiered"])
def test_ip_metric_search(unit_store, storage):
    db, idx = unit_store(storage, "ip")
    kw = dict(ef=96, k=10, storage=storage, use_dfloat=storage != "f32",
              trace=True)
    res = idx.evaluate(db, SearchParams(use_fee=True, **kw))
    base = idx.evaluate(db, SearchParams(use_fee=False, **kw))
    assert res["recall"] >= base["recall"] - 0.03
    assert res["dims_per_eval"] <= base["dims_per_eval"]


def test_recall_increases_with_ef(unit_db, unit_index):
    recalls = [unit_index.evaluate(unit_db, SearchParams(ef=ef, k=10,
                                                         use_dfloat=False))["recall"]
               for ef in (8, 32, 96)]
    assert recalls[0] <= recalls[1] + 0.02 <= recalls[2] + 0.04, recalls
    assert recalls[-1] >= 0.93


def test_trace_no_duplicate_evaluations(unit_db, unit_index):
    """Visited-set invariant: a node is distance-evaluated at most once."""
    out = unit_index.search(unit_db.queries[:8],
                            SearchParams(ef=32, k=10, use_fee=False, trace=True))
    nbrs = out.trace["nbrs"]                         # (Q, H, M)
    for qi in range(nbrs.shape[0]):
        ids = nbrs[qi][nbrs[qi] >= 0]
        assert len(ids) == len(set(ids.tolist())), "duplicate evaluation"


def test_trace_hops_bounded_and_consistent(unit_db, unit_index):
    out = unit_index.search(unit_db.queries[:8],
                            SearchParams(ef=16, k=5, trace=True))
    cfg_hops = SearchConfig(ef=16).hops()
    assert (out.hops <= cfg_hops).all()
    # dims accounting consistent with segs trace
    assert (out.dims == out.trace["segs"].sum((1, 2)) * 16).all()


def test_untraced_search_uses_early_termination(unit_db, unit_index):
    """The fast while_loop path and the fixed-budget scan path must agree on
    the returned neighbors (trace is opt-in, not a semantic change)."""
    fast = unit_index.search(unit_db.queries[:16], SearchParams(ef=32, k=10))
    traced = unit_index.search(unit_db.queries[:16],
                               SearchParams(ef=32, k=10, trace=True))
    assert fast.trace is None and traced.trace is not None
    np.testing.assert_array_equal(fast.ids, traced.ids)
