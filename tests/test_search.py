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


def _np_bitmap(n, ids):
    words = np.zeros(-(-n // 32), np.uint32)
    np.bitwise_or.at(words, ids >> 5,
                     np.uint32(1) << (ids & 31).astype(np.uint32))
    return words


# (rows, ids marked per query): word and row edges, the last row of an n
# that is a multiple of neither 32 nor 4,096 (the bits of one bitmap row),
# several distinct bits of one word, and several queries under vmap
_BITMAP_CASES = {
    "id0": (4099, [[0]]),
    "word_edge": (4099, [[31, 32]]),
    "last": (4099, [[4098]]),
    "row_edge": (4099, [[4095, 4096]]),
    "one_word": (4099, [[64, 65, 70, 95]]),
    "exact_row": (4096, [[0, 4095]]),
    "spread": (9001, [list(range(3, 9001, 37))]),
    "vmap": (4099, [[0, 31, 32], [4098, 64, 95], [7, 8, 4000]]),
}


@pytest.mark.parametrize("case", sorted(_BITMAP_CASES))
def test_bitmap_rows_match_numpy(case):
    """Row-granular bitmap lookup and mark against a flat numpy bitmap: a
    masked-off lane marks nothing, and the rows read back as the flat
    words (``bitmap_from_words``)."""
    import jax
    import jax.numpy as jnp

    from repro.core import search as s

    n, marks = _BITMAP_CASES[case]
    ids = np.asarray(marks, np.int32)                       # (Q, k)
    # one extra lane per query, masked off: it must stay unmarked
    spare = np.asarray([(n - 2 - i) for i in range(len(ids))], np.int32)
    lanes = np.concatenate([ids, spare[:, None]], axis=1)
    mask = np.ones(lanes.shape, bool)
    mask[:, -1] = False
    empty = jnp.zeros((s.bitmap_rows(n), s.BITMAP_LANES), jnp.uint32)
    rows = jax.vmap(lambda i, m: s.bitmap_mark(empty, i, m))(lanes, mask)
    every = jnp.arange(n, dtype=jnp.int32)
    seen = np.asarray(jax.vmap(lambda r: s.bitmap_lookup(r, every))(rows))
    for qi, want in enumerate(ids):
        words = _np_bitmap(n, want[~np.isin(want, spare[qi])])
        flat = np.asarray(rows[qi]).reshape(-1)
        np.testing.assert_array_equal(flat[: len(words)], words)
        assert not flat[len(words):].any()
        np.testing.assert_array_equal(
            np.asarray(s.bitmap_from_words(jnp.asarray(words))), rows[qi])
        bits = (words[every >> 5] >> (np.asarray(every) & 31)) & 1
        np.testing.assert_array_equal(seen[qi], bits.astype(bool))


def _take_reference(name, rng):
    """(values, picks) of one select the hop body makes, with the picks
    from ``lax.top_k`` over keys full of ties."""
    import jax

    if name == "merge":             # ef + L candidates, tied distances
        d = rng.integers(0, 6, 104).astype(np.float32)
        d[rng.random(104) < 0.3] = 3.0e38
        _, order = jax.lax.top_k(-d, 64)
        return rng.integers(-1, 40000, 104).astype(np.int32), order
    if name == "compaction":        # the fresh mask, 40 of 80 lanes kept
        fresh = rng.random(80) < 0.3
        _, keep = jax.lax.top_k(fresh.astype(np.float32), 40)
        return rng.integers(-1, 40000, 80).astype(np.int32), keep
    assert name == "pop"            # 4 of an ef beam, most already popped
    d = np.where(rng.random(64) < 0.8, np.float32(3.0e38),
                 rng.integers(0, 3, 64).astype(np.float32))
    _, idxs = jax.lax.top_k(-d, 4)
    return rng.integers(-1, 40000, 64).astype(np.int32), idxs


@pytest.mark.parametrize("name", ["merge", "compaction", "pop"])
def test_onehot_take_matches_take(name):
    """The one-hot selects of the beam merge, the compaction and the
    frontier pop equal ``jnp.take`` for int ids and bool flags, singly and
    under vmap; ``merge_beam`` and ``pop_frontier`` equal their
    index-by-index forms."""
    import jax
    import jax.numpy as jnp

    from repro.core import search as s

    rng = np.random.default_rng(len(name))
    cases = [_take_reference(name, rng) for _ in range(8)]
    for x, idx in cases:
        flags = jnp.asarray(x % 3 == 0)
        np.testing.assert_array_equal(s.onehot_take(jnp.asarray(x), idx),
                                      jnp.take(x, idx))
        np.testing.assert_array_equal(s.onehot_take(flags, idx),
                                      jnp.take(flags, idx))
    xs = jnp.stack([jnp.asarray(x) for x, _ in cases])
    idxs = jnp.stack([i for _, i in cases])
    np.testing.assert_array_equal(jax.vmap(s.onehot_take)(xs, idxs),
                                  jnp.take_along_axis(xs, idxs, axis=1))

    ids = rng.integers(0, 40000, (8, 64)).astype(np.int32)
    d = np.sort(rng.integers(0, 9, (8, 64)).astype(np.float32), axis=1)
    d[:, 50:] = 3.0e38
    exp = rng.random((8, 64)) < 0.5
    cand_ids = rng.integers(0, 40000, (8, 40)).astype(np.int32)
    cand_d = rng.integers(0, 9, (8, 40)).astype(np.float32)

    def merge_ref(bi, bd, ex, ci, cd):
        all_ids = jnp.concatenate([bi, ci])
        all_exp = jnp.concatenate([ex, jnp.zeros(cd.shape[0], bool)])
        neg, order = jax.lax.top_k(-jnp.concatenate([bd, cd]), bi.shape[0])
        return all_ids[order], -neg, all_exp[order] | (-neg >= s.BIG)

    def pop_ref(bi, bd, ex):
        active = (~ex) & (bd < s.BIG)
        _, i = jax.lax.top_k(-jnp.where(active, bd, s.BIG), 4)
        sel = active[i] & active.any()
        return jnp.where(sel, bi[i], -1), sel, ex.at[i].set(True)

    for got, want in ((jax.vmap(s.merge_beam)(ids, d, exp, cand_ids, cand_d),
                       jax.vmap(merge_ref)(ids, d, exp, cand_ids, cand_d)),
                      (jax.vmap(lambda *a: s.pop_frontier(*a, 4))(ids, d, exp),
                       jax.vmap(pop_ref)(ids, d, exp))):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
