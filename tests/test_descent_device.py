"""The entry descent on the device: every upper level resident per index,
all levels descended in one program, entries equal to the per-level host
loop it replaced (kept here as the oracle)."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import fee as fee_mod
from repro.core import graph as gmod
from repro.core import search as search_mod
from repro.index import SearchParams, SearchResult
from repro.index.backends import _dfloat_cfg, _fee

COUNTER = "search.descent_h2d_bytes"
# upper-level sizes, top level last; "loose" levels are drawn independently,
# so some of a level's nodes are missing from the level below it
GRAPHS = {"deep": (400, 60, 9), "single": (100,), "loose": (400, 60, 9),
          "flat": ()}
STORES = {"f32": SearchParams(storage="f32", use_dfloat=False),
          "packed": SearchParams(storage="packed"),
          "tiered": SearchParams(storage="tiered")}


@partial(jax.jit, static_argnames=("metric",))
def _greedy_level(vecs_l, adj_l, queries, cur, *, metric):
    """One upper level's greedy descent for a query batch."""

    def greedy(q, c):
        def body(s):
            c, d, _ = s
            nb = adj_l[c]
            nd = fee_mod.exact_distance(q, vecs_l[nb], metric=metric)
            j = jnp.argmin(nd)
            better = nd[j] < d
            return (jnp.where(better, nb[j], c), jnp.minimum(nd[j], d), better)

        d0 = fee_mod.exact_distance(q, vecs_l[c][None], metric=metric)[0]
        return jax.lax.while_loop(lambda s: s[2], body,
                                  (c, d0, jnp.bool_(True)))[0]

    return jax.vmap(greedy)(queries, cur)


def oracle_entries(fetch, graph, queries, metric):
    """The host loop: per level, route the entries by ``searchsorted``,
    upload the level's rows and run its greedy program, copy back."""
    entries = np.full(len(queries), graph.entry, np.int64)
    for level in range(len(graph.levels) - 1, 0, -1):
        ids, adj = graph.levels[level]
        pos = np.clip(np.searchsorted(ids, entries), 0, len(ids) - 1)
        cur = np.where(ids[pos] == entries, pos, 0).astype(np.int32)
        cur = np.asarray(_greedy_level(jnp.asarray(fetch(ids)),
                                       jnp.asarray(adj, jnp.int32),
                                       jnp.asarray(queries),
                                       jnp.asarray(cur), metric=metric))
        entries = ids[cur]
    return entries.astype(np.int32)


def _graph(rot, sizes, nested, metric, m=8, seed=0):
    """The index's base level under upper levels of the given sizes."""
    rng = np.random.default_rng(seed)
    base = gmod.build_graph(rot, m=m, metric=metric, upper_branch=len(rot),
                            seed=seed)
    levels, ids = list(base.levels), np.arange(len(rot))
    for size in sizes:
        pool = ids if nested else np.arange(len(rot))
        ids = np.sort(rng.choice(pool, size, replace=False))
        adj = gmod._knn_adjacency(rot[ids], min(m, size - 1), metric)
        adj = gmod._add_long_edges(adj, rng, 2)
        levels.append((ids.astype(np.int32), adj.astype(np.int32)))
    return gmod.GraphIndex(levels=levels, entry=int(levels[-1][0][0]), m=m)


@pytest.fixture(scope="module")
def indexes(unit_index_dfloat, unit_ip_index_dfloat):
    """The Dfloat unit index under each graph of ``GRAPHS``, and the
    inner-product one under the "deep" graph ("deep-ip"), each with its own
    (empty) device and searcher caches."""
    def under(idx, name):
        graph = _graph(idx.db_rot, GRAPHS[name], name != "loose", idx.metric)
        return dataclasses.replace(idx, graph=graph, _device={},
                                   _searchers={})

    return {**{name: under(unit_index_dfloat, name) for name in GRAPHS},
            "deep-ip": under(unit_ip_index_dfloat, "deep")}


def _rows(idx, params):
    return (idx.emulated_rows if params.use_dfloat
            else (lambda ids: idx.db_rot[ids]))


def _queries(unit_db, idx, bucket):
    return np.asarray(idx.transform_queries(unit_db.queries[:bucket]))


def test_graphs_have_the_levels_named(indexes):
    assert [len(indexes[g].graph.levels) - 1 for g in GRAPHS] == [3, 1, 3, 0]
    lo, mid = (indexes["loose"].graph.levels[i][0] for i in (1, 2))
    assert not np.isin(mid, lo).all()       # the fallback to index 0 is hit


@pytest.mark.parametrize("graph", [*GRAPHS, "deep-ip"])
@pytest.mark.parametrize("bucket", [1, 32])
@pytest.mark.parametrize("store", list(STORES))
def test_descend_entry_matches_host_loop(unit_db, unit_ip_db, indexes, store,
                                         bucket, graph):
    """``descend_entry`` (levels uploaded, one program) returns the entries
    of the per-level host loop, bit for bit."""
    idx, params = indexes[graph], STORES[store]
    q = _queries(unit_ip_db if idx.metric == "ip" else unit_db, idx, bucket)
    want = oracle_entries(_rows(idx, params), idx.graph, q, idx.metric)
    got = search_mod.descend_entry(_rows(idx, params), idx.graph, q,
                                   idx.metric)
    assert got.dtype == np.int32 and got.shape == (bucket,)
    np.testing.assert_array_equal(got, want)
    # the resident levels the searchers use descend to the same entries
    dev = search_mod.descend(idx.device_levels(params.use_dfloat), q,
                             idx.metric)
    assert isinstance(dev, jax.Array)
    np.testing.assert_array_equal(np.asarray(dev), want)


@pytest.mark.parametrize("graph", ["deep", "single"])
@pytest.mark.parametrize("bucket", [1, 32])
@pytest.mark.parametrize("store", list(STORES))
def test_local_searcher_matches_host_loop_path(unit_db, indexes, store,
                                               bucket, graph):
    """The local searcher's answers equal the search program run from the
    host loop's entries: ids and dists identical."""
    idx, params = indexes[graph], STORES[store]
    raw = unit_db.queries[:bucket]
    q = _queries(unit_db, idx, bucket)
    entries = oracle_entries(_rows(idx, params), idx.graph, q, idx.metric)
    program = search_mod.make_searcher(
        idx.device_db(params.use_dfloat, params.storage),
        idx.device_adjacency(), params.to_config(idx.metric, idx.seg),
        fee=_fee(idx, params), dfloat_cfg=_dfloat_cfg(idx, params))
    want = SearchResult.from_raw(program(jnp.asarray(q), jnp.asarray(entries)))
    got = idx.searcher("local", params)(raw)
    np.testing.assert_array_equal(got.ids, want.ids)
    np.testing.assert_array_equal(got.dists, want.dists)


def _level_bytes(graph, d):
    """Bytes of one upload: f32 rows, int32 adjacency, int32 down map."""
    return sum(len(ids) * (4 * d + 4 * adj.shape[1] + 4)
               for ids, adj in graph.levels[1:])


def test_levels_upload_once_per_generation(unit_db, unit_index_dfloat):
    """``search.descent_h2d_bytes`` grows once when a searcher is built and
    stays flat over further batches; a new snapshot generation uploads its
    own levels once, and its entries equal the host loop's."""
    from repro.streaming import MutableIndex

    reg = obs.default_registry()
    params = STORES["packed"]
    idx = dataclasses.replace(unit_index_dfloat, _device={}, _searchers={})
    c0 = reg.counter(COUNTER).value
    run = idx.searcher("local", params)
    c1 = reg.counter(COUNTER).value
    assert c1 - c0 == _level_bytes(idx.graph, idx.dim) > 0
    for i in range(10):
        run(unit_db.queries[i:i + 4])
    assert reg.counter(COUNTER).value == c1
    idx.searcher("local", STORES["tiered"])
    assert reg.counter(COUNTER).value == c1   # one copy for both stores

    mi = MutableIndex(idx, ef_build=32, sub_batch=64)
    snap0 = mi.freeze()
    mi.append(unit_db.train_queries[:40])
    snap1 = mi.freeze()
    assert snap1.generation != snap0.generation
    for snap in (snap0, snap1):
        c = reg.counter(COUNTER).value
        run = snap.searcher("local", params)
        assert reg.counter(COUNTER).value - c == _level_bytes(snap.graph,
                                                              snap.dim)
        c = reg.counter(COUNTER).value
        for i in range(10):
            run(unit_db.queries[i:i + 4])
        assert reg.counter(COUNTER).value == c
    q = _queries(unit_db, snap1, 32)
    np.testing.assert_array_equal(
        np.asarray(search_mod.descend(snap1.device_levels(), q, snap1.metric)),
        oracle_entries(snap1.emulated_rows, snap1.graph, q, snap1.metric))
    snap1.drop_device()
    assert ("levels", True) not in snap1._device


def test_served_batches_upload_no_level_rows(unit_db, unit_index):
    """After the server's warm-up no served batch uploads level arrays."""
    from repro.serve import Server, ServeConfig

    reg = obs.default_registry()
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(1, 4), k_max=10,
                      slo_ms=5000.0)
    with Server(unit_index, cfg) as srv:
        c = reg.counter(COUNTER).value
        futs = [srv.submit(unit_db.queries[i], k=10, ef=32,
                           deadline_ms=5000.0) for i in range(16)]
        resps = [f.result(timeout=60) for f in futs]
        assert reg.counter(COUNTER).value == c
    assert all(r.status == "ok" for r in resps)
