"""Serving-path observability: a live server under tracing must produce, for
every request, a complete ordered stage timeline whose durations sum to the
reported ``total_ms`` — the acceptance criterion of the observability PR (5%
tolerance; in practice the sum is exact by construction, because ``total_ms``
is stamped at the end of the traced resolve stage).

Also covers: serve counters landing in the per-server registry, library-level
search counters landing in the default registry, and hot-swap install spans.
"""
import threading

import numpy as np
import pytest

from repro import obs
from repro.serve import ServeConfig, Server
from repro.streaming import MutableIndex

K = 10


@pytest.fixture()
def traced():
    """Fresh process-wide tracer state around each test (the tracer is a
    module global shared with launch/serve.py)."""
    obs.enable_tracing(capacity=65536)
    obs.tracer.clear()
    yield obs.tracer
    obs.disable_tracing()
    obs.tracer.clear()


def test_request_timeline_sums_to_total_ms(unit_db, unit_index, traced):
    cfg = ServeConfig(ef_buckets=(16, 32), batch_buckets=(1, 4, 8), k_max=K,
                      slo_ms=5000.0)
    with Server(unit_index, cfg) as srv:
        futs = [srv.submit(unit_db.queries[i % len(unit_db.queries)],
                           k=K, ef=16 if i % 2 else 32, deadline_ms=5000.0)
                for i in range(40)]
        resps = [f.result(timeout=60) for f in futs]
        summary = srv.metrics.summary()
        snap = srv.metrics.registry.snapshot()

    by_req = {}
    for s in traced.spans():
        if s.req is not None and s.name in obs.SERVE_STAGES:
            by_req.setdefault(s.req, []).append(s)

    assert all(r.status == "ok" for r in resps)
    n_checked = 0
    for r in resps:
        spans = by_req.get(r.id)
        assert spans, f"request {r.id} has no stage spans"
        tl = traced.request_timeline(r.id)
        stages = [row["stage"] for row in tl if row["stage"] in
                  obs.SERVE_STAGES]
        # complete, ordered lifecycle: queue_wait ... resolve
        assert stages == list(obs.SERVE_STAGES), (r.id, stages)
        stage_sum_ms = sum(row["dur_ms"] for row in tl
                           if row["stage"] in obs.SERVE_STAGES)
        # the acceptance criterion: stage durations sum to total_ms within 5%
        assert stage_sum_ms == pytest.approx(r.total_ms, rel=0.05), \
            (r.id, stage_sum_ms, r.total_ms)
        n_checked += 1
    assert n_checked == 40

    # façade summary carries the per-stage percentiles the bench row reports
    assert set(summary["stages"]) == {"queue", "exec", "resolve"}
    # serve counters landed in the private registry...
    assert snap["serve.requests"]["value"] == 40
    assert snap["serve.latency_ms"]["count"] == 40
    # ...and the local-search instrumentation fed the default registry
    assert obs.default_registry().counter("search.queries").value > 0
    assert obs.default_registry().counter("search.hops").value > 0


def test_stage_spans_share_batch_boundaries(unit_db, unit_index, traced):
    """Requests co-batched into one device execution share the same traced
    device_exec window — the per-request spans are views of batch-level
    timestamps, not per-request clock reads."""
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(8,), k_max=K,
                      slo_ms=5000.0)
    with Server(unit_index, cfg) as srv:
        futs = [srv.submit(unit_db.queries[i], k=K, ef=32, deadline_ms=5000.0)
                for i in range(8)]
        [f.result(timeout=60) for f in futs]
    execs = [s for s in traced.spans() if s.name == "device_exec"]
    assert execs
    windows = {(s.t0_ns, s.dur_ns) for s in execs}
    # far fewer distinct exec windows than requests: batching is visible
    assert len(windows) < len(execs)
    by_window = {}
    for s in execs:
        by_window.setdefault((s.t0_ns, s.dur_ns), []).append(s.req)
    assert any(len(reqs) > 1 for reqs in by_window.values())


def test_swap_install_span_and_counters(unit_db, unit_index, traced):
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(1, 4), k_max=K,
                      slo_ms=5000.0, swap_poll_s=0.05)
    mi = MutableIndex(unit_index, ef_build=32, sub_batch=64)
    rng = np.random.default_rng(0)
    with Server(mi, cfg) as srv:
        f = srv.submit(unit_db.queries[0], k=K, ef=32, deadline_ms=5000.0)
        assert f.result(timeout=60).status == "ok"
        mi.append(rng.standard_normal((4, unit_db.dim)).astype(np.float32))
        deadline = threading.Event()
        for _ in range(100):
            if any(s.name == "swap.install" for s in traced.spans()):
                break
            deadline.wait(0.1)
        snap = srv.metrics.registry.snapshot()
    installs = [s for s in traced.spans() if s.name == "swap.install"]
    assert installs, "no swap.install span after an append"
    assert all(s.attrs and "generation" in s.attrs for s in installs)
    assert snap["serve.swap.installs"]["value"] >= 1


def test_disabled_tracing_serves_identically(unit_db, unit_index):
    """With the process tracer disabled (the default), serving works and no
    spans accumulate — the hot path stays dark."""
    obs.disable_tracing()
    obs.tracer.clear()
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(1, 4), k_max=K,
                      slo_ms=5000.0)
    with Server(unit_index, cfg) as srv:
        futs = [srv.submit(unit_db.queries[i], k=K, ef=32, deadline_ms=5000.0)
                for i in range(8)]
        resps = [f.result(timeout=60) for f in futs]
    assert all(r.status == "ok" for r in resps)
    assert obs.tracer.spans() == []


def test_batch_stage_spans_tile_the_batcher_thread(unit_db, unit_index,
                                                   traced):
    """Every served batch yields each stage span once on the batcher thread
    (``search.descent`` enqueues the one program that descends every upper
    level, attrs ``levels`` and ``rows``); the spans never overlap and
    cover >= 90 % of the thread's time from the end of ``serve.take`` to the
    end of ``serve.resolve``.  The per-request timelines still sum to
    ``total_ms``."""
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(8,), k_max=K,
                      slo_ms=5000.0)
    with Server(unit_index, cfg) as srv:
        futs = [srv.submit(unit_db.queries[i], k=K, ef=32, deadline_ms=5000.0)
                for i in range(16)]
        resps = [f.result(timeout=60) for f in futs]
    assert all(r.status == "ok" for r in resps)
    spans = traced.spans()
    takes = [s for s in spans if s.name == "serve.take" and s.attrs["n"]]
    assert takes and sum(s.attrs["n"] for s in takes) == 16
    (tid,) = {s.tid for s in takes}
    mine = sorted((s for s in spans if s.tid == tid
                   and s.name in obs.SERVE_BATCH_STAGES),
                  key=lambda s: s.t0_ns)
    levels = len(unit_index.graph.levels) - 1
    assert levels >= 1
    rows = sum(len(ids) for ids, _ in unit_index.graph.levels[1:])
    for take in takes:
        resolve = next(s for s in mine if s.name == "serve.resolve"
                       and s.t0_ns >= take.t1_ns)
        batch = [s for s in mine if take.t0_ns <= s.t0_ns <= resolve.t0_ns]
        names = [s.name for s in batch]
        assert names == ["serve.take", "serve.admit", "serve.pad",
                         "search.pca", "search.descent", "search.dispatch",
                         "search.wait", "search.count", "serve.resolve"], names
        assert [s.attrs for s in batch if s.name == "search.descent"] == [
            {"levels": levels, "rows": rows}]
        assert all(s.depth == 0 for s in batch)
        for a, b in zip(batch, batch[1:]):
            assert a.t1_ns <= b.t0_ns, (a, b)
        covered = sum(s.dur_ns for s in batch[1:])
        assert covered >= 0.9 * (resolve.t1_ns - take.t1_ns)
    for r in resps:
        tl = traced.request_timeline(r.id)
        assert sum(row["dur_ms"] for row in tl
                   if row["stage"] in obs.SERVE_STAGES) == \
            pytest.approx(r.total_ms, rel=0.05)


def test_search_counters_hop_slots_batches_and_compiles(unit_db, unit_index):
    """``search.hop_slots`` adds len(hops) * max(hops) per batch and
    ``search.batches`` one per program execution; the compile and trace
    counters rise for a new batch shape and stay for a repeated one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.index import SearchParams

    obs.count_compiles()
    obs.count_compiles()            # idempotent: one listener per process
    reg = obs.default_registry()
    names = ("search.batches", "search.hops", "search.hop_slots",
             "jax.backend_compiles", "jax.traces")

    def read():
        return {n: reg.counter(n).value for n in names}

    # no persistent cache: a new shape must compile, not load
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        run = unit_index.searcher("local", SearchParams(ef=32, k=K))
        run(unit_db.queries[:5])
        c0 = read()
        results = [run(unit_db.queries[i:i + 5]) for i in (0, 5, 10)]
        c1 = read()
        run(unit_db.queries[:7])
        c2 = read()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()
    assert c1["search.batches"] - c0["search.batches"] == 3
    assert c1["search.hops"] - c0["search.hops"] == \
        sum(int(r.hops.sum()) for r in results)
    assert c1["search.hop_slots"] - c0["search.hop_slots"] == \
        sum(len(r.hops) * int(r.hops.max()) for r in results)
    assert c1["search.hops"] - c0["search.hops"] <= \
        c1["search.hop_slots"] - c0["search.hop_slots"]
    assert c1["jax.backend_compiles"] == c0["jax.backend_compiles"]
    assert c1["jax.traces"] == c0["jax.traces"]
    assert c2["jax.backend_compiles"] > c1["jax.backend_compiles"]
    assert c2["jax.traces"] > c1["jax.traces"]
    assert c2["search.batches"] - c1["search.batches"] == 1


def test_server_start_registers_the_compile_counters(unit_index):
    """A started server counts compiles into the default registry, which
    ``launch/serve.py --metrics-out`` exports."""
    cfg = ServeConfig(ef_buckets=(32,), batch_buckets=(3,), k_max=K,
                      slo_ms=5000.0)
    with Server(unit_index, cfg):
        pass
    snap = obs.default_registry().snapshot()
    assert snap["jax.backend_compiles"]["value"] > 0
    assert snap["jax.traces"]["value"] > 0
