"""Observability: quantile sketch, telemetry registry, span tracer, the
Metrics façade's bounded footprint, and the perf-regression gate.

The serving-integration half (request timelines whose stage durations sum to
the reported total) lives in test_serve_obs.py next to the other live-server
tests.
"""
import json
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import (Counter, Histogram, PeriodicExporter, QuantileSketch,
                       Registry, Tracer)
from repro.serve.metrics import Metrics


# ---------------------------------------------------------------------------
# quantile sketch
# ---------------------------------------------------------------------------
def test_sketch_quantile_accuracy():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(1.0, 1.0, 200_000)
    s = QuantileSketch()
    s.add_many(vals)
    for q in (0.5, 0.9, 0.99, 0.999):
        est, true = s.quantile(q), float(np.quantile(vals, q))
        assert abs(est - true) / true < 0.05, (q, est, true)
    assert s.count == len(vals)
    assert s.min == pytest.approx(vals.min())
    assert s.max == pytest.approx(vals.max())


def test_sketch_memory_is_bounded():
    s = QuantileSketch(max_buckets=128)
    rng = np.random.default_rng(1)
    s.add_many(rng.lognormal(0.0, 4.0, 500_000))   # huge dynamic range
    assert len(s._buckets) <= 128
    assert s.count == 500_000
    # clamped tails still produce ordered, in-range quantiles
    qs = [s.quantile(q) for q in (0.01, 0.5, 0.99)]
    assert qs == sorted(qs)
    assert s.min <= qs[0] and qs[-1] <= s.max


def test_sketch_histogram_rebin():
    s = QuantileSketch()
    s.add_many(np.linspace(0.1, 100.0, 10_000))
    h = s.histogram(20)
    assert len(h["counts"]) == len(h["bins"]) - 1 == 20
    assert sum(h["counts"]) == 10_000


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_typed_instruments():
    r = Registry("t")
    c = r.counter("serve.shed", "sheds")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)                       # counters are monotonic
    with pytest.raises(TypeError):
        r.gauge("serve.shed")           # kind mismatch on an existing name
    assert r.counter("serve.shed") is c  # get-or-create returns the same one
    g = r.gauge("queue.depth")
    g.set(7)
    assert g.value == 7
    h = r.histogram("lat_ms")
    h.observe_many([1.0, 2.0, 3.0, 4.0])
    assert h.count == 4 and h.mean == pytest.approx(2.5)

    snap = r.snapshot()
    assert snap["serve.shed"]["value"] == 4
    assert snap["lat_ms"]["count"] == 4
    text = r.expose_text()
    assert "serve_shed 4" in text
    assert "lat_ms_count 4" in text and 'quantile="99"' in text


def test_periodic_exporter_atomic_snapshot(tmp_path):
    r = Registry("x")
    r.counter("a").inc(5)
    path = tmp_path / "metrics.json"
    with PeriodicExporter({"x": r}, path, interval_s=0.05) as ex:
        time.sleep(0.2)
        r.counter("a").inc(5)
    # stop() wrote a final snapshot with the last value
    snap = json.loads(path.read_text())
    assert snap["x"]["a"]["value"] == 10
    assert ex.writes >= 2
    assert not path.with_suffix(".json.tmp").exists()


# ---------------------------------------------------------------------------
# Metrics façade
# ---------------------------------------------------------------------------
def _resp(status="ok", total=5.0, queue=1.0, service=3.5, degraded=False,
          missed=False):
    import types

    return types.SimpleNamespace(status=status, degraded=degraded,
                                 deadline_missed=missed, total_ms=total,
                                 queue_ms=queue, service_ms=service)


def test_metrics_summary_keys_and_stages():
    m = Metrics(slo_ms=50.0)
    for i in range(100):
        m.record(_resp(total=5.0 + i * 0.1))
    m.record(_resp(status="shed", total=0.0))
    m.record(_resp(status="timeout", total=60.0, missed=True))
    s = m.summary()
    for key in ("requests", "ok", "shed", "timeout", "degraded",
                "degraded_fraction", "goodput_qps", "elapsed_s", "slo_ms",
                "cold_start_ms", "errors", "p50_ms", "p99_ms", "p999_ms",
                "mean_ms", "max_ms"):
        assert key in s, key
    assert s["requests"] == 102 and s["ok"] == 100
    assert s["shed"] == 1 and s["timeout"] == 1
    # per-stage percentiles (queue / exec / resolve) ride along
    assert set(s["stages"]) == {"queue", "exec", "resolve"}
    for st in s["stages"].values():
        assert st["p50_ms"] >= 0 and st["p99_ms"] >= st["p50_ms"] * 0.9
    h = m.histogram(16)
    assert sum(h["counts"]) == 100 and len(h["bins_ms"]) == 17


def test_metrics_errors_by_type():
    m = Metrics(slo_ms=50.0)
    m.record_error(ValueError("bad query"))
    m.record_error(ValueError("bad query again"))
    m.record_error(RuntimeError("backend down"))
    m.record_error()
    s = m.summary()
    assert s["errors"] == 4
    assert s["errors_by_type"] == {"ValueError": 2, "RuntimeError": 1,
                                   "unknown": 1}


def test_metrics_fee_exit_fraction():
    m = Metrics(slo_ms=50.0)
    m.record_batch(n_eval=100.0, dims=3200.0, dim=64)   # 3200/6400 touched
    assert m.summary()["fee_exit_fraction"] == pytest.approx(0.5)


def test_metrics_memory_bounded_at_1m_records():
    """The old Metrics kept every latency in a list (~8 MB per million
    requests, unbounded).  The sketch-backed façade must stay under its fixed
    ``footprint_bytes`` bound no matter how many records stream through."""
    m = Metrics(slo_ms=50.0)
    bound = m.footprint_bytes()
    assert bound < 2 << 20                      # the bound itself is small
    rng = np.random.default_rng(2)
    lat = rng.lognormal(1.5, 0.7, 1_000_000)
    # drive the same sketches record() feeds, via the vectorized path (a
    # million python-loop record() calls would dominate the test's runtime)
    m._lat._sketch.add_many(lat)
    m._stage["queue"]._sketch.add_many(lat * 0.2)
    m._stage["exec"]._sketch.add_many(lat * 0.7)
    m._stage["resolve"]._sketch.add_many(lat * 0.1)
    for _ in range(1000):
        m.record(_resp())                       # the scalar path too
    assert m.footprint_bytes() == bound         # bound is state-independent
    used = sum(h.footprint_bytes()
               for h in (m._lat, *m._stage.values()))
    assert used <= bound
    assert m._lat.count == 1_001_000
    assert m.summary()["p99_ms"] > 0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def test_spans_nest_and_order():
    tr = Tracer(enabled=True)
    with tr.span("outer", req=7):
        with tr.span("inner", req=7):
            time.sleep(0.001)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]   # completion order
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert outer.t0_ns <= inner.t0_ns
    assert inner.t1_ns <= outer.t1_ns + 1000
    tl = tr.request_timeline(7)
    assert [row["stage"] for row in tl] == ["outer", "inner"]  # start order


def test_spans_across_threads_do_not_interleave_depth():
    tr = Tracer(enabled=True)

    def work(tid):
        with tr.span("outer", req=tid):
            with tr.span("inner", req=tid):
                time.sleep(0.002)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 16
    for tid in range(8):
        mine = [s for s in spans if s.req == tid]
        depths = {s.name: s.depth for s in mine}
        assert depths == {"outer": 0, "inner": 1}
        # each thread's stack is private: inner nests inside its own outer
        inner = next(s for s in mine if s.name == "inner")
        outer = next(s for s in mine if s.name == "outer")
        assert outer.t0_ns <= inner.t0_ns and inner.t1_ns <= outer.t1_ns + 1000


def test_disabled_tracer_is_allocation_free_singleton():
    tr = Tracer(enabled=False)
    a = tr.span("x", req=1, attr="v")
    b = tr.span("y")
    assert a is b                                # one shared no-op object
    with a:
        pass
    assert tr.spans() == []
    tr.instant("z")
    tr.add_span("w", 0, 10)
    assert tr.spans() == []


def test_disabled_after_enable_is_still_the_noop_singleton():
    """Disabling a tracer that was on (and so has the profiler mirror
    loaded) returns the disabled path to the shared no-op object."""
    from repro.obs.trace import _NOOP

    tr = Tracer(enabled=True)
    tr.disable()
    assert tr.span("serve.pad") is _NOOP
    assert tr.span("search.descent", levels=2, rows=10) is _NOOP
    with tr.span("serve.resolve"):
        pass
    assert tr.spans() == []


def test_live_span_is_mirrored_into_the_profiler_trace(tmp_path):
    """A span entered while the JAX profiler traces lands on a host plane of
    the written xplane under its exact name (no attributes folded in), on
    the line of the thread that entered it; the ring holds the same name."""
    import jax
    from jax.profiler import ProfileData

    tr = Tracer(enabled=True)

    def worker():
        with tr.span("obs.test.worker", req=3, n=32):
            with jax.profiler.TraceAnnotation("obs.test.worker_marker"):
                pass

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with tr.span("obs.test.main", level=1):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    where = {}                      # event name -> {(plane, line index)}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    where.setdefault(e.name, set()).add((plane.name, i))
    assert len(where["obs.test.worker"]) == 1
    assert where["obs.test.worker"] == where["obs.test.worker_marker"]
    assert len(where["obs.test.main"]) == 1
    assert where["obs.test.main"] != where["obs.test.worker"]
    assert [s.name for s in tr.spans()] == ["obs.test.worker",
                                            "obs.test.main"]


def test_disabled_hot_path_cost_is_negligible():
    """`span()` when disabled must be ~an attribute check — bound the cost
    relative to a bare function call rather than wall-clock (CI noise)."""
    tr = Tracer(enabled=False)
    n = 50_000

    def bare():
        pass

    t0 = time.perf_counter()
    for _ in range(n):
        bare()
    t_bare = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        tr.span("x")
    t_span = time.perf_counter() - t0
    # generous 10x bound: the point is "no lock, no allocation, no commit",
    # not micro-benchmark precision
    assert t_span < max(t_bare * 10, 0.05), (t_span, t_bare)


def test_ring_wraps_without_corrupting_inflight_spans():
    tr = Tracer(capacity=16, enabled=True)
    with tr.span("inflight", req=99) as live:
        # 64 completed spans wrap the 16-slot ring while `inflight` is open
        for i in range(64):
            with tr.span(f"s{i}"):
                pass
        assert tr.dropped == 64 - 16 + 0        # oldest fell off
        assert live.name == "inflight"          # untouched by the wrap
    spans = tr.spans()
    assert len(spans) == 16
    assert spans[-1].name == "inflight"         # committed after the wrap
    assert spans[-1].req == 99
    assert all(s.dur_ns >= 0 for s in spans)


def test_ring_capacity_resize_and_clear():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(12):
        tr.instant(f"e{i}")
    assert len(tr.spans()) == 8
    tr.enable(capacity=32)
    assert len(tr.spans()) == 8                 # survivors kept on resize
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_chrome_trace_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("stage", req=3, ef=32):
        pass
    path = tr.write_chrome_trace(tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "stage"
    assert ev["args"] == {"ef": 32, "req": 3}
    assert ev["dur"] >= 0 and ev["pid"] == 0


def test_window_view():
    tr = Tracer(enabled=True)
    t0 = time.perf_counter()
    tr.instant("a")
    time.sleep(0.02)
    tr.instant("b")
    t_mid = time.perf_counter()
    names = {s.name for s in tr.window(t0, t_mid)}
    assert names == {"a", "b"}
    assert tr.window(t_mid + 10.0, t_mid + 11.0) == []


# ---------------------------------------------------------------------------
# perf-regression gate
# ---------------------------------------------------------------------------
@pytest.fixture()
def bench_pair(tmp_path):
    base = dict(
        dataset="unit", n_vectors=2000, dim=64, storage="f32",
        fast_mode=True, platform=dict(machine="x86_64"),
        baseline=dict(qps=1000.0, recall_at_10=0.99, p99_latency_ms=5.0),
        multi_expansion=dict(qps=1500.0, recall_at_10=0.99,
                             p99_latency_ms=3.0),
        serving=dict(goodput_qps=40.0, p99_ms=100.0),
    )
    bp = tmp_path / "base.json"
    bp.write_text(json.dumps(base))
    return base, bp, tmp_path


_BENCH_DIR = str(__import__("pathlib").Path(__file__).parent.parent
                 / "benchmarks")


def _run_gate(args):
    sys.path.insert(0, _BENCH_DIR)
    try:
        import check_regression
        return check_regression.main(args)
    finally:
        sys.path.remove(_BENCH_DIR)


def test_regression_gate_passes_on_identical(bench_pair):
    _, bp, _ = bench_pair
    assert _run_gate(["--baseline", str(bp), "--current", str(bp)]) == 0


def test_regression_gate_fails_on_20pct_qps_drop(bench_pair):
    base, bp, tmp = bench_pair
    cur = json.loads(json.dumps(base))
    cur["multi_expansion"]["qps"] *= 0.8
    cp = tmp / "cur.json"
    cp.write_text(json.dumps(cur))
    assert _run_gate(["--baseline", str(bp), "--current", str(cp)]) == 1


def test_regression_gate_fails_on_recall_drop(bench_pair):
    base, bp, tmp = bench_pair
    cur = json.loads(json.dumps(base))
    cur["baseline"]["recall_at_10"] -= 0.006    # > 0.5 pt hard threshold
    cp = tmp / "cur.json"
    cp.write_text(json.dumps(cur))
    assert _run_gate(["--baseline", str(bp), "--current", str(cp)]) == 1


def test_regression_gate_soft_on_small_drift(bench_pair):
    base, bp, tmp = bench_pair
    cur = json.loads(json.dumps(base))
    cur["multi_expansion"]["qps"] *= 0.93       # 7%: soft, not hard
    cp = tmp / "cur.json"
    cp.write_text(json.dumps(cur))
    assert _run_gate(["--baseline", str(bp), "--current", str(cp)]) == 0


def test_regression_gate_context_mismatch_is_soft(bench_pair, capsys):
    base, bp, tmp = bench_pair
    cur = json.loads(json.dumps(base))
    cur["dataset"] = "sift"
    cur["n_vectors"] = 40000
    cur["multi_expansion"]["qps"] *= 0.5        # would be hard...
    cp = tmp / "cur.json"
    cp.write_text(json.dumps(cur))
    assert _run_gate(["--baseline", str(bp), "--current", str(cp)]) == 0
    out = capsys.readouterr().out
    assert "context mismatch" in out and "soft" in out


def test_regression_gate_writes_report(bench_pair):
    base, bp, tmp = bench_pair
    cur = json.loads(json.dumps(base))
    cur["serving"]["goodput_qps"] *= 0.7        # > 20% hard threshold
    cp = tmp / "cur.json"
    cp.write_text(json.dumps(cur))
    rp = tmp / "report.json"
    assert _run_gate(["--baseline", str(bp), "--current", str(cp),
                      "--report", str(rp)]) == 1
    rep = json.loads(rp.read_text())
    assert rep["failed"] is True and rep["n_hard"] == 1
    hard = [f for f in rep["findings"] if f["level"] == "hard"]
    assert hard[0]["row"] == "serving"


def test_regression_gate_committed_baseline_self_compare():
    """The acceptance criterion straight from the issue: the committed
    BENCH_search.json diffed against itself must exit 0, and a synthetic
    20% qps drop must exit non-zero."""
    import tempfile
    from pathlib import Path

    committed = Path(__file__).parent.parent / "BENCH_search.json"
    assert _run_gate(["--baseline", str(committed),
                      "--current", str(committed)]) == 0
    d = json.loads(committed.read_text())
    d["multi_expansion"]["qps"] *= 0.8
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(d, f)
    assert _run_gate(["--baseline", str(committed),
                      "--current", f.name]) == 1
    Path(f.name).unlink()


# ---------------------------------------------------------------------------
# library-level counters land in the default registry
# ---------------------------------------------------------------------------
def test_fault_fires_counted_in_default_registry():
    from repro.resilience import FaultPlan, FaultSpec, InjectedFault, \
        active_plan, fault_point

    before = obs.default_registry().counter("resilience.faults.raise").value
    plan = FaultPlan({"test.point": FaultSpec("raise", at=(0,))})
    with active_plan(plan):
        with pytest.raises(InjectedFault):
            fault_point("test.point")
    after = obs.default_registry().counter("resilience.faults.raise").value
    assert after == before + 1
