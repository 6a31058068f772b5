"""The readers of the served path's stage spans and hop counters.

On a synthetic window: spans outside the window are left out, sums are
divided by the window's batches (``search.wait`` spans), and a program
without the spans or counters gives no value.  Then one traced run of the
tiny cell on the CPU, through the harness: the readers find the program's
own spans there."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import spec

from .conftest import ROOT

STAGE_READERS = ("batch_wait_ms", "host_path_ms", "descent_ms", "resolve_ms",
                 "device_wait_ms")
READERS = STAGE_READERS + ("hop_utilization",)


def _reader(name):
    return spec._load_reader(ROOT / "bench" / "metrics" / f"{name}.py")


def _span(name, t0_ms, dur_ms, **attrs):
    return NS(name=name, t0_ns=int(t0_ms * 1e6), dur_ns=int(dur_ms * 1e6),
              attrs=attrs or None)


def _batch(t0_ms, scale=1.0, levels=3):
    """One batch's stage spans from ``t0_ms``; durations times ``scale``."""
    stages = [("serve.take", 1.0, {"n": 32}), ("serve.admit", 0.1, {}),
              ("serve.pad", 0.2, {}), ("search.pca", 0.3, {})]
    stages += [("search.descent", 0.4, {"level": lv, "rows": 10})
               for lv in range(levels, 0, -1)]
    stages += [("search.dispatch", 0.5, {}), ("search.wait", 6.0, {}),
               ("search.count", 0.05, {}), ("serve.resolve", 1.0, {})]
    out, t = [], t0_ms
    for name, dur, attrs in stages:
        out.append(_span(name, t, dur * scale, **attrs))
        t += dur * scale
    return out


def _ctx(spans=(), counters=None):
    # the window is [1000 ms, 2000 ms) on the span clock
    return NS(t0=1.0, t_end=2.0, spans=list(spans), counters=counters or {})


# per batch, for the two batches inside the window (scale 1)
EXPECTED = {"batch_wait_ms": 1.0, "descent_ms": 1.2, "resolve_ms": 1.0,
            "device_wait_ms": 6.0,
            "host_path_ms": 0.1 + 0.2 + 0.3 + 1.2 + 0.5 + 0.05 + 1.0}


@pytest.mark.parametrize("name", STAGE_READERS)
def test_stage_reader_sums_window_spans_per_batch(name):
    spans = (_batch(900.0, scale=7.0)            # before the window
             + [_span("serve.take", 1100.0, 5.0, n=0)]    # an empty poll
             + _batch(1200.0) + _batch(1500.0)
             + _batch(2000.0, scale=5.0))        # starts at the window end
    assert _reader(name)(_ctx(spans)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_without_spans_or_counters(name):
    # a program without the stage spans: only the per-request stages
    old = [_span("bucket_pad", 1100.0, 1.0, n=32, bucket=32),
           _span("device_exec", 1101.0, 6.0)]
    assert _reader(name)(_ctx(old, {"search.hops": 10.0,
                                    "search.queries": 2.0})) is None
    assert _reader(name)(_ctx()) is None


def test_hop_utilization_reads_the_window_counters():
    read = _reader("hop_utilization")
    assert read(_ctx(counters={"search.hops": 600.0,
                               "search.hop_slots": 1000.0})) == 0.6
    assert read(_ctx(counters={"search.hops": 0.0,
                               "search.hop_slots": 0.0})) is None


@pytest.fixture(scope="module")
def tiny_cell(tiny_bench, tmp_path_factory):
    """The tiny packed cell, with the harness's process-wide settings (the
    compile cache, the program's artifact directory) kept to this module."""
    import jax
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = {k: os.environ.get(k)
           for k in ("REPRO_CACHE", "JAX_COMPILATION_CACHE_DIR")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    yield spec.load_cell(tiny_bench, "tiny.closed", tiny_bench / "bench")
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_traced_run_reports_the_stage_metrics(tiny_cell):
    import jax

    from bench import run

    out = run.run_cell(tiny_cell, 7, 1.0, True, jax.devices()[:1])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(m)
    assert 0.0 < m["hop_utilization"] <= 1.0
    for name in STAGE_READERS:
        assert m[name] >= 0.0
    assert m["device_wait_ms"] > 0.0 and m["host_path_ms"] > 0.0
    assert m["descent_ms"] <= m["host_path_ms"]
    assert m["resolve_ms"] <= m["host_path_ms"]
