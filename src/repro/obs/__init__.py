"""repro.obs — end-to-end observability: request tracing + telemetry registry.

    from repro import obs

    obs.enable_tracing()                      # span ring buffer on
    with obs.span("wal.flush", n_ops=3):
        ...
    obs.tracer.write_chrome_trace("trace.json")

    reg = obs.default_registry()              # process-wide counters
    reg.counter("streaming.append_rows").inc(64)
    print(reg.expose_text())                  # Prometheus-style exposition

Two halves, one import surface:

* **Tracing** (``repro.obs.trace``): a bounded-ring span recorder with a
  zero-allocation disabled path.  The serving tier instruments the full
  request lifecycle (``queue_wait -> admission -> bucket_pad -> device_exec
  -> topk_slice -> resolve``), one span per stage of every served batch
  on the batcher thread (``serve.take`` ... ``serve.resolve``), plus hot-swap
  installs, WAL flushes and watchdog restarts.  Live spans are mirrored into
  the JAX profiler's trace; ``launch/serve.py --trace`` exports a
  Chrome-trace timeline artifact.
* **Telemetry** (``repro.obs.registry``): typed counters / gauges /
  histograms (bounded quantile sketches — no unbounded sample lists) with
  JSON-snapshot and text expositions and a periodic file exporter.
  Library-level counters live in :func:`default_registry`, JAX's own
  compile and trace counts among them once :func:`count_compiles` ran;
  :class:`repro.serve.Metrics` is a façade over a private registry.
"""
from repro.obs.registry import (  # noqa: F401
    Counter, Gauge, Histogram, PeriodicExporter, QuantileSketch, Registry,
    count_compiles, default_registry)
from repro.obs.trace import (  # noqa: F401
    SERVE_BATCH_STAGES, SERVE_STAGES, Span, Tracer, disable_tracing,
    enable_tracing, span, tracer)
