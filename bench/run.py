#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: make the corpus and query pool from ``--seed``, build
the index with ``Index.build`` (every run: the build is part of ``setup_s``),
start one ``repro.serve.Server`` with the configuration's ``ServeConfig``,
warm it with the cell's own traffic, then drive that traffic for
``--seconds``.  After the window: read the device's peak memory, stop
the server, and compare every answer the window was due against the exact
top-k of the raw corpus.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``; the numbers compared, each beside its
limit, come last there (``checks``) and as the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` turns on
the program's span tracer and a profiler trace of 2 s of the window, and
reports the per-layer metrics instead.  Without a TPU, or with
fewer chips than the cell asks for, the run exits 2 and prints no result.

Artifacts stay inside the checkout: the program's artifact cache (graphs)
under ``.cache/bench/artifacts``, emptied at the start of every run so that
no run reuses another's build, traces under ``.cache/bench/trace``, and the
compile cache where ``repro.serve.enable_compilation_cache`` puts it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import checks as checks_mod  # noqa: E402
from bench import corpus as corpus_mod  # noqa: E402
from bench import loadgen  # noqa: E402
from bench import spec as spec_mod  # noqa: E402

WARM_S = 2.0              # the cell's own traffic, before the window
ANSWER_WAIT_S = 60.0      # how long past the close an answer may come
TRACE_START_S = 1.0       # profiler window inside the measured window
TRACE_LEN_S = 2.0
SPANS_PER_REQUEST = 6     # queue_wait .. resolve
MAX_QPS = 20_000          # sizes the span ring of a traced run


class NoChip(SystemExit):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"bench: needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = spec_mod.load_json(spec_mod.BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def serve_config(cfg: dict, overrides: dict | None = None):
    from repro.serve import ServeConfig

    kw = dict(cfg["serve"])
    kw.update(overrides or {})
    for key in ("ef_buckets", "batch_buckets", "storages"):
        kw[key] = tuple(kw[key])
    return ServeConfig(**kw)


def build_index(cfg: dict, corpus, seed: int, split: dict):
    """``Index.build`` over this run's corpus, as the configuration states."""
    from repro.data.synthetic import VecDB
    from repro.index import Index, IndexSpec

    db = VecDB(name=cfg["name"], vectors=corpus.vectors,
               queries=corpus.queries, train_queries=corpus.train_queries,
               metric=cfg["metric"], gt=np.zeros((0, cfg["k"]), np.int32))
    t = time.perf_counter()
    # the program caches graphs by name, not by data: name this corpus
    idx = Index.build(db, IndexSpec.for_db(db, seed=int(seed) % 2**32,
                                           **cfg["index"]),
                      cache_key=f"bench/{cfg['name']}/{seed}/n{db.n}")
    split["build_s"] = time.perf_counter() - t
    return idx


def stored_shapes(idx, cfg: dict) -> dict:
    """Widths of what one FEE call reads per lane: the adjacency width and
    the uint32 words of one stored row."""
    storage = cfg["search"]["storage"]
    if storage == "packed":
        words = idx.db_packed.shape[1]
    elif storage == "tiered":
        words = sum(a.shape[1] for a in idx.tier_arrays())
    else:
        words = idx.dim
    return {"adj_width": int(idx.graph.base_adjacency.shape[1]),
            "row_words": int(words)}


def _counters() -> dict:
    from repro.obs import default_registry

    return {k: v.get("value") for k, v in default_registry().snapshot().items()
            if v.get("type") == "counter"}


class WindowWatch(threading.Thread):
    """Reads the program's counters at the window's edges and, when asked,
    runs the profiler over part of the window."""

    def __init__(self, t0: float, t_end: float, trace_dir: Path | None):
        super().__init__(daemon=True, name="bench-window-watch")
        self.t0, self.t_end, self.trace_dir = t0, t_end, trace_dir
        self.counters0 = self.counters1 = None
        self.trace_t = None
        self.error = None

    @staticmethod
    def _sleep_until(t: float) -> None:
        while (d := t - time.perf_counter()) > 0:
            time.sleep(min(d, 0.05))

    def run(self) -> None:
        try:
            self._sleep_until(self.t0)
            self.counters0 = _counters()
            if self.trace_dir is not None:
                import jax

                length = min(TRACE_LEN_S, (self.t_end - self.t0) / 2)
                start = self.t0 + min(TRACE_START_S, (self.t_end - self.t0) / 4)
                self._sleep_until(start)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                a = time.perf_counter()
                jax.profiler.start_trace(str(self.trace_dir),
                                         profiler_options=opts)
                b = time.perf_counter()
                self._sleep_until(b + length)
                c = time.perf_counter()
                jax.profiler.stop_trace()
                self.trace_t = (a, b, c, time.perf_counter())
            self._sleep_until(self.t_end)
            self.counters1 = _counters()
        except Exception as e:          # reported by the caller
            self.error = e


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, *,
             serve_overrides: dict | None = None,
             traffic_overrides: dict | None = None,
             alter_index=None) -> dict:
    """Everything after the chip check; returns the result object.

    ``alter_index(idx)``, where given, changes the built index before the
    server starts (a fault planted for a control reading)."""
    cfg = cell.config
    peaks = peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else None
    # the program's artifact cache would hand a later run this run's graph:
    # start every run with it empty, so that every run builds
    art = ROOT / ".cache" / "bench" / "artifacts"
    shutil.rmtree(art, ignore_errors=True)
    art.mkdir(parents=True)
    os.environ["REPRO_CACHE"] = str(art)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    from repro import obs
    from repro.serve import Server, enable_compilation_cache

    split = {}
    t = time.perf_counter()
    enable_compilation_cache()
    corpus = corpus_mod.generate(cfg, seed)
    split["generate_s"] = time.perf_counter() - t
    idx = build_index(cfg, corpus, seed, split)
    if alter_index is not None:
        alter_index(idx)

    traffic = dict(cell.traffic, **(traffic_overrides or {}))
    t = time.perf_counter()
    srv = Server(idx, serve_config(cfg, serve_overrides)).start()
    split["server_start_s"] = time.perf_counter() - t
    load = loadgen.Load(traffic, srv.submit, corpus.queries, cell.name, seed)
    trace_dir = None
    if trace:
        trace_dir = ROOT / ".cache" / "bench" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        obs.tracer.clear()
        obs.enable_tracing(int(SPANS_PER_REQUEST * MAX_QPS
                               * (seconds + WARM_S)) + 10_000)
    t_warm = time.perf_counter()
    watch = WindowWatch(t_warm + WARM_S, t_warm + WARM_S + seconds, trace_dir)
    watch.start()
    try:
        t0, records = load.run(WARM_S, seconds)
    finally:
        watch.join()
    t_end = t0 + seconds
    split["warm_traffic_s"] = t0 - t_warm
    setup_s = t0 - T_START
    window = [r for r in records if r.due >= t0]
    unanswered = loadgen.wait_all(window, t_end + ANSWER_WAIT_S)
    spans = obs.tracer.spans() if trace else []
    obs.disable_tracing()
    if watch.error is not None:
        raise watch.error
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devices) \
        if devices[0].platform == "tpu" else 0
    srv.stop()
    shapes = stored_shapes(idx, cfg)
    idx.drop_device()
    del srv, idx
    gc.collect()

    ctx = SimpleNamespace(
        cell=cell, config=cfg, traffic=traffic, seconds=seconds, t0=t0,
        t_end=t_end, records=records, window=window, setup_s=setup_s,
        split=split, peaks=peaks, spans=spans, device=None, shapes=shapes,
        counters=_delta(watch.counters0, watch.counters1))
    ctx.answers = checks_mod.compare(cfg, corpus, window, unanswered)
    if trace:
        from bench import trace_reduce

        ctx.device = trace_reduce.reduce_dir(trace_dir, watch.trace_t)
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        v = m.read(ctx)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": ctx.answers.correct,
           "attempted": len(window),
           "failed": sum(not r.ok for r in window),
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = ctx.device.busy_s
        dev["window_s"] = ctx.device.window_s
        out["breakdown"] = ctx.device.breakdown
    out["setup_split_s"] = split
    out["values"] = ctx.answers.values
    out["checks"] = ctx.answers.table
    return out


def _delta(c0, c1) -> dict:
    if not c0 or not c1:
        return {}
    return {k: c1[k] - c0.get(k, 0.0) for k in c1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec_mod.load_cell(ROOT, args.workload)
    try:
        devices = find_devices(cell.chips)
    except NoChip as e:
        log(str(e))
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    log(f"setup split (s): {json.dumps(out['setup_split_s'])}")
    log(f"window: {out['attempted']} requests sent, {out['failed']} failed")
    for line in checks_mod.describe(out):
        log(line)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
