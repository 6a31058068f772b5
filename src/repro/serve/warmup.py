"""Warm-start: persistent jit cache + eager compilation of the program set.

``enable_compilation_cache`` turns on JAX's persistent compilation cache
(the maxtext idiom) so a restarted server deserialises its executables
instead of re-tracing them.  The directory is ``$JAX_COMPILATION_CACHE_DIR``
when that is set and otherwise the fixed ``<checkout>/.cache/jax``.  JAX
binds cache availability at
the process's first jit compilation, so the helper resets that decision
after pointing the config at the directory — safe to call any time before
``Server.start()``, but cheapest first thing (nothing to re-decide).  The
serve and search CLIs call it before building anything.

``compile_programs`` then touches every ``(ef bucket x storage x batch
bucket)`` program cell with dummy queries, timing each run to seed the
admission controller's latency model.  The wall time from server start to
the *first* cell responding is the cold-start-to-first-response latency
reported in the bench row — warm cache vs cold cache shows up directly
there.
"""
from __future__ import annotations

import time

import numpy as np


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache in
    ``$JAX_COMPILATION_CACHE_DIR``, or else in ``<checkout>/.cache/jax``, and
    make sure the next compilation actually uses it (JAX freezes the
    enablement decision at the first compile; this resets it).  Returns the
    directory."""
    import os

    import jax
    from jax._src import compilation_cache

    from repro.utils import REPO_ROOT

    # a fixed path, so that the next run of this checkout finds the cache
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(REPO_ROOT / ".cache" / "jax"))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # default thresholds skip small/fast CPU executables; serving programs
    # must all persist for the warm-start win to materialise
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # JAX binds the cache decision at the process's first compilation, and
    # merely importing index/serve modules can compile something tiny — drop
    # back to the uninitialized state so the next compile picks up the dir
    compilation_cache.reset_cache()
    return cache_dir


def compile_programs(snapshot, cfg, model=None, dim: int | None = None,
                     rng_seed: int = 0) -> dict:
    """Compile the full program lattice; returns warmup timings.

    Seeds ``model`` (a :class:`repro.serve.admission.LatencyModel`) with the
    *second* run of each cell — the first includes compile time and would
    poison the admission estimates.
    """
    from repro.serve.batcher import run_bucketed

    d = dim or snapshot.dim
    rng = np.random.default_rng(rng_seed)
    timings: dict = {}
    first_response_s = None
    t0 = time.perf_counter()
    for st in cfg.storages:
        for ef in cfg.ef_buckets:
            for b in cfg.batch_buckets:
                q = rng.standard_normal((b, d)).astype(np.float32)
                t = time.perf_counter()
                run_bucketed(snapshot, cfg, q, ef, cfg.expand, st)
                compile_s = time.perf_counter() - t
                if first_response_s is None:
                    first_response_s = time.perf_counter() - t0
                steady_s = run_bucketed(snapshot, cfg, q, ef,
                                        cfg.expand, st)[3]
                timings[(ef, cfg.expand, st, b)] = (compile_s, steady_s)
                if model is not None:
                    model.observe((ef, cfg.expand, st), b, steady_s)
    return dict(cells=timings, first_response_s=first_response_s,
                total_s=time.perf_counter() - t0)
