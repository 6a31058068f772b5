"""The comparison that decides ``correct`` fails the control and each fault
this kind of cell can have.

Runs the harness past its chip check, on the CPU, on the tiny test cells:
the program as configured must come out correct; the controls (a quarter
of the beam; the reference at bfloat16) and the timed path broken
underneath (an answer altered where it is produced; half of each batch left
out; on the tiered store, the residual tier dropped) must not.  The cells
run on one chip, so there is no exchange between chips to leave out, and
serving keeps no state that a step could return unchanged.
"""
import os

import numpy as np
import pytest

SEED = 0          # the tiny corpus on which the halved beam loses queries
SECONDS = 1.0


@pytest.fixture(scope="module")
def harness(tiny_bench, tmp_path_factory):
    """The tiny cell, with the harness's process-wide settings (the compile
    cache, the program's artifact directory) kept to this module: the
    compile cache goes to a directory of its own, so that no other test
    process loads what these runs compiled."""
    import jax
    from jax._src import compilation_cache

    from bench import spec

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = {k: os.environ.get(k)
           for k in ("REPRO_CACHE", "JAX_COMPILATION_CACHE_DIR")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    cell = spec.load_cell(tiny_bench, "tiny.closed", tiny_bench / "bench")
    yield cell, jax.devices()[:1]
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _run(cell, devices, **kw):
    from bench import run

    return run.run_cell(cell, SEED, SECONDS, False, devices, **kw)


def test_program_as_configured_is_correct(harness):
    out = _run(*harness)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 100 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_control_quarter_beam_is_not_correct(harness):
    from bench import control

    cell, devices = harness
    serve, traffic = control.beam_overrides(cell, control.BEAMS["quarterbeam"])
    assert serve == {"ef_buckets": [10]} and traffic == {"ef": 10}
    out = _run(cell, devices, serve_overrides=serve,
               traffic_overrides=traffic)
    assert not out["correct"]
    c = out["checks"]["lost_share"]
    assert c["value"] > c["limit"]


def test_control_bf16_reference_is_not_correct(harness):
    from bench import control

    cell, devices = harness
    r = control.reading(cell, SEED, SECONDS, devices, "bf16")
    assert not r["correct"]
    assert r["checks"]["dist_gap"] > cell.config["limits"]["dist_gap"]


@pytest.fixture(scope="module")
def tiered(harness, tiny_bench):
    from bench import spec

    return spec.load_cell(tiny_bench, "tiny.tiered", tiny_bench / "bench"), \
        harness[1]


def test_tiered_program_as_configured_is_correct(tiered):
    out = _run(*tiered)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) >= {"lost_share", "dist_gap"}


def test_tiered_residual_tier_dropped_is_not_correct(tiered):
    from bench import control

    out = _run(*tiered, alter_index=control.drop_residual)
    assert not out["correct"]
    assert out["checks"]["dist_gap"]["value"] > \
        out["checks"]["dist_gap"]["limit"]


def _broken(monkeypatch, alter):
    from repro.serve import batcher

    real = batcher.run_bucketed

    def run_bucketed(*a, **kw):
        ids, dists, gen, service_s, res = real(*a, **kw)
        return alter(np.array(ids)), dists, gen, service_s, res

    monkeypatch.setattr(batcher, "run_bucketed", run_bucketed)


def test_answer_altered_where_produced_is_not_correct(harness, monkeypatch):
    cell, devices = harness
    n = cell.config["n_base"]
    _broken(monkeypatch, lambda ids: (ids + 1) % n)
    out = _run(cell, devices)
    assert not out["correct"]
    assert out["checks"]["dist_gap"]["value"] > \
        out["checks"]["dist_gap"]["limit"]


def test_half_the_batch_left_out_is_not_correct(harness, monkeypatch):
    cell, devices = harness
    # only the first half of each batch is searched; the other rows get
    # the answers of the searched ones
    _broken(monkeypatch,
            lambda ids: np.resize(ids[: max(len(ids) // 2, 1)], ids.shape))
    out = _run(cell, devices)
    assert not out["correct"]
    assert out["checks"]["lost_share"]["value"] > \
        out["checks"]["lost_share"]["limit"]
