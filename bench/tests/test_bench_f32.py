"""The float32 store's cell: the comparison that decides ``correct`` passes
the program as configured and fails a store one precision below it.

A tiny CPU cell served like ``sift128-f32`` (float32 rows, no Dfloat, exact
distances): the program must come out correct; the plain reference at
bfloat16 (the control) and the program over a device store rounded to
bfloat16 (the fault its ``dist_gap`` limit is set against) must not.
"""
import json
import os
import shutil

import numpy as np
import pytest

from .conftest import DATA, make_tiny_bench

SEED = 0
SECONDS = 1.0
CELL = "tiny.f32"


@pytest.fixture(scope="module")
def f32_cell(tmp_path_factory):
    """The tiny bench directory plus the f32 configuration and one cell on
    it; the harness's process-wide settings (compile cache, the program's
    artifact directory) are restored afterwards."""
    import jax
    from jax._src import compilation_cache

    from bench import spec

    root = make_tiny_bench(tmp_path_factory.mktemp("tinybench_f32"))
    shutil.copy(DATA / "tiny-f32.json", root / "bench" / "configs")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "tiny-f32",
                               "traffic": "closed16", "chips": 1,
                               "why": "test-only tiny cell"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = {k: os.environ.get(k)
           for k in ("REPRO_CACHE", "JAX_COMPILATION_CACHE_DIR")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache_f32"))
    yield spec.load_cell(root, CELL, root / "bench"), jax.devices()[:1]
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


def round_store_bf16(idx) -> None:
    """Serve ``idx`` from float32 rows rounded to bfloat16: the store one
    precision below what the configuration states."""
    import ml_dtypes

    idx.db_rot = idx.db_rot.astype(ml_dtypes.bfloat16).astype(np.float32)


def test_f32_program_as_configured_is_correct(f32_cell):
    cell, devices = f32_cell
    assert cell.config["search"]["storage"] == "f32"
    assert cell.config["index"]["dfloat_recall_target"] is None
    out = _run(cell, devices)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 100 and out["failed"] == 0
    assert out["checks"]["dist_gap"]["value"] < \
        out["checks"]["dist_gap"]["limit"] / 10


def test_f32_control_bf16_reference_is_not_correct(f32_cell):
    from bench import control

    cell, devices = f32_cell
    r = control.reading(cell, SEED, SECONDS, devices, "bf16")
    assert not r["correct"]
    assert r["checks"]["dist_gap"] > 10 * cell.config["limits"]["dist_gap"]


def test_f32_store_rounded_to_bf16_is_not_correct(f32_cell):
    out = _run(*f32_cell, alter_index=round_store_bf16)
    assert not out["correct"]
    c = out["checks"]["dist_gap"]
    assert c["value"] > 10 * c["limit"]
