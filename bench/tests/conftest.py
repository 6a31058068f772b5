import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CELLS = {"tiny.closed": ("tiny-packed", "closed16"),
              "tiny.closed4": ("tiny-packed", "closed4"),
              "tiny.tiered": ("tiny-tiered", "closed16")}


def make_tiny_bench(dest: Path) -> Path:
    """A checkout-shaped directory: a copy of ``bench/`` plus the tiny test
    configurations, two small traffic files and a ``BENCHMARK.json`` whose
    cells run on them.  Only data files are added: no code changes."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in ("tiny-packed", "tiny-tiered"):
        shutil.copy(DATA / f"{name}.json", dest / "bench" / "configs")
    for n in (16, 4):
        (dest / "bench" / "traffic" / f"closed{n}.json").write_text(
            json.dumps({"loop": "closed", "outstanding": n, "k": 10,
                        "ef": 32}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": name, "config": config, "traffic": traffic,
         "chips": 1, "why": "test-only tiny cell"}
        for name, (config, traffic) in TINY_CELLS.items()]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = list(TINY_CELLS)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dest


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return make_tiny_bench(tmp_path_factory.mktemp("tinybench"))
