"""Finds a cell's files by the names in ``BENCHMARK.json``.

    configs/<config>.json     sizes, index and serving settings, limits
    traffic/<traffic>.json    parameters of the one general generator
    metrics/<metric>.py       one reader per metric: ``read(ctx) -> float | None``

A later change adds a configuration, a traffic mix, a metric or a cell by
adding files and entries; nothing here names any of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: object               # read(ctx) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: dict              # kind -> [Metric], the ones this cell reports


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _load_reader(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, kind: str, bench_dir: Path) -> list:
    """The ``kind`` metrics a cell reports: those whose ``workloads`` list
    names it, or that have no such list."""
    out = []
    for m in bench[kind]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(m["name"], m["unit"],
                          _load_reader(bench_dir / "metrics" / f"{m['name']}.py")))
    return out


def load_cell(root: Path, name: str, bench_dir: Path | None = None) -> Cell:
    """Resolve cell ``name`` from ``<root>/BENCHMARK.json``; raises
    ``KeyError`` for an unknown cell and ``FileNotFoundError`` for a
    missing file."""
    bench_dir = bench_dir or BENCH_DIR
    bench = load_json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    config = load_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                metrics={k: metrics_for(bench, name, k, bench_dir)
                         for k in ("end_to_end", "per_layer")})
