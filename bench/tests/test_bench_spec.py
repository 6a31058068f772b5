import json
import re

import pytest

from bench import spec

from .conftest import ROOT, make_tiny_bench

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in entry.get("reduced", []):
        assert NAME.match(key)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    lines = ("why", "layer", "source") if "file" in entry else ("why", "layer")
    for key in lines:
        if key in entry:
            assert LINE.match(entry[key])


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_load_and_state_their_cuts(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert cfg["file"].startswith("bench/")
    for key in cfg["reduced"]:
        assert data[key] < data["published"][key]
    for key in ("dim", "metric"):
        assert data[key] == data["published"][key]
    assert "lost_share" in data["limits"]
    assert set(data["limits"]) <= {"lost_share", "dist_gap"}
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_and_reports_enough(cell):
    c = spec.load_cell(ROOT, cell)
    assert c.chips in (1, 4)
    assert c.traffic["loop"] == "closed"
    e2e = {m.name for m in c.metrics["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.metrics["per_layer"]
    for m in c.metrics["per_layer"]:
        moves = next(x["moves"] for x in BENCH["per_layer"]
                     if x["name"] == m.name)
        assert moves in e2e


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entries(m):
    assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in m.get("workloads", []):
        assert w in CELLS
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert LINE.match(m["layer"])


@pytest.mark.parametrize("path", sorted(
    p.relative_to(ROOT).as_posix()
    for d in ("configs", "traffic") for p in (ROOT / "bench" / d).iterdir()))
def test_every_data_file_loads(path):
    assert isinstance(json.loads((ROOT / path).read_text()), dict)


@pytest.mark.parametrize("path", sorted(
    p.name for p in (ROOT / "bench" / "metrics").glob("*.py")))
def test_every_metric_reader_loads(path):
    assert callable(spec._load_reader(ROOT / "bench" / "metrics" / path))


def test_a_cell_added_as_files_is_found_without_code_change(tmp_path):
    root = make_tiny_bench(tmp_path)
    (root / "bench" / "metrics" / "answered_share.py").write_text(
        "def read(ctx):\n    return ctx.answered / ctx.sent\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "answered_share", "unit": "fraction", "better": "higher",
        "source": "host_clock", "layer": "front end", "moves": "qps",
        "workloads": ["tiny.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, "tiny.closed", root / "bench")
    assert cell.config["name"] == "tiny-packed"
    assert cell.traffic == {"loop": "closed", "outstanding": 16, "k": 10,
                            "ef": 32}
    added = [m for m in cell.metrics["per_layer"]
             if m.name == "answered_share"]
    assert len(added) == 1

    class Ctx:
        answered, sent = 3, 4

    assert added[0].read(Ctx) == 0.75
    other = spec.load_cell(root, "tiny.closed4", root / "bench")
    assert "answered_share" not in {m.name for m in other.metrics["per_layer"]}
    with pytest.raises(KeyError):
        spec.load_cell(root, "no.such.cell", root / "bench")
