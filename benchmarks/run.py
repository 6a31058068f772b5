# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
# ``--json [target]`` switches to the machine-readable perf-trajectory mode:
# runs the fig15-style search benchmark (benchmarks/bench_search.py) and
# writes ``BENCH_search.json`` next to the repo root.  ``BENCH_DATASET=unit``
# selects the tiny synthetic DB (CI smoke); default is ``sift``.
import sys
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

MODULES = [
    "benchmarks.fig05_feature_usage",
    "benchmarks.fig08_fee_spca",
    "benchmarks.fig15_qps",
    "benchmarks.fig18_latency",
    "benchmarks.fig19_qps_recall",
    "benchmarks.fig20_memory_traffic",
    "benchmarks.fig21_lnc",
    "benchmarks.fig22_batch",
    "benchmarks.fig25_ablation",
    "benchmarks.table4_pca_overhead",
    "benchmarks.kernel_bench",
]

JSON_TARGETS = {
    # target name (as in `run.py --json fig15_qps`) -> (module, output file)
    "fig15_qps": ("benchmarks.bench_search", "BENCH_search.json"),
    "search": ("benchmarks.bench_search", "BENCH_search.json"),
}


def main_json(argv) -> None:
    import importlib

    target = argv[0] if argv else "fig15_qps"
    if target not in JSON_TARGETS:
        raise SystemExit(f"unknown --json target {target!r}; "
                         f"expected one of {sorted(JSON_TARGETS)}")
    mod_name, out_name = JSON_TARGETS[target]
    out_path = Path(__file__).parent.parent / out_name
    mod = importlib.import_module(mod_name)
    mod.run_json(out_path)


def main() -> None:
    import importlib

    from benchmarks.common import Csv

    args = sys.argv[1:]
    if args and args[0] == "--json":
        main_json(args[1:])
        return

    only = args if args else None
    csv = Csv()
    for mod_name in MODULES:
        if only and not any(o in mod_name for o in only):
            continue
        try:
            mod = importlib.import_module(mod_name)
            mod.main(csv)
        except Exception:  # noqa: BLE001 — keep the harness running
            print(f"[bench ERROR] {mod_name}")
            traceback.print_exc()
            csv.rows.append((mod_name.split(".")[-1] + "_ERROR", 0.0, "failed"))
    print("\n==== CSV (name,us_per_call,derived) ====")
    csv.emit()


if __name__ == "__main__":
    main()
