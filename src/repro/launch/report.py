"""Render the EXPERIMENTS.md §Dry-run and §Roofline tables from the JSON
cache.  Usage: PYTHONPATH=src python -m repro.launch.report [--markdown]"""
import argparse
import json

from repro.utils import CACHE_DIR, REPO_ROOT

DRYRUN_DIR = CACHE_DIR / "dryrun"


def load(mesh: str):
    recs = {}
    for f in sorted(DRYRUN_DIR.glob(f"*__{mesh}.json")):
        r = json.loads(f.read_text())
        recs[(r["arch"], r["shape"])] = r
    return recs


def dryrun_table(markdown=False):
    single, multi = load("single"), load("multi")
    sep = "|" if markdown else " "
    hdr = ["arch", "shape", "16x16", "2x16x16", "peakGB(cpu)", "fitGB(analytic)",
           "collGB/dev", "compile_s"]
    lines = []
    if markdown:
        lines.append("| " + " | ".join(hdr) + " |")
        lines.append("|" + "---|" * len(hdr))
    else:
        lines.append(f"{'arch':22s} {'shape':12s} {'16x16':>7s} {'2x16x16':>8s} "
                     f"{'peakGB':>8s} {'fitGB':>7s} {'collGB':>8s} {'cmpl_s':>7s}")
    for key in sorted(single):
        s, m = single[key], multi.get(key, {})
        def st(r):
            if not r:
                return "-"
            if r.get("skipped"):
                return "SKIP"
            return "OK" if r.get("ok") else "FAIL"
        peak = (s.get("memory", {}) or {}).get("peak_bytes") or 0
        ana = (s.get("analytic_memory") or {}).get("total_gb", "")
        coll = ((s.get("collectives") or {}).get("total_bytes") or 0) / 2**30
        comp = s.get("compile_s", "")
        row = [key[0], key[1], st(s), st(m), f"{peak/2**30:.1f}" if peak else "-",
               str(ana), f"{coll:.2f}" if s.get("ok") else "-", str(comp)]
        if markdown:
            lines.append("| " + " | ".join(row) + " |")
        else:
            lines.append(f"{row[0]:22s} {row[1]:12s} {row[2]:>7s} {row[3]:>8s} "
                         f"{row[4]:>8s} {row[5]:>7s} {row[6]:>8s} {row[7]:>7s}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    print("== Dry-run table ==")
    print(dryrun_table(args.markdown))
    print()
    import sys
    sys.path.insert(0, str(REPO_ROOT))
    from benchmarks import roofline
    print("== Roofline (single-pod) ==")
    roofline.report("single")


if __name__ == "__main__":
    main()
