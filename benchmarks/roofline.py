"""Roofline report: reads the dry-run JSON cache and derives the three-term
roofline per (arch x shape x mesh) — EXPERIMENTS.md §Roofline.

  compute   = HLO_FLOPs(per-chip) / 197 TFLOP/s
  memory    = HLO_bytes(per-chip) / 819 GB/s
  collective= collective payload bytes(per-chip) / 50 GB/s per link
"""
from __future__ import annotations

import json

from repro import configs as C
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.utils import CACHE_DIR

DRYRUN_DIR = CACHE_DIR / "dryrun"


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS: 6·N·D train (N=active params, D=tokens); 2·N·B decode."""
    cfg = C.get_config(arch)
    shape = C.SHAPES[shape_name]
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.seq_len * shape.global_batch
    return 2.0 * n_active * shape.global_batch          # one token per request


def load_cells(mesh: str = "single"):
    out = []
    for f in sorted(DRYRUN_DIR.glob(f"*__{mesh}.json")):
        out.append(json.loads(f.read_text()))
    return out


def roofline_row(rec: dict) -> dict | None:
    if not rec.get("ok"):
        return None
    chips = rec["chips"]
    flops = rec["cost"]["flops"] or 0          # per-chip (see dryrun docstring)
    bytes_acc = rec["cost"]["bytes_accessed"] or 0
    coll = rec["collectives"]["total_bytes"]
    t_comp = flops / PEAK_FLOPS_BF16
    t_mem = bytes_acc / HBM_BW
    t_coll = coll / ICI_BW
    dominant = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))
    mf = model_flops(rec["arch"], rec["shape"]) if rec["arch"] in C.ARCHS else 0
    useful = mf / (flops * chips) if flops else 0.0
    bound = max(t_comp, t_mem, t_coll)
    return dict(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"], chips=chips,
        t_compute_s=t_comp, t_memory_s=t_mem, t_collective_s=t_coll,
        dominant=dominant[1],
        roofline_frac=t_comp / bound if bound else 0.0,   # fraction of time at peak flops
        model_flops=mf, hlo_flops_global=flops * chips, useful_ratio=useful,
        peak_gb=(rec["memory"].get("peak_bytes") or 0) / 2**30,
    )


def report(mesh: str = "single"):
    rows = [r for r in (roofline_row(rec) for rec in load_cells(mesh)) if r]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    hdr = (f"{'arch':22s} {'shape':12s} {'comp(s)':>9s} {'mem(s)':>9s} "
           f"{'coll(s)':>9s} {'bound':>10s} {'MFU-frac':>8s} {'useful':>7s} "
           f"{'peakGB':>7s}")
    print(hdr)
    for r in rows:
        print(f"{r['arch']:22s} {r['shape']:12s} {r['t_compute_s']:9.4f} "
              f"{r['t_memory_s']:9.4f} {r['t_collective_s']:9.4f} "
              f"{r['dominant']:>10s} {r['roofline_frac']:8.2f} "
              f"{r['useful_ratio']:7.2f} {r['peak_gb']:7.2f}")
    return rows


def main(csv):
    print("\n== Roofline (single-pod 16x16, per-chip terms) ==")
    rows = report("single")
    ok = len(rows)
    dom = {}
    for r in rows:
        dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
    csv.rows.append(("roofline_cells", 0.0, dict(cells=ok, dominant=dom)))
