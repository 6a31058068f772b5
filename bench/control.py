#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program and its controls.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--which program,quarterbeam,halfbeam,bf16,residual0]

For each seed and reading, in this one process, one JSON line with the
numbers ``correct`` compares; every reading but ``bf16`` builds the seed's
index and serves a window, as a run does.  The benchmark's own runs never
run this.

``program``: a window of the cell's own traffic through the program as the
configuration states it.  ``quarterbeam`` (the control of ``lost_share``):
the same window with every request served at a quarter of the
configuration's beam (``ef_buckets`` and the traffic's ``ef`` divided by 4,
never below k); it breaks the configuration's stated guarantee that every
request is served at its ef.  ``halfbeam``: the same at half the beam, the
smaller step; on sift128-packed it loses too few queries to stand three
times clear of the program (``PERF.md``), so it is a reading, not the
control.  ``bf16`` (the control of ``dist_gap``): the plain reference put
in the program's place one precision below the corpus's float32: exact
top-k on the chip with bfloat16 inputs and float32 accumulation, every pool
query answered with the ids and distances it computes.  ``residual0`` (a
fault in the tiered path, ``storage="tiered"`` only): the program with the
residual tier's bits zeroed on the way to the device, so that every
distance comes from the resident tier alone.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import checks  # noqa: E402
from bench import corpus as corpus_mod  # noqa: E402
from bench import run as run_mod  # noqa: E402
from bench import spec as spec_mod  # noqa: E402


BEAMS = {"quarterbeam": 4, "halfbeam": 2}


def beam_overrides(cell, divisor: int) -> tuple[dict, dict]:
    """(serve overrides, traffic overrides) that serve every request with
    the configuration's beam divided by ``divisor``, never below k."""
    k = int(cell.config["k"])

    def cut(ef):
        return max(int(ef) // divisor, k)

    return ({"ef_buckets": sorted({cut(e) for e in
                                   cell.config["serve"]["ef_buckets"]})},
            {"ef": cut(cell.traffic["ef"])})


def drop_residual(idx) -> None:
    """Serve ``idx`` with its residual tier zeroed: the resident tier's
    bits as built, the residual tier's all 0."""
    coarse, resid = idx.tier_arrays()
    tiers = (coarse, np.zeros_like(resid))
    idx.tier_arrays = lambda: tiers


def bf16_answers(cfg: dict, corpus, block: int = 256) -> list:
    """The reference in the program's place at bfloat16: one answer record
    per pool query, ids and distances as that computation gives them."""
    import jax
    import jax.numpy as jnp

    k = int(cfg["k"])
    x = jnp.asarray(corpus.vectors, jnp.bfloat16)
    xn = (x.astype(jnp.float32) ** 2).sum(1)

    @jax.jit
    def topk(q):
        dot = jnp.dot(q, x.T, preferred_element_type=jnp.float32)
        s = (q.astype(jnp.float32) ** 2).sum(1)[:, None] + xn[None] - 2 * dot
        neg, ids = jax.lax.top_k(-s, k)
        return ids, -neg

    recs = []
    for s in range(0, len(corpus.queries), block):
        q = jnp.asarray(corpus.queries[s:s + block], jnp.bfloat16)
        ids, dists = (np.asarray(a) for a in topk(q))
        for i in range(len(ids)):
            resp = SimpleNamespace(status="ok", ids=ids[i], dists=dists[i])
            recs.append(SimpleNamespace(pool=s + i, ok=True, resp=resp))
    return recs


def reading(cell, seed: int, seconds: float, devices, which: str) -> dict:
    if which == "bf16":
        c = corpus_mod.generate(cell.config, seed)
        window = bf16_answers(cell.config, c)
        ans = checks.compare(cell.config, c, window, 0)
        return {"which": which, "seed": seed, "correct": ans.correct,
                "attempted": len(window), "failed": 0,
                "recall_at_10": ans.recall,
                "checks": ans.values}
    serve, traffic = beam_overrides(cell, BEAMS[which]) if which in BEAMS \
        else (None, None)
    alter = drop_residual if which == "residual0" else None
    out = run_mod.run_cell(cell, seed, seconds, False, devices,
                           serve_overrides=serve, traffic_overrides=traffic,
                           alter_index=alter)
    return {"which": which, "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "recall_at_10": out["metrics"].get("recall_at_10", {}).get("value"),
            "checks": out["values"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--which", default="program,quarterbeam,bf16")
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(ROOT, args.workload)
    try:
        devices = run_mod.find_devices(cell.chips)
    except run_mod.NoChip as e:
        run_mod.log(str(e))
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        for which in args.which.split(","):
            print(json.dumps(reading(cell, seed, args.seconds, devices, which)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
