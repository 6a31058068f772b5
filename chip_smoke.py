"""Chip smoke test: the served ANNS search path, end to end, on a TPU.

    python chip_smoke.py [--seed 0]       # one chip
    python chip_smoke.py --four-chips     # sharded backend over four chips

One chip: builds the ``sift`` stand-in (40k x 128, L2) from ``--seed`` with
``Index.build`` (Dfloat recall target 0.9, so packed and tiered storage get a
real Dfloat layout), starts one ``Server`` over f32, packed and tiered
storage with the default ``fee_backend="auto"`` — the Pallas FEE kernels on
the TPU — and submits all 256 queries per storage through ``Server.submit``
in mixed batch sizes.  It fails unless every response is ok (no error, shed
or timeout), recall@10 >= 0.95 per storage, recall is within 0.5 pt of the
same queries run on the chip through the jnp oracle, and the served
programs contain the Pallas kernels (``tpu_custom_call``).

``--four-chips``: the ``sharded`` backend on a (1, 4) mesh of four chips
(``compact=1.0``) against the ``local`` backend on one chip: recall within
0.5 pt, and every ``ShardedDB`` array spread over the four devices, about a
quarter of its bytes on each.

Nothing here is a speed measurement.  Without a TPU the script exits
non-zero and prints no result.  Index artifacts go to a fresh
``.cache/chip_smoke`` of this checkout; the compile cache to
``$JAX_COMPILATION_CACHE_DIR`` or ``.cache/jax``.  The last line of output
is the JSON result.
"""
import argparse
import dataclasses
import itertools
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_TOP = 10
EF = 64
MAX_RECALL_GAP = 0.005
MIN_RECALL = 0.95
STORAGES = ("f32", "packed", "tiered")
CHUNKS = (1, 5, 8, 19, 32)        # submission bursts -> batch buckets 1, 8, 32


def _overlap(a, b) -> float:
    """Mean fraction of shared ids per query between two (Q, k) id arrays."""
    return sum(len(set(x) & set(y)) for x, y in zip(a.tolist(), b.tolist())) \
        / a.size


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def one_chip(db, idx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import recall_at_k
    from repro.serve import ServeConfig, Server
    from repro.serve.batcher import params_for

    cfg = ServeConfig(ef_buckets=(32, EF), batch_buckets=(1, 8, 32),
                      k_max=N_TOP, storages=STORAGES, use_dfloat=True,
                      slo_ms=600_000.0, degrade=False)
    nq = len(db.queries)
    t0 = time.perf_counter()
    with Server(idx, cfg) as srv:
        print(f"server started in {time.perf_counter() - t0:.1f} s: "
              f"{len(srv.warmup_info['cells'])} programs compiled in "
              f"{srv.warmup_info['total_s']:.1f} s", flush=True)
        served = {}
        for st in STORAGES:
            futs, i = [], 0
            for n in itertools.cycle(CHUNKS):
                if i >= nq:
                    break
                burst = [srv.submit(q, k=N_TOP, ef=EF, storage=st)
                         for q in db.queries[i:i + n]]
                for f in burst:
                    f.exception(timeout=600)       # wait; errors checked below
                futs += burst
                i += n
            served[st] = futs
        summary = srv.metrics.summary()

    print(f"responses: {summary['requests']} requests, {summary['ok']} ok, "
          f"{summary.get('errors', 0)} errors, {summary['shed']} shed, "
          f"{summary['timeout']} timeouts", flush=True)
    _check(summary.get("errors", 0) == 0 and summary["shed"] == 0
           and summary["timeout"] == 0, "errored, shed or timed-out requests")
    d = idx.dim
    shapes = (jax.ShapeDtypeStruct((cfg.batch_max, d), jnp.float32),
              jax.ShapeDtypeStruct((cfg.batch_max,), jnp.int32))
    for st, futs in served.items():
        errs = [f.exception() for f in futs if f.exception() is not None]
        _check(not errs, f"{st}: {len(errs)} requests raised: {errs[:1]}")
        resps = [f.result() for f in futs]
        n_ok = sum(r.status == "ok" for r in resps)
        _check(n_ok == nq, f"{st}: {n_ok}/{nq} responses ok")
        ids = np.stack([r.ids for r in resps])
        recall = recall_at_k(ids, db.gt, N_TOP)
        params = params_for(cfg, EF, cfg.expand, st)
        oracle = idx.searcher(
            "local", dataclasses.replace(params, fee_backend="jnp"))(db.queries)
        recall_o = recall_at_k(oracle.ids, db.gt, N_TOP)
        kernels = "tpu_custom_call" in idx.searcher("local", params) \
            .lower(*shapes).as_text()
        print(f"{st}: {n_ok}/{nq} ok, recall@{N_TOP} {recall:.4f}, jnp oracle "
              f"on chip {recall_o:.4f}, id agreement "
              f"{_overlap(ids, oracle.ids):.4f}, Pallas kernels in served "
              f"program: {kernels}", flush=True)
        _check(recall >= MIN_RECALL, f"{st}: recall {recall} < {MIN_RECALL}")
        _check(abs(recall - recall_o) <= MAX_RECALL_GAP,
               f"{st}: recall {recall} vs oracle {recall_o}")
        _check(kernels, f"{st}: no tpu_custom_call in the served program")


def four_chips(db, idx) -> None:
    import jax
    import numpy as np

    from repro.index import SearchParams

    devs = jax.devices()[:4]
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = SearchParams(ef=EF, k=N_TOP, compact=1.0)
    before = [dev.memory_stats()["bytes_in_use"] for dev in devs]
    sharded = idx.searcher("sharded", params, mesh=mesh)
    grown = np.array([dev.memory_stats()["bytes_in_use"] - b
                      for dev, b in zip(devs, before)], np.float64)
    arrays = {f.name: getattr(sharded.db, f.name)
              for f in dataclasses.fields(sharded.db)
              if getattr(sharded.db, f.name) is not None}
    for name, x in arrays.items():
        n_dev = len(x.sharding.device_set)
        print(f"ShardedDB.{name}: {x.shape} {x.dtype} over {n_dev} devices, "
              f"per-device bytes "
              f"{[s.data.nbytes for s in x.addressable_shards]}", flush=True)
        _check(n_dev == 4, f"ShardedDB.{name} spans {n_dev} devices, not 4")
    share = grown / max(grown.sum(), 1.0)
    print(f"bytes_in_use growth per device on placing the ShardedDB: "
          f"{grown.astype(int).tolist()} (shares "
          f"{[round(float(s), 4) for s in share]})", flush=True)
    _check(bool(np.all(np.abs(share - 0.25) <= 0.05)),
           f"ShardedDB bytes not spread evenly: shares {share.tolist()}")

    res_sh = sharded(db.queries)
    res_lo = idx.searcher("local", params)(db.queries)
    r_sh, r_lo = res_sh.recall(db.gt, N_TOP), res_lo.recall(db.gt, N_TOP)
    print(f"sharded x4 recall@{N_TOP} {r_sh:.4f}, local (one chip) "
          f"{r_lo:.4f}, id agreement {_overlap(res_sh.ids, res_lo.ids):.4f}",
          flush=True)
    _check(abs(r_sh - r_lo) <= MAX_RECALL_GAP,
           f"sharded recall {r_sh} vs local {r_lo}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic corpus and the index build")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-local phase on four chips")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    if len(devs) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips; JAX found {len(devs)}",
              file=sys.stderr)
        return 2

    art = ROOT / ".cache" / "chip_smoke"
    os.environ["REPRO_CACHE"] = str(art)      # read when repro is imported
    sys.path.insert(0, str(ROOT / "src"))
    from repro.data.synthetic import make_dataset
    from repro.index import Index, IndexSpec
    from repro.serve import enable_compilation_cache

    # fresh artifact directory: nothing is read back from an earlier run
    shutil.rmtree(art, ignore_errors=True)
    art.mkdir(parents=True)

    print(f"device: {devs[0].device_kind} x {len(devs)}; compilation cache: "
          f"{enable_compilation_cache()}; artifacts: {art}", flush=True)
    t0 = time.perf_counter()
    db = make_dataset("sift", seed=args.seed)
    idx = Index.build(db, IndexSpec.for_db(db, dfloat_recall_target=0.9,
                                           seed=args.seed))
    print(f"index built in {time.perf_counter() - t0:.1f} s: {db.n} x "
          f"{db.dim} {db.metric}, Dfloat (width, dims) "
          f"{[(s.width, s.n_dims) for s in idx.dfloat_cfg.segments]}, "
          f"tier split {idx.tier_split} segments", flush=True)

    if args.four_chips:
        four_chips(db, idx)
    else:
        one_chip(db, idx)
    peak = [dev.memory_stats().get("peak_bytes_in_use")
            for dev in devs[:n_chips]]
    print(f"peak_bytes_in_use per device: {peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
