import os
# a CPU shape tool: 512 fake host devices, never the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:  jit(step, in_shardings, out_shardings).lower(*specs).compile()
on the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh, then record
memory_analysis / cost_analysis / per-collective byte counts for the roofline
(EXPERIMENTS.md §Dry-run / §Roofline).  Results are cached as JSON per cell;
run cells in subprocesses via --all so one failure doesn't kill the batch.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --retrieval [--multi-pod]
  python -m repro.launch.dryrun --all            # every cell, both meshes
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.distributed import sharding as sh
from repro.launch import mesh as mesh_mod
from repro.models.registry import get_model
from repro.training import OptConfig, optim
from repro.training.train_step import TrainState, make_train_step
from repro.utils import CACHE_DIR

OUT_DIR = CACHE_DIR / "dryrun"

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|u64|c64)\[([0-9,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
          "pred": 1, "f64": 8, "s64": 8, "u64": 8, "c64": 8}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device payload bytes of every collective op in the compiled
    (post-SPMD-partitioning, i.e. per-device-shaped) module."""
    out = {c: {"count": 0, "bytes": 0} for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.search(r"=\s*((?:\([^)]*\)|\S+))\s+(" + "|".join(COLLECTIVES) + r")[-a-z]*\(", ls)
        if not m:
            continue
        shapes = _SHAPE_RE.findall(m.group(1))
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES[dt]
        kind = m.group(2)
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items() if isinstance(v, dict))
    return out


def _spec_leaves(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def sharded_bytes(abstract_tree, specs, mesh) -> int:
    """Per-device bytes of a tree under its PartitionSpecs (exact)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def leaf(x, spec):
        div = 1
        for s in spec:
            if s is None:
                continue
            for ax in (s if isinstance(s, tuple) else (s,)):
                div *= sizes[ax]
        return x.size * x.dtype.itemsize // div

    flat_x = jax.tree.leaves(abstract_tree)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    return sum(leaf(x, s) for x, s in zip(flat_x, flat_s))


def analytic_memory(arch: str, shape_name: str, mesh) -> dict:
    """Per-device TPU memory budget from the sharding specs + activation math.

    This is the 'fits 16 GB' proof: the XLA-CPU buffer assignment inflates
    bf16 matmul operands to f32 and replicates scan-xs weight stacks (both
    measured CPU-pipeline artifacts, see EXPERIMENTS.md §Dry-run); real-TPU
    residency follows the sharding specs, which this budget computes exactly,
    plus standard activation-stack/transient terms."""
    import dataclasses as dc
    cfg = C.get_config(arch)
    shape = C.SHAPES[shape_name]
    mode = "train" if shape.kind == "train" else "serve"
    from repro.distributed import axes as ax
    ax.set_mode(mode)
    api = get_model(cfg)
    params_abs = api.abstract_params()
    pspecs = sh.param_specs(params_abs, mesh, mode=mode)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    tp = sizes.get("model", 1)
    out = dict(params_gb=sharded_bytes(params_abs, pspecs, mesh) / 2**30)

    d, v = cfg.d_model, cfg.vocab
    if shape.kind == "train":
        opt_cfg = OptConfig(name=cfg.optimizer)
        opt_abs = jax.eval_shape(lambda p: optim.init_opt_state(p, opt_cfg), params_abs)
        ospecs = sh.opt_specs(opt_abs, pspecs, mesh)
        mb = max(cfg.microbatch, 1)
        tokens_dev = shape.seq_len * shape.global_batch // (mb * dp)
        gbytes = 2 if cfg.grad_acc_dtype == "bf16" else 4
        grads_gb = sum(x.size * gbytes for x in jax.tree.leaves(params_abs)) / 2**30 / (dp * tp)
        stacks_gb = cfg.n_groups * tokens_dev * d * 2 / 2**30
        ff_loc = max(cfg.d_ff, cfg.d_inner if cfg.ssm_state else 0, d) / tp
        transient_gb = 4 * tokens_dev * max(ff_loc, d) * 4 / 2**30
        logits_gb = 2 * tokens_dev * (v / tp) * 4 / 2**30
        # per-iteration FSDP gather transient: one group's largest weight
        # slice, model-sharded, x2 live (fwd + bwd recompute overlap)
        gather_gb = 2 * max((x.size * x.dtype.itemsize / (x.shape[0] if x.ndim >= 3 else 1)
                             for x in jax.tree.leaves(params_abs)), default=0) / tp / 2**30
        out.update(opt_gb=sharded_bytes(opt_abs, ospecs, mesh) / 2**30,
                   grads_gb=grads_gb, act_stacks_gb=stacks_gb,
                   transient_gb=transient_gb, logits_gb=logits_gb,
                   weight_gather_gb=gather_gb)
    else:
        cache_abs = api.abstract_cache(shape.global_batch, shape.seq_len)
        cspecs = sh.cache_specs(cache_abs, mesh)
        out.update(cache_gb=sharded_bytes(cache_abs, cspecs, mesh) / 2**30)
        if shape.kind == "prefill":
            # no backward pass: only the transient per-layer working set
            tokens_dev = shape.seq_len * shape.global_batch // dp
            ff_loc = max(cfg.d_ff, cfg.d_inner if cfg.ssm_state else 0, d) / tp
            out["act_gb"] = 4 * tokens_dev * max(ff_loc, d) * 4 / 2**30
        else:
            out["act_gb"] = 4 * shape.global_batch * max(d, v // tp) * 4 / 2**30
    out["total_gb"] = round(sum(v for k, v in out.items() if k.endswith("_gb")), 3)
    out["fits_16gb"] = out["total_gb"] <= 16.0
    return {k: (round(v, 3) if isinstance(v, float) else v) for k, v in out.items()}


def build_cell(arch: str, shape_name: str, mesh, variant: str = "memory",
               override_cfg=None, n_groups: int = 0):
    """Returns (jitted_fn, example_args_abstract) for the cell.

    Train cells come in two analysis variants (XLA-CPU cost_analysis counts a
    scan body ONCE — measured in EXPERIMENTS.md §Dry-run — so FLOPs need an
    unrolled lowering, while memory needs the deployed scan+microbatch form):
      * "memory": scan-over-groups + configured microbatch (deployment form)
      * "flops":  unrolled scans + one microbatch slice, truncated to
                  ``n_groups`` layer groups; the roofline recovers the full
                  model exactly from f(1g), f(2g):
                     per_group = f(2g) - f(1g);  total = f(1g) + (G-1)*per_group
                  and scales by the microbatch count.
    """
    import dataclasses as dc
    from repro.distributed import axes as ax

    cfg = override_cfg or C.get_config(arch)
    shape = C.SHAPES[shape_name]
    if variant == "flops":
        repl = dict(scan_unroll=True)
        if n_groups:
            repl["n_layers"] = n_groups * cfg.period
            if cfg.is_encdec:
                repl["encoder_layers"] = n_groups
        cfg = dc.replace(cfg, **repl)
    mode = "train" if shape.kind == "train" else "serve"
    ax.set_mode(mode)
    api = get_model(cfg)
    params_abs = api.abstract_params()
    pspecs = sh.param_specs(params_abs, mesh, mode=mode)
    batch_abs = C.input_specs(cfg, shape)
    if shape.kind == "train" and variant == "flops" and cfg.microbatch > 1:
        batch_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((x.shape[0] // cfg.microbatch,) + x.shape[1:],
                                           x.dtype), batch_abs)
        cfg = dc.replace(cfg, microbatch=1)
        api = get_model(cfg)
    bspecs = sh.batch_specs(batch_abs, mesh)

    if shape.kind == "train":
        opt_cfg = OptConfig(name=cfg.optimizer)
        opt_abs = jax.eval_shape(lambda p: optim.init_opt_state(p, opt_cfg), params_abs)
        ospecs = sh.opt_specs(opt_abs, pspecs, mesh)
        state_abs = TrainState(params=params_abs, opt_state=opt_abs,
                               step=jax.ShapeDtypeStruct((), jnp.int32), error_fb=None)
        state_specs = TrainState(params=pspecs, opt_state=ospecs,
                                 step=jax.sharding.PartitionSpec(), error_fb=None)
        step_fn = make_train_step(api.loss, opt_cfg, microbatch=max(cfg.microbatch, 1),
                                  grad_shardings=sh.named(pspecs, mesh),
                                  grad_acc_dtype=cfg.grad_acc_dtype)
        jitted = jax.jit(step_fn,
                         in_shardings=(sh.named(state_specs, mesh), sh.named(bspecs, mesh)),
                         out_shardings=(sh.named(state_specs, mesh), None),
                         donate_argnums=(0,))
        return jitted, (state_abs, batch_abs)

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            return api.prefill(params, batch, shape.seq_len)
        cache_abs = api.abstract_cache(shape.global_batch, shape.seq_len)
        cspecs = sh.cache_specs(cache_abs, mesh)
        jitted = jax.jit(prefill_fn,
                         in_shardings=(sh.named(pspecs, mesh), sh.named(bspecs, mesh)),
                         out_shardings=(None, sh.named(cspecs, mesh)))
        return jitted, (params_abs, batch_abs)

    # decode: one token against a kv cache of seq_len
    cache_abs = api.abstract_cache(shape.global_batch, shape.seq_len)
    cspecs = sh.cache_specs(cache_abs, mesh)
    jitted = jax.jit(api.decode,
                     in_shardings=(sh.named(pspecs, mesh), sh.named(cspecs, mesh),
                                   sh.named(bspecs, mesh)["tokens"]),
                     out_shardings=(None, sh.named(cspecs, mesh)),
                     donate_argnums=(1,))
    return jitted, (params_abs, cache_abs, batch_abs["tokens"])


def build_retrieval_cell(mesh, n: int = 1_000_000_000, d: int = 128,
                         m_part: int = 8, ef: int = 64, batch: int = 1024):
    """The paper's own workload at BigANN-1B scale as a dry-run cell."""
    from repro.core.fee import FeeParams
    from repro.core.search import SearchConfig
    from repro.distributed import retrieval as rt

    n_shards = mesh.devices.shape[-1]
    db = rt.abstract_db(n, d, n_shards, m_part, jnp.bfloat16)
    seg = 16
    cfg = SearchConfig(ef=ef, k=10, metric="l2", seg=seg, use_fee=True, max_hops=2 * ef)
    searcher = rt.make_sharded_searcher(mesh, cfg, n, fee=FeeParams.identity(d // seg))
    q = jax.ShapeDtypeStruct((batch, d), jnp.float32)
    e = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return searcher, (db, q, e)


def analyze(jitted, args_abs, mesh, meta: dict) -> dict:
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args_abs)
        compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    rec = dict(
        meta,
        ok=True,
        compile_s=round(t1 - t0, 1),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
            peak_bytes=(getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "output_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0)
            - (getattr(mem, "alias_size_in_bytes", 0) or 0),
        ),
        cost=dict(
            flops=cost.get("flops"),
            transcendentals=cost.get("transcendentals"),
            bytes_accessed=cost.get("bytes accessed"),
        ),
        collectives=coll,
    )
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, force=False) -> dict:
    tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
    out_file = OUT_DIR / f"{tag}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    meta = dict(arch=arch, shape=shape_name,
                mesh="2x16x16" if multi_pod else "16x16",
                chips=int(mesh.devices.size))
    try:
        if arch == "retrieval-bigann1b":
            searcher, args_abs = build_retrieval_cell(mesh)
            with jax.set_mesh(mesh):
                lowered = searcher.lower(*args_abs)
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            rec = dict(meta, ok=True,
                       memory=dict(argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                                   temp_bytes=getattr(mem, "temp_size_in_bytes", None)),
                       cost=dict(flops=cost.get("flops"),
                                 bytes_accessed=cost.get("bytes accessed")),
                       collectives=parse_collectives(compiled.as_text()))
        else:
            cfg = C.get_config(arch)
            ok, why = C.shape_applicable(cfg, shape_name)
            if not ok:
                rec = dict(meta, ok=False, skipped=True, reason=why)
            else:
                kind = C.SHAPES[shape_name].kind
                # memory variant (deployed scan form)
                jitted, args_abs = build_cell(arch, shape_name, mesh, "memory")
                rec = analyze(jitted, args_abs, mesh, meta)
                # flops via 1-group / 2-group unrolled compiles + exact
                # linear recovery (scan bodies are counted once by XLA-CPU)
                g_total = cfg.n_groups
                mb = max(cfg.microbatch, 1) if kind == "train" else 1
                f1_j, f1_a = build_cell(arch, shape_name, mesh, "flops", n_groups=1)
                r1 = analyze(f1_j, f1_a, mesh, dict(meta))
                if g_total > 1:
                    f2_j, f2_a = build_cell(arch, shape_name, mesh, "flops", n_groups=2)
                    r2 = analyze(f2_j, f2_a, mesh, dict(meta))
                else:
                    r2 = r1

                def lin(a, b):
                    a, b = a or 0, b or 0
                    return max(0, (a + (g_total - 1) * (b - a)) * mb)

                rec["cost"] = {k: lin(r1["cost"][k], r2["cost"][k])
                               for k in r1["cost"]}
                coll = {}
                for k in r1["collectives"]:
                    if isinstance(r1["collectives"][k], dict):
                        coll[k] = {kk: int(lin(r1["collectives"][k][kk],
                                               r2["collectives"][k][kk]))
                                   for kk in r1["collectives"][k]}
                    else:
                        coll[k] = int(lin(r1["collectives"][k], r2["collectives"][k]))
                rec["collectives"] = coll
                rec["flops_compile_s"] = r1["compile_s"] + r2["compile_s"]
                rec["microbatch_scale"] = mb
                rec["group_extrapolation"] = dict(groups=g_total)
                rec["analytic_memory"] = analytic_memory(arch, shape_name, mesh)
    except Exception as e:  # noqa: BLE001 — record the failure, don't hide it
        rec = dict(meta, ok=False, skipped=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    out_file.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.all:
        import subprocess
        cells = [(a, s) for a, s, ok, _ in C.cells(include_skipped=True)]
        cells.append(("retrieval-bigann1b", "search"))
        for mp in (False, True):
            for arch, shape in cells:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if (OUT_DIR / f"{tag}.json").exists() and not args.force:
                    print(f"[cached] {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape]
                if mp:
                    cmd.append("--multi-pod")
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True, timeout=3600)
                status = "?"
                f = OUT_DIR / f"{tag}.json"
                if f.exists():
                    rec = json.loads(f.read_text())
                    status = ("OK" if rec.get("ok") else
                              ("SKIP" if rec.get("skipped") else "FAIL"))
                print(f"[{status}] {tag} ({time.time()-t0:.0f}s)")
                if status == "?":
                    print(r.stdout[-2000:], r.stderr[-2000:])
        return

    if args.retrieval:
        rec = run_cell("retrieval-bigann1b", "search", args.multi_pod, args.force)
    else:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.force)
    print(json.dumps(rec, indent=1)[:3000])


if __name__ == "__main__":
    main()
