"""Fault-tolerant training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 50 \
      --smoke --devices 8 --ckpt-dir /tmp/ckpt --ckpt-every 10 [--resume]

Fault-tolerance loop (DESIGN.md §7): checkpoints are mesh-agnostic, the data
pipeline is step-indexed (stateless), and a failed step restarts from the last
checkpoint — `--simulate-failure N` kills the step loop at step N to exercise
the restart path (used by the integration test).
"""
import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--devices", type=int, default=0, help="host platform device count")
    ap.add_argument("--mesh", default="", help="e.g. 2x4; default: 1 x ndev")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--simulate-failure", type=int, default=-1)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs as C
    from repro.data.pipeline import TokenPipeline
    from repro.distributed import sharding as sh
    from repro.ft import checkpoint as ckpt
    from repro.models.registry import get_model
    from repro.training import GradCompressor, OptConfig, init_state, make_train_step

    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    cfg = dataclasses.replace(cfg, microbatch=args.microbatch)
    api = get_model(cfg)

    ndev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model")[: len(shape)] if len(shape) == 2
                         else ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))

    pipe = TokenPipeline(cfg.vocab, args.batch, args.seq, seed=1,
                         frontend=cfg.frontend, frontend_tokens=cfg.frontend_tokens,
                         d_model=cfg.d_model, encdec=cfg.is_encdec,
                         decoder_len=min(cfg.decoder_len_train, args.seq))

    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(0))
        pspecs = sh.param_specs(api.abstract_params(), mesh)
        params = jax.tree.map(lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)),
                              params, pspecs)
        opt_cfg = OptConfig(name=cfg.optimizer, lr=args.lr)
        comp = GradCompressor() if args.compress_grads else None
        state = init_state(params, opt_cfg, comp)
        step_fn = make_train_step(api.loss, opt_cfg, microbatch=max(args.microbatch, 1),
                                  compressor=comp,
                                  grad_shardings=sh.named(pspecs, mesh))
        step_jit = jax.jit(step_fn, donate_argnums=(0,))

        start = 0
        if args.resume and args.ckpt_dir:
            last = ckpt.latest_step(args.ckpt_dir)
            if last is not None:
                abstract = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
                state, manifest = ckpt.restore(
                    f"{args.ckpt_dir}/step_{last}", abstract)
                start = manifest["step"]
                print(f"[resume] restored step {start}")

        writer = None
        for step in range(start, args.steps):
            if step == args.simulate_failure:
                # the simulated crash hits the step loop: a checkpoint whose
                # async write is already in flight still lands (exiting would
                # kill its daemon writer thread mid-write)
                if writer is not None:
                    writer.join()
                print(f"[failure] simulated crash at step {step}", flush=True)
                sys.exit(17)
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
            state, metrics = step_jit(state, batch)
            if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if writer is not None:
                    writer.join()
                writer = ckpt.save(f"{args.ckpt_dir}/step_{step + 1}", step + 1,
                                   state, metadata=dict(arch=args.arch),
                                   async_write=True)
        if writer is not None:
            writer.join()
        print(f"[done] final loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
