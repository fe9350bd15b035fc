"""Distributed training driver.

Single-process launcher: builds the mesh from --dp/--tp (and --pods), shards
params/optimizer with the framework sharding rules, and runs the train step
with checkpoint/restart.  On a real fleet the same code runs under
``jax.distributed.initialize()`` with one process per host — the mesh,
shardings, and checkpoint format are already global, so nothing else
changes (the dry-run proves the 512-chip lowering).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
      --steps 20 --batch 8 --seq 128

Use --devices N to request N virtual host devices (sets XLA_FLAGS; must be
the first jax-touching process in the interpreter).
"""

import argparse
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moments", default="float32")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.sharding import param_specs, shardings
    from repro.training.checkpoint import CheckpointManager
    from repro.training.data import make_batch
    from repro.training.optimizer import OptConfig, init_opt_state
    from repro.training.train_loop import (TrainConfig, TrainState,
                                           make_train_step)
    from repro.launch.cells import _opt_specs

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke_config()
    cfg = cfg.scaled(dtype="float32" if args.smoke else cfg.dtype,
                     remat="block")
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"devices={len(jax.devices())}")

    shape = ((args.pods, args.dp, args.tp) if args.pods > 1
             else (args.dp, args.tp))
    axes = (("pod", "data", "model") if args.pods > 1 else ("data", "model"))
    mesh = jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))

    ocfg = OptConfig(moments_dtype=args.moments, warmup_steps=10,
                     decay_steps=max(args.steps, 100))
    tcfg = TrainConfig(microbatches=args.microbatches)
    st = TrainState.create(jax.random.PRNGKey(0), cfg, ocfg)
    pspecs = param_specs(st.params, cfg, mesh)
    psh = shardings(mesh, pspecs)
    osh = shardings(mesh, _opt_specs(pspecs, args.moments))
    st.params = jax.device_put(st.params, psh)
    st.opt_state = jax.device_put(st.opt_state, osh)

    step_fn = jax.jit(make_train_step(cfg, ocfg, tcfg, param_shardings=psh),
                      donate_argnums=(0, 1))

    mgr = CheckpointManager(args.ckpt, every=args.ckpt_every) if args.ckpt \
        else None
    start = 0
    if mgr:
        s, tree, extra = mgr.restore_latest(
            {"params": st.params, "opt": st.opt_state},
            shardings={"params": psh, "opt": osh})
        if s is not None:
            st.params, st.opt_state = tree["params"], tree["opt"]
            start = int(extra["step"])
            print(f"resumed at step {start}")

    import time
    with jax.sharding.set_mesh(mesh):
        t0 = time.time()
        for i in range(start, args.steps):
            b = {k: jnp.asarray(v) for k, v in
                 make_batch(cfg, args.batch, args.seq, step=i).items()}
            st.params, st.opt_state, m = step_fn(st.params, st.opt_state, b)
            if mgr:
                mgr.maybe_save(i + 1,
                               {"params": st.params, "opt": st.opt_state},
                               extra={"step": i + 1})
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(m['loss']):.4f} "
                      f"lr={float(m['lr']):.2e} "
                      f"({(time.time()-t0)/(i-start+1):.2f}s/step)")
    print("done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
