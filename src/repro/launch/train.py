"""Production training launcher: pjit'd train step on the production mesh.

On a real TPU fleet this binary runs per host (jax.distributed.initialize
picks up the pod topology from the environment); with JAX_PLATFORMS=cpu it
drives the same code on --devices virtual host devices for small configs —
the dry-run proves the full-size lowering (launch/dryrun.py).

Usage:
  python -m repro.launch.train --arch granite-moe-1b-a400m --steps 20 \
      --devices 8 --mesh-shape 4,2 [--reduced]
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU devices (JAX_PLATFORMS=cpu only)")
    ap.add_argument("--mesh-shape", default="4,2")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--plan", default=None,
                    help="training DeploymentPlan (e.g. 'qat', inline JSON, "
                         "or a JSON file) routed through the backend registry")
    args = ap.parse_args()

    from repro.launch import runtime
    runtime.force_host_devices(args.devices)
    runtime.enable_compile_cache()

    import jax

    from repro import compat
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import configs as cfg_lib
    from repro.configs.base import TrainConfig
    from repro.checkpoint.manager import CheckpointManager
    from repro.data import synthetic
    from repro.distributed import sharding as shard_lib
    from repro.models import model as M
    from repro.train import optimizer as opt_lib
    from repro.train.train_loop import make_train_step

    shape = runtime.fit_mesh_shape(
        tuple(int(x) for x in args.mesh_shape.split(",")), len(jax.devices()))
    axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    mesh = compat.make_mesh(shape, axes)

    cfg = cfg_lib.reduced_config(args.arch) if args.reduced \
        else cfg_lib.get_config(args.arch)
    tcfg = TrainConfig(lr=1e-3, total_steps=args.steps, warmup_steps=5,
                       checkpoint_every=max(args.steps // 2, 1), remat=True)

    params = M.init(jax.random.PRNGKey(0), cfg)
    opt = opt_lib.init_opt_state(params)
    param_sh = shard_lib.resolve_param_specs(M.pspec(cfg), mesh)
    opt_sh = {"master": param_sh, "m": param_sh, "v": param_sh,
              "step": NamedSharding(mesh, P())}
    params = jax.tree.map(jax.device_put, params, param_sh)
    opt = jax.tree.map(jax.device_put, opt, opt_sh)

    stream = synthetic.TokenStreamConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start = mgr.latest_step() or 0
    if start:
        restored = mgr.restore(start, {"params": params, "opt": opt},
                               {"params": param_sh, "opt": opt_sh})
        params, opt = restored["params"], restored["opt"]
        print(f"resumed from step {start}")

    plan = None
    if args.plan is not None:
        from repro.core import backend as backend_lib
        plan = backend_lib.load_plan(args.plan)
    step_fn = make_train_step(cfg, tcfg, plan=plan)
    with compat.set_mesh(mesh):
        jstep = jax.jit(step_fn, in_shardings=(param_sh, opt_sh, None),
                        out_shardings=(param_sh, opt_sh, None),
                        donate_argnums=(0, 1))
        for step in range(start, args.steps):
            batch = synthetic.lm_batch(stream, step)
            params, opt, metrics = jstep(params, opt, batch)
            print(f"step {step}: loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.2f}")
            if (step + 1) % tcfg.checkpoint_every == 0:
                mgr.save_async(step + 1, {"params": params, "opt": opt})
    mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
