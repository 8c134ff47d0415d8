import os
# A host-only compile tool: pin the CPU platform (for this process and the
# per-cell children, which inherit the environment) so no dry-run process
# ever takes an accelerator, and ask for 512 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the REAL step function (full train step with
AdamW/ZeRO state donation, or the serving prefill/decode step), lowers it
with ShapeDtypeStruct inputs against the production mesh, compiles, and
records:

  * compiled.memory_analysis()   — proves the cell fits 16 GiB/chip
  * compiled.cost_analysis()     — HLO FLOPs / bytes for §Roofline
  * collective bytes parsed from the optimized HLO (per collective kind)

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
Each --all cell runs in a subprocess so XLA compile arenas are reclaimed.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro import compat
from repro import configs as cfg_lib
from repro.configs.base import SHAPES, TrainConfig
from repro.distributed import sharding as shard_lib
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models import transformer
from repro.roofline import analysis as roofline
from repro.train import optimizer as opt_lib
from repro.train.train_loop import make_train_step


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if not isinstance(x, jax.ShapeDtypeStruct) else x, tree)


def build_cell(arch: str, shape_name: str, mesh, *, quant: str = "none",
               plan=None, remat_policy: str = "nothing",
               seq_shard: bool = True, kv_quant: bool = False,
               ssd_chunk: int = 0, capacity_factor: float = 0.0,
               act_shard: bool = False):
    """Returns (lowered, meta) for one cell."""
    cfg = cfg_lib.get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if act_shard:
        cfg = dataclasses.replace(cfg, act_shard=True)
    if ssd_chunk and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssd_chunk))
    if capacity_factor and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         capacity_factor=capacity_factor))
    shape = SHAPES[shape_name]
    ok, reason = cfg_lib.cell_is_runnable(cfg, shape)
    if not ok:
        return None, {"arch": arch, "shape": shape_name, "quant": quant,
                      "skipped": reason}

    frozen = quant == "w8a8" or plan is not None
    deploy_plan = plan if frozen else None
    pspec = model_lib.pspec(cfg)
    if frozen:
        pspec = model_lib.freeze_pspec(pspec, plan=deploy_plan)
    param_sh = shard_lib.resolve_param_specs(pspec, mesh)

    params_shape = jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
    if frozen:
        params_shape = jax.eval_shape(
            lambda: model_lib.freeze_params(
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             params_shape), plan=deploy_plan))

    meta = {
        "arch": arch, "shape": shape_name, "quant": quant,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
    }

    if shape.kind == "train":
        # Auto gradient-accumulation: the remat carry stack [L, B_mb, S, d]
        # must fit ~4 GiB/chip (bf16).  micro >= ceil(L*B*S*d*2 / (4GiB * DP)).
        dp = mesh.devices.size // mesh.shape["model"]
        carry = 2.0 * cfg.n_layers * shape.global_batch * shape.seq_len \
            * cfg.d_model
        micro = max(1, int(-(-carry // (4 * 2**30 * dp))))
        max_micro = max(1, shape.global_batch // dp)
        micro = min(micro, max_micro)
        while max_micro % micro:   # keep the microbatch split even
            micro += 1
        meta_micro = micro
        tcfg = TrainConfig(remat=True, microbatches=micro,
                           remat_policy=remat_policy)
        step = make_train_step(cfg, tcfg)
        opt_shape = jax.eval_shape(
            lambda p: opt_lib.init_opt_state(p), params_shape)
        opt_sh = {
            "master": param_sh, "m": param_sh, "v": param_sh,
            "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        batch = cfg_lib.input_specs(cfg, shape)
        batch_sh = shard_lib.data_specs(mesh, batch)
        meta["microbatches"] = meta_micro
        with compat.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                donate_argnums=(0, 1),
            ).lower(params_shape, opt_shape, batch)
        return lowered, meta

    if shape.kind == "prefill":
        batch = cfg_lib.input_specs(cfg, shape)
        batch_sh = shard_lib.data_specs(mesh, batch)

        def prefill_step(params, batch):
            return model_lib.prefill(params, batch, cfg,
                                     max_len=shape.seq_len, mode=deploy_plan)

        with compat.set_mesh(mesh):
            lowered = jax.jit(
                prefill_step, in_shardings=(param_sh, batch_sh),
            ).lower(params_shape, batch)
        return lowered, meta

    # decode
    specs = cfg_lib.decode_input_specs(cfg, shape)
    batch, caches = specs["batch"], specs["caches"]
    batch_sh = shard_lib.data_specs(mesh, batch)
    caches_sh = shard_lib.cache_specs(mesh, caches, cfg, shape.global_batch,
                                      seq_shard=seq_shard)

    def serve_step(params, batch, caches):
        return model_lib.decode_step(params, batch, caches, cfg,
                                     mode=deploy_plan)

    with compat.set_mesh(mesh):
        lowered = jax.jit(
            serve_step,
            in_shardings=(param_sh, batch_sh, caches_sh),
            out_shardings=(shard_lib.logits_spec(mesh, shape.global_batch),
                           caches_sh),
            donate_argnums=(2,),
        ).lower(params_shape, batch, caches)
    return lowered, meta


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             quant: str = "none", plan=None, out_json: str | None = None,
             seq_shard: bool = True, remat_policy: str = "nothing",
             kv_quant: bool = False, ssd_chunk: int = 0,
             capacity_factor: float = 0.0, act_shard: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    lowered, meta = build_cell(arch, shape_name, mesh, quant=quant, plan=plan,
                               seq_shard=seq_shard,
                               remat_policy=remat_policy, kv_quant=kv_quant,
                               ssd_chunk=ssd_chunk,
                               capacity_factor=capacity_factor,
                               act_shard=act_shard)
    meta["mesh"] = mesh_kind
    meta["kv_quant"] = kv_quant
    if lowered is None:
        result = {**meta, "status": "skipped"}
    else:
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        from repro.roofline import hlo_parse
        agg = hlo_parse.aggregate(compiled.as_text())
        n_chips = mesh.devices.size
        result = {
            **meta,
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "n_chips": n_chips,
            # loop-aware per-device numbers from the optimized HLO:
            "flops_per_device": agg["flops"],
            "traffic_bytes_per_device": agg["traffic_bytes"],
            "unknown_trip_loops": agg["unknown_trip_loops"],
            "top_ops": agg["top_ops"],
            # raw cost_analysis (NOT loop-aware; reference only):
            "xla_cost_flops": cost.get("flops", 0.0),
            "xla_cost_bytes": cost.get("bytes accessed", 0.0),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            },
            "collectives": agg["collectives"],
        }
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind} quant={quant}: "
              f"compiled in {t_compile:.0f}s; "
              f"flops/dev={result['flops_per_device']:.3e} "
              f"temp={result['memory']['temp_bytes']/2**30:.2f}GiB "
              f"coll={sum(c['wire_bytes'] for c in agg['collectives'].values()):.3e}B")
    if out_json:
        os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--quant", default="none", choices=["none", "w8a8"])
    ap.add_argument("--plan", default=None,
                    help="DeploymentPlan: backend name, inline JSON, or path")
    ap.add_argument("--no-seq-shard", action="store_true",
                    help="disable KV sequence sharding (ablation)")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=["nothing", "dots"])
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode shapes)")
    ap.add_argument("--ssd-chunk", type=int, default=0)
    ap.add_argument("--act-shard", action="store_true",
                    help="d_model-sharded residual stream between blocks")
    ap.add_argument("--cf", type=float, default=0.0,
                    help="MoE capacity factor override")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) via subprocesses")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--cell-timeout", type=float, default=2400.0)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        # Worklist: cheap kinds first (decode < prefill < train), small archs
        # before qwen2-vl-72b, single mesh before multi — so partial sweeps
        # maximize coverage.
        size_order = sorted(
            cfg_lib.ARCH_IDS, key=lambda a: cfg_lib.get_config(a).param_count())
        kind_rank = {"decode": 0, "prefill": 1, "train": 2}
        work = []
        for mesh_kind in meshes:
            for shape_name in sorted(
                    SHAPES, key=lambda s: kind_rank[SHAPES[s].kind]):
                for arch in size_order:
                    work.append((arch, shape_name, mesh_kind))
        work.sort(key=lambda w: (w[2] == "multi",
                                 kind_rank[SHAPES[w[1]].kind]))

        def launch(item):
            arch, shape_name, mesh_kind = item
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", mesh_kind, "--quant", args.quant,
                   "--out", args.out]
            # The child inherits JAX_PLATFORMS=cpu (set at the top).
            return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True)

        failures, running, idx = [], [], 0
        t_start = time.time()
        while idx < len(work) or running:
            while idx < len(work) and len(running) < args.jobs:
                arch, shape_name, mesh_kind = work[idx]
                tag = f"{arch}__{shape_name}__{mesh_kind}__{args.quant}"
                out = os.path.join(args.out, tag + ".json")
                if os.path.exists(out):
                    print(f"[dryrun] {tag}: cached")
                    idx += 1
                    continue
                running.append((work[idx], launch(work[idx]), time.time()))
                idx += 1
            still = []
            for item, proc, t0 in running:
                if proc.poll() is None:
                    if time.time() - t0 > args.cell_timeout:
                        proc.kill()
                        failures.append(("timeout", item))
                        print(f"[dryrun] TIMEOUT {item}")
                    else:
                        still.append((item, proc, t0))
                else:
                    out_s, err_s = proc.communicate()
                    sys.stdout.write(out_s[-1500:])
                    sys.stdout.flush()
                    if proc.returncode != 0:
                        failures.append(("error", item))
                        sys.stderr.write(err_s[-3000:])
            running = still
            time.sleep(2)
        print(f"[dryrun] sweep done in {(time.time()-t_start)/60:.1f} min; "
              f"failures: {failures}")
        if failures:
            sys.exit(1)
        return

    assert args.arch and args.shape, "--arch/--shape or --all required"
    for mesh_kind in meshes:
        tag = f"{args.arch}__{args.shape}__{mesh_kind}__{args.quant}" \
            + ("__kvq" if args.kv_quant else "") \
            + (f"__ssd{args.ssd_chunk}" if args.ssd_chunk else "") \
            + (f"__cf{args.cf}" if args.cf else "") \
            + (f"__remat-{args.remat_policy}" if args.remat_policy != "nothing" else "") \
            + ("__actshard" if args.act_shard else "")
        out_json = os.path.join(args.out, tag + ".json")
        from repro.core import backend as backend_lib
        plan = backend_lib.load_plan(args.plan) if args.plan else None
        run_cell(args.arch, args.shape, mesh_kind, quant=args.quant,
                 plan=plan,
                 out_json=out_json, seq_shard=not args.no_seq_shard,
                 remat_policy=args.remat_policy, kv_quant=args.kv_quant,
                 ssd_chunk=args.ssd_chunk, capacity_factor=args.cf,
                 act_shard=args.act_shard)


if __name__ == "__main__":
    main()
