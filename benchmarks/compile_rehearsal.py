#!/usr/bin/env python3
"""Compile the programs of ``chip_smoke.py`` at full qwen3-8b width for a
described TPU v5e (no chip attached) and check that each fits its chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/compile_rehearsal.py

Programs, each compiled by the TPU compiler for a ``v5e:2x2`` topology that
is described, not attached:

* ``init``: the largest parameter group's program of
  :func:`repro.models.model.init_frozen`.  While the model is built the
  device holds the groups frozen so far plus one such program.
* ``engine``: every program the smoke's two engines dispatch (deployed:
  chunked mixed segments and decode segments; reference: blocking prefill
  and decode segments), at full width, with the int8 KV pool the smoke
  sizes for 16 GiB of HBM.  Which programs run and with what arguments is
  recorded from the same requests served by a small model on the CPU;
  the full-width ones are then built from that record.
* ``forced``: the dense forward the smoke checks the served tokens with.

For each program the line printed gives its arguments, outputs and
temporaries (``memory_analysis``) and their sum less aliased bytes, which
must stay within the chip's 16 GiB.  The last line is a JSON summary.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parents[1]
HBM = 16 * 2**30            # v5e HBM per chip


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", choices=("init", "engine", "forced"),
                    action="append", help="compile only these groups")
    args = ap.parse_args(argv)
    groups = set(args.only or ("init", "engine", "forced"))

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import chip_smoke as smoke
    from repro import configs as cfg_lib
    from repro.core import backend as backend_lib

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = dataclasses.replace(cfg_lib.get_config(smoke.ARCH),
                              kv_cache_dtype="int8")
    deployed = backend_lib.DeploymentPlan.from_json(
        json.dumps(smoke.DEPLOYED))
    reference = backend_lib.DeploymentPlan.from_json(
        json.dumps(smoke.REFERENCE))
    report: dict = {"programs": 0, "over_hbm": []}

    def sds(x, sharding=one_chip):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    def frozen_shapes(plan):
        from repro.models import model as M
        return jax.eval_shape(lambda k: M.freeze_params(
            M.init(k, cfg), a_scale=smoke.A_SCALE, plan=plan),
            jax.random.PRNGKey(smoke.SEED))

    def account(name, exe):
        m = exe.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name}: args {m.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"out {m.output_size_in_bytes / 2**30:.3f}, temp "
              f"{m.temp_size_in_bytes / 2**30:.3f}, aliased "
              f"{m.alias_size_in_bytes / 2**30:.3f}; total "
              f"{total / 2**30:.3f} GiB", flush=True)
        report["programs"] += 1
        if total > HBM:
            report["over_hbm"].append(name)
        return total

    params = frozen_shapes(deployed)
    w_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    report["weights_gib"] = w_bytes / 2**30
    print(f"frozen {cfg.name}: {w_bytes / 2**30:.3f} GiB", flush=True)

    # The kernels pick their Pallas path from jax.default_backend(); the
    # chip is described, not attached, so report it while tracing.
    on_tpu = mock.patch.object(jax, "default_backend", lambda: "tpu")

    if "init" in groups:
        init_program(jax, cfg, deployed, smoke, one_chip, account, report)
    if "engine" in groups:
        engine_programs(jax, cfg, deployed, reference, smoke, params, sds,
                        w_bytes, on_tpu, account, report)
    if "forced" in groups:
        n_new = smoke.NEW_TOKENS
        r = len(smoke.PROMPT_LENS)
        tok = jax.ShapeDtypeStruct((r, smoke.forced_len(n_new)),
                                   jax.numpy.int32, sharding=one_chip)
        start = jax.ShapeDtypeStruct((r,), jax.numpy.int32,
                                     sharding=one_chip)
        p_abs = jax.tree.map(sds, params)
        with on_tpu:
            for tag, plan in (("deployed", deployed),
                              ("reference", reference)):
                exe = smoke.forced_logits_fn(cfg, plan, n_new).lower(
                    p_abs, tok, start).compile()
                account(f"forced forward ({tag} plan)", exe)

    print(json.dumps(report))
    return 1 if report["over_hbm"] else 0


def init_program(jax, cfg, plan, smoke, one_chip, account, report):
    """The largest group's program of ``init_frozen``."""
    from repro.models import model as M

    def full(k):
        return M.freeze_params(M.init(k, cfg), a_scale=smoke.A_SCALE,
                               plan=plan)

    shapes = jax.eval_shape(full, jax.random.PRNGKey(smoke.SEED))
    path, size = max(M.frozen_groups(shapes), key=lambda g: g[1])

    def group(k):
        node = full(k)
        for p in path:
            node = node[p]
        return node

    key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32, sharding=one_chip)
    exe = jax.jit(group).lower(key).compile()
    total = account(f"init group {'/'.join(path)}", exe)
    report["init_largest_group_gib"] = total / 2**30


def engine_programs(jax, cfg, deployed, reference, smoke, params, sds,
                    w_bytes, on_tpu, account, report):
    """Record what the smoke's engines dispatch on a small model, then
    compile the same programs at full width."""
    import numpy as np

    from repro import configs as cfg_lib
    from repro.serve import ContinuousEngine, kv_pool

    kv_blocks = smoke.blocks_for_bytes(cfg, HBM - w_bytes)
    report["kv_blocks"] = kv_blocks
    kw = smoke.engine_kw(kv_blocks)
    # The chunk the full-width engine autotunes; the small one must match.
    chunk = ContinuousEngine(None, cfg, **dict(kw, kv_blocks=2)).prefill_chunk
    small = dataclasses.replace(cfg_lib.reduced_config(smoke.ARCH),
                                kv_cache_dtype="int8")
    small_params = None
    seen: dict = {}
    for tag, plan, chunked in (("deployed", deployed, True),
                               ("reference", reference, False)):
        if small_params is None:
            from repro.launch import serve
            small_params = serve.build_params(small, plan, seed=smoke.SEED,
                                              a_scale=smoke.A_SCALE)
        ce = ContinuousEngine(small_params, small, plan=plan,
                              chunked_prefill=chunked, prefill_chunk=chunk,
                              **dict(kw, kv_blocks=1 + 8 * kw[
                                  "max_blocks_per_req"]))
        dispatch = ce._dispatch

        def record(fn, *args, name="dispatch", ce=ce, dispatch=dispatch,
                   tag=tag):
            key = next((k for k, v in ce._fn_cache.items() if v is fn),
                       None)
            marks = tuple("params" if a is ce.params else
                          "pages" if a is ce.pages else
                          jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                              np.shape(x), np.asarray(x).dtype), a)
                          for a in args)
            seen.setdefault((tag, name, key if key else fn.__name__),
                            (key, fn, marks))
            return dispatch(fn, *args, name=name)

        ce._dispatch = record
        res = ce.run(smoke.requests(small))
        assert len(res) == len(smoke.PROMPT_LENS)

    full_ce = {tag: ContinuousEngine(
        None, cfg, plan=plan, chunked_prefill=chunked, prefill_chunk=chunk,
        **dict(kw, kv_blocks=2))
        for tag, plan, chunked in (("deployed", deployed, True),
                                   ("reference", reference, False))}
    dtype = jax.numpy.bfloat16
    pages = jax.eval_shape(lambda: kv_pool.init_pages(
        cfg, kv_blocks, smoke.BLOCK_SIZE, dtype))
    p_abs = jax.tree.map(sds, params)
    pg_abs = jax.tree.map(sds, pages)
    builders = {"cb_prefill": "_prefill_fn", "cb_suffix": "_suffix_prefill_fn",
                "cb_segment": "_segment_fn", "cb_mixed": "_mixed_segment_fn"}
    worst = 0
    for (tag, name, _), (key, fn, marks) in sorted(
            seen.items(), key=lambda kv: str(kv[0])):
        if key is not None:
            fn = getattr(full_ce[tag], builders[key[0]])(*key[1:])
        elif not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        args = [p_abs if m == "params" else pg_abs if m == "pages" else
                jax.tree.map(sds, m) for m in marks]
        with on_tpu:
            exe = fn.lower(*args).compile()
        label = key[0] if key else name
        detail = "" if key is None else " " + ",".join(
            str(k) for k in key[2:])
        worst = max(worst, account(f"engine {tag} {label}{detail}", exe))
    report["engine_worst_gib"] = worst / 2**30


if __name__ == "__main__":
    sys.exit(main())
