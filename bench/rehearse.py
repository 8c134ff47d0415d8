#!/usr/bin/env python3
"""Compile a cell's full-width programs for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a device that is
described, not attached (``jax.experimental.topologies``).  For the cell
given, this compiles the weight-making program, the two pool-size probes
of the widest decode and mixed segment programs (so the pool the chip
run would choose, for a stated HBM limit), the widest segment programs at
that pool, and the reference's forward; and prints each program's memory
analysis.  A program the TPU compiler refuses raises here.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py \\
        --workload qwen3-8b.chat-poisson
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
HBM_LIMIT = 15.75 * 2**30       # bytes_limit a v5e chip reports (about)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="qwen3-8b.chat-poisson")
    ap.add_argument("--bytes-limit", type=float, default=HBM_LIMIT)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(BENCH.parent / "src"), str(BENCH)):
        sys.path.insert(0, p)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchlib import engine as eng, model as bm, spec
    jax.config.update("jax_enable_compilation_cache", False)

    cell = spec.load_cell(args.workload)
    conf, adapter = cell.config, cell.adapter
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg, plan = adapter.program_config(conf), bm.deployment_plan(conf)
    a_scale = float(conf["engine"]["a_scale"])
    plain = cell.generator().generate(cell.traffic, 0,
                                      int(conf["vocab_size"]))
    s = eng.settings(conf, cell.generator().max_tokens(cell.traffic),
                     adapter.kv_layout(cfg))
    shapes = bm.frozen_shapes(cfg, plan, a_scale)
    placed = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one), shapes)

    def report(tag, compiled, t0):
        m = compiled.memory_analysis()
        print(f"{tag}: compiled in {time.perf_counter() - t0:.1f}s; "
              f"args {m.argument_size_in_bytes / 2**30:.3f} GiB, out "
              f"{m.output_size_in_bytes / 2**30:.3f}, temp "
              f"{m.temp_size_in_bytes / 2**30:.3f}, alias "
              f"{m.alias_size_in_bytes / 2**30:.3f}", flush=True)

    t0 = time.perf_counter()
    lo, hi = bm.seed_words(0)
    words = [jax.ShapeDtypeStruct((), np.uint32, sharding=one)] * 2
    report("weights", bm.weights_fn(shapes, a_scale, adapter.WEIGHT_RULES)
           .lower(*words).compile(), t0)

    probe = eng.build(placed, cfg, plan, s, 2)
    nbr = s.max_blocks_per_req
    probes = [eng.Program("decode", nbr),
              eng.Program("mixed", nbr, s.max_batch, nbr, True)]
    t0 = time.perf_counter()
    kv, fits = eng.pool_blocks(probe, probes, int(args.bytes_limit),
                               sharding=one)
    print(f"pool: {kv} blocks ({(kv - 1) * s.block_size} tokens) under "
          f"{args.bytes_limit / 2**30:.2f} GiB, probes in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    ref = cell.reference()
    rw = adapter.reference_weights(placed, conf)
    seq_len = -(-s.max_blocks_per_req * s.block_size // 256) * 256
    n_read = max(r["max_new"] for r in plain)
    t0 = time.perf_counter()
    fn = ref.jitted(conf, 8)
    report(f"reference forward ({seq_len} tokens)", fn.lower(
        rw, jax.ShapeDtypeStruct((seq_len,), np.int32, sharding=one),
        jax.ShapeDtypeStruct((n_read,), np.int32, sharding=one)).compile(),
        t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
