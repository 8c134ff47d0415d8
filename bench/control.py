#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program on many seeds and
the int4 control on the same served sequences, in one process.

For each seed the weights are made anew, the cell's mix for that seed is
served through a short window at the cell's own load, and a sample of
what it served is compared with the reference (the program's reading)
and with the reference at int4 weights put in the program's place (the
control's reading).  One JSON line per seed.  The benchmark's own runs
do not run this; its readings set ``limits/<cell>.json``.

    python3 bench/control.py --workload qwen3-8b.chat-poisson \\
        --seeds 101,102,103 --seconds 20
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    run.paths()
    from benchlib import model as bm, spec
    cell = spec.load_cell(args.workload)
    run.start_jax()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("FAIL: no TPU")
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    lim = run.limits(cell.bench_dir, cell.name)
    plain, reqs, records = run.requests_of(cell, seeds[0])
    st = run.set_up(cell, seeds[0])
    shapes = jax.eval_shape(lambda: st.params)
    a_scale = float(cell.config["engine"]["a_scale"])
    make = bm.weights_fn(shapes, a_scale, cell.adapter.WEIGHT_RULES)
    for i, seed in enumerate(seeds):
        if i:
            plain, reqs, records = run.requests_of(cell, seed)
            # the engine and its inner decode engine both hold the weights
            st.params = st.engine.params = st.engine.engine.params = None
            gc.collect()
            st.params = jax.block_until_ready(make(*bm.seed_words(seed)))
            st.engine.params = st.engine.engine.params = st.params
            st.seed = seed
        prompts = {r["rid"]: r["prompt"] for r in plain}
        run.prepare(st, reqs, args.seconds)
        w, marks = run.serve(st, reqs, records, args.seconds)
        got = run.check_window(st, w, prompts, control=True)
        ctl = {**got, **got.pop("control")}
        correct, _ = run.verdict(got, lim)
        ctl_correct, _ = run.verdict(ctl, lim)
        print(json.dumps({"seed": seed, "compiles": marks["compiles"],
                          "correct": correct, **got,
                          "control_correct": ctl_correct,
                          "control_widest_gap": ctl["widest_gap"],
                          "control_mean_gap": ctl["mean_gap"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
