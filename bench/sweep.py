#!/usr/bin/env python3
"""Find a step-clock cell's knee: serve its mix at several arrival rates.

For each rate (requests per decode step) the cell's mix is regenerated at
that rate and served through a window of ``--seconds``; one JSON line per
rate gives the requests waiting for admission at the window's opening and
at its end, the arrivals and admissions in it, and its tails and tokens.
The knee is the highest rate whose queue does not grow over the window.
Set-up (weights, pool, warm-up) is paid once for all rates.

    python3 bench/sweep.py --workload qwen3-8b.chat-poisson \\
        --rates 0.06,0.08,0.1,0.12 --seconds 20

With ``--host-blocks`` (the pool a chip run sized) the sweep runs on the
host instead, over ``--steps`` steps of the step clock and each of
``--seeds``: the engine's own scheduler over the benchmark's stand-in of
the segment programs (``benchlib/engine.py``), which admits and
schedules as the chip run does, step for step.  It reads the queue, not
times, and windows many request lifetimes long cost no chip time.

    JAX_PLATFORMS=cpu python3 bench/sweep.py \\
        --workload qwen3-8b.chat-poisson --rates 0.1,0.11 \\
        --host-blocks 1498 --steps 3000 --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

import run


def queue(records: dict, step_open: int, step_close: int) -> dict:
    """The admission queue over the window between two steps."""
    import numpy as np

    def waiting(step):
        return sum(1 for r in records.values() if r.arrival_step <= step
                   and (r.admit_step is None or r.admit_step > step))

    arrived = [r for r in records.values()
               if step_open < r.arrival_step <= step_close]
    delay = [(r.admit_step if r.admit_step is not None else step_close)
             - r.arrival_step for r in arrived]
    return {"steps": step_close - step_open,
            "waiting_at_open": waiting(step_open),
            "waiting_at_close": waiting(step_close),
            "arrived": len(arrived),
            "admitted": sum(1 for r in records.values()
                            if r.admit_step is not None
                            and step_open < r.admit_step <= step_close),
            "queue_delay_p90_steps":
                float(np.percentile(delay, 90)) if delay else 0.0}


def host_sweep(cell, rates, seeds, kv_blocks: int, steps: int) -> None:
    from benchlib import engine as eng, model as bm
    cfg = cell.adapter.program_config(cell.config)
    plan = bm.deployment_plan(cell.config)
    s = eng.settings(cell.config, cell.generator().max_tokens(cell.traffic),
                     cell.adapter.kv_layout(cfg))
    stand_in = cell.adapter.stand_in_config(cfg)
    for rate in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_step"] = rate
        for seed in seeds:
            _, reqs, _ = run.requests_of(cell, seed, mix)
            life = run.mean_lifetime(s, reqs)
            _, w = eng.shadow_programs(stand_in, plan, s, kv_blocks, reqs,
                                       open_after_steps=life,
                                       window_steps=steps)
            print(json.dumps({"rate_per_step": rate, "seed": seed,
                              **queue(w.reqs, w.step_open, w.step_close)}),
                  flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--host-blocks", type=int,
                    help="sweep on the host with a pool of this many blocks")
    ap.add_argument("--steps", type=int, default=3000,
                    help="host sweep: window length in steps")
    ap.add_argument("--seeds", default="1",
                    help="host sweep: seeds, comma-separated")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    run.paths()
    from benchlib import spec
    cell = spec.load_cell(args.workload)
    if args.host_blocks:
        host_sweep(cell, rates, [int(x) for x in args.seeds.split(",")],
                   args.host_blocks, args.steps)
        return 0
    run.start_jax()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("FAIL: no TPU")
        return 2
    st = run.set_up(cell, args.seed)
    for rate in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_step"] = rate
        _, reqs, records = run.requests_of(cell, args.seed, mix)
        run.prepare(st, reqs, args.seconds)
        w, marks = run.serve(st, reqs, records, args.seconds)
        e2e = run.end_to_end(cell, w, float("nan"))
        print(json.dumps({
            "rate_per_step": rate, "window_s": args.seconds,
            **queue(records, w.step_open, w.step_close),
            "steps_per_s": (w.step_close - w.step_open) / w.seconds,
            "output_tok_s": w.tokens_in_window / w.seconds,
            "compiles": marks["compiles"],
            **{k: v["value"] for k, v in e2e.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
