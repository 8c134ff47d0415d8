#!/usr/bin/env python3
"""What the engine's spans cost the host: milliseconds of host loop per
scheduler round, with each span sink on and off, in one process.

The cell's mix for the seed is served once.  From the window's opening
(by the harness's rule) every round is timed from the return of its
harvest to the return of the next segment's dispatch: the turnaround in
which the device waits for the host.  The consumer of the event stream
does nothing, so the whole turnaround is the engine's.  Rounds take
these settings in turn, so that neighbouring rounds, which serve nearly
the same rows, are compared:

* ``none``: the Chrome tracer and the profiler annotations off;
* ``chrome``: the tracer on, as the benchmark's untraced runs serve;
* ``chrome+profiler``: both sinks on, no profile being captured;

then, with a profile being captured, as in a ``--trace 1`` run:

* ``captured_chrome`` and ``captured_chrome+profiler``.

The last line of standard output is one JSON object: per setting the
rounds timed and their median, mean and quartiles in milliseconds.

    python3 bench/span_cost.py --workload qwen3-8b.chat-poisson \\
        --seed 3221226013 --rounds 24 --kv-blocks 1498
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import run

FREE = (("none", False, False), ("chrome", True, False),
        ("chrome+profiler", True, True))
CAPTURED = (("captured_chrome", True, False),
            ("captured_chrome+profiler", True, True))


def measure(cell, seed: int, rounds: int, *, kv_blocks: int | None = None,
            seconds: float = 51.0, steps_per_s: float | None = None,
            trace_dir=run.TRACE_DIR) -> dict:
    """Serve `cell` and time `rounds` turnarounds of each setting."""
    import shutil

    import jax

    plain, reqs, _ = run.requests_of(cell, seed)
    del plain
    st = run.set_up(cell, seed, kv_blocks=kv_blocks, trace=True)
    run.prepare(st, reqs, seconds, steps_per_s=steps_per_s)
    engine, tel = st.engine, st.engine.telemetry
    opens = min(r.arrival_step for r in reqs) \
        + run.mean_lifetime(st.settings, reqs)
    plan = [FREE[i % len(FREE)] for i in range(rounds * len(FREE))] \
        + [CAPTURED[i % len(CAPTURED)] for i in range(rounds * len(CAPTURED))]
    times: dict[str, list[float]] = {name: [] for name, *_ in FREE + CAPTURED}
    shutil.rmtree(trace_dir, ignore_errors=True)
    state = {"i": 0, "t": None, "setting": None, "capturing": False}

    def on_harvest():
        t = time.perf_counter()
        state["t"] = state["setting"] = None
        run_state = engine._run_state
        if run_state is None or run_state.now < opens \
                or state["i"] >= len(plan):
            return
        setting = plan[state["i"]]
        state["i"] += 1
        if setting in CAPTURED and not state["capturing"]:
            jax.profiler.start_trace(str(trace_dir))
            state["capturing"] = True
            t = time.perf_counter()
        tel.set_enabled(setting[1])
        tel.profiler_annotations = setting[2]
        state["setting"], state["t"] = setting[0], t

    def on_dispatch():
        if state["t"] is not None:
            times[state["setting"]].append(
                1e3 * (time.perf_counter() - state["t"]))
            state["t"] = None

    device_get, dispatch = jax.device_get, engine._dispatch

    def timed_get(x):
        out = device_get(x)
        on_harvest()
        return out

    def timed_dispatch(fn, *args, name="dispatch"):
        out = dispatch(fn, *args, name=name)
        if name.endswith("_segment"):
            on_dispatch()
        return out

    jax.device_get, engine._dispatch = timed_get, timed_dispatch
    gen = engine.run_stream(reqs)
    try:
        for _ in gen:
            if state["i"] >= len(plan) and state["t"] is None:
                break
    finally:
        gen.close()
        jax.device_get = device_get
        del engine._dispatch
        if state["capturing"]:
            jax.profiler.stop_trace()
    out = {}
    for name, v in times.items():
        if not v:
            continue
        q = statistics.quantiles(v, n=4) if len(v) > 1 else [v[0]] * 3
        out[name] = {"rounds": len(v), "median_ms": statistics.median(v),
                     "mean_ms": statistics.fmean(v), "q1_ms": q[0],
                     "q3_ms": q[2]}
        run.log(f"{name}: {len(v)} rounds, median {out[name]['median_ms']:.3f}"
                f" ms, mean {out[name]['mean_ms']:.3f} ms")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=24,
                    help="turnarounds timed per setting")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="pin the pool instead of sizing it")
    args = ap.parse_args(argv)
    run.paths()
    from benchlib import spec
    cell = spec.load_cell(args.workload)
    run.start_jax()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.log("FAIL: no TPU")
        return 2
    res = measure(cell, args.seed, args.rounds, kv_blocks=args.kv_blocks)
    print(json.dumps({"device": dev.device_kind, "seed": args.seed,
                      "settings": res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
