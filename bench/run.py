#!/usr/bin/env python3
"""The serving benchmark: one cell of ``BENCHMARK.json`` on one chip.

    python3 bench/run.py --workload qwen3-8b.chat-poisson --seed 7 \\
        --seconds 45 --trace 0

Set-up makes the weights on the device from the seed, sizes the int8 KV
pool from the compiled segment programs' memory analysis, warms every
segment program the cell's traffic can dispatch, and serves the traffic
up to the window's opening.  The window then runs for ``--seconds``; the
stream is abandoned at its end, the engine freed, and a sample of what
the window served is compared with the configuration's plain reference.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window's last seconds), ``device`` and, last, ``checks`` (each
number compared with its limit).  With no TPU, or fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import pathlib           # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench_out" / "trace"
TRACE_SECONDS = 5.0        # traced stretch at the end of a --trace 1 window
CHECK_TOKENS = 600         # served tokens the correctness sample aims for
CHECK_REQUESTS = 6


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def paths() -> None:
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def start_jax() -> None:
    """The compile cache in the checkout, at a fixed path; every program
    cached, however quick its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    paths()
    from repro.launch import runtime
    runtime.enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def limits(bench_dir: pathlib.Path, cell: str) -> dict:
    path = bench_dir / "limits" / f"{cell}.json"
    return json.loads(path.read_text()) if path.is_file() else {}


@dataclasses.dataclass
class Setup:
    """A cell set up for serving: weights, sized pool, warm engine."""
    cell: object
    seed: int
    cfg: object
    settings: object
    params: dict
    engine: object
    kv_blocks: int
    meter: object
    device: object
    peaks: dict


def requests_of(cell, seed: int, mix: dict | None = None):
    """(plain request dicts, the program's Requests, harness records)."""
    from benchlib import window as win
    from repro.serve import Request
    plain = cell.generator().generate(mix or cell.traffic, seed,
                                      int(cell.config["vocab_size"]))
    reqs = [Request(rid=r["rid"], prompt=r["prompt"], max_new=r["max_new"],
                    arrival_step=r["arrival_step"]) for r in plain]
    records = {r["rid"]: win.Req(r["rid"], r["arrival_step"],
                                 len(r["prompt"]), r["max_new"])
               for r in plain}
    return plain, reqs, records


def set_up(cell, seed: int, *, kv_blocks: int | None = None,
           trace: bool = False) -> Setup:
    """Weights from the seed and an engine over the sized pool.
    `kv_blocks` pins the pool instead of sizing it."""
    import jax

    from benchlib import engine as eng, model as bm, spec
    from repro.launch import runtime

    conf, adapter = cell.config, cell.adapter
    dev = jax.devices()[0]
    peaks = spec.peaks(dev.device_kind, cell.bench_dir) \
        if dev.platform == "tpu" else {}
    meter = runtime.CompileMeter()
    cfg = adapter.program_config(conf)
    plan = bm.deployment_plan(conf)
    a_scale = float(conf["engine"]["a_scale"])
    s = eng.settings(conf, cell.generator().max_tokens(cell.traffic),
                     adapter.kv_layout(cfg))

    t0 = time.perf_counter()
    shapes = bm.frozen_shapes(cfg, plan, a_scale)
    params = jax.block_until_ready(
        bm.make_weights(shapes, seed, a_scale, adapter.WEIGHT_RULES))
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"weights: {n_bytes / 2**30:.3f} GiB in "
        f"{time.perf_counter() - t0:.1f}s")

    if kv_blocks is None:
        t0 = time.perf_counter()
        probe = eng.build(params, cfg, plan, s, 2)
        nbr = s.max_blocks_per_req
        probes = [eng.Program("decode", nbr),
                  eng.Program("mixed", nbr, s.max_batch, nbr, True)]
        kv_blocks, _ = eng.pool_blocks(
            probe, probes, dev.memory_stats()["bytes_limit"], log)
        del probe
        log(f"pool sized in {time.perf_counter() - t0:.1f}s")
    need = 1 + s.max_blocks_per_req
    if kv_blocks < need:
        raise RuntimeError(f"int8 KV pool: {kv_blocks} blocks fit, a "
                           f"longest request needs {need}")
    engine = eng.build(params, cfg, plan, s, kv_blocks, annotate=trace)
    return Setup(cell, seed, cfg, s, params, engine, kv_blocks, meter, dev,
                 peaks)


def restarts(conf: dict) -> bool:
    """Whether a preempted request starts over: the engine recomputes an
    int8 pool's victims from their prompt."""
    eng = conf["engine"]
    return eng["kv_cache_dtype"] == "int8" and eng["preemption"] == "recompute"


def mean_lifetime(s, reqs) -> float:
    """Steps after the first arrival at which the window opens: one mean
    request lifetime under the engine settings `s`."""
    from benchlib import window as win
    return win.mean_lifetime_steps(
        [{"max_new": r.max_new, "prompt": r.prompt} for r in reqs],
        s.prefill_chunk, s.segment_len)


def prepare(st: Setup, reqs, seconds: float, *,
            steps_per_s: float | None = None) -> None:
    """Warm every segment program that serving `reqs` dispatches up to
    the window's end.  The end is bounded in steps by `steps_per_s`, by
    default the most decode steps a second the chip's HBM bandwidth
    allows when each step reads every weight once."""
    import math

    import jax

    from benchlib import engine as eng
    if steps_per_s is None:
        w_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(st.params))
        steps_per_s = st.peaks["hbm_bytes_per_s"] / w_bytes
    t0 = time.perf_counter()
    progs, shadow = eng.shadow_programs(
        st.cell.adapter.stand_in_config(st.cfg), st.engine.plan,
        st.settings, st.kv_blocks, reqs,
        open_after_steps=mean_lifetime(st.settings, reqs),
        window_steps=math.ceil(seconds * steps_per_s))
    t1 = time.perf_counter()
    c0 = st.meter.snapshot()
    eng.warm(st.engine, [p for p, _ in progs], log)
    c1 = st.meter.snapshot()
    log(f"pool {st.kv_blocks} blocks x {st.settings.block_size} tokens; "
        f"chunk {st.settings.prefill_chunk}; {len(progs)} segment programs "
        f"found in {t1 - t0:.1f}s up to {steps_per_s:.1f} steps/s "
        f"({sum(f <= shadow.step_open for _, f in progs)} first used "
        f"before the window opens at step {shadow.step_open}), warmed in "
        f"{time.perf_counter() - t1:.1f}s: {c1[0] - c0[0]} compiled or "
        f"loaded in {c1[1] - c0[1]:.1f}s ({c1[2] - c0[2]} cache hits, "
        f"{c1[3] - c0[3]} misses)")


def serve(st: Setup, reqs, records, seconds: float, *, trace: bool = False,
          trace_dir: pathlib.Path = TRACE_DIR):
    """Serve `reqs` through the window; returns (window, marks) where
    marks holds the compile counts at the window's edges and, traced,
    the harness time the trace began."""
    from benchlib import tracing, window as win
    cap = tracing.Capture(str(trace_dir)) if trace else None
    if cap is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    marks = {}

    def on_open(w):
        marks["c_open"] = st.meter.snapshot()

    def start_trace(w):
        cap.start()

    try:
        w = win.drive(st.engine, reqs, records,
                      open_after_steps=mean_lifetime(st.settings, reqs),
                      restart_on_preempt=restarts(st.cell.config),
                      seconds=seconds, on_open=on_open,
                      before_close=(min(TRACE_SECONDS, seconds), start_trace)
                      if trace else None, annotate=trace)
        marks["c_close"] = st.meter.snapshot()
    finally:
        if cap is not None:
            cap.stop()
    marks["compiles"] = marks["c_close"][0] - marks["c_open"][0]
    return w, marks


def attempted(w) -> list:
    """Requests the window worked on: in flight at its opening, or
    eligible during it."""
    return [r for r in w.reqs.values()
            if r.t_eligible is not None and r.t_eligible < w.t_close
            and (r.t_finish is None or r.t_finish >= w.t_open)
            and (r.admit_step is not None or r.t_eligible >= w.t_open)]


def check_window(st: Setup, w, prompts: dict, control: bool = False
                 ) -> dict:
    """Readings of a sample of what the window served (see
    ``benchlib/check.py``); with `control`, also the int4 control's
    readings on the same sequences, under ``"control"``."""
    import numpy as np

    from benchlib import check
    t0 = time.perf_counter()
    sample = check.pick(w, st.seed, CHECK_TOKENS, CHECK_REQUESTS)
    s = st.settings
    seq_len = -(-s.max_blocks_per_req * s.block_size // 256) * 256
    n_read = max(r.max_new for r in w.reqs.values())
    items = [(prompts[r.rid], np.asarray(r.tokens, np.int32))
             for r in sample]
    got = check.readings(st.cell.reference().reference_logits,
                         st.cell.adapter.reference_weights(
                             st.params, st.cell.config),
                         st.cell.config, items, seq_len, n_read,
                         control=control) if items else {}
    got["requests"] = len(sample)
    got["complete"] = sum(len(r.tokens) == r.max_new for r in sample)
    log(f"check: {len(sample)} requests against the reference in "
        f"{time.perf_counter() - t0:.1f}s: " + ", ".join(
            f"{k} {v:.4g}" for k, v in got.items() if k != "control")
        + "".join(f", control {k} {v:.4g}"
                  for k, v in got.get("control", {}).items()))
    return got


def verdict(got: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, checks): each number of `got` that `lim` names beside
    its limit, and whether the sample is whole and every one is within."""
    checks = {name: {"value": got.get(name, float("nan")),
                     "limit": v["limit"]} for name, v in lim.items()}
    checks["sampled_complete"] = {"value": got["complete"],
                                  "limit": got["requests"]}
    correct = bool(lim) and got["requests"] > 0 \
        and got["complete"] == got["requests"] and all(
            c["value"] <= c["limit"] for n, c in checks.items()
            if n != "sampled_complete")
    return correct, checks


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             control: bool = False, kv_blocks: int | None = None,
             t_process: float = T_PROCESS,
             trace_dir: pathlib.Path = TRACE_DIR,
             steps_per_s: float | None = None) -> dict:
    """Set up, serve and check one cell; returns the result object.
    With `control` the int4 control's tokens take the served tokens'
    place in the comparison, and the run must come out not correct.
    `kv_blocks` pins the pool instead of sizing it and `steps_per_s`
    bounds the steps to warm for (small test runs)."""
    import jax

    from benchlib import readers, tracing
    plain, reqs, records = requests_of(cell, seed)
    prompts = {r["rid"]: r["prompt"] for r in plain}
    del plain
    st = set_up(cell, seed, kv_blocks=kv_blocks, trace=trace)
    prepare(st, reqs, seconds, steps_per_s=steps_per_s)
    w, marks = serve(st, reqs, records, seconds, trace=trace,
                     trace_dir=trace_dir)
    setup_s = w.t_open - t_process
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = max(m.get("peak_bytes_in_use", 0) for m in stats)
    log(f"window {seconds}s: steps {w.step_open}->{w.step_close}, "
        f"{w.tokens_in_window} tokens, {len(w.eligible_in_window())} "
        f"requests eligible, {len(w.finished_in_window())} finished, "
        f"{w.preempts_in_window} preemptions, {marks['compiles']} compiles; "
        f"set-up {setup_s:.2f}s; peak HBM {peak / 2**30:.3f} GiB")
    st.engine = None
    gc.collect()

    got = check_window(st, w, prompts, control)
    if control:
        got = {**got, **got.pop("control")}
    correct, checks = verdict(got, limits(cell.bench_dir, cell.name))

    att = attempted(w)
    failed = [r for r in att if r.status not in (None, "ok")]
    dev = st.device
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(att),
              "failed": len(failed)}
    if trace:
        tr = tracing.extract(str(trace_dir))
        span = tracing.window(tr)
        ctx = readers.Context(
            window=w, conf=cell.config, adapter=cell.adapter,
            settings=st.settings, kv_blocks=st.kv_blocks, peaks=st.peaks,
            compiles_in_window=marks["compiles"], trace=tr, span=span)
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tracing.busy_seconds(tr, span)
        device["window_s"] = (span[1] - span[0]) / 1e9
        result["breakdown"] = {"device_ops": tracing.top_ops(tr, span),
                               "idle_gaps": tracing.label_gaps(tr, span)}
        log(f"trace: busy {device['busy_s']:.3f}s of "
            f"{device['window_s']:.3f}s; {len(tracing.ops(tr))} ops")
    else:
        metrics = end_to_end(cell, w, setup_s)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"compared: {name} {c['value']} limit {c['limit']}")
    return result


def end_to_end(cell, w, setup_s: float) -> dict:
    """The cell's end-to-end metrics from the harness's own clock."""
    import numpy as np
    vals = {"setup_s": setup_s}
    ttft = [(min(r.t_first, w.t_close) if r.t_first is not None
             else w.t_close) - r.t_eligible for r in w.eligible_in_window()]
    if ttft:
        vals["ttft_p90_s"] = float(np.percentile(ttft, 90))
    tpot = [(r.t_last - r.t_first) / (r.n_tokens - 1)
            for r in w.finished_in_window() if r.n_tokens > 1]
    if tpot:
        vals["tpot_p90_s"] = float(np.percentile(tpot, 90))
    log(f"end to end: {len(ttft)} requests eligible, {len(tpot)} finished "
        f"in the window; " + ", ".join(f"{k} {v:.5g}"
                                       for k, v in vals.items()))
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in vals}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the int4 control's tokens in the served "
                    "tokens' place (the run must read not correct)")
    args = ap.parse_args(argv)

    paths()
    from benchlib import spec
    cell = spec.load_cell(args.workload)
    start_jax()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"FAIL: the cell needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
