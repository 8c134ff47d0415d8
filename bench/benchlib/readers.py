"""What the per-layer metric readers share.

A reader is ``read(ctx) -> float | None`` in ``metrics/<name>.py``; it
returns None where it finds nothing to read, and the harness then leaves
the metric out of the result line.  ``ctx`` is a :class:`Context`.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from benchlib import costs, tracing


@dataclasses.dataclass
class Context:
    window: object                  # window.Window
    conf: dict                      # the configuration file
    adapter: object                 # its adapters/<name>.py
    settings: object                # engine.Settings
    kv_blocks: int
    peaks: dict
    compiles_in_window: int
    trace: dict | None = None       # tracing.extract(...)
    span: tuple | None = None       # traced window, trace clock (ns)

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_idle(ctx: Context):
    """Per cent of the traced window in which no operation ran."""
    if ctx.trace is None or ctx.span is None:
        return None
    win = (ctx.span[1] - ctx.span[0]) / 1e9
    return 100.0 * (1.0 - tracing.busy_seconds(ctx.trace, ctx.span) / win)


def segment_ms(ctx: Context):
    """Mean device milliseconds per segment program in the traced window."""
    if ctx.trace is None or ctx.span is None:
        return None
    per = tracing.segment_programs(ctx.trace, ctx.span)
    times = [t for v in per.values() for t in v]
    if not times:
        return None
    ctx.log("segment programs traced: " + ", ".join(
        f"{k} {len(v)} x {1e3 * np.mean(v):.2f} ms"
        for k, v in sorted(per.items())))
    return 1e3 * float(np.mean(times))


def _token_positions(w):
    """(emitted tokens in the window, keys they attended in all, prompts
    whose first token came in the window and their tokens, keys)."""
    toks = keys = 0
    for t, rid, n, ctx0 in w.token_log:
        if w.t_open <= t < w.t_close:
            toks += n
            keys += n * ctx0 + n * (n + 1) // 2
    prompts = [r.prompt_len for r in w.reqs.values()
               if r.t_first is not None and w.t_open <= r.t_first < w.t_close]
    p_keys = sum(p * (p + 1) // 2 for p in prompts)
    return toks, keys, prompts, p_keys


def mfu(ctx: Context):
    """Model operations of the window's prompt and output tokens over the
    window's seconds times the int8 peak, per cent."""
    w = ctx.window
    toks, keys, prompts, p_keys = _token_positions(w)
    if toks == 0 or not ctx.peaks:
        return None
    ops = ctx.adapter.model_ops(ctx.conf, toks + sum(prompts),
                                toks + len(prompts), keys + p_keys)
    return 100.0 * ops / (w.seconds * ctx.peaks["int8_ops_per_s"])


def w8a8_roofline(ctx: Context):
    """Least time of every ``cim_w8a8_matmul`` call in the traced window
    (from each call's operand shapes) over the kernel's device time."""
    if ctx.trace is None or ctx.span is None or not ctx.peaks:
        return None
    evs = tracing.kernel_events(ctx.trace, ctx.span, "cim_w8a8_matmul")
    least = spent = 0.0
    for e in evs:
        oc = costs.w8a8_call(e["name"])
        if oc is None:
            return None
        least += costs.least_seconds(oc[0], oc[1],
                                     ctx.peaks["int8_ops_per_s"],
                                     ctx.peaks["hbm_bytes_per_s"])
        spent += e["dur"] / 1e9
    return 100.0 * least / spent if spent > 0 else None


def queue_delay_p90(ctx: Context):
    """p90 over requests eligible in the window of the steps from arrival
    to admission (those not yet admitted: the steps waited so far)."""
    w = ctx.window
    d = [(r.admit_step if r.admit_step is not None else w.step_close)
         - r.arrival_step for r in w.eligible_in_window()]
    return float(np.percentile(d, 90)) if d else None


def compiles_in_window(ctx: Context):
    return float(ctx.compiles_in_window)
