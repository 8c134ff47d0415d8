"""The engine's round-phase spans in a profiler trace, reduced to the host
loop's own time.

The engine marks each scheduler round with host spans: ``serve/schedule``
(admission, growth, preemption), ``serve/inputs`` (the segment's
operands), the dispatch (``serve/decode_segment`` or
``serve/mixed_segment``), ``serve/harvest`` (the join on the device) and
``serve/emit`` (token events, retirement).  It closes them across every
event it hands the harness, which marks its own work ``bench/client``.

A *turnaround* runs from the end of one segment's ``serve/harvest`` to the
end of the next segment's dispatch: the stretch in which the device waits
for the host.  Each reading is a median over the turnarounds that lie
wholly in the traced window, in milliseconds, so that one long round (a
defragmentation, a slow enqueue) among the few a short window holds does
not decide it; where the trace holds none (a program without these spans)
it is None.
"""
from __future__ import annotations

import statistics
from typing import Callable

SCHEDULE = "serve/schedule"
INPUTS = "serve/inputs"
HARVEST = "serve/harvest"
EMIT = "serve/emit"
CLIENT = "bench/client"
PHASES = (SCHEDULE, INPUTS, HARVEST, EMIT)


def is_dispatch(name: str) -> bool:
    return name.startswith("serve/") and name.endswith("_segment")


def _iv(tr: dict, span, keep) -> list[tuple[float, float]]:
    """Merged intervals of the host spans whose name `keep` accepts,
    clipped to `span`."""
    lo, hi = span
    iv = sorted((max(h["t0"], lo), min(h["t0"] + h["dur"], hi))
                for h in tr["host"] if keep(h["name"]))
    out: list[list[float]] = []
    for a, b in iv:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _intersect(xs, ys) -> list[tuple[float, float]]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _within(iv, a: float, b: float) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in iv)


def turnarounds(tr: dict, span) -> list[tuple[float, float]]:
    """``(end of a harvest, end of the next dispatch)`` for every
    turnaround whose harvest and dispatch both lie in `span`."""
    lo, hi = span

    def inside(h):
        return lo <= h["t0"] and h["t0"] + h["dur"] <= hi

    ends = sorted(h["t0"] + h["dur"] for h in tr["host"]
                  if h["name"] == HARVEST and inside(h))
    disp = sorted((h["t0"], h["t0"] + h["dur"]) for h in tr["host"]
                  if is_dispatch(h["name"]) and inside(h))
    out, j = [], 0
    for e in ends:
        while j < len(disp) and disp[j][0] < e:
            j += 1
        if j == len(disp):
            break
        out.append((e, disp[j][1]))
    return out


def _median_ms(ctx, part):
    if ctx.trace is None or ctx.span is None:
        return None
    tas = turnarounds(ctx.trace, ctx.span)
    if not tas:
        return None
    return statistics.median(part(a, b) for a, b in tas) / 1e6


def _own(ctx, name: str, minus) -> Callable[[float, float], float]:
    """Time of span `name` in a turnaround, less the spans `minus`
    accepts that lie inside it."""
    tr, span = ctx.trace, ctx.span
    own = _iv(tr, span, lambda n: n == name)
    cut = _intersect(own, _iv(tr, span, minus))
    return lambda a, b: _within(own, a, b) - _within(cut, a, b)


def engine_host_ms(ctx):
    """A turnaround's length less the harness's ``bench/client`` time
    inside it: the engine loop's host time between two segments."""
    if ctx.trace is None or ctx.span is None:
        return None
    client = _iv(ctx.trace, ctx.span, lambda n: n == CLIENT)
    return _median_ms(ctx, lambda a, b: (b - a) - _within(client, a, b))


def scheduler_ms(ctx):
    """``serve/schedule`` time in a turnaround, less the spans nested in
    it (the dispatches and pool copies it issues, and any harness time)."""
    if ctx.trace is None or ctx.span is None:
        return None
    return _median_ms(ctx, _own(ctx, SCHEDULE, lambda n: n == CLIENT or (
        n.startswith("serve/") and n not in PHASES)))


def emit_ms(ctx):
    """``serve/emit`` time in a turnaround, less any harness time inside
    it."""
    if ctx.trace is None or ctx.span is None:
        return None
    return _median_ms(ctx, _own(ctx, EMIT, lambda n: n == CLIENT))
