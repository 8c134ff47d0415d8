"""The profiler trace of a window, and its reduction to numbers.

:class:`Capture` starts JAX's profiler and marks the traced part of the
window with a host span named ``bench/traced_window``; :func:`extract`
reads the ``.xplane.pb`` it wrote into a small dict of events, kept only
for the device planes and the host spans this reduction reads.  The
reduction works on that dict alone, so it can be checked on a recorded
trace without a chip:

* busy time: the union of the intervals in which an operation ran on a
  device (line ``XLA Ops``), clipped to the traced window;
* time of a kernel: the summed durations of the operations whose HLO
  instruction name is the kernel's (the trace names each TPU op by its
  whole HLO text, ``%cim_w8a8_matmul.3 = f32[32,4096] custom-call(...)``);
* idle gaps: the stretches of the window with no operation, each labelled
  with the innermost host span that covers its middle (the engine's
  ``serve/<dispatch>`` spans, the harness's ``bench/engine`` around the
  engine's host work and ``bench/client`` around its own), and
  ``host (no span)`` where none does.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW_SPAN = "bench/traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("serve/", "bench/")


class Capture:
    """Profiler on, with the traced window marked by a host span."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self._ann = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.out_dir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()

    def stop(self):
        import jax
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            jax.profiler.stop_trace()


def extract(out_dir: str) -> dict:
    """The events of the newest trace under `out_dir`:
    ``{"device": [{"plane", "line", "name", "t0", "dur"}],
    "host": [{"name", "t0", "dur"}]}``, times in nanoseconds.  A TPU op's
    name is its whole HLO instruction text."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    data = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if is_dev:
                    device.append({
                        "plane": plane.name, "line": line.name,
                        "name": ev.name, "t0": float(ev.start_ns),
                        "dur": float(ev.duration_ns)})
                elif ev.name.startswith(HOST_PREFIXES):
                    host.append({"name": ev.name, "t0": float(ev.start_ns),
                                 "dur": float(ev.duration_ns)})
    return {"device": device, "host": host}


def window(tr: dict) -> tuple[float, float] | None:
    spans = [h for h in tr["host"] if h["name"] == WINDOW_SPAN]
    if not spans:
        return None
    s = max(spans, key=lambda h: h["dur"])
    return s["t0"], s["t0"] + s["dur"]


def ops(tr: dict, line: str = OPS_LINE) -> list[dict]:
    return [e for e in tr["device"] if e["line"] == line]


def _clip(evs, lo, hi):
    for e in evs:
        a, b = max(e["t0"], lo), min(e["t0"] + e["dur"], hi)
        if b > a:
            yield a, b, e


def busy_intervals(tr: dict, span) -> dict[str, list[tuple[float, float]]]:
    """Per device plane: the union of its op intervals within `span`."""
    lo, hi = span
    per = collections.defaultdict(list)
    for a, b, e in _clip(ops(tr), lo, hi):
        per[e["plane"]].append((a, b))
    out = {}
    for plane, iv in per.items():
        iv.sort()
        merged = [list(iv[0])]
        for a, b in iv[1:]:
            if a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        out[plane] = [tuple(m) for m in merged]
    return out


def busy_seconds(tr: dict, span) -> float:
    """Busy time averaged over the devices that ran anything."""
    per = busy_intervals(tr, span)
    if not per:
        return 0.0
    return sum(sum(b - a for a, b in iv) for iv in per.values()) \
        / len(per) / 1e9


# Ops that only enclose others: their time is their children's.
CONTROL_OPS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """An op's HLO instruction name without its numeric suffix: the
    trace names TPU ops by their whole HLO text (``%fusion.3 = ...``)."""
    m = re.match(r"%?([\w.\-]+)", name)
    return re.sub(r"(\.\d+)+$", "", m.group(1) if m else name)


def kernel_events(tr: dict, span, kernel: str) -> list[dict]:
    """Ops of `kernel` (by instruction name) in `span`."""
    lo, hi = span
    return [e for a, b, e in _clip(ops(tr), lo, hi)
            if op_name(e["name"]) == kernel]


def seconds_of(evs) -> float:
    return sum(e["dur"] for e in evs) / 1e9


def top_ops(tr: dict, span, n: int = 10) -> list[list]:
    """The `n` op names (:func:`op_name`) that took the most device time,
    as ``[name, seconds]``; enclosing control flow left out."""
    lo, hi = span
    tot = collections.Counter()
    for a, b, e in _clip(ops(tr), lo, hi):
        name = op_name(e["name"])
        if name not in CONTROL_OPS:
            tot[name] += (b - a) / 1e9
    return [[k, v] for k, v in tot.most_common(n)]


def idle_gaps(tr: dict, span) -> list[tuple[float, float]]:
    """Stretches of `span` in which the first busy device ran nothing."""
    lo, hi = span
    per = busy_intervals(tr, span)
    if not per:
        return [(lo, hi)]
    iv = per[sorted(per)[0]]
    gaps, t = [], lo
    for a, b in iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_gaps(tr: dict, span, n: int = 10) -> list[list]:
    """Idle seconds by the innermost host span covering each gap's middle,
    the `n` largest as ``[label, seconds]``."""
    host = [h for h in tr["host"] if h["name"] != WINDOW_SPAN]
    tot = collections.Counter()
    for a, b in idle_gaps(tr, span):
        mid = (a + b) / 2
        cover = [h for h in host if h["t0"] <= mid < h["t0"] + h["dur"]]
        label = min(cover, key=lambda h: h["dur"])["name"] if cover \
            else "host (no span)"
        tot[label] += (b - a) / 1e9
    return [[k, v] for k, v in tot.most_common(n)]


def module_events(tr: dict, span) -> list[dict]:
    """Program executions (line ``XLA Modules``) starting in `span`."""
    lo, hi = span
    return sorted((e for e in tr["device"] if e["line"] == MODULES_LINE
                   and lo <= e["t0"] < hi), key=lambda e: e["t0"])


def segment_programs(tr: dict, span, prefix: str = "serve/"
                     ) -> dict[str, list[float]]:
    """Device seconds of each segment program in `span`, by the engine's
    dispatch span it was launched under: the k-th program execution whose
    name holds ``seg`` belongs to the k-th ``serve/*_segment`` dispatch
    (one device program per dispatch, run in order)."""
    lo, hi = span
    disp = sorted((h for h in tr["host"] if h["name"].startswith(prefix)
                   and h["name"].endswith("_segment")
                   and lo <= h["t0"] < hi), key=lambda h: h["t0"])
    if not disp:
        return {}
    mods = [e for e in module_events(tr, (disp[0]["t0"], hi))
            if "seg" in e["name"]]
    plane = sorted({e["plane"] for e in mods})[:1]
    mods = [e for e in mods if e["plane"] in plane]
    out = collections.defaultdict(list)
    for h, m in zip(disp, mods):
        out[h["name"][len(prefix):]].append(m["dur"] / 1e9)
    return dict(out)
