"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: exact on a hand-made trace, and consistent with a brute-force
count on a trace recorded on a TPU v5e."""
import json
import pathlib

import numpy as np
import pytest

from benchlib import costs, tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def op(name, t0, dur, line=tracing.OPS_LINE, plane=DEV):
    return {"plane": plane, "line": line, "name": name, "t0": float(t0),
            "dur": float(dur)}


def span(name, t0, dur):
    return {"name": name, "t0": float(t0), "dur": float(dur)}


@pytest.fixture
def made():
    return {"device": [
        op("%cim_w8a8_matmul.1 = f32[8,8]{1,0} custom-call(...)", 100, 50),
        op("%fusion.2 = f32[8]{0} fusion(...)", 140, 30),   # overlaps
        op("%while.7 = (s32[]) while(...)", 100, 70),       # encloses both
        op("%cim_w8a8_matmul.3 = f32[8,8]{1,0} custom-call(...)", 300, 100),
        op("%paged_attention_decode.2 = (f32[8]) custom-call(...)", 450,
           40),
        op("%copy.9 = f32[8]{0} copy(...)", 950, 100),    # past the end
        op("jit_seg", 100, 390, line=tracing.MODULES_LINE),
        op("jit_seg", 450, 40, line=tracing.MODULES_LINE),
        op("%fusion.2 = f32[8]{0} fusion(...)", 0, 60,
           plane="/device:TPU:1"),
    ], "host": [
        span(tracing.WINDOW_SPAN, 50, 950),
        span("serve/mixed_segment", 60, 30),
        span("serve/decode_segment", 420, 10),
        span("bench/engine", 170, 200),
        span("bench/client", 490, 400),
        span("serve/decode_segment", 900, 5),
    ]}


def test_busy_union_and_kernel_time(made):
    w = tracing.window(made)
    assert w == (50.0, 1000.0)
    per = tracing.busy_intervals(made, w)
    assert per[DEV] == [(100.0, 170.0), (300.0, 400.0), (450.0, 490.0),
                        (950.0, 1000.0)]
    assert per["/device:TPU:1"] == [(50.0, 60.0)]
    assert tracing.busy_seconds(made, w) == pytest.approx(
        (260 + 10) / 2 / 1e9)
    assert tracing.seconds_of(tracing.kernel_events(
        made, w, "cim_w8a8_matmul")) == pytest.approx(150e-9)
    assert tracing.seconds_of(tracing.kernel_events(
        made, w, "paged_attention_decode")) == pytest.approx(40e-9)
    assert tracing.top_ops(made, w)[0] == ["cim_w8a8_matmul", 150e-9]


def test_gaps_labelled_by_innermost_host_span(made):
    w = tracing.window(made)
    assert tracing.idle_gaps(made, w) == [(50.0, 100.0), (170.0, 300.0),
                                          (400.0, 450.0), (490.0, 950.0)]
    labels = dict(tracing.label_gaps(made, w))
    assert labels == pytest.approx({"serve/mixed_segment": 50e-9,
                                    "bench/engine": 130e-9,
                                    "serve/decode_segment": 50e-9,
                                    "bench/client": 460e-9})


def test_segment_programs_follow_dispatch_order(made):
    per = tracing.segment_programs(made, tracing.window(made))
    assert per == {"mixed_segment": [pytest.approx(390e-9)],
                   "decode_segment": [pytest.approx(40e-9)]}


def test_w8a8_call_cost_from_hlo_text():
    text = ("%cim_w8a8_matmul.7 = f32[32,12288]{1,0} custom-call("
            "bf16[32,4096]{1,0} %a, s8[4096,12288]{1,0} %w, f32[1,1]{1,0} "
            "%s, f32[1,12288]{1,0} %ws, f32[1,12288]{1,0} %b, "
            "f32[1,1]{1,0} %o)")
    ops, nbytes = costs.w8a8_call(text)
    assert ops == 2 * 32 * 4096 * 12288
    assert nbytes == 4096 * 12288 + 32 * 4096 * 2 + 32 * 12288 * 4 \
        + 2 * 12288 * 4 + 2 * 4
    assert costs.w8a8_call("fusion(f32[3])") is None


def _brute_busy(tr, span_, plane):
    lo, hi = int(span_[0]), int(span_[1])
    grid = np.zeros(hi - lo, bool)
    for e in tracing.ops(tr):
        if e["plane"] != plane:
            continue
        a = max(int(np.floor(e["t0"])), lo) - lo
        b = min(int(np.ceil(e["t0"] + e["dur"])), hi) - lo
        if b > a:
            grid[a:b] = True
    return grid.sum() / 1e9


def test_recorded_trace_is_consistent():
    tr = json.loads((DATA / "trace_tpu_v5e.json").read_text())
    w = tracing.window(tr)
    assert w is not None and w[1] > w[0]
    busy = tracing.busy_seconds(tr, w)
    win = (w[1] - w[0]) / 1e9
    assert 0 < busy <= win
    planes = sorted(tracing.busy_intervals(tr, w))
    assert busy == pytest.approx(_brute_busy(tr, w, planes[0]), rel=1e-3,
                                 abs=2e-6)
    gaps = sum(b - a for a, b in tracing.idle_gaps(tr, w)) / 1e9
    assert gaps + busy == pytest.approx(win, rel=1e-6)
    assert sum(v for _, v in tracing.label_gaps(tr, w, n=100)) \
        == pytest.approx(gaps, rel=1e-6)
    k = tracing.seconds_of(tracing.kernel_events(tr, w, "cim_w8a8_matmul"))
    assert 0 < k <= busy
