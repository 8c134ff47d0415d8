"""The engine's round-phase spans as the benchmark reads them: served at a
toy size under the CPU profiler, each round shows its phases in order and
none straddles the harness's own spans; on a hand-made trace the three
host-loop readers and the idle-gap labels come out exact; the segment
programs carry stable names."""
import re

import pytest

from benchlib import engine as eng, readers, spans, spec, tracing

import tiny

run = spec.load_module(spec.BENCH_DIR / "run.py", "bench_run_spans")
SEED = 2**32 + 91
DEV = "/device:TPU:0"
PHASE_LETTERS = {"serve/schedule": "S", "serve/inputs": "I",
                 "serve/harvest": "H", "serve/emit": "E"}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A toy cell served for a short window, all of it traced."""
    tmp = tmp_path_factory.mktemp("spans")
    d = tiny.make(tmp)
    cell = spec.load_cell("tiny.chat", d / "BENCHMARK.json", d)
    plain, reqs, records = run.requests_of(cell, SEED)
    st = run.set_up(cell, SEED, kv_blocks=64, trace=True)
    run.prepare(st, reqs, 2.0, steps_per_s=100)
    w, marks = run.serve(st, reqs, records, 2.0, trace=True,
                         trace_dir=tmp / "trace")
    tr = tracing.extract(str(tmp / "trace"))
    ctx = readers.Context(window=w, conf=cell.config, adapter=cell.adapter,
                          settings=st.settings, kv_blocks=st.kv_blocks,
                          peaks={},
                          compiles_in_window=marks["compiles"], trace=tr,
                          span=tracing.window(tr))
    return st, tr, ctx


def _inside(h, span):
    return span[0] <= h["t0"] and h["t0"] + h["dur"] <= span[1]


def test_rounds_show_their_phases_in_order(served):
    _, tr, ctx = served
    seq = []
    for h in sorted(tr["host"], key=lambda h: h["t0"]):
        if not _inside(h, ctx.span):
            continue
        c = "D" if spans.is_dispatch(h["name"]) \
            else PHASE_LETTERS.get(h["name"])
        if c and (not seq or seq[-1] != c):     # slices of one phase
            seq.append(c)
    text = "".join(seq)
    text = text[text.index("S"):]               # from the first whole round
    assert text.count("D") >= 2, text
    assert re.fullmatch(r"(SIDHE)+(S(I(D(H(E)?)?)?)?)?", text), text


def test_no_program_span_straddles_the_harness(served):
    _, tr, _ = served
    engine = [(h["t0"], h["t0"] + h["dur"]) for h in tr["host"]
              if h["name"] == "bench/engine"]
    prog = [h for h in tr["host"] if h["name"].startswith("serve/")]
    assert prog
    for h in prog:
        a, b = h["t0"], h["t0"] + h["dur"]
        assert any(lo <= a and b <= hi for lo, hi in engine), h
    ends = {h["name"] for h in prog if h["name"].endswith("_segment")}
    assert ends and ends <= {"serve/decode_segment", "serve/mixed_segment"}


def test_readers_on_a_served_trace(served):
    _, _, ctx = served
    host, sched, emit = (spans.engine_host_ms(ctx), spans.scheduler_ms(ctx),
                         spans.emit_ms(ctx))
    assert host is not None and sched is not None and emit is not None
    assert 0 < sched and 0 < emit
    # Each is part of every turnaround, so its median is below the host's.
    assert sched <= host and emit <= host


def test_segment_programs_have_stable_names(served):
    st = served[0]
    for kind, width, extra in (("decode", 4, {}),
                               ("mixed", 4, dict(pb=2, pf_width=2))):
        p = eng.Program(kind, width, **extra)
        fn = eng._fn(st.engine, p)
        eng.check_signature(fn, kind)
        text = fn.lower(*eng._args(st.engine, p, st.engine.pages)).as_text()
        assert f"module @jit_serve_{kind}_segment" in text
    e = st.engine
    names = {e._prefill_fn(e.plan, True, 32, False).__name__,
             e._suffix_prefill_fn(e.plan, True, 16, 4, False).__name__}
    assert names == {"serve_prefill", "serve_suffix_prefill"}


def op(name, t0, dur, line=tracing.OPS_LINE):
    return {"plane": DEV, "line": line, "name": name, "t0": float(t0),
            "dur": float(dur)}


def span(name, t0, t1):
    return {"name": name, "t0": float(t0), "dur": float(t1 - t0)}


@pytest.fixture
def made():
    """Four rounds in a window of 1400 ns.  Round 1's emit holds a
    harness span (as a program that kept it open across a yield would);
    round 2's schedule holds a copy-on-write dispatch."""
    host = [span(tracing.WINDOW_SPAN, 0, 1400),
            span("bench/engine", 5, 320), span("bench/client", 320, 330),
            span("bench/engine", 330, 1400)]
    rounds = [(10, 30, 40, 50, 300, 360, "decode"),
              (360, 420, 430, 450, 800, 850, "mixed"),
              (850, 870, 880, 890, 1000, 1100, "decode"),
              (1100, 1190, 1200, 1210, 1400, None, "decode")]
    for s0, i0, d0, h0, e0, e1, kind in rounds:
        host += [span("serve/schedule", s0, i0), span("serve/inputs", i0, d0),
                 span(f"serve/{kind}_segment", d0, h0),
                 span("serve/harvest", h0, e0)]
        if e1 is not None:
            host.append(span("serve/emit", e0, e1))
    host.append(span("serve/cow_copy", 380, 390))
    device = [op("%fusion.1 = f32[8] fusion()", 45, 250),
              op("%copy.2 = f32[8] copy()", 345, 10),
              op("%fusion.3 = f32[8] fusion()", 445, 350),
              op("%fusion.4 = f32[8] fusion()", 885, 115),
              op("%fusion.5 = f32[8] fusion()", 1205, 195),
              op("jit_serve_decode_segment", 45, 250, tracing.MODULES_LINE),
              op("jit_serve_mixed_segment", 445, 350, tracing.MODULES_LINE),
              op("jit_serve_decode_segment", 885, 115, tracing.MODULES_LINE),
              op("jit_serve_decode_segment", 1205, 195,
                 tracing.MODULES_LINE)]
    tr = {"device": device, "host": host}
    return readers.Context(window=None, conf={}, adapter=None,
                           settings=None, kv_blocks=0,
                           peaks={}, compiles_in_window=0, trace=tr,
                           span=tracing.window(tr))


def test_readers_exact_on_a_made_trace(made):
    # Turnarounds: 300 -> 450 (150 ns, 10 of them the harness's),
    # 800 -> 890 (90 ns) and 1000 -> 1210 (210 ns); the last harvest has
    # no dispatch after it.  Each reading is the median of the three.
    assert spans.turnarounds(made.trace, made.span) == [
        (300.0, 450.0), (800.0, 890.0), (1000.0, 1210.0)]
    assert spans.engine_host_ms(made) == pytest.approx(140 / 1e6)
    # schedule 360-420 less the copy 380-390 (50); 850-870 (20);
    # 1100-1190 (90).
    assert spans.scheduler_ms(made) == pytest.approx(50 / 1e6)
    # emit 300-360 less the harness's 320-330 (50); 800-850 (50);
    # 1000-1100 (100).
    assert spans.emit_ms(made) == pytest.approx(50 / 1e6)


def test_gaps_named_by_program_phases(made):
    labels = dict(tracing.label_gaps(made.trace, made.span))
    assert labels == pytest.approx({"serve/schedule": 340e-9,
                                    "bench/client": 50e-9,
                                    "serve/emit": 90e-9})
    per = tracing.segment_programs(made.trace, made.span)
    assert per == {"decode_segment": [pytest.approx(250e-9),
                                      pytest.approx(115e-9),
                                      pytest.approx(195e-9)],
                   "mixed_segment": [pytest.approx(350e-9)]}


def test_readers_find_nothing_without_phase_spans(made):
    made.trace["host"] = [h for h in made.trace["host"]
                          if h["name"] not in spans.PHASES]
    assert spans.engine_host_ms(made) is None
    assert spans.scheduler_ms(made) is None
    assert spans.emit_ms(made) is None
    made.trace = None
    assert spans.engine_host_ms(made) is None
