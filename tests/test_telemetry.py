"""Unified serve telemetry: registry, tracer, exports, and e2e wiring.

Three layers of coverage:

* unit — instruments (counter/gauge/histogram semantics, in-place
  ``reset_run``, bounded sample rings), the shared :func:`percentile`
  helper, Prometheus text exposition, the tracer's ring buffer and
  request-timeline phase spans, and :func:`validate_chrome_trace`'s
  rejection paths;
* e2e — a traffic run with real preemption pressure and scripted faults,
  over (fp | int8) x (blocking | chunked) prefill: every registry counter
  must match the ground truth reconstructed from the ``run_stream`` event
  stream, and the exported trace must be schema-valid Chrome JSON with the
  lifecycle/fault events present;
* identity — a ``telemetry=False`` engine must produce bit-identical
  token streams to a fully-instrumented one (observability can never
  perturb the datapath).
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro import configs as cfg_lib
from repro.models import model as M
from repro.serve import (ContinuousEngine, FaultInjector, Request,
                         RequestStatus)
from repro.serve import faults as faults_lib
from repro.serve import telemetry as T


# ---------------------------------------------------------------------------
# percentile (the one shared helper)
# ---------------------------------------------------------------------------

def test_percentile_matches_numpy_and_empty_policy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0]
    for q in (0, 25, 50, 90, 99, 100):
        assert T.percentile(xs, q) == float(np.percentile(xs, q))
    assert np.isnan(T.percentile([], 50))
    assert T.percentile([], 50, empty=0.0) == 0.0
    assert T.percentile(iter([2.0]), 99) == 2.0     # any iterable


# ---------------------------------------------------------------------------
# Registry instruments
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_reset_in_place():
    reg = T.MetricsRegistry()
    c = reg.counter("serve_x_total", "help text")
    c.inc()
    c.inc(4)
    assert reg.counter("serve_x_total") is c          # same handle
    assert reg.value("serve_x_total") == 5
    life = reg.counter("serve_life_total", run_scoped=False)
    life.inc(3)
    g = reg.gauge("serve_g")
    g.set(2)
    g.set_max(7)
    g.set_max(1)                                       # high-water only
    assert g.value == 7
    reg.reset_run()
    assert c.value == 0                                # zeroed IN PLACE
    assert g.value == 0
    assert life.value == 3                             # lifetime survives
    c.inc()
    assert reg.value("serve_x_total") == 1             # handle still live
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("serve_x_total")                     # kind mismatch
    assert reg.value("absent", default=-1) == -1


def test_registry_labels_are_distinct_series():
    reg = T.MetricsRegistry()
    reg.counter("req_total", labels={"status": "ok"}).inc(2)
    reg.counter("req_total", labels={"status": "shed"}).inc()
    assert reg.value("req_total", labels={"status": "ok"}) == 2
    assert reg.value("req_total", labels={"status": "shed"}) == 1
    assert len(reg.series("req_total")) == 2


def test_histogram_buckets_percentiles_and_bounded_ring():
    reg = T.MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 5.0, 10.0), max_samples=8)
    for v in (0.5, 2.0, 2.0, 7.0, 20.0):
        h.observe(v)
    assert h.count == 5 and h.sum == 31.5
    assert h.bucket_counts == [1, 2, 1, 1]             # le1, le5, le10, +Inf
    assert h.percentile(50) == 2.0
    assert h.n_dropped == 0
    for v in range(100):
        h.observe(float(v))
    assert len(h.samples) == 8                         # ring bounded
    assert h.n_dropped == 105 - 8
    assert h.percentile(100) == 99.0                   # over surviving ring


def test_prometheus_exposition_format():
    reg = T.MetricsRegistry()
    reg.counter("a_total", "things done").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# HELP a_total things done" in text
    assert "# TYPE a_total counter" in text
    assert "a_total 3" in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1.0"} 2' in text          # cumulative
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert "lat_s_count 3" in text
    # snapshot round-trips through JSON
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["a_total"] == 3
    assert snap["lat_s"]["count"] == 3


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_request_timeline_phases_and_validity():
    tr = T.Tracer()
    tr.request_point(7, "arrive", step=0)
    tr.request_point(7, "admit", step=2, row=1)
    tr.request_point(7, "first_token", step=3)
    tr.request_point(7, "preempt", step=5, n_out=2)
    tr.request_point(7, "resume", step=6)
    tr.request_retire(7, "ok", step=9, n_tokens=4)
    t0 = tr.now()
    tr.span("segment", t0, tr.now() + 1.0, args={"step": 9})
    tr.counter("pool blocks", {"live": 3, "free": 5})
    trace = T.validate_chrome_trace(
        tr.to_chrome(),
        require_names={"queued", "prefill", "decode", "retire", "segment",
                       "preempt", "resume"})
    by_name = {}
    for ev in trace["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    # Phase spans chain with no gaps: queued -> prefill -> decode.
    q, p, d = (by_name[n][0] for n in ("queued", "prefill", "decode"))
    assert q["ph"] == p["ph"] == d["ph"] == "X"
    assert q["ts"] + q["dur"] == pytest.approx(p["ts"])
    assert p["ts"] + p["dur"] == pytest.approx(d["ts"])
    assert q["tid"] == T.Tracer.req_tid(7)
    # Request track is named in the metadata.
    assert any(ev["ph"] == "M" and ev["args"].get("name") == "req 7"
               for ev in trace["traceEvents"])
    assert by_name["retire"][0]["args"]["status"] == "ok"


def test_tracer_ring_is_bounded_and_drops_are_counted():
    tr = T.Tracer(max_events=16)
    for i in range(100):
        tr.instant(f"e{i}", args={"step": i})
    assert len(tr.events()) == 16
    assert tr.n_dropped == 84
    trace = tr.to_chrome()
    assert trace["otherData"] == {"n_recorded": 100, "n_dropped": 84}
    T.validate_chrome_trace(trace, require_phases="iM")


# ---------------------------------------------------------------------------
# Span API (Chrome sink + profiler sink)
# ---------------------------------------------------------------------------

def test_span_with_both_sinks_off_is_one_null_context(monkeypatch):
    tel = T.Telemetry(enabled=False)

    def no_clock():
        raise AssertionError("a disabled span read the clock")

    monkeypatch.setattr(tel.tracer, "now", no_clock)
    spans = [tel.span("schedule", round=1, step=0), tel.span("emit"),
             tel.span("segment", profile=False)]
    assert all(sp is T.NULL_SPAN for sp in spans)
    with tel.span("harvest", round=2) as sp:
        sp.set(steps=8)
        with tel.suspended():
            pass
    assert tel.suspended() is T.NULL_SPAN
    assert tel.tracer.events() == [] and tel.tracer.n_recorded == 0


def test_span_records_chrome_event_with_args():
    tel = T.Telemetry(enabled=True)
    with tel.span("segment", cat="pool", profile=False, step=3) as sp:
        sp.set(steps=8)
    with tel.span("emit", chrome=False, round=4, step=32):
        pass                            # profiler-only: no Chrome event
    assert tel.span("emit", chrome=False) is T.NULL_SPAN
    with tel.span("cow_copy", cat="pool", rid=1):
        with tel.suspended():           # cuts profiler slices only
            pass
    evs = tel.tracer.events()
    assert [e["name"] for e in evs] == ["segment", "cow_copy"]
    seg = evs[0]
    assert seg["ph"] == "X" and seg["cat"] == "pool"
    assert seg["args"] == {"step": 3, "steps": 8}
    assert evs[1]["args"] == {"rid": 1}
    assert all(e["dur"] >= 0 for e in evs)
    T.validate_chrome_trace(tel.tracer.to_chrome(), require_phases="X",
                            require_names={"segment", "cow_copy"})


def test_span_profiler_annotation_carries_args(tmp_path):
    """Under jax.profiler on the CPU, a span is a host event named
    serve/<name> whose stats carry its args; a suspension cuts it into
    slices, and profile=False keeps a span out of the profile."""
    import glob
    from jax.profiler import ProfileData
    tel = T.Telemetry(enabled=False, profiler_annotations=True)
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("schedule", round=5, step=40):
            jax.numpy.ones(4).block_until_ready()
            with tel.suspended():
                pass
        with tel.span("segment", profile=False, step=40):
            pass
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = sorted((ev for plane in ProfileData.from_file(path[0]).planes
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith("serve/")),
                    key=lambda ev: ev.start_ns)
    assert [ev.name for ev in events] == ["serve/schedule"] * 2
    assert all({k: v for k, v in ev.stats} == {"round": 5, "step": 40}
               for ev in events)
    assert events[0].start_ns + events[0].duration_ns <= events[1].start_ns
    assert tel.tracer.events() == []


def test_disabled_tracer_records_nothing():
    tr = T.Tracer(enabled=False)
    tr.instant("x")
    tr.request_point(1, "arrive", step=0)
    tr.request_retire(1, "ok", step=1)
    tr.span("s", 0.0, 1.0)
    tr.counter("c", {"v": 1})
    assert tr.events() == [] and tr.n_recorded == 0


def test_validate_chrome_trace_rejections(tmp_path):
    with pytest.raises(ValueError, match="traceEvents"):
        T.validate_chrome_trace({"foo": []})
    with pytest.raises(ValueError, match="non-empty"):
        T.validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="missing 'ph'"):
        T.validate_chrome_trace(
            {"traceEvents": [{"name": "a", "pid": 1, "tid": 0}]})
    with pytest.raises(ValueError, match="unknown phase"):
        T.validate_chrome_trace(
            {"traceEvents": [{"name": "a", "ph": "z", "pid": 1, "tid": 0,
                              "ts": 0}]})
    with pytest.raises(ValueError, match="bad dur"):
        T.validate_chrome_trace(
            {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 0,
                              "ts": 0, "dur": -1}]})
    good = {"traceEvents": [{"name": "a", "ph": "i", "s": "t", "pid": 1,
                             "tid": 0, "ts": 0}]}
    with pytest.raises(ValueError, match="required phases absent"):
        T.validate_chrome_trace(good, require_phases="X")
    with pytest.raises(ValueError, match="required event names"):
        T.validate_chrome_trace(good, require_phases="i",
                                require_names={"b"})
    path = tmp_path / "t.json"
    path.write_text(json.dumps(good))
    T.validate_chrome_trace(str(path), require_phases="i")


def test_faults_describe_flattens_actions():
    acts = {"hide": 2, "unhide": True, "poison": [3, 4], "preempt": 1}
    got = dict(faults_lib.describe(acts))
    assert got == {"fault:hide": {"n": 2}, "fault:unhide": {},
                   "fault:poison": {"rids": [3, 4]},
                   "fault:preempt": {"n": 1}}


def test_allocator_stats_snapshot():
    from repro.serve.kv_pool import BlockAllocator
    al = BlockAllocator(9)
    blocks = al.alloc(3)
    al.hide_blocks(2)
    st = al.stats()
    assert st["capacity"] == 8 and st["live"] == 3 and st["hidden"] == 2
    assert st["free"] == 3
    assert st["occupancy"] == al.occupancy()
    assert st["fragmentation"] == al.fragmentation()
    al.unhide_all()
    al.free(blocks)


# ---------------------------------------------------------------------------
# E2E: registry vs the run_stream event stream, under pressure + faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_setup():
    cfg = cfg_lib.reduced_config("qwen3-8b", n_layers=2)
    params = M.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, n, *, prompt_len=4, max_new=10, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=10 + i,
                    prompt=rng.integers(0, cfg.vocab, prompt_len),
                    max_new=max_new, arrival_step=0)
            for i in range(n)]


@pytest.mark.parametrize("int8,chunked", [(False, False), (False, True),
                                          (True, False), (True, True)])
def test_registry_matches_event_stream_e2e(dense_setup, tmp_path, int8,
                                           chunked):
    """Acceptance: over a run with real growth-failure preemptions AND a
    scripted fault schedule, every registry counter equals the ground
    truth independently reconstructed from run_stream events, and the
    trace exports as schema-valid Chrome JSON carrying the lifecycle."""
    cfg, params = dense_setup
    if int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    # Pool far below aggregate worst case: growth preempts organically;
    # the script adds pool pressure, a forced eviction, and one cancel.
    ce = ContinuousEngine(params, cfg, max_batch=3, kv_blocks=9,
                          block_size=4, max_blocks_per_req=8,
                          segment_len=4, seq_bucket=8,
                          chunked_prefill=chunked, prefill_chunk=4)
    reqs = _reqs(cfg, 4)
    fi = FaultInjector.scripted({1: {"hide": 2}, 2: {"preempt": 1},
                                 3: {"cancel": [13]}, 4: {"unhide": True}})
    events = list(ce.run_stream(reqs, faults=fi))

    # ---- ground truth from the event stream --------------------------
    finishes = [ev for ev in events if ev["event"] == "finish"]
    by_status: dict[str, int] = {}
    for ev in finishes:
        s = ev["result"].status.value
        by_status[s] = by_status.get(s, 0) + 1
    n_preempts = sum(ev["event"] == "preempt" for ev in events)
    admits = [ev for ev in events if ev["event"] == "admit"]
    n_recomputes = sum(ev["recompute"] for ev in admits)
    assert n_preempts >= 2, "workload must exercise preemption"
    assert len(finishes) == len(reqs)

    m = ce.metrics
    assert m.value("serve_submitted_total") == len(reqs)
    assert m.value("serve_preemptions_total") == n_preempts
    assert m.value("serve_admissions_total") == len(admits)
    assert m.value("serve_recomputes_total") == n_recomputes
    assert m.value("serve_cancels_total") == by_status.get("cancelled", 0)
    assert m.value("serve_timeouts_total") == by_status.get("timeout", 0)
    assert m.value("serve_failed_total") == by_status.get("failed", 0)
    assert m.value("serve_sheds_total") == by_status.get("shed", 0)
    for status, n in by_status.items():
        assert m.value("serve_requests_total",
                       labels={"status": status}) == n
    # Dispatch accounting: chunked serves prefill inside the segment.
    segs = m.value("serve_segments_total")
    prefills = m.value("serve_prefills_total")
    assert m.value("serve_dispatches_total") == segs + prefills
    if chunked:
        assert prefills == 0 and m.value("serve_prefill_chunks_total") > 0
    else:
        assert prefills == len(admits)
    # Legacy attributes ARE the registry (same object of truth).
    assert ce.last_run_preemptions == n_preempts
    assert ce.last_run_segments == segs
    # TTFT: one sample per request that emitted a first token.
    ttft_h = m.histogram("serve_ttft_seconds")
    assert ttft_h.count == len(ce.last_run_ttft_seconds)
    assert set(ce.last_run_ttft_seconds) <= {r.rid for r in reqs}
    lat_h = m.histogram("serve_request_latency_steps")
    assert lat_h.count == by_status.get("ok", 0)
    assert 1 <= m.value("serve_max_concurrency") <= 3
    assert 0 < len(ce.occupancy_trace) <= ce.telemetry.trace_samples

    # ---- trace export ------------------------------------------------
    tracefile = tmp_path / f"trace_{int8}_{chunked}.json"
    ce.export_trace(str(tracefile))
    need = {"segment", "arrive", "admit", "first_token", "preempt",
            "retire", "fault:hide", "fault:preempt", "fault:cancel",
            "fault:unhide", "pool blocks", "requests"}
    trace = T.validate_chrome_trace(str(tracefile), require_names=need)
    retired = [ev for ev in trace["traceEvents"] if ev["name"] == "retire"]
    assert len(retired) == len(reqs)
    # JSONL flavor: every line parses, same event count.
    jl = tmp_path / "trace.jsonl"
    ce.export_trace(str(jl))
    lines = [json.loads(x) for x in jl.read_text().splitlines()]
    assert len(lines) == len(trace["traceEvents"])
    # Metrics exports: Prometheus text + JSON snapshot agree.
    prom = tmp_path / "m.prom"
    ce.export_metrics(str(prom))
    assert f"serve_preemptions_total {n_preempts}" in prom.read_text()
    mjson = tmp_path / "m.json"
    ce.export_metrics(str(mjson))
    snap = json.loads(mjson.read_text())
    assert snap["serve_preemptions_total"] == n_preempts
    assert snap["serve_ttft_seconds"]["count"] == ttft_h.count


@pytest.mark.parametrize("annotations", [False, True])
def test_disabled_telemetry_is_token_identical(dense_setup, annotations):
    """Acceptance: telemetry off produces bit-identical token streams —
    the tracer and rings go quiet, the registry stays live (back-compat
    reads keep working).  Profiler annotations on the instrumented engine
    change nothing either."""
    cfg, params = dense_setup
    kw = dict(max_batch=3, kv_blocks=9, block_size=4, max_blocks_per_req=8,
              segment_len=4, seq_bucket=8)
    reqs = _reqs(cfg, 4)
    ce_on = ContinuousEngine(params, cfg, profiler_annotations=annotations,
                             **kw)
    ce_off = ContinuousEngine(params, cfg, telemetry=False, **kw)
    key = jax.random.PRNGKey(3)
    res_on = ce_on.run(reqs, key=key, temperature=0.8)
    res_off = ce_off.run(reqs, key=key, temperature=0.8)
    assert set(res_on) == set(res_off)
    for rid in res_on:
        np.testing.assert_array_equal(res_on[rid].tokens,
                                      res_off[rid].tokens)
        np.testing.assert_array_equal(res_on[rid].logprobs,
                                      res_off[rid].logprobs)
        assert res_on[rid].status is res_off[rid].status
    # Off: no trace, no rings; registry still counts (legacy reads work).
    assert ce_off.tracer.events() == []
    assert len(ce_off.occupancy_trace) == 0
    assert ce_off.last_run_segments == ce_on.last_run_segments > 0
    assert ce_on.tracer.n_recorded > 0
    assert len(ce_on.occupancy_trace) > 0


def test_dispatch_span_names_the_decode_grouping(dense_setup, tmp_path):
    """Under jax.profiler, each segment dispatch span of a paged-attention
    engine carries its table width and the flash decode kernel's pages per
    grid step, as the autotuner chooses them for that width."""
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core import backend as backend_lib
    from repro.kernels import autotune
    cfg, params = dense_setup
    plan = backend_lib.DeploymentPlan(default="exact", paged_attn=True)
    ce = ContinuousEngine(params, cfg, plan=plan, max_batch=2, kv_blocks=12,
                          block_size=4, segment_len=4, seq_bucket=8,
                          profiler_annotations=True)
    with jax.profiler.trace(str(tmp_path)):
        ce.run(_reqs(cfg, 2, max_new=6))
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stats = [dict(ev.stats) for plane in ProfileData.from_file(path[0]).planes
             for line in plane.lines for ev in line.events
             if ev.name in ("serve/decode_segment", "serve/mixed_segment")]
    assert stats
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    for st in stats:
        w = st["table_width"]
        assert st["pages_per_step"] == autotune.choose_paged_decode(
            2, kvh, w, 4, jnp.float32, head_dim=hd,
            groups=cfg.n_heads // kvh)[1]


def test_reused_engine_resets_run_scope(dense_setup):
    """Back-to-back runs on ONE engine: run-scoped counters restart from
    zero (one reset, no drift), lifetime dispatch count accumulates."""
    cfg, params = dense_setup
    ce = ContinuousEngine(params, cfg, max_batch=2, kv_blocks=12,
                          block_size=4, segment_len=4, seq_bucket=8)
    reqs = _reqs(cfg, 2, max_new=6)
    ce.run(reqs)
    seg1, disp1 = ce.last_run_segments, ce.last_run_dispatches
    life1 = ce.dispatch_count
    assert seg1 > 0 and life1 == disp1
    ce.run(reqs)
    assert ce.last_run_segments == seg1          # same workload, fresh count
    assert ce.dispatch_count == life1 + ce.last_run_dispatches
    assert len(ce.tracer.events()) > 0           # trace is last-run-only
