"""Continuous-batching serve driver: segment-scanned decode over a paged
KV pool.

``ContinuousEngine.run`` is a synchronous traffic simulator with real model
execution: requests carry an ``arrival_step`` (sim time, measured in decode
steps), join the running batch as soon as the scheduler admits them, and
retire the moment they emit a stop token or hit ``max_new`` — no request
ever idles behind a slower batch neighbor, which is the whole point: the
serving layer keeps every batch row busy the way the paper's fully-parallel
adder network keeps every bitline busy.

Execution shape:

* **Prefill** — two modes:

  - *blocking* (default): one jitted dispatch per admitted request,
    cached per prompt bucket — ``model.prefill_paged`` runs the bucketed
    prompt forward, packs its K/V into the request's pool blocks
    (``pack_prompt``), and samples the first token with the
    request-id-folded RNG.  Admission rounds join with ONE batched
    device->host tok0 read (never one blocking ``int(tok0[0])`` per
    request).
  - *chunked* (``chunked_prefill=True``): admission dispatches nothing.
    Each PREFILL request advances ``prefill_chunk`` tokens per segment
    inside the SAME jitted segment body as the decoding rows (mixed
    batch, one dispatch): a pow2-bucketed sub-batch of prefilling rows
    runs ``model.prefill_chunk``, whose causal chunk attends past pool
    pages plus its own prefix and lands its K/V straight in the pool —
    no dense intermediate cache, no ``pack_prompt``, and with
    ``paged_attn=True`` the write happens in-kernel
    (kernels/paged_attention flash prefill).  The final chunk samples
    the first token in-segment, so the admission host sync disappears
    from the steady state and one long prompt never stalls the loop
    (Sarathi/vLLM-style chunked prefill).
* **Decode segments** (ONE jitted dispatch each) — a ``lax.while_loop`` of
  up to ``segment_len`` fused decode+sample steps over the whole batch,
  carrying (pages, per-row tokens/steps/lengths/done) on device and
  early-exiting when every row is done.  PR 2's O(1)-dispatch property is
  preserved *per segment* instead of per call: the host syncs once per
  segment to harvest tokens, retire finished rows, and join newly
  prefilled ones.  ``segment_len`` is the join/retire granularity knob —
  larger segments amortize dispatch overhead, smaller ones admit faster.
* **Deterministic per-request RNG** — row keys fold the request id
  (``Engine.make_sample``), so every request's token stream is independent
  of batch composition and *token-identical* to ``Engine.generate`` run on
  that request alone with the same key (tested, greedy and sampled).

Robustness layer (the serving analog of the paper's non-linearity
compensation: a fast datapath is only useful if it degrades gracefully):

* **Preemptive admission** (``preemption='recompute'``, the default) —
  admission commits only actual prompt blocks; when decode growth finds
  the pool exhausted, the newest-admitted victim is preempted (blocks
  freed, row released) and later *recomputed* through the normal
  (re-)admission prefill over prompt + generated-so-far tokens.  The
  request-id-folded RNG re-samples the identical continuation, so a
  preempted request's stream stays bit-identical to an undisturbed run.
  ``preemption='off'`` keeps the legacy worst-case-reservation contract.
* **Lifecycle** — per-request ``deadline_steps`` and an engine
  :meth:`ContinuousEngine.cancel` API retire requests between segments
  with all blocks returned; every outcome is surfaced as
  ``RequestResult.status`` (:class:`~repro.serve.scheduler.RequestStatus`:
  OK / PREEMPTED / TIMEOUT / CANCELLED / SHED / FAILED).
* **Overload protection** — ``max_queue`` bounds the arrival queue
  (tail arrivals shed), and the fused step's non-finite-logits guard
  quarantines a NaN row as FAILED instead of letting it poison the
  jitted segment.
* **Fault injection** — ``run_stream(..., faults=FaultInjector(...))``
  drives a seeded chaos schedule (hidden pool blocks, forced preemption
  storms, poisoned logits, surprise cancels, crash points) through the
  real code paths; see serve/faults.py and tests/test_serve_faults.py.

Durability layer (PR 9 — the serving analog of the paper's charge-domain
persistence: MAC state survives until a single A/D conversion; here a
request's KV state survives eviction and even process death):

* **Page-out preemption** (``preemption='page_out'``) — instead of
  discarding a victim's KV and recomputing it, the victim's live pool
  blocks are gathered to a host-side :class:`~repro.serve.kv_pool
  .SpillStore` (int8 codes+scales or fp bytes, exact) together with its
  host cursors (ctx_len / n_out / the pending sampled-but-unemitted
  token).  Re-admission allocates fresh (possibly different) blocks,
  scatters the bytes back, rewrites the table, and resumes decode with
  ZERO recompute — bit-identical for fp AND int8 pools, since the exact
  quantized codes round-trip.  Mid-chunked-prefill victims fall back to
  the recompute path (their prompt is not fully resident yet).
* **Snapshot / restore / drain** — every scheduler round starts at a
  *segment boundary*: all device progress has been harvested and host
  state (scheduler queues, block tables, streams, RNG, sim clock) is
  consistent.  ``snapshot_dir`` + ``snapshot_interval`` checkpoint these
  boundaries to an ``.npz`` (serve/snapshot.py: live pool blocks, spill
  store, allocator free-list order, everything); a NEW engine with the
  same geometry can :meth:`ContinuousEngine.restore` the file and
  :meth:`ContinuousEngine.resume` all in-flight requests bit-identically.
  :meth:`ContinuousEngine.drain` stops admissions, lets running requests
  finish until a deadline, spills the stragglers (page_out mode), and
  writes a final snapshot.
* **Crash recovery** — a ``{"crash": True}`` fault action raises
  :class:`~repro.serve.faults.CrashPoint` out of the loop mid-flight (no
  finish events, like a kill -9); the chaos harness restores the last
  periodic snapshot into a fresh engine and asserts every non-retired
  request completes with the identical stream (benchmarks/serve_traffic
  ``--recover``, ``make serve-recover``).

Finished and idle rows still occupy compute lanes within a segment (static
shapes); their writes are masked to the pool's null block and their outputs
discarded on the host.

Decode-attention traffic scales with live tokens, not the pool: each
segment dispatches only the power-of-two-bucketed live-width prefix of the
block tables, and ``paged_attn=True`` additionally routes the attention
read through the fused flash-decoding kernel (kernels/paged_attention —
no gathered cache, int8 pages dequantized in-registers).  The engine
defrags adaptively (``defrag_threshold``: live-span hole fraction) so the
kernel's sequential page walks stay contiguous; ``defrag_interval`` still
forces a fixed cadence when set.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_lib
from repro.kernels import autotune
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import model as model_lib
from repro.serve import faults as faults_lib
from repro.serve import kv_pool
from repro.serve import snapshot as snapshot_lib
from repro.serve import telemetry as telemetry_lib
from repro.serve.engine import Engine
from repro.serve.scheduler import (Request, RequestStatus, ScheduledRequest,
                                   Scheduler, State)


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray            # [n_out] int32
    logprobs: np.ndarray          # [n_out] float32
    finish_reason: str            # 'stop' | 'length' | a non-OK status value
    arrival_step: int
    admitted_step: int
    first_token_step: int
    finished_step: int
    ttft_seconds: float = float("nan")   # eligible -> first token, wall
    status: RequestStatus = RequestStatus.OK
    n_preemptions: int = 0        # evictions survived (recompute re-admits)

    @property
    def latency_steps(self) -> int:
        """Arrival -> completion, in sim decode steps."""
        return self.finished_step - self.arrival_step

    @property
    def ttft_steps(self) -> int:
        """Arrival -> first sampled token, in sim decode steps."""
        return self.first_token_step - self.arrival_step


@dataclasses.dataclass
class _RunState:
    """Everything one serve run owns besides the device pages: scheduler,
    host row arrays, emitted streams, and the sim clock.  Factoring it out
    of the loop's locals is what makes the run *durable* — a snapshot is a
    faithful serialization of this record (plus pages / allocator / spill
    store) at a segment boundary, and ``restore`` rebuilds it so
    ``resume`` re-enters the same loop."""
    sched: Scheduler
    requests: dict[int, Request]
    rng: Any                      # raw PRNGKey (uint32 [2])
    temperature: float
    greedy: bool
    stop_w: int
    tok: np.ndarray               # [mb] pending (sampled, unemitted) token
    n_out: np.ndarray             # [mb] emitted counts (post-harvest)
    lens: np.ndarray              # [mb] cache positions written
    done: np.ndarray              # [mb] idle/finished row mask
    rids: np.ndarray              # [mb]
    max_new: np.ndarray           # [mb]
    stops: np.ndarray             # [mb, stop_w]
    tables: np.ndarray            # [mb, max_blocks_per_req]
    streams: dict[int, tuple[list, list]]
    now: int = 0                  # sim clock (decode steps)
    n_loops: int = 0              # scheduler rounds completed
    drain_at: int | None = None   # sim deadline of an active drain
    drain_path: str | None = None


class ContinuousEngine:
    """Continuous-batching engine over a paged KV pool.

    Wraps a :class:`~repro.serve.engine.Engine` (whose bucketed prefill,
    fused decode+sample step, and request-id RNG it reuses) with a
    :class:`~repro.serve.scheduler.Scheduler` and a
    :class:`~repro.serve.kv_pool.BlockAllocator` over ``kv_blocks`` pool
    blocks of ``block_size`` tokens.  Dense-attention archs only (same
    restriction as bucketed prefill; the int8 KV pool follows
    ``cfg.kv_cache_dtype``).

    With ``prefix_cache=True`` (requires a preemptive mode) the pool is
    content-addressable: full prompt blocks are indexed by a chained
    token hash, admissions map the longest cached prefix at refcount+1
    and prefill only the unique suffix, an exact-full-prompt hit
    copy-on-writes the shared tail block, and ``Request.priority``
    classes steer both admission order and victim selection.  Token
    streams are bit-identical to the uncached engine.
    """

    def __init__(self, params, cfg, *, plan=None, mode=None,
                 max_batch: int = 8, kv_blocks: int = 64,
                 block_size: int = 16, max_blocks_per_req: int | None = None,
                 segment_len: int = 8, seq_bucket: int = 32,
                 defrag_interval: int | None = None,
                 defrag_threshold: float | None = 0.5,
                 defrag_min_holes: int = 4,
                 paged_attn: bool = False,
                 chunked_prefill: bool = False,
                 prefill_chunk: int | None = None,
                 preemption: str = "recompute",
                 prefix_cache: bool = False,
                 max_queue: int | None = None,
                 debug_invariants: bool = False,
                 telemetry=None,
                 trace_samples: int = 4096,
                 profiler_annotations: bool = False,
                 snapshot_dir: str | None = None,
                 snapshot_interval: int | None = None):
        if cfg.arch_type != "dense" or cfg.sliding_window is not None:
            raise ValueError(
                "continuous batching serves dense-attention archs without "
                f"sliding windows (got {cfg.arch_type!r}, "
                f"window={cfg.sliding_window})")
        if cfg.mrope_sections is not None:
            raise ValueError(
                "continuous batching does not support M-RoPE archs: paged "
                "decode derives per-row positions from the pool lengths, "
                "which has no 3-axis (t/h/w) position layout")
        if preemption not in ("off", "recompute", "page_out"):
            raise ValueError("preemption must be 'off' (worst-case "
                             "reservation), 'recompute' (preempt + "
                             "re-prefill), or 'page_out' (spill victim KV "
                             f"to the host, no recompute), got "
                             f"{preemption!r}")
        if snapshot_interval is not None:
            if snapshot_interval < 1:
                raise ValueError(
                    f"snapshot_interval must be >= 1, got {snapshot_interval}")
            if snapshot_dir is None:
                raise ValueError(
                    "snapshot_interval requires snapshot_dir (where else "
                    "would the periodic checkpoints land?)")
        if plan is None and mode is not None:
            plan = backend_lib.as_plan(mode)
        if paged_attn:
            # Route paged decode attention through the fused flash-decoding
            # kernel (kernels/paged_attention) instead of gather+attend.
            plan = dataclasses.replace(
                backend_lib.as_plan(plan), paged_attn=True)
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.max_batch = max_batch
        self.block_size = block_size
        self.segment_len = segment_len
        self.chunked_prefill = chunked_prefill
        self.preemption = preemption
        if prefix_cache and preemption == "off":
            raise ValueError(
                "prefix_cache requires a preemptive mode ('recompute' or "
                "'page_out'): reservation admission sizes every request "
                "for its worst case, so shared blocks would break the "
                "free-list accounting")
        self.prefix_cache = bool(prefix_cache)
        self.max_queue = max_queue
        self.debug_invariants = debug_invariants
        self._int8_pool = getattr(cfg, "kv_cache_dtype", "bf16") == "int8"
        if prefill_chunk is None:
            # Autotuned tokens-per-chunk (measured entry when a tuned table
            # is loaded, deterministic heuristic otherwise).
            kvh = cfg.n_kv_heads
            dtype = (jnp.int8 if getattr(cfg, "kv_cache_dtype", "bf16")
                     == "int8" else jnp.float32)
            prefill_chunk = autotune.choose_prefill_chunk(
                max_batch, kvh, block_size, dtype,
                head_dim=cfg.resolved_head_dim,
                groups=cfg.n_heads // kvh)
        if prefill_chunk % block_size != 0 or prefill_chunk < block_size:
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must be a positive "
                f"multiple of block_size ({block_size}) so chunk starts "
                "stay page-aligned")
        self.prefill_chunk = int(prefill_chunk)
        self.defrag_interval = defrag_interval
        self.defrag_threshold = defrag_threshold
        self.defrag_min_holes = defrag_min_holes
        self.max_blocks_per_req = (kv_blocks - 1 if max_blocks_per_req is None
                                   else max_blocks_per_req)
        self.max_seq_len = self.max_blocks_per_req * block_size
        # The inner engine's max_len bounds prompt bucketing AND is the
        # dense-cache geometry isolated `generate` parity runs against.
        self.engine = Engine(params, cfg, max_len=self.max_seq_len,
                             plan=plan, seq_bucket=seq_bucket)
        self.allocator = kv_pool.BlockAllocator(kv_blocks)
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.pages = kv_pool.init_pages(cfg, kv_blocks, block_size, dtype)
        self._fn_cache: dict = {}
        self._cancel_req: set[int] = set()
        # Durability: host spill store (page-out preemption), periodic
        # snapshot config, and the restore/resume handshake state.
        self.spill = kv_pool.SpillStore()
        self.snapshot_dir = snapshot_dir
        self.snapshot_interval = snapshot_interval
        self.last_snapshot_path: str | None = None
        self._run_state: _RunState | None = None
        self._restored: _RunState | None = None
        self._at_boundary = False
        self._drain_req: tuple[int, str | None] | None = None
        # {"round": segment index, "step": sim clock} of the scheduler
        # round in progress: the args of every round-phase span.
        self._round_args: dict = {}
        # All run accounting lives in ONE place: the telemetry registry
        # (counters/gauges/histograms) plus the tracer's event timeline.
        # The legacy `last_run_*` attributes are thin registry reads (see
        # the property loop below the class) and the old hand-maintained
        # reset blocks collapse into Telemetry.reset_run().
        if isinstance(telemetry, telemetry_lib.Telemetry):
            self.telemetry = telemetry
        else:
            self.telemetry = telemetry_lib.Telemetry(
                enabled=True if telemetry is None else bool(telemetry),
                trace_samples=trace_samples,
                profiler_annotations=profiler_annotations)

    # ------------------------------------------------------------ telemetry

    @property
    def metrics(self) -> telemetry_lib.MetricsRegistry:
        return self.telemetry.metrics

    @property
    def tracer(self) -> telemetry_lib.Tracer:
        return self.telemetry.tracer

    @property
    def dispatch_count(self) -> int:
        """Jitted dispatches since engine construction (lifetime)."""
        return self.metrics.value("serve_lifetime_dispatches_total")

    @property
    def last_run_ttft_seconds(self) -> dict[int, float]:
        """{rid: wall TTFT seconds} over the last run."""
        return self.telemetry.ttft_seconds

    @property
    def occupancy_trace(self):
        """Bounded per-round ring of (sim_step, pool occupancy)."""
        return self.telemetry.occupancy_trace

    @property
    def fragmentation_trace(self):
        """Bounded per-round ring of (sim_step, pool fragmentation)."""
        return self.telemetry.fragmentation_trace

    def export_metrics(self, path: str) -> None:
        """Write the registry: .json -> snapshot, else Prometheus text."""
        self.metrics.write(path)

    def export_trace(self, path: str) -> None:
        """Write the event timeline: .jsonl -> one event per line, else
        Chrome trace-event JSON (opens in perfetto / chrome://tracing)."""
        self.tracer.write(path)

    def ttft_percentile(self, pct: float) -> float:
        """Wall-clock time-to-first-token percentile over the last run
        (eligible-for-admission -> first sampled token harvested)."""
        return telemetry_lib.percentile(
            self.telemetry.ttft_seconds.values(), pct)

    def cancel(self, rid: int) -> None:
        """Request cancellation of `rid`.  Honored at the next scheduler
        round (segment boundary): a running request retires with its
        partial output, a queued one before ever being admitted — either
        way all its pool blocks are returned and its result carries
        ``status=CANCELLED``.  Unknown / already-finished rids are
        ignored."""
        self._cancel_req.add(rid)

    def _dispatch(self, fn, *args, name: str = "dispatch"):
        # The enqueue of one jitted program: in a device profile the
        # serve/<name> span sits on the device ops' clock, just before
        # the program it launched (profiler-only: the Chrome trace has
        # the segment span instead).
        with self.telemetry.span(name, chrome=False, **self._round_args):
            return self._launch(fn, *args)

    def _segment_args(self, plan, rnd: dict, batch: int,
                      width: int) -> dict:
        """The dispatch span's args: the round's, plus the table width and,
        with paged attention, the flash decode kernel's pages per grid
        step (``ops.decode_grouping``, as the kernel chooses it).  Only
        the profiler sink reads them, so without it they are the round's
        alone."""
        if not self.telemetry.profiler_annotations:
            return rnd
        args = dict(rnd, table_width=int(width))
        if backend_lib.paged_attn_enabled(plan):
            _, args["pages_per_step"] = paged_ops.decode_grouping(
                batch, self.pages["k"], width,
                self.cfg.n_heads // self.cfg.n_kv_heads)
        return args

    def _launch(self, fn, *args):
        """Call a jitted program, counted as one dispatch."""
        self.metrics.counter("serve_dispatches_total").inc()
        self.metrics.counter("serve_lifetime_dispatches_total").inc()
        return fn(*args)

    # ------------------------------------------------------------------ jit

    def _prefill_fn(self, plan, greedy: bool, bucket_len: int,
                    with_length: bool):
        """Jitted prefill+pack+first-sample, cached per prompt bucket.
        ``t0`` (traced) is the sampler step for the first token: 0 for a
        fresh admission, the request's emitted-token count for a
        recompute re-admission (so the re-sampled pending token folds the
        same (key, rid, step) triple it did originally)."""
        key = ("cb_prefill", plan, greedy, bucket_len, with_length)
        if key in self._fn_cache:
            return self._fn_cache[key]
        cfg = self.cfg
        sample = self.engine.make_sample(plan, greedy)
        pf_len = kv_pool.blocks_for(bucket_len, self.block_size) \
            * self.block_size

        def serve_prefill(params, pages, tokens, length, block_table, rid,
                          rng, t0, temperature):
            batch = {"tokens": tokens}
            if with_length:
                batch["length"] = length
            logits, pages = model_lib.prefill_paged(
                params, batch, cfg, pages=pages, block_table=block_table,
                max_len=pf_len, mode=plan)
            with jax.named_scope("sample"):
                tok0 = sample(logits[:, -1], rng, rid, t0, temperature)
            return tok0, pages

        fn = jax.jit(serve_prefill)
        self._fn_cache[key] = fn
        return fn

    def _suffix_prefill_fn(self, plan, greedy: bool, chunk: int,
                           table_w: int, skip_write: bool):
        """Jitted B=1 suffix prefill + first-sample for a prefix-cache hit
        on the blocking path: the shared prompt blocks are already mapped
        into the row's table, so only the unique suffix (block-aligned
        start ``pos``, ``n_tok`` real tokens inside a pow2-bucketed
        ``chunk``) runs through ``prefill_chunk`` with past-page reads
        enabled.  First-token sampling folds the same (key, rid, step)
        triple as a full prefill.

        ``skip_write`` (exact-full-prompt hit): the CoW page copy already
        placed byte-exact K/V for every suffix position in the dst block,
        so the chunk computes logits from its in-flight K/V but masks the
        page writes — rewriting would replace exact bytes with
        reduction-order-noisy ones, which the int8 quantizer amplifies
        into token flips."""
        key = ("cb_suffix", plan, greedy, chunk, table_w, skip_write)
        if key in self._fn_cache:
            return self._fn_cache[key]
        cfg = self.cfg
        sample = self.engine.make_sample(plan, greedy)

        def serve_suffix_prefill(params, pages, tokens, pos, n_tok,
                                 block_table, rid, rng, t0, temperature):
            wm = jnp.asarray([not skip_write])
            logits0, pages = model_lib.prefill_chunk(
                params, tokens, cfg, pages=pages, block_tables=block_table,
                pos=pos, n_tok=n_tok, write_mask=wm, has_past=True,
                mode=plan)
            with jax.named_scope("sample"):
                tok0 = sample(logits0, rng, rid, t0, temperature)
            return tok0, pages

        fn = jax.jit(serve_suffix_prefill)
        self._fn_cache[key] = fn
        return fn

    def _decode_loop(self, step, seg_len: int):
        """Shared decode-segment body: up to `seg_len` fused decode+sample
        steps over the whole batch, early-exiting when every row is done.

        Carries a ``failed`` mask alongside ``done``: a row whose step
        returns non-finite logits (``ok`` False — organic overflow or an
        injected ``poison``) has that step's emission retracted (its
        logprob came from the bad logits), takes no length/count credit,
        and is marked failed+done so the segment's remaining iterations
        mask it like any finished row.  The host quarantines failed rows
        as FAILED; their batch neighbors never see the NaN."""
        def decode_loop(params, pages, tables, tok, n_out, lens, done,
                        failed, rids, max_new, stops, poison, rng,
                        temperature, pad_token):
            mb = tok.shape[0]
            out_t = jnp.full((mb, seg_len), pad_token, jnp.int32)
            out_lp = jnp.zeros((mb, seg_len), jnp.float32)

            def cond(carry):
                i, _, _, _, done = carry[:5]
                return (i < seg_len) & ~jnp.all(done)

            def body(carry):
                i, tok, n_out, lens, done, failed, pages, out_t, out_lp = \
                    carry
                # Emit the pending token (per-row position n_out -> column
                # i: a live row emits every iteration until done, so its
                # segment output is a column prefix).
                out_t = out_t.at[:, i].set(jnp.where(done, pad_token, tok))
                caches = {"kv": pages, "block_tables": tables, "lens": lens,
                          "write_mask": ~done}
                nxt, lp, ok, caches = step(params, tok, caches, rng, rids,
                                           n_out + 1, temperature, poison)
                bad = ~ok & ~done
                out_t = out_t.at[:, i].set(
                    jnp.where(bad, pad_token, out_t[:, i]))
                out_lp = out_lp.at[:, i].set(
                    jnp.where(done | bad, 0.0, lp))
                live = (~done & ~bad).astype(jnp.int32)
                lens = lens + live
                n_out = n_out + live
                failed = failed | bad
                done = done | bad \
                    | jnp.any(tok[:, None] == stops, axis=-1) \
                    | (n_out >= max_new)
                return (i + 1, nxt, n_out, lens, done, failed,
                        caches["kv"], out_t, out_lp)

            i, tok, n_out, lens, done, failed, pages, out_t, out_lp = \
                jax.lax.while_loop(
                    cond, body,
                    (jnp.asarray(0, jnp.int32), tok, n_out, lens, done,
                     failed, pages, out_t, out_lp))
            return pages, tok, n_out, lens, done, failed, out_t, out_lp, i

        return decode_loop

    def _segment_fn(self, plan, greedy: bool, seg_len: int, stop_w: int):
        """ONE jitted dispatch: a pure decode segment.  Reuses the inner
        engine's fused decode+sample step over the paged-pool cache view."""
        key = ("cb_segment", plan, greedy, seg_len, stop_w)
        if key in self._fn_cache:
            return self._fn_cache[key]
        loop = self._decode_loop(self.engine.make_step(plan, greedy),
                                 seg_len)

        def serve_decode_segment(params, pages, tables, tok, n_out, lens,
                                 done, rids, max_new, stops, poison, rng,
                                 temperature, pad_token):
            failed = jnp.zeros(done.shape, bool)
            return loop(params, pages, tables, tok, n_out, lens, done,
                        failed, rids, max_new, stops, poison, rng,
                        temperature, pad_token)

        fn = jax.jit(serve_decode_segment)
        self._fn_cache[key] = fn
        return fn

    def _mixed_segment_fn(self, plan, greedy: bool, seg_len: int,
                          stop_w: int, chunk: int, pb: int,
                          has_past: bool):
        """ONE jitted dispatch: a chunked-prefill prologue (rows in PREFILL
        advance up to `chunk` prompt tokens straight into the pool — no
        dense intermediate cache, no pack_prompt) followed by the same
        decode segment as :meth:`_segment_fn`.

        The prologue runs over a ``pb``-row sub-batch holding ONLY the
        prefilling rows (``pf_rows`` gathers their tables/rids inside the
        jit; ``pb`` is pow2-bucketed so the compile count stays O(log
        max_batch)) — decode-only rows cost no chunk FLOPs, exactly like
        the blocking path's B=1 prefill, but without its extra dispatch.
        Rows whose final chunk lands this segment sample their first token
        from the chunk logits (identical request-id-folded RNG as the
        blocking prefill; ``pf_t0`` carries the per-row sampler step — 0
        for fresh prompts, the emitted count for a recompute re-admission)
        and join decode inside the same dispatch; the per-admission
        ``int(tok0[0])`` host sync is gone from the steady state.  A final
        chunk whose logits come back non-finite (organic or ``poison``)
        does NOT join decode: its row stays parked and is flagged in the
        returned ``failed`` mask for host-side FAILED quarantine.

        ``pf_tables`` rides in separately at its own tight width (the
        prefilling rows' span only, pow2-bucketed) and ``has_past`` is a
        static all-first-chunks hint — short prompts, the common case,
        pay no past-page gather at all."""
        key = ("cb_mixed", plan, greedy, seg_len, stop_w, chunk, pb,
               has_past)
        if key in self._fn_cache:
            return self._fn_cache[key]
        cfg = self.cfg
        sample = self.engine.make_sample(plan, greedy)
        loop = self._decode_loop(self.engine.make_step(plan, greedy),
                                 seg_len)

        def serve_mixed_segment(params, pages, tables, pf_rows, pf_tables,
                                pf_tok, pf_pos, pf_cnt, pf_on, pf_nw,
                                pf_fin, pf_t0, tok, n_out, lens, done, rids,
                                max_new, stops, poison, rng, temperature,
                                pad_token):
            # pf_nw: rows whose chunk span is a CoW-copied block holding
            # byte-exact K/V already — compute logits, mask the write.
            logits0, pages = model_lib.prefill_chunk(
                params, pf_tok, cfg, pages=pages, block_tables=pf_tables,
                pos=pf_pos, n_tok=pf_cnt, write_mask=pf_on & ~pf_nw,
                has_past=has_past, mode=plan)
            logits0 = jnp.where(poison[pf_rows][:, None], jnp.nan, logits0)
            ok0 = jnp.all(jnp.isfinite(logits0.astype(jnp.float32)),
                          axis=-1)
            with jax.named_scope("sample"):
                tok0 = sample(logits0, rng, rids[pf_rows], pf_t0,
                              temperature)
            fin = pf_on & pf_fin
            good = fin & ok0
            bad = fin & ~ok0
            # Scatter the sub-batch back onto the full rows.  Padding
            # entries point at a non-prefilling row and write its own
            # current value (a deterministic no-op), so duplicate indices
            # never race a real update.
            tok = tok.at[pf_rows].set(jnp.where(good, tok0, tok[pf_rows]))
            done = done.at[pf_rows].set(done[pf_rows] & ~good)
            lens = lens.at[pf_rows].set(
                jnp.where(pf_on, pf_pos + pf_cnt, lens[pf_rows]))
            failed = jnp.zeros(done.shape, bool).at[pf_rows].set(bad)
            return loop(params, pages, tables, tok, n_out, lens, done,
                        failed, rids, max_new, stops, poison, rng,
                        temperature, pad_token)

        fn = jax.jit(serve_mixed_segment)
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------------ run

    def _maybe_defrag(self, sched: Scheduler, tables: np.ndarray,
                      now: int = -1) -> np.ndarray:
        """Compact live blocks onto the lowest page slots (maintenance;
        correctness never depends on placement, tested).  Rewrites the row
        block tables AND every running request's scheduler-side block list
        so later growth/free operate on the moved ids."""
        if not self.allocator.fragmented:
            return tables
        remap = self.allocator.defrag()
        if remap:
            with self.telemetry.span("defrag", cat="pool", step=now,
                                     moved=len(remap)):
                self.pages, tables = kv_pool.apply_defrag(
                    self.pages, tables, remap)
                for sr in sched.running.values():
                    sr.blocks = [remap.get(b, b) for b in sr.blocks]
            self.metrics.counter("serve_defrags_total").inc()
        return tables

    def run(self, requests: Sequence[Request], *, key=None,
            temperature: float = 0.0,
            faults=None) -> dict[int, RequestResult]:
        """Serve a request stream to completion; returns {rid: result}."""
        results: dict[int, RequestResult] = {}
        for ev in self.run_stream(requests, key=key,
                                  temperature=temperature, faults=faults):
            if ev["event"] == "finish":
                results[ev["rid"]] = ev["result"]
        return results

    def run_stream(self, requests: Sequence[Request], *, key=None,
                   temperature: float = 0.0,
                   faults=None) -> Iterator[dict]:
        """Generator form of :meth:`run`: yields per-request events as the
        sim advances — {'event': 'admit'|'tokens'|'preempt'|'finish',
        'rid': ..., 'step': sim_time, ...}.  'tokens' events carry the new
        tokens and logprobs harvested after each decode segment; 'finish'
        events carry the RequestResult (every terminal status, not just
        OK).  ``faults`` is an optional chaos driver (serve/faults.py):
        its per-round action dict is applied through the real scheduler /
        allocator / sampler code paths."""
        requests = list(requests)
        rid_set = {r.rid for r in requests}
        if len(rid_set) != len(requests):
            raise ValueError("request ids must be unique within a run "
                             "(they seed the per-request RNG)")
        for r in requests:
            if r.prompt_len + r.max_new > self.max_seq_len:
                raise ValueError(
                    f"request {r.rid}: prompt {r.prompt_len} + max_new "
                    f"{r.max_new} exceeds max_blocks_per_req * block_size "
                    f"= {self.max_seq_len}")
        greedy = temperature <= 0 or key is None
        rng = key if key is not None else jax.random.PRNGKey(0)
        stop_w = max((len(r.stop_tokens) for r in requests), default=0) or 1

        # ONE run-scoped reset for every counter, histogram, ring, and the
        # trace buffer (the two hand-maintained last_run_* blocks this
        # replaces had already drifted once; the registry cannot).
        self._cancel_req = set()
        self._restored = None
        self.telemetry.reset_run()

        sched = Scheduler(self.allocator, self.max_batch, self.block_size,
                          preemptive=self.preemption != "off",
                          prefix_cache=self.prefix_cache,
                          max_queue=self.max_queue,
                          debug=self.debug_invariants,
                          metrics=self.metrics)
        for r in sorted(requests, key=lambda r: r.arrival_step):
            sched.submit(r)

        mb, nbr = self.max_batch, self.max_blocks_per_req
        st = _RunState(
            sched=sched, requests={r.rid: r for r in requests}, rng=rng,
            temperature=float(temperature), greedy=greedy, stop_w=stop_w,
            tok=np.zeros(mb, np.int32), n_out=np.zeros(mb, np.int32),
            lens=np.zeros(mb, np.int32),
            done=np.ones(mb, bool),         # idle rows are 'done'
            rids=np.zeros(mb, np.int32), max_new=np.zeros(mb, np.int32),
            stops=np.full((mb, stop_w), -1, np.int32),
            tables=np.zeros((mb, nbr), np.int32), streams={})
        yield from self._drive(st, faults)

    def _drive(self, st: _RunState, faults) -> Iterator[dict]:
        """Run the serve loop over a (fresh or restored) run state with the
        end-of-run cleanup both paths share."""
        self._run_state = st
        loop = self._serve_loop(st, faults)
        try:
            for ev in loop:
                # No span stays open while the consumer holds an event:
                # the open ones close here and reopen as the loop resumes.
                with self.telemetry.suspended():
                    yield ev
        finally:
            loop.close()
            # The generator may be abandoned mid-run (client drops the
            # stream) or killed by a CrashPoint: release every in-flight
            # request's blocks — running AND preempted-but-requeued —
            # return any fault-hidden blocks, and drop host spill entries,
            # so the shared allocator is exactly full for the next run.
            # (Crash recovery reads the snapshot FILE, never this
            # in-memory state.)
            self._run_state = None
            self._round_args = {}
            self._at_boundary = False
            self._drain_req = None
            self.allocator.unhide_all()
            for sr in list(st.sched.running.values()):
                st.sched.finish(sr, -1)
            for sr in list(st.sched.preempted):
                st.sched.finish(sr, -1)
            self.spill.clear()

    # ----------------------------------------------------------- durability

    def snapshot(self, path: str) -> str:
        """Serialize the active run at its current segment boundary (see
        serve/snapshot.py for the format).  Valid on a restored-not-yet-
        resumed engine; DURING a run use ``snapshot_dir`` +
        ``snapshot_interval`` (periodic checkpoints) or :meth:`drain` — in
        between events the loop is suspended mid-round and host state is
        not snapshot-consistent."""
        st = self._run_state
        if st is None:
            raise RuntimeError(
                "snapshot() requires an active or restored run (nothing to "
                "serialize on an idle engine)")
        if not self._at_boundary:
            raise RuntimeError(
                "snapshot() is only valid at a segment boundary — use "
                "snapshot_dir/snapshot_interval for periodic in-run "
                "checkpoints, or drain() for a final one")
        return self._write_snapshot(st, path=path)

    def _write_snapshot(self, st: _RunState, path: str | None = None) -> str:
        if path is None:
            path = os.path.join(self.snapshot_dir, "serve_snap.npz")
        with self.telemetry.span("snapshot", cat="durability", step=st.now,
                                 round=st.n_loops) as sp:
            path = snapshot_lib.save_snapshot(path, engine=self, state=st)
            sp.set(path=str(path))
        self.last_snapshot_path = path
        self.metrics.counter("serve_snapshots_total").inc()
        return path

    def restore(self, path: str) -> "ContinuousEngine":
        """Load a snapshot into this engine: allocator books, pool pages
        (live blocks scattered back), spill store, scheduler queues, and
        the run state — then :meth:`resume` / :meth:`resume_stream`
        continues every in-flight request bit-identically.  The engine
        must be idle and built with the snapshot's geometry (checked);
        pass the same params/cfg/plan — weights are NOT in the file."""
        if self._run_state is not None and self._restored is None:
            raise RuntimeError("restore() on an engine with an active run")
        meta, arrays = snapshot_lib.load_snapshot(path)
        snapshot_lib.check_geometry(self, meta["geometry"])
        self.allocator = kv_pool.BlockAllocator.from_state(meta["allocator"])
        dtype = (jnp.bfloat16 if self.cfg.dtype == "bfloat16"
                 else jnp.float32)
        self.pages = kv_pool.init_pages(
            self.cfg, self.allocator.num_blocks, self.block_size, dtype)
        live = [int(b) for b in meta["live_blocks"]]
        if live:
            pool_kv = {k[len("pool_"):]: v for k, v in arrays.items()
                       if k.startswith("pool_")}
            self.pages = kv_pool.insert_blocks(self.pages, pool_kv, live)
        self.spill = kv_pool.SpillStore()
        for srid, e in meta["spill"].items():
            rid = int(srid)
            self.spill.put(rid, kv_pool.SpillEntry(
                kv={k: arrays[f"spill_{rid}_{k}"] for k in e["kv_keys"]},
                n_blocks=int(e["n_blocks"]), ctx_len=int(e["ctx_len"]),
                n_out=int(e["n_out"]), pending_tok=int(e["pending_tok"])))
        requests: dict[int, Request] = {}
        for rm in meta["requests"]:
            rid = int(rm["rid"])
            requests[rid] = Request(
                rid=rid, prompt=arrays[f"prompt_{rid}"],
                max_new=int(rm["max_new"]),
                arrival_step=int(rm["arrival_step"]),
                stop_tokens=tuple(int(t) for t in rm["stop_tokens"]),
                deadline_steps=rm["deadline_steps"],
                priority=int(rm.get("priority", 0)))
        sched = Scheduler(self.allocator, self.max_batch, self.block_size,
                          preemptive=self.preemption != "off",
                          prefix_cache=self.prefix_cache,
                          max_queue=self.max_queue,
                          debug=self.debug_invariants,
                          metrics=self.metrics)
        sched.load_state(
            meta["scheduler"], requests,
            {int(k[len("resume_"):]): v for k, v in arrays.items()
             if k.startswith("resume_")})
        run = meta["run"]
        streams = {
            int(rid): ([int(t) for t in arrays[f"stream_tok_{rid}"]],
                       [float(x) for x in arrays[f"stream_lp_{rid}"]])
            for rid in meta["streams"]}
        st = _RunState(
            sched=sched, requests=requests,
            rng=jnp.asarray(arrays["rng"]),
            temperature=float(run["temperature"]),
            greedy=bool(run["greedy"]), stop_w=int(run["stop_w"]),
            tok=np.array(arrays["tok"]), n_out=np.array(arrays["n_out"]),
            lens=np.array(arrays["lens"]), done=np.array(arrays["done"]),
            rids=np.array(arrays["rids"]),
            max_new=np.array(arrays["max_new"]),
            stops=np.array(arrays["stops"]),
            tables=np.array(arrays["tables"]), streams=streams,
            now=int(run["now"]), n_loops=int(run["n_loops"]))
        self._run_state = st
        self._restored = st
        self._at_boundary = True
        self.last_snapshot_path = str(path)
        return self

    def resume_stream(self, *, faults=None) -> Iterator[dict]:
        """Continue a :meth:`restore`d run: the event stream picks up at
        the snapshot's segment boundary, and every request the snapshot
        holds in flight (running / preempted / spilled / queued) completes
        with the token stream an uninterrupted run would have produced."""
        st = self._restored
        if st is None:
            raise RuntimeError(
                "resume_stream() requires a prior restore(path)")
        self._restored = None
        self._cancel_req = set()
        self._at_boundary = False
        self.telemetry.reset_run()
        sched = st.sched
        n_flight = (len(sched.running) + len(sched.preempted)
                    + len(sched.arrived) + len(sched.pending))
        self.metrics.counter("serve_recoveries_total").inc(n_flight)
        self.tracer.instant(
            "recover", cat="durability",
            args={"step": st.now, "round": st.n_loops,
                  "in_flight": n_flight, "spilled": len(self.spill),
                  "path": self.last_snapshot_path})
        yield from self._drive(st, faults)

    def resume(self) -> dict[int, RequestResult]:
        """Blocking form of :meth:`resume_stream`; returns {rid: result}
        for every request that retires after the restore point."""
        results: dict[int, RequestResult] = {}
        for ev in self.resume_stream():
            if ev["event"] == "finish":
                results[ev["rid"]] = ev["result"]
        return results

    def drain(self, deadline_steps: int, path: str | None = None) -> None:
        """Begin a graceful drain of the active run: admissions stop
        (queued arrivals are checkpointed as queued), running requests get
        up to ``deadline_steps`` more sim steps to finish, stragglers are
        spilled (page_out mode) or checkpointed in place, and a final
        snapshot lands at ``path`` (default ``snapshot_dir/
        serve_snap.npz``).  The run then ends with a ``'drain'`` event;
        a warm restart restores the file and serves the remainder."""
        if deadline_steps < 0:
            raise ValueError(f"drain deadline must be >= 0, "
                             f"got {deadline_steps}")
        if path is None and self.snapshot_dir is None:
            raise ValueError("drain() needs an explicit path or an engine "
                             "snapshot_dir")
        self._drain_req = (int(deadline_steps), path)

    # ------------------------------------------------------------- lifecycle

    def _retire_unadmitted(self, req: Request, status: RequestStatus,
                           now: int) -> dict:
        """Finish event for a request dropped before it ever held a row or
        a block (shed / cancelled / timed out while queued)."""
        result = RequestResult(
            rid=req.rid, tokens=np.zeros(0, np.int32),
            logprobs=np.zeros(0, np.float32), finish_reason=status.value,
            arrival_step=req.arrival_step, admitted_step=-1,
            first_token_step=-1, finished_step=now, status=status)
        self.metrics.counter(
            "serve_requests_total", "Requests retired, by terminal status",
            labels={"status": status.value}).inc()
        self.tracer.request_retire(req.rid, status.value, step=now,
                                   n_tokens=0)
        return {"event": "finish", "rid": req.rid, "step": now,
                "result": result}

    def _retire_record(self, st: _RunState, sr: ScheduledRequest,
                       status: RequestStatus, now: int) -> dict:
        """Retire a scheduled record (running OR detached/preempted) with a
        non-OK status: blocks returned, row state cleared, any host spill
        entry dropped, partial output surfaced in the finish event."""
        row = sr.row
        st.sched.finish(sr, now)
        self.spill.discard(sr.rid)
        if row >= 0:
            st.tables[row] = kv_pool.NULL_BLOCK
            st.lens[row] = 0
            st.done[row] = True
        toks, lps = st.streams.pop(sr.rid, ([], []))
        result = RequestResult(
            rid=sr.rid, tokens=np.asarray(toks, np.int32),
            logprobs=np.asarray(lps, np.float32),
            finish_reason=status.value,
            arrival_step=sr.req.arrival_step,
            admitted_step=sr.admitted_step,
            first_token_step=sr.first_token_step,
            finished_step=sr.finished_step,
            ttft_seconds=self.last_run_ttft_seconds.get(
                sr.rid, float("nan")),
            status=status, n_preemptions=sr.n_preempt)
        self.metrics.counter(
            "serve_requests_total", "Requests retired, by terminal status",
            labels={"status": status.value}).inc()
        self.tracer.request_retire(sr.rid, status.value, step=now,
                                   n_tokens=len(toks))
        return {"event": "finish", "rid": sr.rid, "step": now,
                "result": result}

    def _preempt_one(self, st: _RunState, victim: ScheduledRequest,
                     now: int) -> Iterator[dict]:
        """Evict one running request, free its blocks, clear its row, and
        requeue it.  Three resume flavors, all bit-identical:

        * page_out (``preemption='page_out'``, victim not mid-chunked-
          prefill) — ``device_get`` the victim's live KV blocks (exact
          int8 codes+scales or fp bytes) plus its host cursors into the
          SpillStore; re-admission scatters them into fresh blocks and
          decode continues as if nothing happened.  No recompute, fp AND
          int8.  A mid-chunked-prefill victim's prompt is only partially
          resident, so it falls through to the recompute flavors below.
        * fp recompute — stash original prompt + every token generated so
          far as ``resume_prompt``; re-admission prefills the grown prompt
          in one pass and re-samples the pending (never-emitted) token at
          the same (key, rid, step) RNG triple.  Sound because fp decode
          and fp prefill read the same K/V values.
        * int8 recompute — full restart: the stream is discarded and the
          request re-admits from its original prompt with ``n_out = 0``.
          Decode reads *dequantized* codes, and the codes a prefill would
          write for generated positions come from fp-attention hidden
          states, so a stapled prefill cannot reproduce the interrupted
          stream; replaying the identical prefill-then-decode computation
          from scratch can, exactly.

        Emits the 'preempt' event plus any overload fallout (a shed
        arrival evicted from a full queue, or the victim itself dropped as
        PREEMPTED when the queue holds only preempted peers)."""
        sched = st.sched
        row = victim.row
        spill = (self.preemption == "page_out"
                 and not (self.chunked_prefill
                          and victim.state is State.PREFILL))
        if spill:
            # Spill exactly the blocks that hold written positions; any
            # growth-preallocated tail blocks past ctx hold no live state.
            ctx = int(st.lens[row])
            nb = kv_pool.blocks_for(max(ctx, 1), self.block_size)
            with self.telemetry.span("spill", cat="durability", step=now,
                                     rid=victim.rid, blocks=nb) as sp:
                entry = kv_pool.SpillEntry(
                    kv=kv_pool.extract_blocks(self.pages,
                                              victim.blocks[:nb]),
                    n_blocks=nb, ctx_len=ctx, n_out=victim.n_out,
                    pending_tok=int(st.tok[row]))
                self.spill.put(victim.rid, entry)
                sp.set(bytes=entry.nbytes)
            self.metrics.counter("serve_spills_total").inc()
            self.metrics.counter("serve_spill_bytes_total").inc(entry.nbytes)
            victim.resume_prompt = None
            requeued, evicted = sched.preempt(victim, now, spill_blocks=nb)
        else:
            emitted = st.streams.get(victim.rid, ([], []))
            if not self._int8_pool:
                victim.resume_prompt = np.concatenate(
                    [np.asarray(victim.req.prompt, np.int32),
                     np.asarray(emitted[0], np.int32)])
            requeued, evicted = sched.preempt(victim, now)
        st.tables[row] = kv_pool.NULL_BLOCK
        st.lens[row] = 0
        st.done[row] = True
        self.metrics.counter("serve_preemptions_total").inc()
        self.tracer.request_point(victim.rid, "preempt", step=now,
                                  n_out=victim.n_out, spilled=spill)
        yield {"event": "preempt", "rid": victim.rid, "step": now,
               "n_out": victim.n_out, "spilled": spill}
        if evicted is not None:
            self.metrics.counter("serve_sheds_total").inc()
            yield self._retire_unadmitted(evicted, RequestStatus.SHED, now)
        if not requeued:
            yield self._retire_record(st, victim,
                                      RequestStatus.PREEMPTED, now)
        elif not spill and self._int8_pool:
            st.streams.pop(victim.rid, None)
            victim.resume_prompt = None
            victim.n_out = 0

    def _grow(self, st: _RunState, sr: ScheduledRequest, target: int,
              now: int):
        """Grow sr's blocks to cover `target` positions, preempting
        newest-admitted victims until the pool yields (generator: preempt /
        shed events stream out; the grown block list is the return value,
        or None when sr itself had to be preempted — only reachable under
        fault-injected pool pressure, since submit() guarantees the oldest
        request's worst case fits a victim-free pool)."""
        while True:
            got = st.sched.ensure_capacity(sr, target)
            if got is not None:
                return got
            victim = st.sched.pick_victim(exclude_rid=sr.rid) or sr
            yield from self._preempt_one(st, victim, now)
            if victim is sr:
                return None

    def _cow_writes(self, st: _RunState, sr: ScheduledRequest, start: int,
                    end: int, now: int, tables: np.ndarray) -> Iterator[dict]:
        """Copy-on-write guard for a segment's upcoming writes: any block
        in sr's write span [start, end) still referenced elsewhere (a
        sharer's table or the prefix index holding it live) gets a private
        copy — alloc, device page copy, table swap, decref — BEFORE the
        dispatch that would scribble on it.  Admission already un-shares
        the only organically shared write target (the exact-hit tail), so
        this normally never fires; it is what turns 'decode never corrupts
        a sharer' from an argument into a checked property."""
        bs = self.block_size
        for i in range(start // bs,
                       min(kv_pool.blocks_for(end, bs), len(sr.blocks))):
            src = sr.blocks[i]
            if self.allocator.refcount(src) <= 1:
                continue
            while True:
                got = self.allocator.alloc(1)
                if got is not None:
                    break
                victim = st.sched.pick_victim(exclude_rid=sr.rid)
                if victim is None:
                    raise RuntimeError(
                        "copy-on-write guard: pool exhausted with no "
                        f"victim (rid={sr.rid}, block={src})")
                yield from self._preempt_one(st, victim, now)
            dst = got[0]
            with self.telemetry.span("cow_copy", cat="pool", step=now,
                                     rid=sr.rid, src=src, dst=dst):
                self.pages = self._launch(kv_pool.copy_block, self.pages,
                                          src, dst)
                sr.blocks[i] = dst
                tables[sr.row, i] = dst
                self.allocator.free([src])
            self.metrics.counter("serve_cow_copies_total").inc()

    # ------------------------------------------------------------ main loop

    def _serve_loop(self, st: _RunState, faults) -> Iterator[dict]:
        sched = st.sched
        plan = self.plan
        greedy, stop_w = st.greedy, st.stop_w
        rng = st.rng
        temp = jnp.asarray(max(st.temperature, 1e-6), jnp.float32)
        pad = jnp.asarray(-1, jnp.int32)
        seg_fn = self._segment_fn(plan, greedy, self.segment_len, stop_w)
        # Hot locals alias the run-state arrays; the only rebinding sites
        # (defrag's table rewrite, the post-segment harvest) sync st.*
        # immediately, so st is always the authoritative view the
        # preempt/retire helpers and the snapshot writer see.
        tok, n_out, lens, done = st.tok, st.n_out, st.lens, st.done
        rids, max_new, stops, tables = (st.rids, st.max_new, st.stops,
                                        st.tables)
        streams = st.streams
        now = st.now
        n_loops = st.n_loops
        n_stalled = 0
        chunked = self.chunked_prefill
        chunk = self.prefill_chunk
        mb = tok.shape[0]
        eligible_wall: dict[int, float] = {}
        tel = self.telemetry
        segments = self.metrics.counter("serve_segments_total")
        while sched.has_work:
            n_loops += 1
            t_round = time.perf_counter()
            poison_rids: set[int] = set()

            # Round-phase spans (schedule, inputs, the dispatch, harvest,
            # emit) are profiler-only and carry the index of the segment
            # this round dispatches and the sim clock.
            self._round_args = rnd = {"round": segments.value + 1,
                                      "step": now}
            with tel.span("schedule", chrome=False, **rnd):
                # ---- segment boundary: every device result is harvested
                # and host state is self-consistent — the ONLY place a
                # snapshot is sound.  Sync the run state, then (a)
                # checkpoint on the periodic cadence, (b) finish an
                # elapsed drain.
                st.tok, st.n_out, st.lens, st.done = tok, n_out, lens, done
                st.tables = tables
                st.now, st.n_loops = now, n_loops
                self._at_boundary = True
                if self._drain_req is not None and st.drain_at is None:
                    st.drain_at = now + self._drain_req[0]
                    st.drain_path = self._drain_req[1]
                    self._drain_req = None
                    self.tracer.instant(
                        "drain_start", cat="durability",
                        args={"step": now, "deadline": st.drain_at})
                if st.drain_at is not None and (now >= st.drain_at
                                                or not sched.running):
                    # Deadline hit or the batch quiesced: spill the
                    # stragglers (page_out — their KV rides the snapshot's
                    # spill section; other modes checkpoint them
                    # running/queued as-is), write the final snapshot, and
                    # end the run.
                    if self.preemption == "page_out":
                        while sched.running:
                            victim = sched.pick_victim()
                            yield from self._preempt_one(st, victim, now)
                    path = self._write_snapshot(st, path=st.drain_path)
                    self._at_boundary = False
                    yield {"event": "drain", "step": now, "path": path,
                           "running": len(sched.running),
                           "spilled": len(self.spill),
                           "queued": sched.queue_len}
                    return
                if (self.snapshot_interval
                        and (n_loops - 1) % self.snapshot_interval == 0):
                    self._write_snapshot(st)
                self._at_boundary = False

                # ---- fault hook: chaos actions ride the real code paths ----
                if faults is not None:
                    acts = faults.on_round(
                        n_loops - 1, now,
                        [sr.rid for sr in sched.running.values()],
                        [r.rid for r in sched.arrived]
                        + [s.rid for s in sched.preempted])
                    # Every injected action lands in the trace as a named
                    # instant, so a chaos run is visually replayable: the
                    # preemption storm that follows a fault:hide is right
                    # there on the timeline.
                    for ev_name, ev_args in faults_lib.describe(acts):
                        self.tracer.instant(ev_name, cat="fault",
                                            args={"step": now, **ev_args})
                    if acts.get("crash"):
                        # Simulated hard death: no retires, no finish
                        # events — recovery must come from the last
                        # snapshot file.
                        raise faults_lib.CrashPoint(n_loops - 1, now)
                    if acts.get("unhide"):
                        self.allocator.unhide_all()
                    if acts.get("hide"):
                        self.allocator.hide_blocks(int(acts["hide"]))
                    if acts.get("flush"):
                        # Drop every cached-free prefix entry: cache loss is
                        # always correctness-neutral (future admissions just
                        # miss), which is exactly what chaos should verify.
                        self.allocator.drop_cached()
                    for rid in acts.get("cancel", ()):
                        self._cancel_req.add(rid)
                    poison_rids = set(acts.get("poison", ()))
                    n_force = int(acts.get("preempt", 0))
                    if n_force and sched.preemptive:
                        for _ in range(n_force):
                            victim = sched.pick_victim()
                            if victim is None:
                                break
                            yield from self._preempt_one(st, victim, now)

                # ---- arrivals, overload shedding, cancels, deadlines -------
                if st.drain_at is None:
                    for req in sched.poll_arrivals(now):
                        self.metrics.counter("serve_sheds_total").inc()
                        yield self._retire_unadmitted(req, RequestStatus.SHED,
                                                      now)
                if self._cancel_req:
                    cancels = self.metrics.counter("serve_cancels_total")
                    for rid in sorted(self._cancel_req):
                        sr = next((s for s in sched.running.values()
                                   if s.rid == rid), None)
                        if sr is not None:
                            cancels.inc()
                            yield self._retire_record(
                                st, sr, RequestStatus.CANCELLED, now)
                            continue
                        obj = sched.remove_queued(rid)
                        if isinstance(obj, Request):
                            cancels.inc()
                            yield self._retire_unadmitted(
                                obj, RequestStatus.CANCELLED, now)
                        elif obj is not None:      # preempted, holds progress
                            cancels.inc()
                            yield self._retire_record(
                                st, obj, RequestStatus.CANCELLED, now)
                    self._cancel_req.clear()
                for sr in list(sched.running.values()) + list(sched.preempted):
                    dl = sr.req.deadline_steps
                    if dl is not None and now - sr.req.arrival_step >= dl:
                        self.metrics.counter("serve_timeouts_total").inc()
                        yield self._retire_record(
                            st, sr, RequestStatus.TIMEOUT, now)
                for req in [r for r in sched.arrived
                            if r.deadline_steps is not None
                            and now - r.arrival_step >= r.deadline_steps]:
                    sched.arrived.remove(req)
                    self.metrics.counter("serve_timeouts_total").inc()
                    yield self._retire_unadmitted(req, RequestStatus.TIMEOUT,
                                                  now)

                # TTFT clock: a request becomes eligible the first round the
                # sim reaches its arrival; wall TTFT is eligible -> first
                # sampled token harvested (so queueing behind a busy pool AND
                # head-of-line prefill stalls both count).
                for r in sched.arrived:
                    if r.rid not in eligible_wall:
                        eligible_wall[r.rid] = t_round
                        self.tracer.request_point(r.rid, "arrive", step=now)
                # Defrag policy: a fixed interval when configured (tests /
                # worst-case bounding), else adaptively whenever the live
                # span's hole fraction crosses the threshold — keeps block
                # tables contiguous for the fused kernel's sequential page
                # walks without paying a page permutation on every round.
                # The absolute hole-count floor stops a near-empty pool (one
                # live block at slot 2 -> ratio 0.5) from buying a full-pool
                # page permutation to relocate a couple of blocks.
                if self.defrag_interval:
                    if n_loops % self.defrag_interval == 0:
                        tables = st.tables = self._maybe_defrag(sched, tables,
                                                                now)
                elif (self.defrag_threshold is not None
                      and self.allocator.hole_blocks >= self.defrag_min_holes
                      and self.allocator.fragmentation()
                      >= self.defrag_threshold):
                    tables = st.tables = self._maybe_defrag(sched, tables, now)

                # ---- admission (fresh arrivals, recompute re-admits, AND
                # page-out restores); frozen while draining ----
                pending_tok0: list[tuple[ScheduledRequest, Any]] = []
                pf_wall = 0.0
                admits = [] if st.drain_at is not None else \
                    sched.admit_ready(now)
                for sr in admits:
                    row, req = sr.row, sr.req
                    rids[row] = req.rid
                    max_new[row] = req.max_new
                    stops[row] = -1
                    stops[row, :len(req.stop_tokens)] = req.stop_tokens
                    tables[row] = kv_pool.NULL_BLOCK
                    tables[row, :len(sr.blocks)] = sr.blocks
                    streams.setdefault(req.rid, ([], []))
                    had_cow = sr.cow_src >= 0
                    if had_cow:
                        # Exact-hit copy-on-write: the scheduler mapped a fresh
                        # dst block into the shared tail slot and decref'd the
                        # src; copy the cached page NOW — dispatch order puts
                        # this device copy ahead of any later prefill that
                        # could recycle the src page.
                        dst = sr.blocks[sr.pf_start // self.block_size]
                        with self.telemetry.span(
                                "cow_copy", cat="pool", step=now, rid=req.rid,
                                src=sr.cow_src, dst=dst):
                            self.pages = self._launch(
                                kv_pool.copy_block, self.pages, sr.cow_src,
                                dst)
                        self.metrics.counter("serve_cow_copies_total").inc()
                        sr.cow_src = -1
                    if self.prefix_cache and not sr.spilled:
                        if sr.shared_tokens > 0:
                            self.metrics.counter(
                                "serve_prefix_hits_total").inc()
                            self.metrics.counter(
                                "serve_prefix_hit_tokens_total").inc(
                                    sr.pf_start)
                            self.tracer.request_point(
                                req.rid, "prefix_hit", step=now,
                                shared_tokens=sr.shared_tokens,
                                suffix_start=sr.pf_start)
                        else:
                            self.metrics.counter(
                                "serve_prefix_misses_total").inc()
                    if sr.spilled:
                        # Page-out restore: scatter the spilled KV bytes into
                        # the freshly allocated blocks, restore the host
                        # cursors (incl. the pending sampled-but-unemitted
                        # token), and rejoin decode directly — no prefill, no
                        # recompute, bit-identical by construction.
                        entry = self.spill.pop(req.rid)
                        with self.telemetry.span(
                                "spill_restore", cat="durability", step=now,
                                rid=req.rid, blocks=entry.n_blocks,
                                bytes=entry.nbytes):
                            self.pages = kv_pool.insert_blocks(
                                self.pages, entry.kv, sr.blocks)
                        sr.spilled = False
                        sr.spill_blocks = 0
                        sr.state = State.DECODE
                        sr.ctx_len = entry.ctx_len
                        sr.n_out = entry.n_out
                        sr.pf_written = 0
                        n_out[row] = entry.n_out
                        lens[row] = entry.ctx_len
                        done[row] = False
                        tok[row] = entry.pending_tok
                        self.metrics.counter("serve_restores_total").inc()
                        self.tracer.request_point(req.rid, "restore", step=now,
                                                  row=row, n_out=sr.n_out)
                        # The restored bytes are the original prefill's bytes:
                        # re-index the prompt blocks for future sharers.
                        self._register_prefix(sr, entry.ctx_len)
                        yield {"event": "admit", "rid": req.rid, "step": now,
                               "recompute": False, "restored": True}
                        continue
                    n_out[row] = sr.n_out       # >0 on a recompute re-admit
                    if sr.n_preempt > 0:
                        self.metrics.counter("serve_recomputes_total").inc()
                    else:
                        self.metrics.histogram(
                            "serve_queue_delay_steps").observe(
                                now - req.arrival_step)
                    self.tracer.request_point(
                        req.rid, "resume" if sr.n_preempt > 0 else "admit",
                        step=now, row=row, blocks=len(sr.blocks))
                    if chunked:
                        # The (possibly resumed) prompt streams into the pool
                        # chunk by chunk inside the mixed segments; the row
                        # idles in the decode loop (done) until its final
                        # chunk samples the pending token.  Admission itself
                        # dispatches nothing.  A prefix-cache hit seeds the
                        # chunk cursor past the shared blocks (block-aligned),
                        # so chunking starts at the unique suffix.
                        sr.pf_written = sr.pf_start
                        sr.ctx_len = sr.pf_start
                        sr.cow_skip = had_cow
                        lens[row] = 0
                        done[row] = True
                        tok[row] = 0
                    else:
                        lens[row] = sr.cur_prompt_len
                        done[row] = False
                        t0 = time.perf_counter()
                        with self.telemetry.span("admit_prefill",
                                                 cat="prefill", step=now,
                                                 rid=req.rid):
                            pending_tok0.append(
                                (sr, self._admit(sr, plan, greedy, rng, temp,
                                                 skip_write=had_cow)))
                        pf_wall += time.perf_counter() - t0
                        self._register_prefix(sr, sr.cur_prompt_len)
                    yield {"event": "admit", "rid": req.rid, "step": now,
                           "recompute": sr.n_preempt > 0}
                if pending_tok0:
                    # ONE device->host transfer for the whole admission round:
                    # the per-request prefill dispatches pipeline on device and
                    # the round joins once, instead of each admission blocking
                    # on its own int(tok0[0]).
                    t0 = time.perf_counter()
                    with self.telemetry.span("admit_join", cat="prefill",
                                             step=now,
                                             n_requests=len(pending_tok0)):
                        vals = jax.device_get([t for _, t in pending_tok0])
                    self.metrics.counter("serve_host_syncs_total").inc()
                    for (sr, _), v in zip(pending_tok0, vals):
                        sr._tok0 = int(v[0])
                        tok[sr.row] = sr._tok0
                    # Dispatch + join time only: the run_stream consumer's
                    # per-event work between admissions is not prefill cost.
                    self.metrics.counter("serve_prefill_seconds_total").inc(
                        pf_wall + (time.perf_counter() - t0))
                self.metrics.gauge("serve_max_concurrency").set_max(
                    len(sched.running))
                # Pool / batch health sampled once per round: gauges carry the
                # latest value, bounded rings keep the raw per-round series,
                # and 'C' trace events render stacked charts in perfetto.
                stats = self.allocator.stats()
                self.metrics.gauge("serve_pool_occupancy").set(
                    stats["occupancy"])
                self.metrics.gauge("serve_pool_fragmentation").set(
                    stats["fragmentation"])
                self.metrics.gauge("serve_pool_shared_blocks").set(
                    stats["shared"])
                self.metrics.gauge("serve_pool_owned_blocks").set(
                    stats["owned"])
                self.metrics.gauge("serve_pool_cached_blocks").set(
                    stats["cached"])
                self.metrics.gauge("serve_running").set(len(sched.running))
                if self.telemetry.enabled:
                    self.telemetry.occupancy_trace.append(
                        (now, stats["occupancy"]))
                    self.telemetry.fragmentation_trace.append(
                        (now, stats["fragmentation"]))
                    ts_round = self.tracer.now()
                    self.tracer.counter(
                        "pool blocks", {"live": stats["live"],
                                        "free": stats["free"],
                                        "hidden": stats["hidden"],
                                        "shared": stats["shared"],
                                        "cached": stats["cached"]},
                        ts=ts_round)
                    self.tracer.counter(
                        "requests", {"running": len(sched.running),
                                     "queued": sched.queue_len}, ts=ts_round)

                if not sched.running:
                    if not sched.has_work:
                        break                   # everything retired this round
                    nxt = sched.next_arrival()
                    if nxt is not None and nxt > now:
                        now = nxt           # idle pool: jump to next arrival
                        n_stalled = 0
                        continue
                    # Admission blocked with nothing running (fault-hidden
                    # blocks, pathological max_queue): tick the clock and let
                    # the fault schedule advance; a bounded stall counter
                    # turns a genuine livelock into a loud failure.
                    now += 1
                    n_stalled += 1
                    if n_stalled > 10_000:
                        raise RuntimeError(
                            "scheduler stalled: nothing running and the "
                            "admission head cannot be admitted "
                            f"(free={self.allocator.free_blocks}, "
                            f"hidden={self.allocator.hidden_blocks})")
                    continue
                n_stalled = 0

                # ---- growth (oldest-first; may preempt newest-admitted) ----
                # Grow block tables to cover this segment's worst-case writes.
                # Mid-prefill rows need no growth — their prompt blocks were
                # allocated at admission and chunk-page writes past them land
                # on null-table entries; a row whose FINAL chunk lands this
                # segment starts decoding inside it, so it grows like a decode
                # row.  Oldest-admitted rows grow first: a growth failure
                # preempts the NEWEST victim, so the head of the FCFS line is
                # never starved by a younger request's growth.
                w_need = 1
                for sr in sorted(sched.running.values(),
                                 key=lambda s: s.admit_seq):
                    if sched.running.get(sr.row) is not sr:
                        continue               # preempted earlier this round
                    target = None
                    if chunked and sr.state is State.PREFILL:
                        cnt = min(chunk, sr.cur_prompt_len - sr.pf_written)
                        fin = sr.pf_written + cnt >= sr.cur_prompt_len
                        span = sr.pf_written + chunk
                        if fin:
                            span = max(span,
                                       sr.cur_prompt_len + self.segment_len)
                            target = sr.cur_prompt_len + self.segment_len
                    else:
                        span = int(lens[sr.row]) + self.segment_len
                        target = sr.ctx_len + self.segment_len
                    if target is not None:
                        new_blocks = yield from self._grow(st, sr, target, now)
                        if new_blocks is None:
                            continue       # self-preempted (fault pressure)
                        if new_blocks:
                            n_have = len(sr.blocks)
                            tables[sr.row,
                                   n_have - len(new_blocks):n_have] = \
                                new_blocks
                    if self.prefix_cache:
                        ws = (sr.pf_written
                              if chunked and sr.state is State.PREFILL
                              else int(lens[sr.row]))
                        yield from self._cow_writes(st, sr, ws, span, now,
                                                    tables)
                        if sched.running.get(sr.row) is not sr:
                            continue           # self-preempted under pressure
                    w_need = max(w_need,
                                 kv_pool.blocks_for(span, self.block_size))

                if not sched.running:
                    continue                   # the whole batch got preempted

            with tel.span("inputs", chrome=False, **rnd):
                # The prefill-chunk work list (rows still streaming their
                # prompt), built AFTER growth so preemption victims drop out.
                pf_rows: list[tuple[int, ScheduledRequest, int, bool]] = []
                if chunked:
                    for row, sr in sched.running.items():
                        if sr.state is State.PREFILL:
                            cnt = min(chunk,
                                      sr.cur_prompt_len - sr.pf_written)
                            fin = sr.pf_written + cnt >= sr.cur_prompt_len
                            pf_rows.append((row, sr, cnt, fin))

                # Poison vector: fault-injected NaN logits for these rids'
                # rows, applied inside the jitted step (traced arg — changing
                # targets never recompiles).
                poison_v = np.zeros(mb, bool)
                for row, sr in sched.running.items():
                    if sr.rid in poison_rids:
                        poison_v[row] = True

                # Dispatch only the live-width prefix of the tables: every
                # row's blocks (incl. this segment's growth and prefill-chunk
                # span) sit in the first w_need columns, so the device never
                # sees the pool-sized table tail.  The width is bucketed to a
                # power of two, bounding recompiles at O(log
                # max_blocks_per_req) while both the gather reference and the
                # fused kernel scale with live tokens instead of kv_blocks.
                w = min(tables.shape[1], autotune.next_pow2(w_need))
                seg_tables = np.ascontiguousarray(tables[:, :w])

                if pf_rows:
                    # Mixed batch, ONE dispatch: chunk-prefill prologue over a
                    # pow2-bucketed sub-batch of ONLY the prefilling rows +
                    # the decode segment for everyone else.  Padding slots
                    # point at a non-prefilling row (a masked no-op, see
                    # _mixed_segment_fn).
                    pb = min(mb, autotune.next_pow2(len(pf_rows)))
                    pf_set = {row for row, *_ in pf_rows}
                    pad_row = next((r for r in range(mb) if r not in pf_set),
                                   0)
                    pf_idx = np.full(pb, pad_row, np.int32)
                    pf_tok = np.zeros((pb, chunk), np.int32)
                    pf_pos = np.zeros(pb, np.int32)
                    pf_cnt = np.zeros(pb, np.int32)
                    pf_on = np.zeros(pb, bool)
                    pf_nw = np.zeros(pb, bool)
                    pf_fin = np.zeros(pb, bool)
                    pf_t0 = np.zeros(pb, np.int32)
                    for i, (row, sr, cnt, fin) in enumerate(pf_rows):
                        start = sr.pf_written
                        pf_idx[i] = row
                        pf_tok[i, :cnt] = sr.cur_prompt[start:start + cnt]
                        pf_pos[i] = start
                        pf_cnt[i] = cnt
                        pf_on[i] = True
                        pf_nw[i] = sr.cow_skip  # CoW dst already byte-exact
                        pf_fin[i] = fin
                        pf_t0[i] = sr.n_out     # >0: recompute re-admission
                    # The prologue's tables at their own tight width: just the
                    # prefilling rows' chunk spans, pow2-bucketed.  First-chunk
                    # rounds (all pos 0 — every short prompt) additionally
                    # skip the past gather entirely (static has_past hint).
                    pf_w_need = kv_pool.blocks_for(
                        int((pf_pos + pf_cnt).max()), self.block_size)
                    pf_w = min(tables.shape[1],
                               autotune.next_pow2(max(pf_w_need, 1)))
                    pf_tables = np.ascontiguousarray(tables[pf_idx, :pf_w])
                    has_past = bool(pf_pos.max() > 0)
                    mixed_fn = self._mixed_segment_fn(
                        plan, greedy, self.segment_len, stop_w, chunk, pb,
                        has_past)
                    args = (self.params, self.pages, seg_tables, pf_idx,
                            pf_tables, pf_tok, pf_pos, pf_cnt, pf_on, pf_nw,
                            pf_fin, pf_t0, tok, n_out, lens, done, rids,
                            max_new, stops, poison_v, rng, temp, pad)
                    fn, kind = mixed_fn, "mixed"
                else:
                    args = (self.params, self.pages, seg_tables, tok, n_out,
                            lens, done, rids, max_new, stops, poison_v, rng,
                            temp, pad)
                    fn, kind = seg_fn, "decode"

            # The Chrome segment span covers dispatch -> harvested (device
            # work + the one blocking join); a device profile has the
            # dispatch and harvest spans instead.  Harvest runs from the
            # dispatch's return and emit from harvest's end, so no host
            # work of the round falls between two phase spans.
            with tel.span("segment", profile=False, step=now,
                          index=rnd["round"], kind=kind) as seg_span:
                self._round_args = self._segment_args(plan, rnd, mb, w)
                outs = self._dispatch(fn, *args, name=kind + "_segment")
                self._round_args = rnd
                with tel.span("harvest", chrome=False, **rnd):
                    del args               # the old pool's last reference
                    (pages, tok_d, n_out_d, lens_d, done_d, failed_d, out_t,
                     out_lp, i_exec) = outs
                    self.pages = pages
                    segments.inc()
                    if pf_rows:
                        self.metrics.counter(
                            "serve_prefill_chunks_total").inc(len(pf_rows))
                    # ONE device->host transfer for the whole harvest
                    # (np.array copies: the row state is mutated on
                    # admit/finish and raw jax buffers are read-only); the
                    # pages stay device-resident.
                    (tok, n_out_new, lens, done, failed, out_t, out_lp,
                     i_exec) = (np.array(a) for a in jax.device_get(
                        (tok_d, n_out_d, lens_d, done_d, failed_d, out_t,
                         out_lp, i_exec)))
                    seg_span.set(rows_live=len(sched.running),
                                 rows_prefill=len(pf_rows),
                                 steps=int(i_exec), table_width=int(w),
                                 occupancy=stats["occupancy"],
                                 fragmentation=stats["fragmentation"])

            with tel.span("emit", chrome=False, **rnd):
                # The harvest rebinds the row arrays: re-point the run
                # state at the fresh copies so retires below (and the next
                # boundary's snapshot) mutate/see the live ones.
                st.tok, st.n_out, st.lens, st.done = (tok, n_out_new, lens,
                                                      done)
                self.metrics.counter("serve_host_syncs_total").inc()
                t_harvest = time.perf_counter()
                # sr.n_out still holds the pre-segment count until each row
                # is harvested.
                n_out = n_out_new
                for row, sr, cnt, fin in pf_rows:
                    sr.pf_written += cnt
                    sr.ctx_len = sr.pf_written
                    sr.cow_skip = False        # write-skip covers one chunk
                    self.tracer.request_point(
                        sr.rid, "prefill_chunk", step=now, n_tok=cnt,
                        written=sr.pf_written, final=fin)
                    if fin and not failed[row]:
                        # Index the prompt blocks only once the whole prompt
                        # landed cleanly (a poisoned/NaN final chunk must not
                        # publish pages future sharers would read).
                        self._register_prefix(sr, sr.pf_written)

                for row, sr in list(sched.running.items()):
                    if chunked and sr.state is State.PREFILL \
                            and sr.pf_written < sr.cur_prompt_len:
                        continue           # mid-prefill: nothing to harvest
                    cnt = int(n_out_new[row]) - sr.n_out
                    if cnt > 0:
                        if sr.n_out == 0:
                            sr.first_token_step = now + 1
                            ttft = (t_harvest
                                    - eligible_wall.get(sr.rid, t_harvest))
                            if sr.rid not in self.telemetry.ttft_seconds:
                                # First token ever for this rid: one histogram
                                # sample + one timeline milestone per request
                                # (an int8 full-restart recompute re-enters
                                # n_out==0 and would otherwise double-count).
                                self.metrics.histogram(
                                    "serve_ttft_seconds").observe(ttft)
                                self.tracer.request_point(
                                    sr.rid, "first_token", step=now + 1,
                                    ttft_s=ttft)
                            self.telemetry.ttft_seconds[sr.rid] = ttft
                        if sr.state is State.PREFILL:
                            sr.state = State.DECODE
                        streams[sr.rid][0].extend(
                            int(t) for t in out_t[row, :cnt])
                        streams[sr.rid][1].extend(
                            float(x) for x in out_lp[row, :cnt])
                        yield {"event": "tokens", "rid": sr.rid,
                               "step": now + cnt,
                               "tokens": list(out_t[row, :cnt]),
                               "logprobs": list(out_lp[row, :cnt])}
                    sr.n_out = int(n_out_new[row])
                    sr.ctx_len = int(lens[row])
                    if failed[row]:
                        # Non-finite logits quarantined this row mid-segment:
                        # its clean prefix was harvested above; the batch
                        # peers never saw the NaN.
                        self.metrics.counter("serve_failed_total").inc()
                        yield self._retire_record(
                            st, sr, RequestStatus.FAILED, now + cnt)
                    elif done[row]:
                        toks, lps = streams.pop(sr.rid)
                        # Stop wins ties (a stop token emitted ON the last
                        # allowed step), matching Engine.generate's done flag.
                        reason = ("stop" if toks and
                                  toks[-1] in sr.req.stop_tokens else "length")
                        sched.finish(sr, now + cnt)
                        # Hygiene: retired rows point at the null block with no
                        # valid positions until the row is reused.
                        tables[row] = kv_pool.NULL_BLOCK
                        lens[row] = 0
                        self.metrics.counter(
                            "serve_requests_total",
                            "Requests retired, by terminal status",
                            labels={"status": RequestStatus.OK.value}).inc()
                        self.metrics.histogram(
                            "serve_request_latency_steps").observe(
                                sr.finished_step - sr.req.arrival_step)
                        self.tracer.request_retire(
                            sr.rid, RequestStatus.OK.value,
                            step=sr.finished_step, n_tokens=len(toks),
                            finish_reason=reason)
                        result = RequestResult(
                            rid=sr.rid,
                            tokens=np.asarray(toks, np.int32),
                            logprobs=np.asarray(lps, np.float32),
                            finish_reason=reason,
                            arrival_step=sr.req.arrival_step,
                            admitted_step=sr.admitted_step,
                            first_token_step=sr.first_token_step,
                            finished_step=sr.finished_step,
                            ttft_seconds=self.last_run_ttft_seconds.get(
                                sr.rid, float("nan")),
                            status=RequestStatus.OK,
                            n_preemptions=sr.n_preempt)
                        yield {"event": "finish", "rid": sr.rid,
                               "step": sr.finished_step, "result": result}
                now += int(i_exec)

    # ---------------------------------------------------------------- admit

    def _admit(self, sr: ScheduledRequest, plan, greedy, rng, temp,
               skip_write: bool = False):
        """Blocking-prefill admission: bucketed prompt forward packed into
        the pool + first-token sample (one jitted dispatch, cached per
        bucket).  A recompute re-admission prefills ``sr.cur_prompt``
        (original prompt + generated-so-far) and samples at step
        ``sr.n_out``, reproducing the pending token the preemption
        discarded.  Returns the DEVICE tok0 array — the caller joins one
        admission round with a single batched device->host read instead of
        a per-request ``int(tok0[0])`` sync."""
        req = sr.req
        prompt = sr.cur_prompt
        if sr.pf_start > 0:
            # Prefix-cache hit: the mapped shared blocks already hold
            # positions [0, pf_start) (block-aligned), so only the unique
            # suffix runs through prefill_chunk — TTFT scales with the
            # suffix, not the prompt.  Same sampler fold as a full
            # prefill: bit-identical first token.
            s_len = sr.cur_prompt_len - sr.pf_start
            cw = autotune.next_pow2(
                kv_pool.blocks_for(s_len, self.block_size)) \
                * self.block_size
            tw_need = max(kv_pool.blocks_for(sr.cur_prompt_len,
                                             self.block_size),
                          len(sr.blocks))
            tw = min(self.max_blocks_per_req,
                     autotune.next_pow2(tw_need))
            toks = np.zeros((1, cw), np.int32)
            toks[0, :s_len] = prompt[sr.pf_start:]
            table = np.zeros((1, tw), np.int32)
            table[0, :len(sr.blocks)] = sr.blocks
            fn = self._suffix_prefill_fn(plan, greedy, cw, tw, skip_write)
            tok0, self.pages = self._dispatch(
                fn, self.params, self.pages, jnp.asarray(toks),
                jnp.asarray([sr.pf_start], jnp.int32),
                jnp.asarray([s_len], jnp.int32), jnp.asarray(table),
                jnp.asarray([req.rid], jnp.int32), rng,
                jnp.asarray([sr.n_out], jnp.int32), temp,
                name="suffix_prefill")
            self.metrics.counter("serve_prefills_total").inc()
            self.metrics.counter("serve_suffix_prefills_total").inc()
            return tok0
        batch = self.engine.bucket(
            {"tokens": jnp.asarray(prompt[None, :])})
        bucket_len = int(batch["tokens"].shape[1])
        with_length = "length" in batch
        bt_pf = np.zeros(kv_pool.blocks_for(bucket_len, self.block_size),
                         np.int32)
        bt_pf[:len(sr.blocks)] = sr.blocks
        fn = self._prefill_fn(plan, greedy, bucket_len, with_length)
        tok0, self.pages = self._dispatch(
            fn, self.params, self.pages, batch["tokens"],
            jnp.asarray(sr.cur_prompt_len, jnp.int32), bt_pf,
            jnp.asarray([req.rid], jnp.int32), rng,
            jnp.asarray(sr.n_out, jnp.int32), temp, name="prefill")
        self.metrics.counter("serve_prefills_total").inc()
        return tok0

    def _register_prefix(self, sr: ScheduledRequest, covered: int) -> None:
        """Publish sr's fully-written ORIGINAL-prompt blocks in the
        allocator's prefix index so later admissions can map them.  Caps
        at the original prompt: a recompute re-admission's regenerated
        suffix blocks hold this request's sampled history, not shareable
        prompt content (and in int8 mode decode-written pages would not
        be byte-identical to a prefill of the same tokens).  Existing
        keys are left in place — first writer wins, sharers no-op."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        n = min(int(covered), sr.req.prompt_len) // bs
        if n <= 0:
            return
        prompt = np.asarray(sr.req.prompt)
        for i, key in enumerate(kv_pool.prefix_keys(prompt[:n * bs], bs)):
            self.allocator.register_prefix(sr.blocks[i], key)


# ---------------------------------------------------------------------------
# Back-compat: the legacy hand-maintained ``last_run_*`` integers are now
# read-only views of the registry (one metric each).  Existing callers
# (benchmarks, launch printouts, tests) keep working unchanged; new code
# should read the registry / exports directly.
# ---------------------------------------------------------------------------

_RUN_METRIC_ATTRS = {
    "last_run_segments": "serve_segments_total",
    "last_run_prefills": "serve_prefills_total",
    "last_run_prefill_chunks": "serve_prefill_chunks_total",
    "last_run_dispatches": "serve_dispatches_total",
    "last_run_host_syncs": "serve_host_syncs_total",
    "last_run_defrags": "serve_defrags_total",
    "last_run_preemptions": "serve_preemptions_total",
    "last_run_recomputes": "serve_recomputes_total",
    "last_run_spills": "serve_spills_total",
    "last_run_spill_bytes": "serve_spill_bytes_total",
    "last_run_restores": "serve_restores_total",
    "last_run_snapshots": "serve_snapshots_total",
    "last_run_recoveries": "serve_recoveries_total",
    "last_run_sheds": "serve_sheds_total",
    "last_run_timeouts": "serve_timeouts_total",
    "last_run_cancels": "serve_cancels_total",
    "last_run_failed": "serve_failed_total",
    "last_run_max_concurrency": "serve_max_concurrency",
    "last_run_prefill_seconds": "serve_prefill_seconds_total",
    "last_run_prefix_hits": "serve_prefix_hits_total",
    "last_run_prefix_misses": "serve_prefix_misses_total",
    "last_run_prefix_hit_tokens": "serve_prefix_hit_tokens_total",
    "last_run_cow_copies": "serve_cow_copies_total",
    "last_run_suffix_prefills": "serve_suffix_prefills_total",
}


def _run_metric_property(metric: str) -> property:
    def read(self):
        return self.metrics.value(metric)
    read.__doc__ = f"Legacy run stat: reads the {metric!r} registry value."
    return property(read)


for _attr, _metric in _RUN_METRIC_ATTRS.items():
    setattr(ContinuousEngine, _attr, _run_metric_property(_metric))
del _attr, _metric
