"""Driving the engine's request stream and timing it from the client's side.

The engine offers load on its own step clock (``Request.arrival_step`` in
decode steps): ``run_stream`` takes every request up front and yields
events as the simulation advances.  Every time here is the wall clock at
which an event reached the harness; nothing is read from the engine's own
histograms.

* A request becomes *eligible* when the harness first sees the step clock
  at or past its arrival step (an event carrying that step).
* Its first token is the first ``tokens`` event for it; its last token the
  last such event.
* The window opens one mean request lifetime, in steps, after the first
  arrival, and then lasts ``seconds``.  Tokens count when their event arrives
  before the window's end.  At the end the stream is abandoned; the
  engine's ``finally`` frees the pool.
* With an int8 KV pool a preempted request restarts from its prompt (the
  engine discards its stream and serves it again), so its tokens so far
  are dropped: from its record, and from the window's count of tokens
  delivered.  The first token a request ever delivered keeps its time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable


@dataclasses.dataclass
class Req:
    rid: int
    arrival_step: int
    prompt_len: int
    max_new: int
    t_eligible: float | None = None
    admit_step: int | None = None
    t_first: float | None = None
    t_last: float | None = None
    n_tokens: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    logprobs: list = dataclasses.field(default_factory=list)
    t_finish: float | None = None
    status: str | None = None


@dataclasses.dataclass
class Window:
    """What one run recorded, by the harness's clock."""
    reqs: dict[int, Req]
    t_open: float = float("nan")
    t_close: float = float("nan")
    step_open: int = 0
    step_close: int = 0
    tokens_in_window: int = 0         # delivered and kept (see above)
    preempts_in_window: int = 0
    opened: bool = False
    # (time, rid, tokens, context before them) of every tokens event
    token_log: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def eligible_in_window(self) -> list[Req]:
        return [r for r in self.reqs.values() if r.t_eligible is not None
                and self.t_open <= r.t_eligible < self.t_close]

    def finished_in_window(self) -> list[Req]:
        return [r for r in self.reqs.values() if r.t_finish is not None
                and self.t_open <= r.t_finish < self.t_close]


def mean_lifetime_steps(reqs, chunk: int, segment_len: int) -> float:
    """Mean steps a request holds a row: its output, plus one segment per
    prefill chunk of its prompt."""
    life = [r["max_new"] + segment_len * -(-len(r["prompt"]) // chunk)
            for r in reqs]
    return sum(life) / max(len(life), 1)


class _Client:
    """The harness's side of one run: accounts each event as it arrives."""

    def __init__(self, records, open_after_steps, seconds, on_open,
                 before_close, clock, restart_on_preempt=True):
        self.records = records
        self.arrivals = sorted(records.values(), key=lambda r: r.arrival_step)
        self.first_arrival = (self.arrivals[0].arrival_step
                              if self.arrivals else 0)
        self.open_after = open_after_steps
        self.seconds, self.on_open = seconds, on_open
        self.before_close, self.pre_fired = before_close, False
        self.clock = clock
        self.restart = restart_on_preempt
        self.w = Window(reqs=records)
        self.next = 0                 # next request to become eligible
        self.sim_now = -1

    def event(self, ev: dict, t: float) -> None:
        w, step, kind = self.w, int(ev["step"]), ev["event"]
        if step > self.sim_now:
            self.sim_now = step
            while self.next < len(self.arrivals) \
                    and self.arrivals[self.next].arrival_step <= step:
                self.arrivals[self.next].t_eligible = t
                self.next += 1
        r = self.records.get(ev.get("rid"))
        if kind == "admit" and r.admit_step is None:
            r.admit_step = step
        elif kind == "tokens":
            n = len(ev["tokens"])
            if r.t_first is None:
                r.t_first = t
            w.token_log.append((t, r.rid, n, r.prompt_len + r.n_tokens))
            r.t_last = t
            r.n_tokens += n
            r.tokens.extend(int(x) for x in ev["tokens"])
            r.logprobs.extend(float(x) for x in ev["logprobs"])
            if w.opened:
                w.tokens_in_window += n
        elif kind == "finish":
            r.t_finish = t
            status = ev["result"].status
            r.status = getattr(status, "value", str(status))
        elif kind == "preempt":
            if w.opened:
                w.preempts_in_window += 1
            if self.restart and not ev.get("spilled"):
                if w.opened:
                    w.tokens_in_window -= sum(
                        n for tt, rid, n, _ in w.token_log
                        if rid == r.rid and tt >= w.t_open)
                r.tokens, r.logprobs, r.n_tokens = [], [], 0
        if w.opened:
            self._maybe_before_close(t)
        elif self.sim_now >= self.first_arrival + self.open_after:
            w.opened = True
            w.step_open = self.sim_now
            if self.on_open is not None:
                self.on_open(w)
            w.t_open = self.clock()
            self._maybe_before_close(w.t_open)

    def _maybe_before_close(self, t: float) -> None:
        bc = self.before_close
        if bc and not self.pre_fired \
                and t >= self.w.t_open + self.seconds - bc[0]:
            self.pre_fired = True
            bc[1](self.w)


def drive(engine, requests, records: dict[int, Req], *,
          open_after_steps: float, seconds: float,
          on_open: Callable[[Window], None] | None = None,
          before_close: tuple[float, Callable[[Window], None]] | None = None,
          annotate: bool = False, restart_on_preempt: bool = True,
          clock=time.perf_counter) -> Window:
    """Serve `requests` (the program's ``Request`` objects, whose ids key
    `records`) and record the window, which opens `open_after_steps`
    steps after the first arrival.  `on_open` runs as the window opens;
    ``before_close = (s, fn)`` runs `fn` once `s` seconds before its end.
    With `annotate`, the engine's host work between events and the
    harness's own are marked as profiler spans ``bench/engine`` and
    ``bench/client``.  `restart_on_preempt`: a preempted request starts
    over (the int8 pool's recompute)."""
    span = contextlib.nullcontext
    if annotate:
        import jax
        span = jax.profiler.TraceAnnotation
    d = _Client(records, open_after_steps, seconds, on_open, before_close,
                clock, restart_on_preempt)
    w = d.w
    gen = engine.run_stream(requests)
    try:
        while True:
            with span("bench/engine"):
                ev = next(gen, None)
            if ev is None:
                raise RuntimeError("the traffic ran out before the window "
                                   "closed: the mix needs more requests")
            t = clock()
            if w.opened and t >= w.t_open + seconds:
                break
            with span("bench/client"):
                d.event(ev, t)
    finally:
        gen.close()
    w.t_close = w.t_open + seconds
    w.step_close = d.sim_now
    return w
