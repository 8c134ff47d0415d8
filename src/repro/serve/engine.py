"""Serving engine: batched prefill + device-resident decode with KV caches.

The engine wraps model.prefill / model.decode_step into a request-batched
greedy/temperature sampler:

* **Bucketed prefill** — prompt lengths are right-padded to `seq_bucket`
  multiples (with the true length threaded to model.prefill), so the jit
  cache holds one prefill per bucket instead of one per distinct prompt
  length.  Pads are causally invisible to real positions and the KV write
  cursor is rewound past them, so results match the unbucketed path up to
  shape-dependent XLA fusion rounding (measured ~1e-7 in logprobs; greedy
  tokens agree in practice).  Dense attention only — MoE capacity and SSM
  state depend on the padded token count.
* **Device-resident decode** — `generate` compiles prefill + the entire
  decode loop into ONE jitted function per (plan, bucket, greedy,
  max_new_tokens, stop_tokens): a `lax.while_loop` carries (token, done
  mask, caches, output buffers) across all `max_new_tokens` steps and
  early-exits once every sequence has emitted a stop token.  One
  host->device dispatch per `generate` call — the per-token Python loop of
  jitted steps (kept as ``decode_loop="eager"`` for parity tests and
  benchmarks) paid one dispatch + one device sync per token.
* **Stop tokens** — ``stop_tokens=`` marks sequences done once they emit
  any of the given ids; finished rows emit ``pad_token`` with logprob 0
  and the loop stops as soon as every row is done.
* **Batch-composition-independent sampling** — each row's sampler key is
  ``fold_in(fold_in(key, request_id), step)`` (``request_ids=``, default
  arange(B)), never a positional split of a batch key: the same request
  draws the same tokens whatever batch it shares.  This is what lets the
  continuous-batching driver (serve/server.py) join and retire requests
  mid-flight while staying token-identical to isolated `generate` calls.
* **Deployment plans** — the engine takes a
  :class:`~repro.core.backend.DeploymentPlan` (or a legacy mode string,
  which resolves through the same registry) and threads it through prefill
  and decode; `generate` can override it per call.  Plans with
  ``residency=True`` additionally keep activations int8-resident between
  quantized layers (see core/backend.py).

`dispatch_count` / `last_dispatch_count` count jitted executions (the
O(1)-dispatches contract is tested, not just claimed).

Production decode shapes are what launch/dryrun.py lowers for the roofline
(serve_step == decode_step by construction — the dry-run proves the full
engine step, not a toy).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import backend as backend_lib
from repro.models import model as model_lib


@dataclasses.dataclass
class GenerationResult:
    tokens: Any           # [B, T_new]
    logprobs: Any         # [B, T_new]
    steps: int            # decode steps actually executed (<= T_new)
    done: Any = None      # [B] bool: emitted a stop token (None: no stops)


class Engine:
    def __init__(self, params, cfg, *, max_len: int = 512, plan=None,
                 mode=None, seq_bucket: int = 32):
        if plan is None and mode is not None:
            plan = backend_lib.as_plan(mode)
        self.params = params
        self.cfg = cfg
        self.max_len = max_len
        self.plan = plan                  # DeploymentPlan | None (exact)
        self.seq_bucket = seq_bucket
        self._fn_cache: dict = {}
        # Host->device dispatch accounting (jitted executions).
        self.dispatch_count = 0           # lifetime
        self.last_dispatch_count = 0      # most recent generate() call

    def _dispatch(self, fn, *args):
        self.dispatch_count += 1
        self.last_dispatch_count += 1
        return fn(*args)

    # ------------------------------------------------------------------ jit

    def prefill_fn(self, plan):
        """Jitted model.prefill for this engine (once per plan).  Public:
        the continuous-batching driver and benchmarks reuse it."""
        key = ("prefill", plan)
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.jit(functools.partial(
                model_lib.prefill, cfg=self.cfg, max_len=self.max_len,
                mode=plan))
        return self._fn_cache[key]

    def make_sample(self, plan, greedy: bool):
        """sample(logits [B,V], rng, rids [B], t, temperature) -> [B] int32.

        Each row's key is fold_in(fold_in(rng, request_id), t): the draw
        depends only on (run key, request id, step), NEVER on the row's
        position or its batch neighbors — the same request sampled in any
        batch mix produces identical tokens.  `t` may be a scalar (static
        batch: all rows on the same step) or a [B] per-row step vector
        (continuous batching)."""
        del plan

        def sample(logits, rng, rids, t, temperature):
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), rids.shape)

            def row(lg, rid, tr):
                k = jax.random.fold_in(jax.random.fold_in(rng, rid), tr)
                return jax.random.categorical(
                    k, lg.astype(jnp.float32) / temperature)

            return jax.vmap(row)(logits, rids, t).astype(jnp.int32)

        return sample

    def make_step(self, plan, greedy: bool):
        """One fused decode+sample step.  Public: the continuous-batching
        segment loop reuses it verbatim — `caches` may be the dense per-call
        cache OR a paged-pool cache dict (block_tables/lens/write_mask), and
        `t` may be scalar or per-row.

        Returns ``(nxt, lp_tok, ok, caches)``: ``ok`` is a [B] bool that is
        False for any row whose logits came back non-finite (an overflowed
        activation, a poisoned weight) — the continuous engine quarantines
        such rows as FAILED instead of letting one NaN corrupt the batch.
        ``poison`` ([B] bool, fault injection) overwrites a row's logits
        with NaN *before* the finite check, exercising the guard through
        the real datapath."""
        cfg = self.cfg
        sample = self.make_sample(plan, greedy)

        def step(params, tok, caches, rng, rids, t, temperature,
                 poison=None):
            """decode + logprob-of-tok + next-token sample, all on device."""
            logits, caches = model_lib.decode_step(
                params, {"tokens": tok[:, None]}, caches, cfg, mode=plan)
            with jax.named_scope("sample"):    # profile metadata only
                last = logits[:, -1]
                if poison is not None:
                    last = jnp.where(poison[:, None], jnp.nan, last)
                ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)),
                             axis=-1)
                lp = jax.nn.log_softmax(last.astype(jnp.float32))
                lp_tok = jnp.take_along_axis(lp, tok[:, None],
                                             axis=-1)[:, 0]
                nxt = sample(last, rng, rids, t, temperature)
                # Quarantined rows must still carry well-defined values
                # through the jitted loop (NaN would propagate into buffers
                # the caller keeps); the engine retracts their emission
                # host-side.
                nxt = jnp.where(ok, nxt, 0)
                lp_tok = jnp.where(ok, lp_tok, 0.0)
            return nxt, lp_tok, ok, caches

        return step

    def _fns(self, plan, greedy: bool):
        """(prefill, sample, step) for the eager loop; jitted per
        (plan, greedy)."""
        prefill = self.prefill_fn(plan)
        key = ("eager", plan, greedy)
        if key not in self._fn_cache:
            self._fn_cache[key] = (
                prefill,
                jax.jit(self.make_sample(plan, greedy)),
                jax.jit(self.make_step(plan, greedy)),
            )
        return self._fn_cache[key]

    def _gen_fn(self, plan, greedy: bool, max_new: int,
                stop_tokens: tuple[int, ...] | None):
        """ONE jitted function: prefill + the whole decode loop.

        The decode loop is a lax.while_loop whose carry holds the current
        token, per-sequence done mask, KV caches, and the stacked
        token/logprob output buffers; with stop tokens the predicate also
        early-exits once every row is done.  Compiled once per
        (plan, greedy, max_new, stop_tokens) x input bucket — `generate`
        then costs exactly one host->device dispatch.
        """
        key = ("gen", plan, greedy, max_new, stop_tokens)
        if key in self._fn_cache:
            return self._fn_cache[key]
        cfg, max_len = self.cfg, self.max_len
        sample = self.make_sample(plan, greedy)
        step = self.make_step(plan, greedy)

        def gen(params, batch, rng, rids, temperature, pad_token):
            logits, caches = model_lib.prefill(
                params, batch, cfg, max_len=max_len, mode=plan)
            tok = sample(logits[:, -1], rng, rids,
                         jnp.asarray(0, jnp.int32), temperature)
            b = tok.shape[0]
            toks = jnp.full((b, max_new), pad_token, jnp.int32)
            lps = jnp.zeros((b, max_new), jnp.float32)
            done = jnp.zeros((b,), bool)
            stop = (None if stop_tokens is None
                    else jnp.asarray(stop_tokens, jnp.int32))

            def cond(carry):
                t, _, done, *_ = carry
                live = t < max_new
                if stop is not None:
                    live = live & ~jnp.all(done)
                return live

            def body(carry):
                t, tok, done, caches, toks, lps = carry
                # Finished rows emit pads and their logprob gather is
                # masked; once ALL rows finish the while predicate stops
                # the loop entirely.
                toks = toks.at[:, t].set(jnp.where(done, pad_token, tok))
                nxt, lp, _, caches = step(params, tok, caches, rng, rids,
                                          t + 1, temperature)
                lps = lps.at[:, t].set(jnp.where(done, 0.0, lp))
                if stop is not None:
                    done = done | jnp.any(tok[:, None] == stop[None, :], -1)
                return (t + 1, nxt, done, caches, toks, lps)

            t, _, done, _, toks, lps = jax.lax.while_loop(
                cond, body,
                (jnp.asarray(0, jnp.int32), tok, done, caches, toks, lps))
            return toks, lps, done, t

        fn = jax.jit(gen)
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------- prefill

    def bucket(self, batch: dict) -> dict:
        """Right-pad the prompt to a seq_bucket multiple when the arch
        supports length-aware prefill; otherwise return batch unchanged.

        Dense attention only: pads are causally invisible there, but MoE
        capacity is computed from the (padded) token count, so bucketing
        could drop real tokens; SSM state would integrate the pads."""
        if (self.seq_bucket <= 1
                or set(batch) != {"tokens"}
                or self.cfg.arch_type != "dense"
                or self.cfg.sliding_window is not None):
            return batch
        s = batch["tokens"].shape[1]
        s_pad = min(-(-s // self.seq_bucket) * self.seq_bucket, self.max_len)
        if s_pad <= s:
            return batch
        return {
            "tokens": jnp.pad(batch["tokens"], ((0, 0), (0, s_pad - s))),
            "length": jnp.asarray(s, jnp.int32),
        }

    # ------------------------------------------------------------ generate

    def generate(self, batch: dict, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, key=None, plan=None,
                 stop_tokens: Sequence[int] | None = None,
                 pad_token: int = 0, request_ids=None,
                 decode_loop: str = "scan") -> GenerationResult:
        """Generate up to `max_new_tokens` per sequence.

        decode_loop='scan' (default) runs prefill + the whole decode loop
        as ONE jitted device call; 'eager' is the legacy per-token Python
        loop (one dispatch per token), kept as the parity/benchmark
        reference.  `stop_tokens` marks a row done once it emits any of
        the ids; finished rows emit `pad_token` with logprob 0.

        `request_ids` ([B] ints, default arange(B)) seed each row's
        sampler: row keys are fold_in(fold_in(key, request_id), step), so a
        request's tokens depend only on (key, its id) — not on which batch
        it happens to share (see make_sample).
        """
        plan = self.plan if plan is None else backend_lib.as_plan(plan)
        greedy = temperature <= 0 or key is None
        rng = key if key is not None else jax.random.PRNGKey(0)
        temp = jnp.asarray(max(temperature, 1e-6), jnp.float32)
        # Batch size from the token/embedding leaf — NOT an arbitrary tree
        # leaf: a pre-bucketed batch also carries a scalar 'length'.
        for lead in ("tokens", "embeds", "frames"):
            if lead in batch:
                b = batch[lead].shape[0]
                break
        else:
            raise ValueError(f"batch has no sequence input: {set(batch)}")
        rids = (jnp.arange(b, dtype=jnp.int32) if request_ids is None
                else jnp.asarray(request_ids, jnp.int32))
        stops = None if stop_tokens is None else \
            tuple(int(t) for t in stop_tokens)
        self.last_dispatch_count = 0

        if decode_loop == "scan":
            fn = self._gen_fn(plan, greedy, max_new_tokens, stops)
            toks, lps, done, t = self._dispatch(
                fn, self.params, self.bucket(batch), rng, rids, temp,
                jnp.asarray(pad_token, jnp.int32))
            # Without stop tokens the loop always runs to max_new_tokens;
            # reading `t` would force a host sync and make the one-dispatch
            # call blocking, so only materialize it when early exit exists.
            return GenerationResult(
                tokens=toks, logprobs=lps,
                steps=max_new_tokens if stops is None else int(t),
                done=None if stops is None else done)
        if decode_loop != "eager":
            raise ValueError(f"decode_loop must be 'scan' or 'eager', "
                             f"got {decode_loop!r}")

        # ---- eager reference loop (one jitted dispatch per token) --------
        prefill, sample, step = self._fns(plan, greedy)
        logits, caches = self._dispatch(prefill, self.params,
                                        self.bucket(batch))
        tok = self._dispatch(sample, logits[:, -1], rng, rids,
                             jnp.asarray(0, jnp.int32), temp)
        done = jnp.zeros((b,), bool)
        stop = None if stops is None else jnp.asarray(stops, jnp.int32)
        toks, lps = [], []
        steps = 0
        for t in range(max_new_tokens):
            # Without stop tokens `done` is constant False: append
            # unmasked so the baseline loop stays exactly the pre-scan
            # per-token loop (no extra un-jitted device ops per step).
            toks.append(tok if stop is None
                        else jnp.where(done, pad_token, tok))
            nxt, lp, _, caches = self._dispatch(
                step, self.params, tok, caches, rng, rids,
                jnp.asarray(t + 1, jnp.int32), temp)
            lps.append(lp if stop is None else jnp.where(done, 0.0, lp))
            if stop is not None:
                done = done | jnp.any(tok[:, None] == stop[None, :], -1)
            tok = nxt
            steps = t + 1
            if stop is not None and bool(jnp.all(done)):
                break
        pad_col = jnp.full((b,), pad_token, jnp.int32)
        zero_col = jnp.zeros((b,), jnp.float32)
        toks += [pad_col] * (max_new_tokens - len(toks))
        lps += [zero_col] * (max_new_tokens - len(lps))
        return GenerationResult(
            tokens=jnp.stack(toks, axis=1),
            logprobs=jnp.stack(lps, axis=1),
            steps=steps,
            done=None if stops is None else done,
        )
