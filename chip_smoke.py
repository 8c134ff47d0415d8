#!/usr/bin/env python3
"""Start-up check of the serving stack on a TPU, at full width.

On one chip it drives full-width qwen3-8b (36 layers,
d_model 4096, 32 query heads over 8 KV heads, head_dim 128, d_ff 12288,
vocab 151936, qk_norm; random weights from a seed) through the path the
benchmark measures: ``ContinuousEngine`` over an int8 paged KV pool with
the deployed plan ``{"default": "w8a8_kernel", "paged_attn": true}`` and
chunked prefill.  In order:

1. the first device is a TPU; anything else exits 1 with no result line;
2. the Pallas kernels of that path (``cim_matmul``, flash decode, flash
   prefill; fp and int8 pages) compiled at full width match their
   references on the chip, and every compiled kernel program holds a
   ``tpu_custom_call``, so no interpret or emulate path ran;
3. 8 seeded requests (prompts of 128 to 1024 tokens, 32 new tokens each)
   are served through the deployed plan, twice on one engine (cold, then
   warm), and once through the reference plan (``{"default": "w8a8"}``: the XLA int8
   matmul, gather attention, blocking prefill) on the same frozen
   weights;
4. a dense forward (fp K/V, no pool, no paged kernels) fed each prompt
   and then each engine's own tokens is the reference for every served
   position: first tokens must be its argmax or a near-tie, and the
   deployed engine's logprobs and tokens may stray from it no further
   than the reference engine's do, within the tolerances below.

Every check raises on failure, so a failing run exits non-zero and never
prints the result line.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device kind and count JAX reports.
Times printed are a smoke run's (set-up and compilation included where
stated), not a benchmark's.

    python chip_smoke.py
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "qwen3-8b"
SEED = 0
A_SCALE = 0.05          # static activation scale of every frozen linear
PROMPT_LENS = (128, 1000, 256, 777, 512, 1024, 384, 640)
NEW_TOKENS = 32
MAX_BATCH = 8
BLOCK_SIZE = 16
SEGMENT_LEN = 8
DEPLOYED = {"default": "w8a8_kernel", "paged_attn": True}
REFERENCE = {"default": "w8a8"}

# cim_matmul vs the int32 jnp reference: identical int8 codes and exact
# int32 sums, only the f32 epilogue's rounding order differs.
MATMUL_TOL = 1e-5
# Flash decode / prefill vs the gather path (max |diff| over max |ref|):
# the same f32 math over bf16 or dequantized int8 pages, but the MXU may
# run f32 dots as bf16 passes (2^-8 relative per product).
ATTN_TOL = 2e-2
# Prefill page bytes: the in-kernel quantizer is quantize_kv's formula, so
# only a value within f32 rounding of a .5 code boundary may differ by 1.
CODE_FLIP_FRAC = 1e-3
# Random weights give flat logits (the top two often within a few % of
# their rms in CPU rehearsals at reduced width), and one bf16 rounding that
# moves an activation across an int8 code boundary changes every later
# layer a little, so two correct paths that round differently part after a
# few tokens.  The served path is held to a dense forward (fp K/V, no
# pool, no paged kernels) fed each engine's own tokens: at every position
# it gives the distribution the token was drawn from and the logprob the
# engine read.
#
# Dense forward, deployed vs reference plan: rms of the logits' difference
# over the logits' rms.  The two matmuls agree exactly on identical inputs
# (the kernel checks), but XLA may skip a bf16 rounding inside a fusion
# (excess precision), and the fusions around a Pallas call differ from the
# all-XLA program's.  On a v5e, quantizing the first norm's output inside
# its fusion instead of from the rounded bf16 array moves 2.4% of the int8
# codes, and every later layer carries such a change on: the full smoke
# measured 0.077 on a v5e.  On the CPU (bf16, 8 layers) nudging the
# deployed matmul's input by 2^-9 gave 0.11, a 3% input scale error 0.17.
# So this bound only stops a gross fault (uncorrelated logits give ~1.4);
# small ones are the kernel checks' and the served-path bounds' to catch.
DENSE_RMS_TOL = 0.5
# A first token that differs from the reference's argmax is accepted only
# within this fraction of the logits' rms of the top (a near-tie): chunked
# prefill reads earlier chunks back from int8 pages (about 0.4% error per
# element) where the reference attends fp K/V.  Also what counts as a
# near-tie at later positions.
FLIP_MARGIN = 0.05
# ... or within FLIP_NOISE times the dense forwards' measured rms
# difference: two correct plans that round differently move each logit by
# about that much, so the top two swap when their gap is below about
# sqrt(2) * 3 of it (3 sigma over the 16 first tokens checked).
FLIP_NOISE = 4.0
# Later positions are held to the reference engine, a correct path with
# different rounding (gather attention with int8 q and probabilities, XLA
# matmuls): the deployed engine's mean |logprob error| against the dense
# forward may be at most this many times the reference engine's (or the
# floor, rounding level), and its share of argmax-or-near-tie tokens at
# most AGREE_SLACK lower.  In a CPU rehearsal at reduced width a decode
# that drops the newest token raised the mean 1.8x and cut the share by
# 0.14; a correct deployed engine matched the reference engine.
SERVED_RATIO = 1.5
SERVED_FLOOR = 0.01
AGREE_SLACK = 0.1

# A segment program holds the pool about four times: its argument, its
# output and about two pool-sized temporaries (at 1.70 GiB of pool, 2.92 to
# 3.63 GiB: benchmarks/compile_rehearsal.py); the headroom takes the rest.
POOL_COPIES = 4
HBM_HEADROOM = 1 << 30


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def peak_hbm(devices) -> float:
    """Highest ``peak_bytes_in_use`` over `devices`, in GiB."""
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices) / 2**30


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def token_loss(lg_ref, tok):
    """How far below the reference's top each token ranks, in units of
    the reference logits' rms: 0 for its argmax.  ``lg_ref`` [R, T, V],
    ``tok`` [R, T]."""
    import numpy as np
    rms = np.sqrt((lg_ref ** 2).mean(-1))
    picked = np.take_along_axis(lg_ref, tok[..., None], -1)[..., 0]
    return (lg_ref.max(-1) - picked) / rms


def rel_rms(got, want) -> float:
    """rms of ``got - want`` over the rms of ``want``."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean()))


def check_first_tokens(tag, lg_ref, tok, margin) -> None:
    """Each first token ``tok`` [R] is the argmax of ``lg_ref`` [R, V] or
    within `margin` of it."""
    import numpy as np
    lost = token_loss(lg_ref[:, None], tok[:, None])[:, 0]
    for r in np.flatnonzero(lost > 0):
        log(f"{tag} request {r}: first token {tok[r]} is {lost[r]:.4f} of "
            f"rms below the reference's top {lg_ref[r].argmax()} (tol "
            f"{margin:.4f})")
    check(bool((lost <= margin).all()), f"{tag}: a first token "
          "differs from the reference beyond the stated margin")


def compiled(fn, *args, reference: bool = False):
    """Compile ``jax.jit(fn)`` for `args`; a kernel program must hold a
    ``tpu_custom_call``.  Returns the compiled executable."""
    import jax
    if reference:
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn).lower(*args).compile()
    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
          "compiled program (an interpret or emulate path ran)")
    return exe


# ---------------------------------------------------------------------------
# Kernels at full width
# ---------------------------------------------------------------------------

def kernel_checks(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quant
    from repro.kernels.cim_matmul import ops as mm_ops
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention import ref as pa_ref

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 1), 64))

    # -- cim_matmul: every linear shape of the model, decode and prefill M
    d, f = cfg.d_model, cfg.d_ff
    for m in (8, 256):
        for k, n in ((d, f), (f, d)):
            w = jax.random.randint(next(keys), (k, n), -127, 128, jnp.int8)
            w_s = jax.random.uniform(next(keys), (n,), jnp.float32,
                                     1e-3, 2e-3)
            a_s = jnp.float32(A_SCALE)
            a_i8 = jax.random.randint(next(keys), (m, k), -127, 128,
                                      jnp.int8)
            a_f32 = jax.random.normal(next(keys), (m, k)) * 2.0
            for a in (a_i8, a_f32):
                def kern(a, w, a_s, w_s):
                    return mm_ops.cim_matmul(a, w, a_s, w_s)

                def ref(a, w, a_s, w_s):
                    a_q = a if a.dtype == jnp.int8 else quant.quantize(a, a_s)
                    acc = quant.int8_matmul_int32(a_q, w)
                    return acc.astype(jnp.float32) * (a_s * w_s)

                args = (a, w, a_s, w_s)
                got = compiled(kern, *args)(*args)
                want = compiled(ref, *args, reference=True)(*args)
                err = rel_err(got, want)
                log(f"cim_matmul {m}x{k}x{n} {a.dtype} input: rel err "
                    f"{err:.2e} (tol {MATMUL_TOL:g})")
                check(err <= MATMUL_TOL, f"cim_matmul {m}x{k}x{n} {a.dtype}")

    # -- paged attention pools: 8 rows x 64 pages, ragged lengths
    b, kvh, hd, bs = MAX_BATCH, cfg.n_kv_heads, cfg.resolved_head_dim, \
        BLOCK_SIZE
    h, w_pages = cfg.n_heads, 64
    nb = 1 + b * w_pages
    tables = (1 + np.random.default_rng(SEED).permutation(b * w_pages)
              ).reshape(b, w_pages).astype(np.int32)
    tables = jnp.asarray(tables)
    nv = jnp.asarray([1, 17, 130, 333, 512, 700, 1000, 1024], jnp.int32)
    shape = (nb, bs, kvh, hd)

    def pool(int8: bool):
        if int8:
            def qt():
                return quant.QTensor(
                    jax.random.randint(next(keys), shape, -127, 128,
                                       jnp.int8),
                    jax.random.uniform(next(keys), (*shape[:-1], 1),
                                       jnp.float32, 5e-3, 2e-2
                                       ).astype(jnp.bfloat16))
            return qt(), qt()
        return (jax.random.normal(next(keys), shape, jnp.bfloat16),
                jax.random.normal(next(keys), shape, jnp.bfloat16))

    for int8 in (False, True):
        tag = "int8" if int8 else "bf16"
        pk, pv = pool(int8)
        q = jax.random.normal(next(keys), (b, 1, h, hd), jnp.bfloat16)

        def decode(q, pk, pv, t, n):
            return pa_ops.paged_attention(q, pk, pv, t, n, backend="pallas")

        ref_fn = pa_ref.dequant_attention_ref if int8 else \
            pa_ref.paged_attention_ref
        args = (q, pk, pv, tables, nv)
        got = compiled(decode, *args)(*args)
        want = compiled(ref_fn, *args, reference=True)(*args)
        err = rel_err(got.astype(jnp.float32), want.astype(jnp.float32))
        log(f"flash decode {tag} pages: rel err {err:.2e} vs gather path "
            f"(tol {ATTN_TOL:g})")
        check(err <= ATTN_TOL, f"flash decode {tag}")

        # flash prefill: one chunk per row at a page-aligned start
        c = 64
        pos = jnp.asarray([0, 0, 64, 128, 256, 512, 896, 960], jnp.int32)
        n_tok = jnp.asarray([64, 5, 64, 33, 64, 17, 64, 64], jnp.int32)
        qc = jax.random.normal(next(keys), (b, c, h, hd), jnp.bfloat16)
        kn = jax.random.normal(next(keys), (b, c, kvh, hd), jnp.bfloat16)
        vn = jax.random.normal(next(keys), (b, c, kvh, hd), jnp.bfloat16)

        def prefill(qc, kn, vn, pk, pv, t, pos, n_tok):
            return pa_ops.paged_prefill(qc, kn, vn, pk, pv, t, pos, n_tok,
                                        backend="pallas")

        args = (qc, kn, vn, pk, pv, tables, pos, n_tok)
        got_o, got_k, got_v = compiled(prefill, *args)(*args)
        want_o, want_k, want_v = compiled(pa_ref.paged_prefill_ref, *args,
                                          reference=True)(*args)
        # rows attend only their valid chunk positions; compare those
        mask = (jnp.arange(c)[None, :] < n_tok[:, None])[..., None, None]
        err = rel_err(jnp.where(mask, got_o, 0).astype(jnp.float32),
                      jnp.where(mask, want_o, 0).astype(jnp.float32))
        log(f"flash prefill {tag} pages: rel err {err:.2e} vs "
            f"paged_prefill_ref (tol {ATTN_TOL:g})")
        check(err <= ATTN_TOL, f"flash prefill {tag}")
        for name, g, r in (("k", got_k, want_k), ("v", got_v, want_v)):
            live = np.ones(nb, bool)
            live[0] = False      # the null block's content is unspecified
            if int8:
                dq = np.abs(np.asarray(g.q, np.int32)[live]
                            - np.asarray(r.q, np.int32)[live])
                frac = float((dq > 0).mean())
                check(dq.max() <= 1 and frac <= CODE_FLIP_FRAC,
                      f"flash prefill {tag} {name} codes: max diff "
                      f"{dq.max()}, {frac:.2e} differ")
                check(rel_err(np.asarray(g.scale, np.float32)[live],
                              np.asarray(r.scale, np.float32)[live])
                      <= 1e-2, f"flash prefill {tag} {name} scales")
            else:
                check(np.array_equal(np.asarray(g)[live],
                                     np.asarray(r)[live]),
                      f"flash prefill {tag} {name} pages differ")
        log(f"flash prefill {tag} pages written: match the reference")


# ---------------------------------------------------------------------------
# Serving, one chip
# ---------------------------------------------------------------------------

def requests(cfg):
    import numpy as np

    from repro.serve import Request
    rng = np.random.default_rng(SEED)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n),
                    max_new=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]


def engine_kw(kv_blocks: int) -> dict:
    """ContinuousEngine settings of the smoke's engines."""
    return dict(max_batch=MAX_BATCH, kv_blocks=kv_blocks,
                block_size=BLOCK_SIZE, segment_len=SEGMENT_LEN,
                max_blocks_per_req=-(-(max(PROMPT_LENS) + NEW_TOKENS)
                                     // BLOCK_SIZE))


def blocks_for_bytes(cfg, free: int) -> int:
    """Int8 KV pool blocks that `free` bytes hold, at ``POOL_COPIES``
    pool-sized buffers per segment program and ``HBM_HEADROOM`` spare."""
    import jax

    from repro.serve import kv_pool
    one = jax.eval_shape(lambda: kv_pool.init_pages(cfg, 1, BLOCK_SIZE))
    per_block = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(one))
    return int((free - HBM_HEADROOM) // (POOL_COPIES * per_block))


def pool_blocks(cfg, device) -> int:
    """The HBM left after the weights, as int8 KV pool blocks."""
    stats = device.memory_stats()
    blocks = blocks_for_bytes(cfg, stats["bytes_limit"]
                              - stats["bytes_in_use"])
    need = 1 + MAX_BATCH * -(-(max(PROMPT_LENS) + NEW_TOKENS) // BLOCK_SIZE)
    check(blocks >= need, f"int8 KV pool: {blocks} blocks fit, {need} "
          "needed")
    return blocks


def forced_len(n_new: int) -> int:
    """Sequence length of the forced forward: the longest prompt plus
    `n_new` served tokens, rounded up to 32."""
    return -(-(max(PROMPT_LENS) + n_new) // 32) * 32


def forced_logits_fn(cfg, plan, n_new: int):
    """The jitted forward of :func:`forced_logits`: ``(params, tokens
    [R, forced_len(n_new)], start [R]) -> logits [R, n_new + 1, vocab]``
    at positions ``start ... start + n_new``."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    def fn(p, t, start):
        h, _ = M.forward(p, {"tokens": t}, cfg, mode=plan)
        idx = start[:, None] + jnp.arange(n_new + 1)
        h = jnp.take_along_axis(h, idx[..., None], axis=1)
        return M.logits_fn(p, h, cfg, plan)[..., :cfg.vocab]
    return jax.jit(fn)


def forced_logits(fn, params, reqs, toks):
    """One dense forward (fp K/V, no pool, no paged kernels) over each
    request's prompt followed by the served tokens ``toks`` [R, T]:
    logits [R, T + 1, vocab] at positions ``prompt_len - 1 ...
    prompt_len + T - 1``.  Entry t < T is the distribution token t was
    drawn from; entry t + 1 the one the engine read token t's logprob from
    (its next step).  `fn` is :func:`forced_logits_fn`'s."""
    import jax.numpy as jnp
    import numpy as np

    n_new = toks.shape[1]
    seq = np.zeros((len(reqs), forced_len(n_new)), np.int32)
    for i, r in enumerate(reqs):
        seq[i, :r.prompt_len] = r.prompt
        seq[i, r.prompt_len:r.prompt_len + n_new] = toks[i]
    start = np.asarray([r.prompt_len - 1 for r in reqs], np.int32)
    out = fn(params, jnp.asarray(seq), jnp.asarray(start))
    return np.asarray(out.astype(jnp.float32))


def served_error(lg, toks, lps):
    """(mean |served logprob - forward logprob| in units of the logits'
    rms, share of tokens that are the forward's argmax or within
    ``FLIP_MARGIN``) for one engine's tokens and logprobs against
    ``lg = forced_logits(..., toks)``."""
    import numpy as np
    nxt = lg[:, 1:].astype(np.float64)
    top = nxt.max(-1, keepdims=True)
    lse = top[..., 0] + np.log(np.exp(nxt - top).sum(-1))
    lp = np.take_along_axis(nxt, toks[..., None], -1)[..., 0] - lse
    dlp = np.abs(lps - lp) / np.sqrt((nxt ** 2).mean(-1))
    near = token_loss(lg[:, :-1], toks) <= FLIP_MARGIN
    return float(dlp.mean()), float(near.mean())


def serve_checks(cfg, dev, meter) -> None:
    import jax
    import numpy as np

    from repro.core import backend as backend_lib
    from repro.launch import serve
    from repro.serve import RequestStatus

    deployed = backend_lib.DeploymentPlan.from_json(json.dumps(DEPLOYED))
    reference = backend_lib.DeploymentPlan.from_json(json.dumps(REFERENCE))
    check(deployed.default == "w8a8_kernel" and deployed.paged_attn,
          "deployed plan")

    t0 = time.perf_counter()
    params = serve.build_params(cfg, deployed, seed=SEED, a_scale=A_SCALE)
    jax.block_until_ready(params)
    setup_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    p_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"set-up: frozen {cfg.name} ({cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab}), {n_params / 1e9:.3f}B "
        f"values, {p_bytes / 2**30:.3f} GiB on the device, built in "
        f"{setup_s:.1f}s; peak HBM {peak_hbm([dev]):.3f} GiB")

    kv_blocks = pool_blocks(cfg, dev)
    kw = engine_kw(kv_blocks)
    reqs = requests(cfg)
    n_prompt = sum(r.prompt_len for r in reqs)
    log(f"int8 KV pool: {kv_blocks} blocks of {BLOCK_SIZE} tokens; "
        f"{len(reqs)} requests, {n_prompt} prompt tokens, "
        f"{NEW_TOKENS} new tokens each")

    def finished(tag, ce, res, dt, c0):
        c1 = meter.snapshot()
        ok = [r.status is RequestStatus.OK and len(r.tokens) == NEW_TOKENS
              for r in res.values()]
        check(len(res) == len(reqs) and all(ok),
              f"{tag}: {sum(ok)}/{len(reqs)} requests served in full")
        toks = np.stack([np.asarray(res[r.rid].tokens) for r in reqs])
        lps = np.stack([np.asarray(res[r.rid].logprobs) for r in reqs])
        check(bool(np.isfinite(lps).all()), f"{tag}: non-finite logprobs")
        log(f"{tag}: {serve.continuous_report(ce, res, dt, tag)}")
        log(f"{tag}: {toks.size} tokens in {dt:.2f}s ({toks.size / dt:.1f} "
            f"tok/s, smoke, not a benchmark); {c1[0] - c0[0]} programs "
            f"compiled in {c1[1] - c0[1]:.1f}s ({c1[2] - c0[2]} persistent-"
            f"cache hits, {c1[3] - c0[3]} misses); peak HBM "
            f"{peak_hbm([dev]):.3f} GiB")
        return toks, lps

    def served(tag, plan, chunked, repeat=False):
        c0 = meter.snapshot()
        ce, res, dt = serve.serve_continuous(params, cfg, reqs, plan=plan,
                                             chunked_prefill=chunked,
                                             **kw)
        out = [finished(f"{tag} cold", ce, res, dt, c0)]
        if repeat:
            # The same engine again: every program is compiled by now.
            c0 = meter.snapshot()
            t0 = time.perf_counter()
            res = ce.run(reqs)
            out.append(finished(f"{tag} warm", ce, res,
                                time.perf_counter() - t0, c0))
            check(np.array_equal(out[0][0], out[1][0]),
                  f"{tag}: cold and warm runs emitted different tokens")
        del ce
        gc.collect()             # the engine's pool leaves the device
        return out[-1]

    toks_dep, lps_dep = served("deployed", deployed, True, repeat=True)
    toks_ref, lps_ref = served("reference", reference, False)

    fwd_ref = forced_logits_fn(cfg, reference, NEW_TOKENS)
    lg_dep = forced_logits(fwd_ref, params, reqs, toks_dep)
    lg_ref = forced_logits(fwd_ref, params, reqs, toks_ref)
    lg_kern = forced_logits(forced_logits_fn(cfg, deployed, NEW_TOKENS),
                            params, reqs, toks_dep)
    check(bool(np.isfinite(lg_dep).all() and np.isfinite(lg_ref).all()
               and np.isfinite(lg_kern).all()), "non-finite forward logits")

    # Every number is printed before any of these checks can stop the run.
    noise = rel_rms(lg_kern, lg_dep)
    noise_last = rel_rms(lg_kern[:, 0], lg_dep[:, 0])
    margin = max(FLIP_MARGIN, FLIP_NOISE * noise_last)
    log(f"dense forward, deployed vs reference plan: logits rel rms err "
        f"{noise:.3e} over {lg_dep.shape[1]} positions per request, "
        f"{noise_last:.3e} at the last prompt position (tol "
        f"{DENSE_RMS_TOL:g}); rel max err {rel_err(lg_kern, lg_dep):.3e}, "
        f"{rel_err(lg_kern[:, 0], lg_dep[:, 0]):.3e} at the last prompt "
        f"position; first-token margin {margin:.4f} of logit rms")
    d_dep, near_dep = served_error(lg_dep, toks_dep, lps_dep)
    d_ref, near_ref = served_error(lg_ref, toks_ref, lps_ref)
    log(f"served vs dense forward, {toks_dep.size} positions: mean "
        f"|logprob err| deployed {d_dep:.4f}, reference engine {d_ref:.4f} "
        f"of logit rms (tol {SERVED_RATIO:g}x, floor {SERVED_FLOOR:g}); "
        f"argmax-or-near-tie share deployed {near_dep:.4f}, reference "
        f"engine {near_ref:.4f} (slack {AGREE_SLACK:g})")
    agree = toks_dep == toks_ref
    prefix = [int(np.argmin(np.append(a, False))) for a in agree]
    log(f"token agreement, deployed vs reference engine: {agree.mean():.4f} "
        f"of {agree.size} positions; common prefix per request {prefix}; "
        f"first tokens {int(agree[:, 0].sum())}/{len(reqs)} identical")

    check(noise <= DENSE_RMS_TOL, "dense forward logits disagree")
    check_first_tokens("deployed engine", lg_ref[:, 0], toks_dep[:, 0],
                       margin)
    check_first_tokens("reference engine", lg_ref[:, 0], toks_ref[:, 0],
                       margin)
    check(d_dep <= max(SERVED_RATIO * d_ref, SERVED_FLOOR),
          "deployed engine logprobs stray further from the dense forward "
          "than the stated bound")
    check(near_dep >= near_ref - AGREE_SLACK, "deployed engine tokens "
          "follow the dense forward less often than the stated bound")
    log(f"served {len(reqs)} requests through w8a8_kernel + flash decode + "
        "flash prefill (chunked): checks passed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import runtime
    cache = runtime.enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.platform} ({dev.device_kind}); "
        f"compile cache {cache}")
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    meter = runtime.CompileMeter()

    from repro import configs as cfg_lib
    cfg = dataclasses.replace(cfg_lib.get_config(ARCH), kv_cache_dtype="int8")
    t0 = time.perf_counter()
    kernel_checks(cfg)
    serve_checks(cfg, dev, meter)
    log(f"all checks passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
