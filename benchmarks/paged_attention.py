"""Fused paged-attention decode benchmark: kernel vs gather-dense.

Two measurements, one JSON (BENCH_PR4.json):

1. **Attention-level occupancy scan** — single-layer paged decode
   attention at 25 / 50 / 90% pool occupancy, fp32 and int8 pools, three
   arms:

   * ``gather_full``  — PR 3's shipped path: gather the FULL
     [B, max_blocks_per_req] block tables into a dense cache, then attend.
     Traffic is O(pool) regardless of live tokens.
   * ``gather_tight`` — the kept reference after this PR's fix: tables
     truncated to the live-page bound before dispatch (what the serve loop
     now does every segment), gather scales with live tokens.
   * ``fused``        — kernels/paged_attention: flash decoding over the
     table-referenced pages, int8 dequant in-registers, split-KV merge
     (compiled Pallas on TPU; the same-math vectorized emulation on CPU).

   Besides wall-clock tok/s the report carries an analytic KV-bytes-moved
   model per decode step, evaluated at the configured pool AND at a 2x
   pool with the same live tokens: the fused (and tight) bytes are
   invariant, the full-gather bytes double — decode attention traffic is
   O(live tokens), independent of ``kv_blocks``.

2. **End-to-end serve delta** — the PR 3 baseline ``serve_traffic`` smoke
   configuration through ``ContinuousEngine`` with the gather reference
   and with ``paged_attn=True``; decode tok/s for both.

On CPU absolute numbers are structural (kernels interpret/emulated); the
headline fields are the fused/gather ratios and the bytes model, which
transfer.  ``--check`` asserts the CI gate: fused decode tok/s >= the
gather-dense (full) path at every occupancy >= 50%.

``--kernel-scan`` replaces both with the decode kernel alone on a TPU:
``paged_attention_kernel`` over each ``--pages-per-step`` (0 = the
autotuner's) at each ``--widths`` table width, ``--layers`` back-to-back
calls per jitted program, reported per call with the share of the least
time of the live pages' bytes (HBM bandwidth from ``bench/peaks.json``).
Row contexts fill the table, or with ``--traffic`` are a prompt plus a
uniform share of an output drawn from a traffic file's length
distributions (``bench/traffic/*.json``); pages are scattered over the
pool and dead table entries point at null block 0.  A kernel without
``pages_per_step`` (one page per grid step) is timed once per width.

Usage:
  PYTHONPATH=src python benchmarks/paged_attention.py --smoke --check \
      --out BENCH_PR4.json
  PYTHONPATH=src python benchmarks/paged_attention.py --kernel-scan \
      --batch 32 --kv-heads 8 --groups 4 --head-dim 128 --kv-blocks 1498 \
      --traffic bench/traffic/chat-poisson.json --pages-per-step 1 2 4 8 0
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels import autotune
from repro.kernels.paged_attention import ops as pops
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.models import attention as attn_lib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "bench" / "peaks.json"


def build_pool(key, *, kv_blocks, block_size, kvh, head_dim, int8):
    shape = (kv_blocks, block_size, kvh, head_dim)
    k1, k2 = jax.random.split(key)
    if int8:
        def qt(k):
            codes = jax.random.randint(k, shape, -127, 128,
                                       jnp.int32).astype(jnp.int8)
            scale = jnp.full((*shape[:-1], 1), 0.05, jnp.bfloat16)
            return quant.QTensor(codes, scale)
        return qt(k1), qt(k2)
    return (jax.random.normal(k1, shape, jnp.float32),
            jax.random.normal(k2, shape, jnp.float32))


def live_layout(batch, nbr, block_size, occupancy, capacity):
    """Evenly-shared live pages at the target pool occupancy; returns
    (block tables [B, NBR], n_valid [B], live pages per row)."""
    live_total = max(batch, int(round(occupancy * capacity)))
    per_row = max(1, min(live_total // batch, nbr))
    tables = np.zeros((batch, nbr), np.int32)
    nxt = 1
    for row in range(batch):
        tables[row, :per_row] = np.arange(nxt, nxt + per_row)
        nxt += per_row
    n_valid = np.full((batch,), per_row * block_size, np.int32)
    return tables, n_valid, per_row


def kv_bytes_per_step(pages_touched, block_size, kvh, head_dim, int8):
    """Analytic KV traffic for one decode step (K + V reads)."""
    elems = pages_touched * block_size * kvh * head_dim
    per = 1 if int8 else 4
    scale = pages_touched * block_size * kvh * 2 if int8 else 0
    return 2 * elems * per + scale * 2


def fused_pages_touched(n_valid, block_size, nbr):
    """Pages the fused kernel fetches per request: it copies no page past
    a row's live length, so the walk touches min(ceil(n_valid / BS), nbr)
    pages — evaluated at the ACTUAL table width, so a regression to
    full-table walking shows up as pool-size-dependent bytes."""
    return int(sum(min(-(-int(v) // block_size), nbr) for v in n_valid))


def time_fn(fn, *args, iters):
    """Median seconds per call (autotune's shared timing methodology)."""
    return autotune.time_median_us(lambda: fn(*args), iters) / 1e6


def attention_scan(args):
    nbr = args.kv_blocks - 1        # engine default: max_blocks_per_req
    capacity = args.kv_blocks - 1
    h = args.kv_heads * args.groups
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (args.batch, 1, h, args.head_dim),
                          jnp.float32)

    ref_fn = jax.jit(lambda q, pk, pv, bt, nv:
                     attn_lib.attend_decode_paged(q, pk, pv, bt, nv))
    fus_fn = jax.jit(lambda q, pk, pv, bt, nv:
                     attn_lib.attend_decode_paged(q, pk, pv, bt, nv,
                                                  impl="fused"))

    rows = []
    nbr_2x = 2 * args.kv_blocks - 1
    for int8 in (False, True):
        pk, pv = build_pool(key, kv_blocks=args.kv_blocks,
                            block_size=args.block_size, kvh=args.kv_heads,
                            head_dim=args.head_dim, int8=int8)
        # Same live layout over a doubled pool: the fused arm's cost and
        # bytes must not move (the gather-full arm's double).
        pk2, pv2 = build_pool(key, kv_blocks=2 * args.kv_blocks,
                              block_size=args.block_size,
                              kvh=args.kv_heads, head_dim=args.head_dim,
                              int8=int8)
        for occ in args.occupancies:
            tables, n_valid, per_row = live_layout(
                args.batch, nbr, args.block_size, occ, capacity)
            bt_full = jnp.asarray(tables)
            bt_tight = jnp.asarray(tables[:, :per_row])
            nv = jnp.asarray(n_valid)

            t_full = time_fn(ref_fn, q, pk, pv, bt_full, nv,
                             iters=args.iters)
            t_tight = time_fn(ref_fn, q, pk, pv, bt_tight, nv,
                              iters=args.iters)
            t_fused = time_fn(fus_fn, q, pk, pv, bt_tight, nv,
                              iters=args.iters)
            t_fused_2x = time_fn(fus_fn, q, pk2, pv2, bt_tight, nv,
                                 iters=args.iters)
            mk = dict(block_size=args.block_size, kvh=args.kv_heads,
                      head_dim=args.head_dim, int8=int8)
            rows.append({
                "dtype": "int8" if int8 else "float32",
                "occupancy": occ,
                "live_tokens": int(n_valid.sum()),
                "tok_s_gather_full": args.batch / t_full,
                "tok_s_gather_tight": args.batch / t_tight,
                "tok_s_fused": args.batch / t_fused,
                "tok_s_fused_2x_pool": args.batch / t_fused_2x,
                "speedup_fused_vs_full": t_full / t_fused,
                "bytes_per_step_gather_full": kv_bytes_per_step(
                    args.batch * nbr, **mk),
                "bytes_per_step_gather_tight": kv_bytes_per_step(
                    args.batch * per_row, **mk),
                "bytes_per_step_fused": kv_bytes_per_step(
                    fused_pages_touched(n_valid, args.block_size, nbr),
                    **mk),
                # Same live tokens, 2x pool: fused invariant, full 2x.
                "bytes_per_step_gather_full_2x_pool": kv_bytes_per_step(
                    args.batch * nbr_2x, **mk),
                "bytes_per_step_fused_2x_pool": kv_bytes_per_step(
                    fused_pages_touched(n_valid, args.block_size, nbr_2x),
                    **mk),
            })
            print(f"[{rows[-1]['dtype']:7s} occ={occ:.2f}] "
                  f"full {rows[-1]['tok_s_gather_full']:9.1f} tok/s  "
                  f"tight {rows[-1]['tok_s_gather_tight']:9.1f}  "
                  f"fused {rows[-1]['tok_s_fused']:9.1f}  "
                  f"(x{rows[-1]['speedup_fused_vs_full']:.2f} vs full)")
    return rows


def draw_contexts(rng, batch, cap, traffic):
    """Per-row live tokens, clipped to the table's `cap` tokens: the whole
    table, or a prompt plus a uniform share of an output drawn from the
    traffic file's lognormal length distributions."""
    if traffic is None:
        return np.full((batch,), cap, np.int32)

    def draw(d):
        x = rng.lognormal(np.log(d["median"]), d["sigma"], batch)
        return np.clip(x, d["min"], d["max"])
    ctx = draw(traffic["prompt"]) + rng.uniform(0, 1, batch) \
        * draw(traffic["output"])
    return np.clip(ctx, 1, cap).astype(np.int32)


def kernel_inputs(args, width, rng):
    """The kernel's operands at one table width, and the live pages."""
    bs, kvh = args.block_size, args.kv_heads
    nv = draw_contexts(rng, args.batch, width * bs, args.traffic)
    live = -(-nv // bs)
    tables = np.zeros((args.batch, width), np.int32)
    for r in range(args.batch):
        tables[r, :live[r]] = rng.integers(1, args.kv_blocks, live[r])
    int8 = args.dtype == "int8"
    pk, pv = build_pool(jax.random.PRNGKey(args.seed),
                        kv_blocks=args.kv_blocks, block_size=bs, kvh=kvh,
                        head_dim=args.head_dim, int8=int8)
    if not int8:
        pk, pv = pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16)
    (kq, ks), (vq, vs) = pops._split_pages(pk), pops._split_pages(pv)
    q = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                          (args.batch, kvh, args.groups, args.head_dim),
                          jnp.bfloat16)
    return (q, kq, vq, ks, vs, jnp.asarray(tables), jnp.asarray(nv)), \
        int(live.sum())


def kernel_scan(args):
    """Per-call device time of the decode kernel alone, per pages_per_step
    and table width (one JSON row each)."""
    dev = jax.devices()[0]
    peaks = json.loads(PEAKS.read_text())["devices"].get(dev.device_kind, {})
    grouped = "pages_per_step" in inspect.signature(
        paged_attention_kernel).parameters
    rng = np.random.default_rng(args.seed)
    rows = []
    for width in args.widths:
        ops, live = kernel_inputs(args, width, rng)
        for p in args.pages_per_step if grouped else [None]:
            if p == 0:
                _, p = pops.decode_grouping(args.batch, ops[1], width,
                                            args.groups)
            kw = {} if p is None else {"pages_per_step": p}
            interpret = jax.default_backend() != "tpu"    # CPU: structural

            def layers(q, *rest, kw=kw):
                def body(_, q):
                    acc, _m, _l = paged_attention_kernel(
                        q, *rest, interpret=interpret, **kw)
                    return q + (1e-6 * acc.sum()).astype(q.dtype)
                return jax.lax.fori_loop(0, args.layers, body, q)
            fn = jax.jit(layers)
            us = autotune.time_median_us(lambda: fn(*ops), args.iters) \
                / args.layers
            # K + V pages (int8: one byte a value plus a bf16 scale a row).
            per_row = (2 * args.head_dim + 2 * 2 if args.dtype == "int8"
                       else 2 * args.head_dim * 2)
            nbytes = live * args.block_size * args.kv_heads * per_row
            row = {"device_kind": dev.device_kind, "dtype": args.dtype,
                   "batch": args.batch, "kv_heads": args.kv_heads,
                   "groups": args.groups, "block_size": args.block_size,
                   "width": width, "pages_per_step": kw.get(
                       "pages_per_step"), "call_us": us,
                   "live_pages": live}
            if "hbm_bytes_per_s" in peaks:
                row["bytes_roofline_pct"] = \
                    100 * nbytes / peaks["hbm_bytes_per_s"] / (us / 1e6)
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def serve_delta(args):
    """PR 3's serve_traffic smoke config, gather reference vs fused."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from serve_traffic import make_workload, run_continuous

    from repro import configs as cfg_lib
    from repro.core import backend as backend_lib
    from repro.models import model as model_lib
    from repro.serve import ContinuousEngine

    cfg = cfg_lib.reduced_config("qwen3-8b", n_layers=2)
    plan = backend_lib.load_plan("w8a8")
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    frozen = model_lib.freeze_params(params, a_scale=0.05, plan=plan)
    p_lo, p_hi, n_lo, n_hi = 4, 20, 8, 128
    block_size, seq_bucket = 8, 8
    max_blocks_per_req = -(-(p_hi + n_hi + seq_bucket) // block_size)
    reqs = make_workload(12, vocab=cfg.vocab, mean_interarrival=1.0,
                         prompt_lo=p_lo, prompt_hi=p_hi, new_lo=n_lo,
                         new_hi=n_hi, tail_frac=0.25, seed=0)
    useful = sum(r.max_new for r in reqs)
    out = {}
    for name, paged in (("reference", False), ("fused", True)):
        ce = ContinuousEngine(
            frozen, cfg, plan=plan, max_batch=4, kv_blocks=96,
            block_size=block_size, max_blocks_per_req=max_blocks_per_req,
            segment_len=8, seq_bucket=seq_bucket, paged_attn=paged)
        (wall, pf), res, metrics = run_continuous(ce, reqs,
                                                  iters=args.iters)
        assert len(res) == len(reqs)
        dec = wall - pf if wall > pf else wall
        out[f"serve_decode_tok_s_{name}"] = useful / dec
        out[f"serve_defrags_{name}"] = metrics["defrags"]
        out[f"serve_fragmentation_max_{name}"] = metrics[
            "fragmentation_max"]
        print(f"[serve|{name}] decode {useful / dec:.1f} tok/s "
              f"({metrics['defrags']} defrags)")
    out["serve_decode_speedup_fused_vs_reference"] = (
        out["serve_decode_tok_s_fused"]
        / out["serve_decode_tok_s_reference"])
    return out


def run_check(rows) -> None:
    """The CI gate over an occupancy scan (fresh or loaded from JSON)."""
    for row in rows:
        if row["occupancy"] >= 0.5:
            assert row["tok_s_fused"] >= row["tok_s_gather_full"], (
                f"fused paged attention must beat the full-table "
                f"gather-dense path at >= 50% occupancy, got "
                f"{row['tok_s_fused']:.1f} < "
                f"{row['tok_s_gather_full']:.1f} tok/s "
                f"({row['dtype']}, occ {row['occupancy']})")
        # Pool-size independence, two ways: the bytes model evaluated at
        # the 2x-pool table width (the index-map clamp must pick the live
        # bound, not the width), and the measured 2x-pool run (same live
        # layout, doubled pool) staying within noise of the 1x run.
        assert (row["bytes_per_step_fused"]
                == row["bytes_per_step_fused_2x_pool"]), \
            "fused bytes-moved must be independent of the pool size"
        assert (row["tok_s_fused_2x_pool"]
                >= 0.5 * row["tok_s_fused"]), (
            f"fused decode slowed down on a 2x pool with identical live "
            f"tokens ({row['tok_s_fused_2x_pool']:.1f} vs "
            f"{row['tok_s_fused']:.1f} tok/s) — paged traffic is no "
            f"longer O(live)")
    print("check OK: fused >= gather-dense at >= 50% occupancy, "
          "bytes and throughput independent of kv_blocks")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--groups", type=int, default=2,
                    help="GQA query heads per kv head")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--kv-blocks", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--occupancies", default="0.25,0.5,0.9")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: fewer timing iterations")
    ap.add_argument("--no-serve", action="store_true",
                    help="skip the end-to-end serve delta")
    ap.add_argument("--check", action="store_true",
                    help="assert fused >= gather-dense(full) decode tok/s "
                    "at every occupancy >= 0.5 (the CI gate)")
    ap.add_argument("--check-file", default=None, metavar="JSON",
                    help="run the --check assertions against an existing "
                    "report instead of re-benchmarking (CI re-asserts the "
                    "bench-smoke artifact this way)")
    ap.add_argument("--kernel-scan", action="store_true",
                    help="time the decode kernel alone (TPU) instead of "
                    "the occupancy scan and the serve delta")
    ap.add_argument("--pages-per-step", type=int, nargs="+", default=[1, 0],
                    help="kernel scan: pages per grid step (0 = the "
                    "autotuner's)")
    ap.add_argument("--widths", type=int, nargs="+", default=[64, 128],
                    help="kernel scan: block-table widths")
    ap.add_argument("--dtype", choices=("int8", "bf16"), default="int8",
                    help="kernel scan: pool dtype")
    ap.add_argument("--traffic", default=None, metavar="JSON",
                    help="kernel scan: draw row contexts from this traffic "
                    "file's prompt and output lengths")
    ap.add_argument("--layers", type=int, default=36,
                    help="kernel scan: calls per timed program")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_PR4.json")
    args = ap.parse_args()
    if args.smoke:
        args.iters = 5
    args.occupancies = [float(x) for x in args.occupancies.split(",")]

    if args.check_file:
        with open(args.check_file) as f:
            run_check(json.load(f)["occupancy_scan"])
        return
    if args.kernel_scan:
        if args.traffic:
            with open(args.traffic) as f:
                args.traffic = json.load(f)
        rows = kernel_scan(args)
        with open(args.out, "w") as f:
            json.dump({"bench": "paged_attention_kernel", "rows": rows}, f,
                      indent=2)
        return

    rows = attention_scan(args)
    report = {
        "bench": "paged_attention",
        "backend": jax.default_backend(),
        "interpret_kernels": jax.default_backend() != "tpu",
        "batch": args.batch,
        "kv_heads": args.kv_heads,
        "q_heads": args.kv_heads * args.groups,
        "head_dim": args.head_dim,
        "kv_blocks": args.kv_blocks,
        "block_size": args.block_size,
        "occupancy_scan": rows,
    }
    if not args.no_serve:
        report.update(serve_delta(args))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")

    if args.check:
        run_check(rows)


if __name__ == "__main__":
    main()
