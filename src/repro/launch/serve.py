"""Production serving launcher: pjit'd prefill/decode on a device mesh with
the W8A8 (CiM) datapath.

Usage:
  python -m repro.launch.serve --arch qwen3-8b --devices 8 --mesh-shape 4,2 \
      --batch 8 --tokens 16 [--quant w8a8] [--plan plan.json]
  python -m repro.launch.serve --arch qwen3-8b --continuous --devices 1 \
      --batch 16 --max-batch 8 --kv-blocks 128 --segment-len 8

--plan takes a DeploymentPlan (backend name, inline JSON, or a JSON file)
for per-layer mixed deployment; --quant w8a8 is shorthand for the default
all-w8a8 plan.

--continuous serves a synthetic Poisson request stream through the
continuous-batching engine (serve/server.py): paged KV pool of --kv-blocks
x --block-size tokens, up to --max-batch concurrent requests, decode in
jitted segments of --segment-len steps (single-device data path for now;
--batch is the number of requests in the stream).

The arch runs at the reduced smoke widths unless --full-width is given.
--devices is the virtual-device count when JAX_PLATFORMS=cpu; on an
accelerator the mesh spans the devices present, and a --mesh-shape that
does not fit them is re-derived (runtime.fit_mesh_shape).  The functions
below main() are the entry points chip_smoke.py drives.
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU devices (JAX_PLATFORMS=cpu only)")
    ap.add_argument("--mesh-shape", default="4,2")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--quant", default="none", choices=["none", "w8a8"])
    ap.add_argument("--plan", default=None,
                    help="DeploymentPlan: backend name, inline JSON, or path")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a paged KV pool")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="continuous: concurrent request rows")
    ap.add_argument("--kv-blocks", type=int, default=128,
                    help="continuous: KV pool blocks (incl. null block)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="continuous: tokens per KV block")
    ap.add_argument("--segment-len", type=int, default=8,
                    help="continuous: decode steps per jitted segment")
    ap.add_argument("--paged-attn", action="store_true",
                    help="continuous: fused flash-decoding paged-attention "
                    "kernel (in-kernel int8 KV dequant, split-KV) instead "
                    "of gather+attend")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="continuous: stream each prompt into the paged "
                    "pool --prefill-chunk tokens per mixed segment (one "
                    "dispatch serves prefill AND decode; admission never "
                    "blocks the loop) instead of a blocking B=1 prefill "
                    "per admission")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous: tokens per prefill chunk (block-size "
                    "multiple; default: autotuned)")
    ap.add_argument("--preemption", default="recompute",
                    choices=["off", "recompute", "page_out"],
                    help="continuous: 'recompute' admits on actual prompt "
                    "blocks and evicts+recomputes the newest request when "
                    "KV growth fails; 'page_out' spills the victim's KV "
                    "pages to host memory and scatters them back on "
                    "re-admission (zero recompute, bit-identical resume); "
                    "'off' reserves worst-case blocks at admission "
                    "(preemption-free baseline)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous: content-addressable KV pool — cached "
                    "prompt-prefix blocks are shared into new requests at "
                    "refcount+1 and only the unique suffix is prefilled "
                    "(requires a preemptive mode); the synthetic stream "
                    "then gives 80%% of requests a common system prefix "
                    "so hits actually occur")
    ap.add_argument("--snapshot-dir", default=None,
                    help="continuous: directory for engine checkpoints; "
                    "with --snapshot-interval the run writes serve_snap.npz "
                    "at every Nth segment boundary (crash-recoverable)")
    ap.add_argument("--snapshot-interval", type=int, default=None,
                    help="continuous: scheduler rounds between periodic "
                    "snapshots (requires --snapshot-dir)")
    ap.add_argument("--drain-deadline", type=int, default=None,
                    help="continuous: graceful-shutdown demo — at the "
                    "first completion stop admissions, give in-flight "
                    "requests this many sim steps, spill/checkpoint the "
                    "stragglers, and end the run with a final snapshot "
                    "(serve the remainder later with --restore)")
    ap.add_argument("--restore", default=None,
                    help="continuous: cold-start from this snapshot file "
                    "instead of a fresh request stream — resumes every "
                    "in-flight request bit-identically")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="continuous: bound the admission queue; arrivals "
                    "beyond the bound are load-shed (default: unbounded)")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="continuous: retire any request still unfinished "
                    "this many decode steps after arrival as TIMEOUT")
    ap.add_argument("--metrics-out", default=None,
                    help="continuous: write the run's metrics registry "
                    "here (.json -> snapshot, else Prometheus text)")
    ap.add_argument("--trace-out", default=None,
                    help="continuous: write the run's event timeline here "
                    "(.jsonl -> one event per line, else Chrome "
                    "trace-event JSON for perfetto / chrome://tracing)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="continuous: disable the tracer and raw rings "
                    "(registry counters stay live; the token stream is "
                    "identical either way)")
    ap.add_argument("--profiler-annotations", action="store_true",
                    help="continuous: also open every engine span as a "
                    "jax.profiler.TraceAnnotation serve/<span> (round "
                    "phases schedule, inputs, harvest, emit; dispatches "
                    "decode_segment, mixed_segment, prefill, ...; "
                    "cow_copy, defrag, ...), on the device ops' clock in "
                    "a captured profile")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the arch at its published widths instead "
                    "of the reduced smoke config")
    args = ap.parse_args()

    from repro.launch import runtime
    runtime.force_host_devices(args.devices)
    runtime.enable_compile_cache()

    from repro import configs as cfg_lib
    from repro.core import backend as backend_lib
    from repro.models import model as M

    cfg = (cfg_lib.get_config(args.arch) if args.full_width
           else cfg_lib.reduced_config(args.arch))
    plan = None
    if args.plan is not None:
        plan = backend_lib.load_plan(args.plan)
    elif args.quant == "w8a8":
        plan = M.DEFAULT_DEPLOY_PLAN
    tag = "plan" if args.plan is not None else args.quant

    if not args.continuous:
        import jax
        shape = runtime.fit_mesh_shape(
            tuple(int(x) for x in args.mesh_shape.split(",")),
            len(jax.devices()))
        mesh = mesh_for(shape)
        param_sh = param_shardings(cfg, plan, mesh)
        params = build_params(cfg, plan, shardings=param_sh)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab)
        t0 = time.perf_counter()
        generate_on_mesh(params, cfg, plan, mesh, param_sh, prompts,
                         args.tokens)
        dt = time.perf_counter() - t0
        total = args.batch * args.tokens
        print(f"[{tag}] served {total} tokens on {mesh.size} devices "
              f"(mesh {dict(mesh.shape)}) in {dt:.2f}s "
              f"({total/dt:.1f} tok/s incl. compile)")
        return

    params = build_params(cfg, plan)
    reqs = synthetic_requests(cfg, n=args.batch, prompt_len=args.prompt_len,
                              max_new=args.tokens,
                              deadline_steps=args.deadline_steps,
                              shared_prefix=args.prefix_cache,
                              block_size=args.block_size)
    ce, res, dt = serve_continuous(
        params, cfg, reqs, plan=plan, restore=args.restore,
        drain_deadline=args.drain_deadline, max_batch=args.max_batch,
        kv_blocks=args.kv_blocks, block_size=args.block_size,
        segment_len=args.segment_len, paged_attn=args.paged_attn,
        chunked_prefill=args.chunked_prefill,
        prefill_chunk=args.prefill_chunk, preemption=args.preemption,
        max_queue=args.max_queue, prefix_cache=args.prefix_cache,
        snapshot_dir=args.snapshot_dir,
        snapshot_interval=args.snapshot_interval,
        telemetry=not args.no_telemetry,
        profiler_annotations=args.profiler_annotations)
    print(continuous_report(ce, res, dt, tag))
    if ce.last_snapshot_path:
        print(f"snapshot -> {ce.last_snapshot_path}")
    if args.metrics_out:
        ce.export_metrics(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        ce.export_trace(args.trace_out)
        print(f"trace -> {args.trace_out} (open in https://ui.perfetto."
              "dev or chrome://tracing)")


def build_params(cfg, plan, *, seed: int = 0, a_scale: float = 0.05,
                 shardings=None):
    """Seeded random weights for `cfg`, frozen by `plan` (None: float
    master).  A frozen model is built group by group
    (:func:`repro.models.model.init_frozen`), so the float master of a
    full-width model never has to fit on the device next to its int8
    copy."""
    import jax

    from repro.models import model as M
    key = jax.random.PRNGKey(seed)
    if plan is not None:
        return M.init_frozen(key, cfg, a_scale=a_scale, plan=plan,
                             shardings=shardings)
    params = M.init(key, cfg)
    if shardings is not None:
        params = jax.device_put(params, shardings)
    return params


def mesh_for(shape, devices=None):
    """A ``(data, model)`` (or ``(pod, data, model)``) Auto-axis mesh."""
    from repro import compat
    axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    return compat.make_mesh(shape, axes, devices=devices)


def param_shardings(cfg, plan, mesh):
    """Per-leaf NamedShardings of the (frozen, when `plan` deploys int8)
    params on `mesh`, from the model's logical-axes rules."""
    from repro.distributed import sharding as shard_lib
    from repro.models import model as M
    pspec = M.pspec(cfg)
    if plan is not None:
        pspec = M.freeze_pspec(pspec, plan=plan)
    return shard_lib.resolve_param_specs(pspec, mesh)


def generate_on_mesh(params, cfg, plan, mesh, param_sh, prompts, n_tokens):
    """Static greedy generation on a device mesh: pjit'd prefill, then
    ``n_tokens - 1`` decode steps.  Returns ``(tokens [B, n_tokens],
    first-step logits [B, cfg.vocab])`` as host arrays (the vocab padding
    columns dropped)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.models import model as M
    max_len = prompts.shape[1] + n_tokens + 8
    with compat.set_mesh(mesh):
        prefill = jax.jit(
            lambda p, b: M.prefill(p, b, cfg, max_len=max_len, mode=plan),
            in_shardings=(param_sh, None))
        decode = jax.jit(lambda p, b, c: M.decode_step(p, b, c, cfg,
                                                       mode=plan),
                         in_shardings=(param_sh, None, None))
        logits, caches = prefill(params, {"tokens": prompts})
        first = logits[:, -1]
        tok = jnp.argmax(first, -1).astype(jnp.int32)
        out = [tok]
        for _ in range(n_tokens - 1):
            logits, caches = decode(params, {"tokens": tok[:, None]}, caches)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            out.append(tok)
        toks = jax.block_until_ready(jnp.stack(out, axis=1))
    return (np.asarray(toks),
            np.asarray(first.astype(jnp.float32))[:, :cfg.vocab])


def synthetic_requests(cfg, *, n: int, prompt_len: int, max_new: int,
                       deadline_steps=None, shared_prefix: bool = False,
                       block_size: int = 16, seed: int = 0):
    """The launcher's seeded Poisson request stream; with `shared_prefix`
    80% of the prompts start with one common system prefix (about half
    the prompt) so prefix-cache hits actually occur."""
    import numpy as np

    from repro.serve import Request
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.poisson(2.0, size=n))
    sys_prefix = None
    if shared_prefix:
        n_sys = max(block_size, (prompt_len // 2) // block_size * block_size)
        sys_prefix = rng.integers(0, cfg.vocab, n_sys)
    reqs = []
    for i, t in enumerate(arrivals):
        prompt = rng.integers(0, cfg.vocab, prompt_len)
        if sys_prefix is not None and rng.random() < 0.8:
            prompt = np.concatenate([sys_prefix, prompt[len(sys_prefix):]])
        reqs.append(Request(rid=i, prompt=prompt, max_new=max_new,
                            arrival_step=int(t),
                            deadline_steps=deadline_steps))
    return reqs


def serve_continuous(params, cfg, requests, *, plan=None, restore=None,
                     drain_deadline=None, **engine_kw):
    """Serve `requests` through a :class:`~repro.serve.ContinuousEngine`
    built with `engine_kw`.  Returns ``(engine, {rid: RequestResult},
    wall seconds)``.

    `restore` cold-starts from a snapshot file instead (the snapshot's
    in-flight requests are served to completion; `requests` is ignored).
    `drain_deadline` latches a graceful drain at the first completion:
    admissions close, in-flight requests get that many sim steps, and
    stragglers spill into a final snapshot."""
    from repro.serve import ContinuousEngine
    ce = ContinuousEngine(params, cfg, plan=plan, **engine_kw)
    t0 = time.perf_counter()
    if restore is not None:
        res = ce.restore(restore).resume()
    elif drain_deadline is not None:
        res, latched = {}, False
        for ev in ce.run_stream(requests):
            if ev["event"] == "finish":
                res[ev["rid"]] = ev["result"]
                if not latched:
                    ce.drain(drain_deadline)
                    latched = True
        if not latched:
            raise SystemExit("--drain-deadline: no request finished before "
                             "the drain could latch; raise --tokens")
    else:
        res = ce.run(requests)
    return ce, res, time.perf_counter() - t0


def continuous_report(ce, res, dt, tag) -> str:
    """One-line summary of a continuous run."""
    from repro.core import backend as backend_lib
    from repro.serve import RequestStatus
    total = sum(len(r.tokens) for r in res.values())
    n_ok = sum(r.status is RequestStatus.OK for r in res.values())
    lat = sorted(r.latency_steps for r in res.values()
                 if r.admitted_step >= 0) or [0]
    attn = ("paged-attn" if backend_lib.paged_attn_enabled(ce.plan)
            else "gather")
    pf = (f"chunked-prefill:{ce.prefill_chunk}" if ce.chunked_prefill
          else "blocking-prefill")
    if ce.prefix_cache:
        pf += "|prefix-cache"
    return (
        f"[{tag}|continuous|{attn}|{pf}|preemption:{ce.preemption}] "
        f"served {len(res)} requests "
        f"/ {total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s incl. "
        f"compile); {ce.last_run_segments} segments, "
        f"{ce.last_run_dispatches} dispatches, "
        f"{ce.last_run_host_syncs} host syncs, "
        f"{ce.last_run_defrags} defrags, "
        f"{n_ok}/{len(res)} OK ({ce.last_run_preemptions} preempts, "
        f"{ce.last_run_recomputes} recomputes, "
        f"{ce.last_run_spills} SPILLED / {ce.last_run_restores} "
        f"restored ({ce.last_run_spill_bytes} spill bytes), "
        f"{ce.last_run_snapshots} snapshots, "
        f"{ce.last_run_recoveries} RECOVERED, "
        f"{ce.last_run_sheds} shed, {ce.last_run_timeouts} timeout), "
        f"{ce.last_run_prefix_hits} prefix hits "
        f"({ce.last_run_prefix_hit_tokens} tok cached, "
        f"{ce.last_run_prefix_misses} misses, "
        f"{ce.last_run_cow_copies} CoW, "
        f"{ce.last_run_suffix_prefills} suffix prefills), "
        f"p50 latency {lat[len(lat)//2]} steps, TTFT p99 "
        f"{ce.ttft_percentile(99)*1e3:.1f}ms, peak pool occupancy "
        f"{max((o for _, o in ce.occupancy_trace), default=0.0):.2f}")


if __name__ == "__main__":
    main()
