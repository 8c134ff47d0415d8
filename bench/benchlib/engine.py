"""The engine as the benchmark builds, sizes and warms it.

Everything here that reaches past ``ContinuousEngine``'s public API (the
jitted segment programs and their arguments) is kept in this one file:

* :func:`shadow_programs` lists the segment programs the cell's traffic
  dispatches up to a horizon in steps: the engine's own scheduler serves
  the requests over a stand-in of the segment programs that does the
  same bookkeeping (lengths, counts, done rows, steps run) without a
  model.  Which program a round dispatches depends on that bookkeeping
  alone, never on the tokens, so the list is the real run's;
* :func:`pool_blocks` sizes the int8 KV pool as the largest that those
  programs fit into the HBM left after the weights, from the compiled
  programs' own memory analysis at two probe sizes (the footprint is
  linear in blocks);
* :func:`warm` calls each listed program once on idle rows, so that the
  window finds every one compiled.

The stand-in and the warm-up pass the programs' operands by the names in
``DECODE_OPERANDS`` and ``MIXED_OPERANDS``; where the engine's programs
take others, :func:`check_signature` stops the run with an error.
"""
from __future__ import annotations

import dataclasses
import math

HBM_HEADROOM = 1 << 30          # bytes kept free beyond what XLA reports
PROBE_BLOCKS = (128, 256)       # pool sizes the memory analysis reads


@dataclasses.dataclass(frozen=True)
class Settings:
    """The engine settings of a cell: configuration file plus traffic."""
    max_batch: int
    block_size: int
    segment_len: int
    prefill_chunk: int
    max_blocks_per_req: int
    preemption: str


def settings(conf: dict, max_tokens: int, kv_layout: tuple[int, int, int]
             ) -> Settings:
    """`max_tokens`: the longest prompt plus output the mix can draw (it
    sizes the block tables, so every seed compiles the same programs).
    `kv_layout`: the adapter's (KV heads, head width, query heads per KV
    head), which the engine's own prefill-chunk choice reads."""
    from repro.kernels import autotune

    eng = conf["engine"]
    mb, bs = int(eng["max_batch"]), int(eng["block_size"])
    chunk = eng.get("prefill_chunk")
    if chunk is None:                # the engine's own choice
        import jax.numpy as jnp
        kvh, hd, groups = kv_layout
        dtype = jnp.int8 if eng["kv_cache_dtype"] == "int8" else jnp.float32
        chunk = autotune.choose_prefill_chunk(mb, kvh, bs, dtype,
                                              head_dim=hd, groups=groups)
    return Settings(
        max_batch=mb, block_size=bs, segment_len=int(eng["segment_len"]),
        prefill_chunk=int(chunk), max_blocks_per_req=-(-max_tokens // bs),
        preemption=eng["preemption"])


def build(params, cfg, plan, s: Settings, kv_blocks: int, *,
          annotate: bool = False):
    """The cell's ``ContinuousEngine`` over `kv_blocks` pool blocks."""
    from repro.serve import ContinuousEngine
    return ContinuousEngine(
        params, cfg, plan=plan, max_batch=s.max_batch, kv_blocks=kv_blocks,
        block_size=s.block_size, max_blocks_per_req=s.max_blocks_per_req,
        segment_len=s.segment_len, chunked_prefill=True,
        prefill_chunk=s.prefill_chunk, preemption=s.preemption,
        profiler_annotations=annotate)


@dataclasses.dataclass(frozen=True)
class Program:
    """One segment program at one set of shapes."""
    kind: str          # "decode" | "mixed"
    width: int         # block-table columns of the decode rows
    pb: int = 0        # prefill sub-batch rows (mixed)
    pf_width: int = 0  # block-table columns of the prefilling rows (mixed)
    has_past: bool = False


# The operands of the engine's jitted segment programs, in order.  The
# stand-in below and the warm-up's arguments follow them; a program whose
# signature differs stops the benchmark with an error naming both.
DECODE_OPERANDS = ("params", "pages", "tables", "tok", "n_out", "lens",
                   "done", "rids", "max_new", "stops", "poison", "rng",
                   "temperature", "pad_token")
PREFILL_OPERANDS = ("pf_rows", "pf_tables", "pf_tok", "pf_pos", "pf_cnt",
                    "pf_on", "pf_nw", "pf_fin", "pf_t0")
MIXED_OPERANDS = DECODE_OPERANDS[:3] + PREFILL_OPERANDS + DECODE_OPERANDS[3:]


def operands(kind: str) -> tuple[str, ...]:
    return MIXED_OPERANDS if kind == "mixed" else DECODE_OPERANDS


def check_signature(fn, kind: str) -> None:
    """Stop with an error where the engine's `kind` segment program takes
    other operands than the benchmark passes it."""
    import inspect
    got = tuple(inspect.signature(fn).parameters)
    if got != operands(kind):
        raise RuntimeError(
            f"the engine's {kind} segment program takes {got}; the "
            f"benchmark's stand-in and warm-up pass {operands(kind)}: "
            f"bench/benchlib/engine.py no longer matches the program")


class _Segment:
    """Stand-in of a jitted segment program: the decode loop's bookkeeping
    in numpy (a row emits its pending token each step until it has
    ``max_new``), after the mixed program's prologue (rows whose final
    chunk lands join decode)."""

    def __init__(self, seg_len: int, mixed: tuple | None):
        self.seg_len, self.mixed = seg_len, mixed

    def __call__(self, *args):
        import numpy as np
        names = operands("mixed" if self.mixed else "decode")
        if len(args) != len(names):
            raise RuntimeError(
                f"the engine dispatched a segment program with "
                f"{len(args)} operands; the stand-in knows {len(names)}")
        a = dict(zip(names, args))
        tok, n_out, lens, done = (np.array(a[k]) for k in
                                  ("tok", "n_out", "lens", "done"))
        if self.mixed:
            pf_on = np.asarray(a["pf_on"])
            good = pf_on & np.asarray(a["pf_fin"])
            rows = np.asarray(a["pf_rows"])
            tok[rows] = np.where(good, 0, tok[rows])
            done[rows] = done[rows] & ~good
            lens[rows] = np.where(pf_on, np.asarray(a["pf_pos"])
                                  + np.asarray(a["pf_cnt"]), lens[rows])
        mb, pad = tok.shape[0], int(a["pad_token"])
        out_t = np.full((mb, self.seg_len), pad, np.int32)
        i = 0
        while i < self.seg_len and not done.all():
            out_t[:, i] = np.where(done, pad, tok)
            live = ~done
            lens = lens + live
            n_out = n_out + live
            done = done | (tok[:, None] == np.asarray(a["stops"])).any(-1) \
                | (n_out >= np.asarray(a["max_new"]))
            i += 1
        return (a["pages"], tok, n_out, lens, done, np.zeros(mb, bool),
                out_t, np.zeros((mb, self.seg_len), np.float32), np.int32(i))


def shadow_programs(stand_in_cfg, plan, s: Settings, kv_blocks: int,
                    requests, *, open_after_steps: float, window_steps: int):
    """``([(program, step of its first dispatch), ...], window)``: the
    segment programs, in first-use order, that serving `requests` with
    these settings dispatches until `window_steps` steps after the window
    opens (by the harness's own rule), and the window as the step clock
    saw it (each request's arrival and admission step; no times).  Runs
    on the host over `stand_in_cfg`, the adapter's ``stand_in_config``
    of the model, whose pool is a byte a block."""
    import jax

    from benchlib import window as win
    from repro.serve import ContinuousEngine
    seen: dict[Program, int] = {}
    now = [0]

    class Shadow(ContinuousEngine):
        def _segment_fn(self, plan, greedy, seg_len, stop_w):
            check_signature(super()._segment_fn(plan, greedy, seg_len,
                                                stop_w), "decode")
            return _Segment(seg_len, None)

        def _mixed_segment_fn(self, plan, greedy, seg_len, stop_w, chunk,
                              pb, has_past):
            check_signature(super()._mixed_segment_fn(
                plan, greedy, seg_len, stop_w, chunk, pb, has_past),
                "mixed")
            return _Segment(seg_len, (pb, has_past))

        def _dispatch(self, fn, *args, name="dispatch"):
            if isinstance(fn, _Segment):
                a = dict(zip(operands("mixed" if fn.mixed else "decode"),
                             args))
                if fn.mixed:
                    p = Program("mixed", a["tables"].shape[1], fn.mixed[0],
                                a["pf_tables"].shape[1], fn.mixed[1])
                else:
                    p = Program("decode", a["tables"].shape[1])
                seen.setdefault(p, now[0])
            return fn(*args)

    records = {r.rid: win.Req(r.rid, r.arrival_step, r.prompt_len,
                              r.max_new) for r in requests}
    d = win._Client(records, open_after_steps, float("inf"), None, None,
                    clock=lambda: 0.0)   # opens by steps alone
    horizon = None
    with jax.default_device(jax.devices("cpu")[0]):
        eng = Shadow(None, stand_in_cfg, plan=plan, max_batch=s.max_batch,
                     kv_blocks=kv_blocks, block_size=s.block_size,
                     max_blocks_per_req=s.max_blocks_per_req,
                     segment_len=s.segment_len, chunked_prefill=True,
                     prefill_chunk=s.prefill_chunk,
                     preemption=s.preemption, telemetry=False)
        for ev in eng.run_stream(requests):
            now[0] = int(ev["step"])
            if horizon is not None and ev["step"] > horizon:
                break
            d.event(ev, 0.0)
            if horizon is None and d.w.opened:
                horizon = d.w.step_open + window_steps
    d.w.step_close = d.sim_now
    return list(seen.items()), d.w


def _fn(engine, p: Program):
    if p.kind == "decode":
        fn = engine._segment_fn(engine.plan, True, engine.segment_len, 1)
    else:
        fn = engine._mixed_segment_fn(engine.plan, True, engine.segment_len,
                                      1, engine.prefill_chunk, p.pb,
                                      p.has_past)
    check_signature(fn, p.kind)
    return fn


def _args(engine, p: Program, pages):
    """Operands of `p` on idle rows, typed as the engine's loop types
    them (the jit caches key on it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    mb, pb, c = engine.max_batch, p.pb, engine.prefill_chunk
    a = dict(params=engine.params, pages=pages,
             tables=np.zeros((mb, p.width), np.int32),
             tok=np.zeros(mb, np.int32), n_out=np.zeros(mb, np.int32),
             lens=np.zeros(mb, np.int32), done=np.ones(mb, bool),
             rids=np.zeros(mb, np.int32), max_new=np.zeros(mb, np.int32),
             stops=np.full((mb, 1), -1, np.int32),
             poison=np.zeros(mb, bool), rng=jax.random.PRNGKey(0),
             temperature=jnp.asarray(1e-6, jnp.float32),
             pad_token=jnp.asarray(-1, jnp.int32),
             pf_rows=np.zeros(pb, np.int32),
             pf_tables=np.zeros((pb, p.pf_width), np.int32),
             pf_tok=np.zeros((pb, c), np.int32),
             pf_pos=np.zeros(pb, np.int32), pf_cnt=np.zeros(pb, np.int32),
             pf_on=np.zeros(pb, bool), pf_nw=np.zeros(pb, bool),
             pf_fin=np.zeros(pb, bool), pf_t0=np.zeros(pb, np.int32))
    return tuple(a[k] for k in operands(p.kind))


def footprint(engine, p: Program, kv_blocks: int, sharding=None) -> int:
    """Bytes the compiled program `p` needs with a pool of `kv_blocks`:
    arguments (weights and pool included), outputs and temporaries.
    `sharding` places the pool (a described device, for rehearsals)."""
    import jax

    from repro.serve import kv_pool
    pages = jax.eval_shape(lambda: kv_pool.init_pages(
        engine.cfg, kv_blocks, engine.block_size))
    if sharding is not None:
        pages = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), pages)
    m = _fn(engine, p).lower(*_args(engine, p, pages)).compile() \
        .memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes
               + m.generated_code_size_in_bytes)


def pool_blocks(engine, probes: list[Program], bytes_limit: int,
                log=print, sharding=None) -> tuple[int, dict]:
    """The largest pool that every program of `probes` fits into
    `bytes_limit` less ``HBM_HEADROOM``, from each program's footprint at
    ``PROBE_BLOCKS`` extended linearly."""
    best, fits = None, {}
    p1, p2 = PROBE_BLOCKS
    for p in probes:
        f1 = footprint(engine, p, p1, sharding)
        f2 = footprint(engine, p, p2, sharding)
        per_block = (f2 - f1) / (p2 - p1)
        fixed = f1 - per_block * p1
        n = int(math.floor((bytes_limit - HBM_HEADROOM - fixed) / per_block))
        fits[f"{p.kind}/w{p.width}/pb{p.pb}"] = {
            "fixed_bytes": fixed, "bytes_per_block": per_block, "blocks": n}
        log(f"pool probe {p}: {fixed / 2**30:.3f} GiB + "
            f"{per_block / 2**20:.3f} MiB per block -> {n} blocks")
        best = n if best is None else min(best, n)
    return best, fits


def warm(engine, progs: list[Program], log=print) -> None:
    """Run each program once on idle rows (outputs dropped), then the
    pool permutation a defrag dispatches."""
    import jax
    import numpy as np

    from repro.serve import kv_pool
    import time
    t0 = time.perf_counter()
    for i, p in enumerate(progs):
        out = _fn(engine, p)(*_args(engine, p, engine.pages))
        jax.block_until_ready(out)
        engine.pages = out[0]     # idle rows: the pool comes back as it was
        del out
        if (i + 1) % 25 == 0:
            log(f"warm-up: {i + 1}/{len(progs)} programs in "
                f"{time.perf_counter() - t0:.1f}s")
    engine.pages, _ = kv_pool.apply_defrag(
        engine.pages, np.zeros((1, 1), np.int32), {})
    jax.block_until_ready(engine.pages)
    log(f"warmed {len(progs)} segment programs and the defrag permutation")
