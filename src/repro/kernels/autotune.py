"""Block-shape autotuner for the Pallas matmul kernels.

The kernels used to hard-code (bm, bn, bk) = (256, 256, 512) and clamp
``bm = min(bm, max(8, m))`` — which snapped a *distinct* block shape (and so
a distinct jit entry) onto every decode batch size.  This module owns block
selection instead:

* **Bucketing** — M is snapped to power-of-two buckets (>= 8), so decode
  batches 1..B share O(log B) compiled kernels instead of B.
* **Heuristic defaults** — MXU-aligned blocks chosen from the (bucketed)
  problem shape and input dtype; float inputs (fused prologue quantization)
  get a smaller K block to respect the 4x VMEM footprint.
* **Measured overrides** — :func:`measure` times candidate blocks on the
  actual kernel and records the winner; the table is JSON-dumpable so a
  fleet can ship a tuned table and :func:`load` it at startup
  (``REPRO_AUTOTUNE_CACHE`` names a default file).

Selection is deterministic: the same (M, K, N, dtype) always returns the
same blocks within a process, and a dumped table reproduces the choices
exactly on load.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

import jax.numpy as jnp

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# The in-process decision table: (m_bucket, k, n, dtype) -> (bm, bn, bk).
# Heuristic choices are memoized here too, so `choose_blocks` is stable even
# if the heuristic changes mid-process (it cannot: it is pure), and measured
# entries transparently override heuristic ones.
_TABLE: dict[tuple[int, int, int, str], tuple[int, int, int]] = {}
_MEASURED: set[tuple[int, int, int, str]] = set()

# Paged-attention decode shapes: (batch_bucket, kvh, width, block_size,
# head_dim, groups, dtype) -> kv_splits.  The kernel walks each split's
# slice of every row's block table as one chain of grid steps; the pages
# it takes a step follow from the shape (:func:`heuristic_pages_per_step`)
# and are not tuned.  The key is shape-complete (head_dim and GQA group
# count included, like the matmul table's (m, k, n)) so dumped fleet
# tables never collide across models.
_PAGED_TABLE: dict[tuple[int, int, int, int, int, int, str], int] = {}
_PAGED_MEASURED: set[tuple[int, int, int, int, int, int, str]] = set()

# Table tokens a decode grid step takes: the 128 lanes of its [G, P*BS]
# score tile.  On a v5e the kernel alone is fastest there for int8 and
# bf16 pools, 8 and 32 KV heads, blocks 16 and 32; a fixed byte budget
# a step fits only some of them.  The bytes of one operand's pages a
# step holds are capped, for VMEM (two slots and an f32 copy of each).
PAGED_STEP_TOKENS = 128
PAGED_STEP_BYTES = 512 << 10

# Chunked-prefill shapes: (batch_bucket, kvh, block_size, head_dim, groups,
# dtype) -> prefill chunk length.  The tuned axis is the tokens-per-chunk
# the continuous engine's mixed segments advance a prefilling request by:
# larger chunks amortize per-segment dispatch and page-walk overhead,
# smaller chunks interleave with decode sooner (lower head-of-line TTFT).
# Always a block_size multiple so chunk starts stay page-aligned.
_PREFILL_TABLE: dict[tuple[int, int, int, int, int, str], int] = {}
_PREFILL_MEASURED: set[tuple[int, int, int, int, int, str]] = set()


def next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def m_bucket(m: int) -> int:
    """Power-of-two M bucket (>= 8): the padded row count kernels compile
    for.  Decode batches 1..256 land in 6 buckets instead of 256."""
    return max(8, next_pow2(m))


def _key(m: int, k: int, n: int, dtype) -> tuple[int, int, int, str]:
    return (m_bucket(m), int(k), int(n), jnp.dtype(dtype).name)


def heuristic_blocks(m: int, k: int, n: int,
                     dtype=jnp.int8) -> tuple[int, int, int]:
    """MXU-aligned (bm, bn, bk) from the problem shape alone.

    bm covers the whole M bucket up to 256 rows; bn/bk prefer 128-multiples
    (the MXU tile) and avoid padding K/N when they are already smaller than
    a block.  Float inputs halve the max K block: the fused-prologue a
    block is f32 (4 bytes/elem), and bk=512 x bm=256 x 4B would crowd VMEM
    double-buffering.
    """
    mb = m_bucket(m)
    bm = min(256, mb)
    if n >= 256 and n % 256 == 0:
        bn = 256
    elif n >= 128:
        bn = 128
    else:
        bn = n                    # pad-free: one block spans all of N
    bk_cap = 256 if jnp.dtype(dtype).itemsize > 1 else 512
    if k >= bk_cap and k % bk_cap == 0:
        bk = bk_cap
    elif k >= 128:
        bk = 128
    else:
        bk = k
    return bm, bn, bk


def choose_blocks(m: int, k: int, n: int,
                  dtype=jnp.int8) -> tuple[int, int, int]:
    """The (bm, bn, bk) for one matmul shape: measured if a measurement (or
    loaded table entry) exists, else the deterministic heuristic."""
    key = _key(m, k, n, dtype)
    if key not in _TABLE:
        _TABLE[key] = heuristic_blocks(m, k, n, dtype)
    return _TABLE[key]


def record(m: int, k: int, n: int, dtype,
           blocks: tuple[int, int, int], *, measured: bool = True) -> None:
    """Pin a block choice for a shape (what `measure` and `load` call)."""
    bm, bn, bk = (int(b) for b in blocks)
    key = _key(m, k, n, dtype)
    _TABLE[key] = (bm, bn, bk)
    if measured:
        _MEASURED.add(key)


def time_median_us(fn, iters: int = 3) -> float:
    """Compile (one warmup call), then median wall time of `iters` runs of
    the zero-arg thunk, in microseconds.  The one timing methodology every
    measure path and benchmark shares."""
    import time

    import jax

    jax.block_until_ready(fn())  # compile / warm caches
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def candidate_blocks(m: int, k: int, n: int,
                     dtype=jnp.int8) -> list[tuple[int, int, int]]:
    """Small MXU-aligned candidate grid around the heuristic choice."""
    mb = m_bucket(m)
    bk_cap = 256 if jnp.dtype(dtype).itemsize > 1 else 512
    bms = sorted({min(mb, b) for b in (64, 128, 256)})
    bns = sorted({b for b in (64, 128, 256) if b <= n} or {n})
    bks = sorted({b for b in (128, 256, bk_cap) if b <= k} or {k})
    cands = [(bm, bn, bk) for bm in bms for bn in bns for bk in bks]
    h = heuristic_blocks(m, k, n, dtype)
    if h not in cands:
        cands.append(h)
    return cands


def measure(m: int, k: int, n: int, dtype=jnp.int8, *,
            candidates: Iterable[tuple[int, int, int]] | None = None,
            iters: int = 3, interpret: bool | None = None,
            ) -> tuple[tuple[int, int, int], dict]:
    """Time the cim kernel over candidate blocks; record + return the best.

    Runs the real `cim_matmul` wrapper (padding included) so the measured
    cost is end-to-end.  On CPU this times interpret mode — structurally
    informative, not silicon-accurate — so CI uses it only as a smoke; on
    TPU the same call tunes the compiled kernel.  Returns
    ``(best_blocks, {blocks: median_us})``.
    """
    import jax

    from repro.kernels.cim_matmul import ops as kops  # lazy: avoid cycle

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    if jnp.dtype(dtype) == jnp.int8:
        a = jax.random.randint(k1, (m, k), -128, 128, jnp.int32).astype(
            jnp.int8)
    else:
        a = jax.random.normal(k1, (m, k), jnp.dtype(dtype))
    w = jax.random.randint(k2, (k, n), -128, 128, jnp.int32).astype(jnp.int8)
    w_s = jnp.ones((n,), jnp.float32)
    a_s = jnp.float32(0.05)

    timings: dict[tuple[int, int, int], float] = {}
    for bm, bn, bk in (candidates or candidate_blocks(m, k, n, dtype)):
        def run(bm=bm, bn=bn, bk=bk):
            return kops.cim_matmul(a, w, a_s, w_s, bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)
        timings[(bm, bn, bk)] = time_median_us(run, iters)
    best = min(timings, key=timings.get)
    record(m, k, n, dtype, best)
    return best, timings


# ---------------------------------------------------------------------------
# Paged-attention decode: kv_splits / pages_per_step
# ---------------------------------------------------------------------------

def _paged_key(batch: int, kvh: int, width: int, block_size: int,
               head_dim: int, groups: int,
               dtype) -> tuple[int, int, int, int, int, int, str]:
    return (m_bucket(batch), int(kvh), int(width), int(block_size),
            int(head_dim), int(groups), jnp.dtype(dtype).name)


def heuristic_pages_per_step(width: int, kv_splits: int, block_size: int,
                             kvh: int, head_dim: int,
                             dtype=jnp.int8) -> int:
    """Table columns per decode grid step: the largest power of two whose
    pages hold at most :data:`PAGED_STEP_TOKENS` tokens and, of one
    operand, :data:`PAGED_STEP_BYTES`, and that fits the split's width:
    8 pages at block 16 and 4 at block 32 for the served heads of 128."""
    page = block_size * kvh * head_dim * jnp.dtype(dtype).itemsize
    split_width = -(-width // max(1, min(kv_splits, width)))
    p = 1
    while (2 * p * block_size <= PAGED_STEP_TOKENS
           and 2 * p * page <= PAGED_STEP_BYTES and 2 * p <= split_width):
        p *= 2
    return p


def choose_paged_decode(batch: int, kvh: int, width: int, block_size: int,
                        dtype=jnp.int8, *, head_dim: int, groups: int = 1,
                        kv_splits: int | None = None) -> tuple[int, int]:
    """``(kv_splits, pages_per_step)`` for one paged decode shape.

    ``kv_splits`` is the caller's if given, else the measured entry
    (:func:`measure_paged`), else 1.  One split is the default because
    the kernel's grid is ``(kv_splits, B, groups)`` and only the split
    axis is parallel; within a split every row of the batch runs as one
    chain, each step copying the next step's pages while it computes.  So
    a split adds parallelism only across TensorCores (two on a megacore
    chip, one on a v5e) and costs a chain restart and a wider merge on
    any chip.  ``pages_per_step`` is always the heuristic's at that split
    count."""
    if kv_splits is None:
        kv_splits = _PAGED_TABLE.get(_paged_key(
            batch, kvh, width, block_size, head_dim, groups, dtype), 1)
    return kv_splits, heuristic_pages_per_step(
        width, kv_splits, block_size, kvh, head_dim, dtype)


def record_paged(batch: int, kvh: int, width: int, block_size: int, dtype,
                 kv_splits: int, *, head_dim: int = 0, groups: int = 1,
                 measured: bool = True) -> None:
    key = _paged_key(batch, kvh, width, block_size, head_dim, groups,
                     dtype)
    _PAGED_TABLE[key] = int(kv_splits)
    if measured:
        _PAGED_MEASURED.add(key)


def paged_split_candidates(width: int) -> list[int]:
    """Pow2 split counts from 1 (whole table per program) up to one page
    per program."""
    cands, s = [], 1
    while s <= width:
        cands.append(s)
        s *= 2
    return cands


def measure_paged(batch: int, kvh: int, width: int, block_size: int,
                  dtype=jnp.int8, *, head_dim: int = 64, groups: int = 2,
                  candidates: Iterable[int] | None = None, iters: int = 3,
                  backend: str | None = None) -> tuple[int, dict]:
    """Time `paged_attention` over candidate split counts (each at the
    heuristic's pages per step) on a synthetic pool; record + return the
    best.  On CPU this times the vectorized emulation (structural); on
    TPU the compiled kernel.  Returns ``(best_kv_splits, {kv_splits:
    median_us})``."""
    import jax

    from repro.kernels.paged_attention import ops as pops  # lazy: no cycle

    key = jax.random.PRNGKey(0)
    nb = width + 1
    shape = (nb, block_size, kvh, head_dim)
    if jnp.dtype(dtype) == jnp.int8:
        from repro.core import quant
        codes = jax.random.randint(key, shape, -127, 128, jnp.int32).astype(
            jnp.int8)
        scale = jnp.full((*shape[:-1], 1), 0.05, jnp.bfloat16)
        pages = quant.QTensor(codes, scale)
    else:
        pages = jax.random.normal(key, shape, jnp.dtype(dtype))
    q = jax.random.normal(key, (batch, 1, kvh * groups, head_dim),
                          jnp.float32)
    tables = jnp.tile(jnp.arange(1, width + 1, dtype=jnp.int32)[None],
                      (batch, 1))
    n_valid = jnp.full((batch,), width * block_size, jnp.int32)

    timings: dict[int, float] = {}
    for s in (candidates or paged_split_candidates(width)):
        def run(s=s):
            return pops.paged_attention(q, pages, pages, tables, n_valid,
                                        kv_splits=s, backend=backend)
        timings[s] = time_median_us(run, iters)
    best = min(timings, key=timings.get)
    record_paged(batch, kvh, width, block_size, dtype, best,
                 head_dim=head_dim, groups=groups)
    return best, timings


# ---------------------------------------------------------------------------
# Chunked prefill: tokens per chunk
# ---------------------------------------------------------------------------

def _prefill_key(batch: int, kvh: int, block_size: int, head_dim: int,
                 groups: int, dtype) -> tuple[int, int, int, int, int, str]:
    return (m_bucket(batch), int(kvh), int(block_size), int(head_dim),
            int(groups), jnp.dtype(dtype).name)


def heuristic_prefill_chunk(block_size: int) -> int:
    """Chunk length from the pool geometry alone: ~64 tokens (a few pages
    of causal tile per segment, enough to amortize the dispatch without
    stalling decode for long), always a block_size multiple."""
    return block_size * max(1, 64 // block_size)


def choose_prefill_chunk(batch: int, kvh: int, block_size: int,
                         dtype=jnp.int8, *, head_dim: int = 0,
                         groups: int = 1) -> int:
    """Prefill chunk length for one serve shape: measured when available,
    else the deterministic heuristic (memoized, like choose_blocks)."""
    key = _prefill_key(batch, kvh, block_size, head_dim, groups, dtype)
    if key not in _PREFILL_TABLE:
        _PREFILL_TABLE[key] = heuristic_prefill_chunk(block_size)
    return _PREFILL_TABLE[key]


def record_prefill(batch: int, kvh: int, block_size: int, dtype,
                   chunk_len: int, *, head_dim: int = 0, groups: int = 1,
                   measured: bool = True) -> None:
    key = _prefill_key(batch, kvh, block_size, head_dim, groups, dtype)
    _PREFILL_TABLE[key] = int(chunk_len)
    if measured:
        _PREFILL_MEASURED.add(key)


def prefill_chunk_candidates(block_size: int, cap: int = 256) -> list[int]:
    """Pow2-spaced block_size multiples from one page up to `cap` tokens."""
    cands, c = [], block_size
    while c <= max(cap, block_size):
        cands.append(c)
        c *= 2
    return cands


def measure_prefill(batch: int, kvh: int, block_size: int, dtype=jnp.int8,
                    *, head_dim: int = 64, groups: int = 2,
                    candidates: Iterable[int] | None = None, iters: int = 3,
                    backend: str | None = None) -> tuple[int, dict]:
    """Time `paged_prefill` over candidate chunk lengths on a synthetic
    pool (one mid-prompt chunk: as many past tokens as the chunk itself)
    and pick the cheapest *per token*; record + return the best.  On CPU
    this times the vectorized emulation (structural); on TPU the compiled
    kernel.  Returns ``(best_chunk, {chunk: median_us_per_token})``."""
    import jax

    from repro.kernels.paged_attention import ops as pops  # lazy: no cycle

    key = jax.random.PRNGKey(0)
    timings: dict[int, float] = {}
    for c in (candidates or prefill_chunk_candidates(block_size)):
        w = 2 * (c // block_size)            # past pages + chunk pages
        nb = w * batch + 1
        shape = (nb, block_size, kvh, head_dim)
        if jnp.dtype(dtype) == jnp.int8:
            from repro.core import quant
            codes = jax.random.randint(key, shape, -127, 128,
                                       jnp.int32).astype(jnp.int8)
            scale = jnp.full((*shape[:-1], 1), 0.05, jnp.bfloat16)
            pages = quant.QTensor(codes, scale)
        else:
            pages = jax.random.normal(key, shape, jnp.dtype(dtype))
        q = jax.random.normal(key, (batch, c, kvh * groups, head_dim),
                              jnp.float32)
        kn = jax.random.normal(key, (batch, c, kvh, head_dim), jnp.float32)
        tables = (jnp.arange(1, batch * w + 1, dtype=jnp.int32)
                  .reshape(batch, w))
        pos = jnp.full((batch,), c, jnp.int32)       # chunk 2: past == chunk
        n_tok = jnp.full((batch,), c, jnp.int32)

        def run(q=q, kn=kn, pages=pages, tables=tables, pos=pos,
                n_tok=n_tok):
            return pops.paged_prefill(q, kn, kn, pages, pages, tables, pos,
                                      n_tok, backend=backend)
        timings[c] = time_median_us(run, iters) / c
    best = min(timings, key=timings.get)
    record_prefill(batch, kvh, block_size, dtype, best, head_dim=head_dim,
                   groups=groups)
    return best, timings


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------

def dump(path: str | None = None) -> str:
    """Write the measured entries (JSON) to `path` (or $REPRO_AUTOTUNE_CACHE).
    Returns the serialized text (also when no path is available)."""
    entries = [
        {"m_bucket": key[0], "k": key[1], "n": key[2], "dtype": key[3],
         "blocks": list(_TABLE[key])}
        for key in sorted(_MEASURED)
    ]
    paged = [
        {"batch_bucket": key[0], "kvh": key[1], "width": key[2],
         "block_size": key[3], "head_dim": key[4], "groups": key[5],
         "dtype": key[6], "kv_splits": _PAGED_TABLE[key]}
        for key in sorted(_PAGED_MEASURED)
    ]
    prefill = [
        {"batch_bucket": key[0], "kvh": key[1], "block_size": key[2],
         "head_dim": key[3], "groups": key[4], "dtype": key[5],
         "chunk_len": _PREFILL_TABLE[key]}
        for key in sorted(_PREFILL_MEASURED)
    ]
    obj: dict = {"version": 1, "entries": entries}
    if paged:
        obj["paged_entries"] = paged
    if prefill:
        obj["prefill_entries"] = prefill
    text = json.dumps(obj, indent=2)
    path = path or os.environ.get(CACHE_ENV)
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text


def load(path_or_text: str) -> int:
    """Load a dumped table (path or inline JSON); returns #entries loaded."""
    text = path_or_text
    if not path_or_text.lstrip().startswith("{"):
        with open(path_or_text) as f:
            text = f.read()
    obj = json.loads(text)
    for e in obj.get("entries", ()):
        record(e["m_bucket"], e["k"], e["n"], e["dtype"],
               tuple(e["blocks"]))
    for e in obj.get("paged_entries", ()):
        record_paged(e["batch_bucket"], e["kvh"], e["width"],
                     e["block_size"], e["dtype"], e["kv_splits"],
                     head_dim=e.get("head_dim", 0),
                     groups=e.get("groups", 1))
    for e in obj.get("prefill_entries", ()):
        record_prefill(e["batch_bucket"], e["kvh"], e["block_size"],
                       e["dtype"], e["chunk_len"],
                       head_dim=e.get("head_dim", 0),
                       groups=e.get("groups", 1))
    return (len(obj.get("entries", ())) + len(obj.get("paged_entries", ()))
            + len(obj.get("prefill_entries", ())))


def clear() -> None:
    """Drop every cached decision (tests)."""
    _TABLE.clear()
    _MEASURED.clear()
    _PAGED_TABLE.clear()
    _PAGED_MEASURED.clear()
    _PREFILL_TABLE.clear()
    _PREFILL_MEASURED.clear()
