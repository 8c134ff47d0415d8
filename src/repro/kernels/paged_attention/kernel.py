"""Fused paged-attention flash-decoding and flash-prefill Pallas TPU kernels.

Serving-cache form of the paper's single-conversion principle: the decode
attention for one token reads the int8 KV pages *as stored* (half the HBM
bytes of bf16), applies the per-token-head scales in-registers, and carries
the softmax in online (running max / sum) form so the only "conversion" —
the normalization acc / l — happens exactly once per head, after the whole
context has been accumulated.  No dense [B, S, KVH, D] gathered cache is
ever materialized and no dequantized fp copy of the pool ever touches HBM;
compare ``attention.attend_decode_paged``'s gather-then-attend reference,
which pays both per decode step per layer.

Layout (flash decoding, split-KV, page groups):

* grid ``(kv_splits, B, ceil(W_split / P))``.  A split is a slice of
  every row's block table (``W_split = ceil(W / kv_splits)`` columns); a
  grid step handles one *group* of ``P`` consecutive table columns of one
  row (``pages_per_step``, chosen from the page bytes and the width by
  :func:`repro.kernels.autotune.choose_paged_decode`).  The split axis is
  the one parallel axis; within a split the rows and groups run in order
  on one core, as one chain.
* **Manual DMA.**  The K/V pools (and the int8 scale pools) stay in HBM
  (``memory_space=ANY``).  A step copies each live page of its group
  ``pool[block_tables[b, col]]`` — the table read from scalar-prefetched
  SMEM — into slot ``[P, BS, KVH, D]`` of a two-slot VMEM buffer, one
  async copy per page and operand, all on the slot's DMA semaphore.
  Whole pages: Mosaic requires a block's last two dims to be
  (8, 128)-divisible or whole, so the ``[NB, BS, KVH, D]`` pool moves by
  whole pages and the kernel loops over the KV heads.
* **Double buffering.**  Before computing its group, a step starts the
  copies of the chain's next live group into the other slot — the row's
  next group, or the first group of the next row whose slice is live —
  so the next group's pages stream in while this one computes, across
  row boundaries.  Only a split's first live group copies its own pages.
  Two SMEM scalars carry the chain: the slot in use and the step whose
  copies were started into it.
* **Dead groups and pages.**  A group that starts past the row's live
  length (or past the table) issues no copy and runs no compute: its step
  is an empty grid step.  In the row's last, partly live group only the
  live pages are copied; the rest of the slot holds stale bytes, which the
  position mask keeps out of the scores and out of V (int8: their scales
  are zeroed; fp: their rows).
* **Compute.**  The group is converted to f32 once, into VMEM scratch, so
  a head's ``[P*BS, D]`` rows are a sublane-strided load of whole f32
  tiles.  Each KV head then takes the whole group at once: one
  ``[G, D] x [D, P*BS]`` score dot and one ``[G, P*BS] x [P*BS, D]``
  value dot, with a per-head ``(m, l, acc)`` online-softmax carry in VMEM
  scratch.  Each ``(split, row)`` emits its partial ``(acc, m, l)``; the
  cross-split combine is a tiny logsumexp merge done by the wrapper
  (:func:`..ops.merge_splits`).

The int8 variant copies the page's int8 codes plus its ``[BS, KVH]``
per-token-head scale tile (lane-padded to 128 by the wrapper: Mosaic
slices an HBM ref only along whole tiles) and dequantizes in-registers
(KIVI-style grid, identical to ``attention.dequantize_kv``): the group's
scale tiles, transposed to one row per head, scale the head's scores
(K) and probabilities (V), ``(q . k) s_k`` and ``(p s_v) . v``, so no
dequantized page is formed.  Unlike the gather reference's
fully-integer path it keeps q and the probabilities in f32 — the int8 win
here is HBM bytes, not MXU width — so parity with the int8 reference is
close-not-bitwise (the reference additionally quantizes q and p; see
tests/test_paged_attention.py).

**Flash prefill** (:func:`flash_prefill_kernel`) walks pages through
BlockSpecs, one page per grid step: grid ``(B, past_pages + 1 +
chunk_pages)``, whole pages and an in-kernel head loop as above,
first walks the request's past pages (scalar-prefetch index maps, dead
steps clamped to the last live page so repeated block indices elide the
DMA), then runs the causal self tile on
the in-hand chunk (kept fp, like the one-shot prefill's ``attend_full``),
and finally QUANTIZES AND WRITES the chunk's K/V into its pool pages —
the page writes are output index maps over the pool buffer itself
(``input_output_aliases``), so the prompt cache never exists densely and
``pack_prompt`` never runs.  Masked rows (``write_mask`` 0) and ragged
dead-tail steps write to the reserved null block 0; every untouched pool
block keeps its bytes (tested).  The in-kernel int8 quantization
reproduces ``attention.quantize_kv`` bit-exactly (f32 absmax / 127,
bf16-rounded scale), so chunked pools match ``pack_prompt``-packed pools.

TPU notes: D is the 128-lane dim (head_dim 64/128) and KVH the sublane
dim of every page; G stays small — fine for VPU-bound decode.
tests/test_tpu_compile.py compiles both kernels for a described v5e at
block sizes 16 and 32, fp and int8.  CPU CI runs the kernels in interpret
mode for parity only (per-grid-step interpreter overhead makes it slow);
the fast CPU path is :func:`..ops.flash_decode_jnp` /
:func:`..ops.flash_prefill_jnp`, the same math vectorized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

NEG_INF = -1e30


def _col(tile, h: int):
    """Column ``h`` of a ``[R, KVH]`` tile as ``[R, 1]`` (exact: a masked
    lane sum, which Mosaic lowers without a lane-offset slice)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == h, tile, 0.0), axis=1, keepdims=True)


def _kernel(
    bt_ref,       # [B, W] int32  (scalar prefetch)
    nv_ref,       # [B]    int32  (scalar prefetch)
    q_ref,        # [1, KVH, G, D]
    *rest,        # pools in HBM (k, [k_scale], v, [v_scale]), out, m, l,
                  # their VMEM slots, DMA sems, chain state, carry
    bs: int,
    pps: int,
    ppg: int,
    n_groups: int,
    batch: int,
    width: int,
    d: int,
    kvh: int,
    int8: bool,
):
    n_ops = 4 if int8 else 2
    pools = rest[:n_ops]
    out_ref, m_ref, l_ref = rest[n_ops:n_ops + 3]
    slots = rest[n_ops + 3:2 * n_ops + 3]
    sems, chain, kf_scr, vf_scr, acc_scr, m_scr, l_scr = \
        rest[2 * n_ops + 3:]

    s = pl.program_id(0)
    b = pl.program_id(1)
    i = pl.program_id(2)
    col0 = s * pps                                  # split's first column

    def row_end(r):
        """One past the last live table column of row ``r`` in this
        split: its live pages, clamped to the table and the split."""
        nv = nv_ref[jnp.minimum(r, batch - 1)]
        return jnp.minimum(jnp.minimum(pl.cdiv(nv, bs), width), col0 + pps)

    def group_live(r, gi):
        return (gi < n_groups) & (col0 + gi * ppg < row_end(r))

    def copies(r, gi, slot):
        """(live, async copy) for every page and operand of group ``gi``
        of row ``r`` into ``slot``; pages past the row's end are dead."""
        c0 = col0 + gi * ppg
        end = row_end(r)
        out = []
        for j in range(ppg):
            page = bt_ref[r, jnp.minimum(c0 + j, width - 1)]
            for pool, buf in zip(pools, slots):
                out.append((c0 + j < end, pltpu.make_async_copy(
                    pool.at[page], buf.at[slot, j], sems.at[slot])))
        return out

    def start(r, gi, slot):
        for live, cp in copies(r, gi, slot):
            pl.when(live)(cp.start)

    def next_live():
        """The chain's next live step after ``(b, i)``: the row's next
        group, else the first group of the next row with a live slice;
        ``batch`` when none is left."""
        def next_row():
            return jax.lax.while_loop(
                lambda r: (r < batch) & (row_end(r) <= col0),
                lambda r: r + 1, b + 1)
        return jax.lax.cond(group_live(b, i + 1),
                            lambda: (b, i + 1),
                            lambda: (next_row(), jnp.int32(0)))

    @pl.when((b == 0) & (i == 0))
    def _chain_reset():
        chain[0] = 0          # slot of the current step's pages
        chain[1] = -1         # flat id of the step started into it

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(group_live(b, i))
    def _step():
        slot = chain[0]

        @pl.when(chain[1] != b * n_groups + i)
        def _first():                   # the chain's first live group
            start(b, i, slot)

        nb, ni = next_live()

        @pl.when(nb < batch)
        def _prefetch():
            start(nb, ni, 1 - slot)
            chain[0] = 1 - slot
            chain[1] = nb * n_groups + ni

        for live, cp in copies(b, i, slot):
            pl.when(live)(cp.wait)

        n = ppg * bs
        base = (col0 + i * ppg) * bs
        end_tok = jnp.minimum(nv_ref[b], row_end(b) * bs)
        valid = base + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) \
            < end_tok                                       # [1, P*BS]
        # The group in f32, once for all heads: a head's rows are then
        # a sublane-strided load of whole f32 tiles.
        kf_scr[...] = slots[0][slot].astype(jnp.float32)
        vf_scr[...] = slots[n_ops // 2][slot].astype(jnp.float32)
        if int8:
            # Scales as rows ([lanes, P*BS], head h in row h): K's scale
            # the scores, V's the probabilities, in-register.  Dead
            # pages' stale scales are zeroed; their int8 codes are finite.
            k_rows = slots[1][slot].astype(jnp.float32).reshape(n, -1).T \
                * (1 / np.sqrt(d))
            v_rows = jnp.where(
                valid, slots[3][slot].astype(jnp.float32).reshape(n, -1).T,
                0.0)
        else:
            valid_rows = base + jax.lax.broadcasted_iota(
                jnp.int32, (n, 1), 0) < end_tok             # [P*BS, 1]
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)                 # [G, D]
            k = kf_scr[:, :, h, :].reshape(n, d)
            v = vf_scr[:, :, h, :].reshape(n, d)
            srs = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [G, n]
            if int8:
                srs = srs * k_rows[h:h + 1]
            else:
                srs = srs / np.sqrt(d)
                # Dead pages of a partly live group hold stale slot
                # bytes: masked out of the scores below, zeroed out of V.
                v = jnp.where(valid_rows, v, 0.0)
            srs = jnp.where(valid, srs, NEG_INF)
            m_prev = m_scr[h]                                   # [G, 1]
            m_new = jnp.maximum(m_prev, srs.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # Explicit zeroing of masked probabilities: for a live group
            # m_new is a real score, so exp(NEG_INF - m_new) underflows to
            # 0 anyway — this just keeps fully-masked tails exact.
            prob = jnp.where(valid, jnp.exp(srs - m_new), 0.0)
            l_scr[h] = l_scr[h] * alpha + prob.sum(-1, keepdims=True)
            if int8:
                prob = prob * v_rows[h:h + 1]
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                prob, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(i == n_groups - 1)
    def _flush():
        out_ref[0, :, 0] = acc_scr[...]
        m_ref[0, :, 0] = m_scr[...]
        l_ref[0, :, 0] = l_scr[...]


@functools.partial(
    jax.jit, static_argnames=("kv_splits", "pages_per_step", "interpret"))
def paged_attention_kernel(
    q: jax.Array,             # [B, KVH, G, D] (any float dtype)
    k_pages: jax.Array,       # [NB, BS, KVH, D] fp or int8
    v_pages: jax.Array,       # [NB, BS, KVH, D]
    k_scale: jax.Array | None,  # [NB, BS, KVH] (int8 pools), else None
    v_scale: jax.Array | None,
    block_tables: jax.Array,  # [B, W] int32
    n_valid: jax.Array,       # [B] int32
    *,
    kv_splits: int = 1,
    pages_per_step: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split-KV partials ``(acc, m, l)`` with shapes
    ``([B,KVH,S,G,D], [B,KVH,S,G,1], [B,KVH,S,G,1])``; combine with
    :func:`..ops.merge_splits`.  ``pages_per_step`` table columns a grid
    step (capped at the split width; ``ops.paged_attention`` takes the
    autotuner's choice)."""
    b, kvh, g, d = q.shape
    _, bs, _, _ = k_pages.shape
    width = block_tables.shape[1]
    int8 = k_pages.dtype == jnp.int8
    assert (k_scale is not None) == int8, "int8 pages need scales"
    ns = max(1, min(kv_splits, width))
    pps = -(-width // ns)
    ppg = max(1, min(pages_per_step, pps))
    n_groups = -(-pps // ppg)

    def q_map(si, bi, gi, bt, nv):
        return (bi, 0, 0, 0)

    def out_map(si, bi, gi, bt, nv):
        return (bi, 0, si, 0, 0)

    if int8:
        # A page's [BS, KVH] scale tile is lane-padded in HBM, and Mosaic
        # slices an HBM ref only along whole tiles: pad the lanes to 128
        # (the bytes the tiled layout holds anyway) so a page is a slice.
        lanes = ((0, 0), (0, 0), (0, -(-kvh // 128) * 128 - kvh))
        pools = [k_pages, jnp.pad(k_scale, lanes),
                 v_pages, jnp.pad(v_scale, lanes)]
    else:
        pools = [k_pages, v_pages]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ns, b, n_groups),
        in_specs=[pl.BlockSpec((1, kvh, g, d), q_map)]
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        out_specs=[
            pl.BlockSpec((1, kvh, 1, g, d), out_map),
            pl.BlockSpec((1, kvh, 1, g, 1), out_map),
            pl.BlockSpec((1, kvh, 1, g, 1), out_map),
        ],
        scratch_shapes=[compat.VMEM((2, ppg, *p.shape[1:]), p.dtype)
                        for p in pools] + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            compat.VMEM((ppg, bs, kvh, d), jnp.float32),
            compat.VMEM((ppg, bs, kvh, d), jnp.float32),
            compat.VMEM((kvh, g, d), jnp.float32),
            compat.VMEM((kvh, g, 1), jnp.float32),
            compat.VMEM((kvh, g, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, bs=bs, pps=pps, ppg=ppg,
                             n_groups=n_groups, batch=b, width=width, d=d,
                             kvh=kvh, int8=int8)
    acc, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, ns, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, ns, g, 1), jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            # One chain per split: rows and groups run in order, because
            # each step starts the copies of the next.
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_attention_decode",
    )(block_tables, n_valid, q, *pools)
    return acc, m, l


# ---------------------------------------------------------------------------
# Flash prefill: causal chunk attention + in-kernel paged KV writes
# ---------------------------------------------------------------------------

def _prefill_kernel(
    bt_ref,       # [B, W] int32   (scalar prefetch)
    pos_ref,      # [B]    int32   chunk start = tokens already in the pool
    nt_ref,       # [B]    int32   valid tokens in this chunk (ragged tail)
    wm_ref,       # [B]    int32   1 = row is prefilling this chunk
    q_ref,        # [1, KVH, C*G, D]
    kn_ref,       # [1, C, KVH, D] in-hand chunk K (fp, post-RoPE)
    vn_ref,       # [1, C, KVH, D]
    k_ref,        # [1, BS, KVH, D] pool page, all kv heads
    *rest,        # (k_scale, v, v_scale | v), outs, scratches
    bs: int,
    width: int,
    c: int,
    g: int,
    d: int,
    kvh: int,
    int8: bool,
    out_dtype,
):
    if int8:
        ks_ref, v_ref, vs_ref = rest[0], rest[1], rest[2]
        rest = rest[3:]
    else:
        v_ref = rest[0]
        rest = rest[1:]
    if int8:
        (out_ref, ko_ref, kso_ref, vo_ref, vso_ref,
         acc_scr, m_scr, l_scr) = rest
    else:
        out_ref, ko_ref, vo_ref, acc_scr, m_scr, l_scr = rest
        kso_ref = vso_ref = None

    b = pl.program_id(0)
    t = pl.program_id(1)
    pos = pos_ref[b]
    n_tok = nt_ref[b]
    on = wm_ref[b] != 0
    cg = c * g
    # query chunk index of each of the C*G query rows (chunk-major layout)
    qi = jax.lax.broadcasted_iota(jnp.int32, (cg, 1), 0) // g

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def online_update(h, srs, valid, v):
        """One online-softmax accumulation step of kv head ``h`` over
        [CG, N] scores."""
        srs = jnp.where(valid, srs, NEG_INF)
        m_prev = m_scr[h]                                   # [CG, 1]
        m_new = jnp.maximum(m_prev, srs.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.where(valid, jnp.exp(srs - m_new), 0.0)
        l_scr[h] = l_scr[h] * alpha + prob.sum(-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            prob, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    # ---- past-page walk: every chunk query sees every past key ----------
    @pl.when((t < width) & on & (t * bs < pos))
    def _past():
        kp = t * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        if int8:
            ks = ks_ref[0].astype(jnp.float32)              # [BS, KVH]
            vs = vs_ref[0].astype(jnp.float32)
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)             # [CG, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)       # [BS, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if int8:
                k = k * _col(ks, h)
                v = v * _col(vs, h)
            srs = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / np.sqrt(d)  # [CG, BS]
            online_update(h, srs, kp < pos, v)

    # ---- self tile: causal within the chunk, in-hand fp K/V -------------
    @pl.when((t == width) & on)
    def _self():
        kj = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)             # [CG, D]
            k = kn_ref[0, :, h, :].astype(jnp.float32)      # [C, D]
            v = vn_ref[0, :, h, :].astype(jnp.float32)
            srs = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / np.sqrt(d)  # [CG, C]
            online_update(h, srs, (kj <= qi) & (kj < n_tok), v)

    @pl.when(t == width)
    def _flush():
        out_ref[0] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(out_dtype)

    # ---- write phase: quantize the chunk K/V into its pool pages --------
    j = t - (width + 1)
    @pl.when((t > width) & on & (j * bs < n_tok))
    def _write():
        for src_ref, co, so in ((kn_ref, ko_ref, kso_ref),
                                (vn_ref, vo_ref, vso_ref)):
            if not int8:
                co[0] = src_ref[0, pl.ds(j * bs, bs)].astype(co.dtype)
                continue
            # Identical math to attention.quantize_kv: f32 absmax scale,
            # bf16 storage rounding, codes from the bf16-rounded scale.
            lane = jax.lax.broadcasted_iota(jnp.int32, (bs, kvh), 1)
            scales = jnp.zeros((bs, kvh), jnp.float32)
            for h in range(kvh):
                x = src_ref[0, pl.ds(j * bs, bs), h, :].astype(jnp.float32)
                scale = jnp.maximum(
                    jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0,
                    1e-8).astype(jnp.bfloat16).astype(jnp.float32)
                co[0, :, h, :] = jnp.clip(
                    jnp.round(x / scale), -127, 127).astype(jnp.int8)
                scales = jnp.where(lane == h, scale, scales)
            so[0] = scales.astype(so.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_prefill_kernel(
    q: jax.Array,              # [B, KVH, C*G, D] (any float dtype)
    k_new: jax.Array,          # [B, C, KVH, D] fp chunk K (post-RoPE)
    v_new: jax.Array,          # [B, C, KVH, D]
    k_pages: jax.Array,        # [NB, BS, KVH, D] fp or int8
    v_pages: jax.Array,
    k_scale: jax.Array | None,  # [NB, BS, KVH] (int8 pools), else None
    v_scale: jax.Array | None,
    block_tables: jax.Array,   # [B, W] int32
    pos: jax.Array,            # [B] int32, page-aligned chunk starts
    n_tok: jax.Array,          # [B] int32 valid tokens this chunk
    write_mask: jax.Array,     # [B] int32 (1 = prefilling row)
    *,
    interpret: bool = False,
):
    """Causal chunk attention over (pool pages [0, pos) + in-hand chunk)
    with the chunk's K/V quantized and written into its pool pages by the
    same kernel — the prompt K/V never exists as a dense cache and never
    round-trips through a host-side ``pack_prompt`` scatter.

    Grid ``(B, W + 1 + C/BS)`` over whole pages, heads looped in-kernel:
    the innermost dimension first walks the request's past pages
    sequentially (scalar-prefetched block-table indirection, dead steps
    clamped to the last live page so repeated indices elide the DMA), then
    runs the causal self tile on the in-hand chunk, then writes the
    chunk's pages.  The page *writes* go through
    output index maps over the pool buffer itself (``input_output_aliases``),
    so masked rows (``write_mask`` 0) and dead tail steps land on the
    reserved null block 0 while every untouched pool block keeps its bytes.

    Returns ``(out [B, KVH, C*G, D], k_pages, v_pages[, k_scale, v_scale])``
    — the attention output plus the updated pool (scales only for int8
    pools).
    """
    b, kvh, cg, d = q.shape
    c = k_new.shape[1]
    g = cg // c
    _, bs, _, _ = k_pages.shape
    width = block_tables.shape[1]
    assert c % bs == 0, f"chunk {c} must be a block_size {bs} multiple"
    cp = c // bs
    int8 = k_pages.dtype == jnp.int8
    assert (k_scale is not None) == int8, "int8 pages need scales"
    out_dtype = q.dtype

    def q_map(bi, ti, bt, ps, nt, wm):
        return (bi, 0, 0, 0)

    def new_map(bi, ti, bt, ps, nt, wm):
        return (bi, 0, 0, 0)

    def page_map(bi, ti, bt, ps, nt, wm):
        # Past walk; dead steps (ti beyond the live past pages, or the
        # self/write phase) clamp to the last live past page so consecutive
        # repeats elide the DMA.
        live_last = jnp.maximum(jax.lax.div(ps[bi] - 1, bs), 0)
        i = jnp.minimum(jnp.minimum(ti, live_last), width - 1)
        return (bt[bi, i], 0, 0, 0)

    def scale_map(bi, ti, bt, ps, nt, wm):
        return page_map(bi, ti, bt, ps, nt, wm)[:3]

    def out_map(bi, ti, bt, ps, nt, wm):
        return (bi, 0, 0, 0)

    def wr_map(bi, ti, bt, ps, nt, wm):
        # Write phase: chunk page j -> table slot pos/BS + j; anything else
        # (attention steps, masked rows, ragged dead tail) -> null block 0,
        # whose content is garbage by contract.
        j = ti - (width + 1)
        slot = jax.lax.div(ps[bi], bs) + jnp.maximum(j, 0)
        live = (j >= 0) & (wm[bi] != 0) & (j * bs < nt[bi]) & (slot < width)
        idx = jnp.where(live, bt[bi, jnp.minimum(slot, width - 1)], 0)
        return (idx, 0, 0, 0)

    def wr_scale_map(bi, ti, bt, ps, nt, wm):
        return wr_map(bi, ti, bt, ps, nt, wm)[:3]

    in_specs = [
        pl.BlockSpec((1, kvh, cg, d), q_map),
        pl.BlockSpec((1, c, kvh, d), new_map),
        pl.BlockSpec((1, c, kvh, d), new_map),
        pl.BlockSpec((1, bs, kvh, d), page_map),
    ]
    args = [block_tables, pos, n_tok, write_mask, q, k_new, v_new, k_pages]
    if int8:
        in_specs.append(pl.BlockSpec((1, bs, kvh), scale_map))
        args.append(k_scale)
    in_specs.append(pl.BlockSpec((1, bs, kvh, d), page_map))
    args.append(v_pages)
    if int8:
        in_specs.append(pl.BlockSpec((1, bs, kvh), scale_map))
        args.append(v_scale)

    out_specs = [pl.BlockSpec((1, kvh, cg, d), out_map),
                 pl.BlockSpec((1, bs, kvh, d), wr_map)]
    out_shape = [jax.ShapeDtypeStruct((b, kvh, cg, d), out_dtype),
                 jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype)]
    # pallas_call input indices COUNT the scalar-prefetch args (tested:
    # the aliased pool buffers keep every unwritten block's bytes).
    if int8:
        out_specs += [pl.BlockSpec((1, bs, kvh), wr_scale_map),
                      pl.BlockSpec((1, bs, kvh, d), wr_map),
                      pl.BlockSpec((1, bs, kvh), wr_scale_map)]
        out_shape += [jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                      jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
                      jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype)]
        aliases = {7: 1, 8: 2, 9: 3, 10: 4}
    else:
        out_specs.append(pl.BlockSpec((1, bs, kvh, d), wr_map))
        out_shape.append(jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype))
        aliases = {7: 1, 8: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, width + 1 + cp),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            compat.VMEM((kvh, cg, d), jnp.float32),
            compat.VMEM((kvh, cg, 1), jnp.float32),
            compat.VMEM((kvh, cg, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_prefill_kernel, bs=bs, width=width, c=c, g=g,
                             d=d, kvh=kvh, int8=int8, out_dtype=out_dtype)
    # Scoped VMEM: double-buffered q/out and chunk K/V blocks, the f32
    # (acc, m, l) carry (m/l pad to 128 lanes) and one head's [CG, C]
    # score/probability temporaries.  Long chunks outgrow the 16 MiB
    # default; v5e has 128 MiB of VMEM.
    qb = kvh * cg * d * q.dtype.itemsize
    vmem = (4 * qb + 4 * c * kvh * d * k_new.dtype.itemsize
            + kvh * cg * (d + 2 * 128) * 4 + 4 * cg * max(c, 128) * 4)
    vmem_limit = int(min(max(2 * vmem, 32 << 20), 100 << 20))
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compat.CompilerParams(
            # b is sequential: masked rows share the null block's out
            # window, so the batch axis must not race across cores.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        ),
        input_output_aliases=aliases,
        interpret=interpret,
        name="paged_attention_prefill",
    )(*args)
