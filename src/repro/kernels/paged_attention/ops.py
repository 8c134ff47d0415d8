"""Public wrappers for the fused paged-attention decode and prefill kernels.

``paged_attention`` is what the model layer calls (the paged branch of
``attention()`` behind ``DeploymentPlan.paged_attn``).  It accepts the
pool's native page pytrees — fp arrays or int8
:class:`~repro.core.quant.QTensor` pages — GQA-reshapes the query, resolves
the split count from :mod:`repro.kernels.autotune`, and dispatches one of
three backends:

* ``"pallas"``    — the compiled TPU kernel (page groups copied by
  manual DMA through the scalar-prefetched block tables).
* ``"interpret"`` — the same kernel through the Pallas interpreter.  This
  is the CPU *correctness* path (CI parity tests); the interpreter costs
  ~1 ms per grid step, so it is not the CPU serving path.
* ``"emulate"``   — the identical split-KV flash-decoding math as
  vectorized jnp (:func:`flash_decode_jnp`): per-split two-pass softmax
  over the table-referenced pages, merged with the same
  :func:`merge_splits`.  This is the fast interpret-mode fallback the
  serve loop uses on CPU; it agrees with the kernel to fp rounding
  (~1e-7, tested) and with the gather reference likewise.

``backend=None`` resolves to ``"pallas"`` on TPU and ``"emulate"``
elsewhere, mirroring ``cim_matmul``'s compiled-or-interpret selection.

Traffic contract: with a width-``W`` block table the kernel copies only
live pages (none past a row's length) and the emulate path gathers only
the ``W`` table columns it is handed — the serve loop truncates tables to a
power-of-two bucket of the live maximum each segment, so decode attention
bytes scale with live tokens, never with ``kv_blocks``
(benchmarks/paged_attention.py measures exactly this).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels import autotune
from repro.kernels.paged_attention.kernel import (NEG_INF,
                                                 flash_prefill_kernel,
                                                 paged_attention_kernel)


def merge_splits(acc, m, l):
    """Logsumexp-combine split-KV partials over the split axis (axis 2).

    acc [B,KVH,S,G,D], m/l [B,KVH,S,G,1] -> [B,KVH,G,D].  Dead splits
    carry (acc=0, m=NEG_INF, l=0) and contribute nothing; a request with
    no live positions at all returns zeros (finite — the gather reference
    returns a mean-of-garbage value there; serve discards both)."""
    m_g = m.max(axis=2, keepdims=True)
    alpha = jnp.exp(m - m_g)
    l_g = (l * alpha).sum(axis=2)                       # [B,KVH,G,1]
    acc_g = (acc * alpha).sum(axis=2)                   # [B,KVH,G,D]
    return acc_g / jnp.maximum(l_g, 1e-30)


def _split_pages(pages):
    """QTensor pages -> (codes, [NB,BS,KVH] scales); fp pages -> (pages,
    None)."""
    if isinstance(pages, quant.QTensor):
        return pages.q, pages.scale[..., 0]
    return pages, None


def flash_decode_jnp(q, k_pages, k_scale, v_pages, v_scale, block_tables,
                     n_valid, *, kv_splits: int = 1) -> jax.Array:
    """The kernel's math as vectorized jnp (the fast CPU path).

    q [B,KVH,G,D]; pages [NB,BS,KVH,D] (+ [NB,BS,KVH] scales for int8);
    block_tables [B,W]; n_valid [B].  Gathers the W referenced pages,
    computes per-split two-pass softmax partials, and merges them with the
    same :func:`merge_splits` the kernel outputs feed — identical
    semantics, fp-rounding-level agreement with the kernel (tested).
    """
    b, kvh, g, d = q.shape
    bs = k_pages.shape[1]
    w = block_tables.shape[1]
    ns = max(1, min(kv_splits, w))
    pps = -(-w // ns)
    pad = ns * pps - w

    def gather(pages, scale):
        gp = pages[block_tables]                        # [B, W, BS, KVH, D]
        gp = gp.astype(jnp.float32)
        if scale is not None:
            gp = gp * scale[block_tables].astype(jnp.float32)[..., None]
        if pad:
            gp = jnp.pad(gp, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        # [B, ns, pps*BS, KVH, D]
        return gp.reshape(b, ns, pps * bs, kvh, d)

    kg = gather(k_pages, k_scale)
    vg = gather(v_pages, v_scale)
    srs = jnp.einsum("bkgd,bsnkd->bksgn", q.astype(jnp.float32), kg) \
        / np.sqrt(d)                                    # [B,KVH,ns,G,pps*BS]
    # positions are global: split s covers [s*pps*bs, (s+1)*pps*bs).  The
    # w*bs bound clamps n_valid to the table like the kernel's page <
    # width check — split padding and out-of-table positions never attend.
    pos = (jnp.arange(ns)[:, None] * pps * bs
           + jnp.arange(pps * bs)[None, :])             # [ns, pps*BS]
    valid = (pos[None] < n_valid[:, None, None]) \
        & (pos[None] < w * bs)                          # [B, ns, pps*BS]
    srs = jnp.where(valid[:, None, :, None, :], srs, NEG_INF)
    m = srs.max(-1, keepdims=True)                      # [B,KVH,ns,G,1]
    prob = jnp.where(valid[:, None, :, None, :],
                     jnp.exp(srs - m), 0.0)
    l = prob.sum(-1, keepdims=True)
    acc = jnp.einsum("bksgn,bsnkd->bksgd", prob, vg)
    return merge_splits(acc, m, l)


def decode_grouping(batch: int, k_pages, width: int, groups: int,
                    kv_splits: int | None = None) -> tuple[int, int]:
    """``(kv_splits, pages_per_step)`` that :func:`paged_attention` runs
    for `batch` rows of `groups` query heads per KV head over a
    width-`width` table into `k_pages` (any leading axes before ``[BS,
    KVH, D]``, so a layer-stacked pool too)."""
    codes, _ = _split_pages(k_pages)
    bs, kvh, d = codes.shape[-3:]
    return autotune.choose_paged_decode(batch, kvh, width, bs, codes.dtype,
                                        head_dim=d, groups=groups,
                                        kv_splits=kv_splits)


def paged_attention(
    q: jax.Array,              # [B, 1, H, D]
    k_pages, v_pages,          # [NB, BS, KVH, D] arrays or QTensors
    block_tables: jax.Array,   # [B, W] int32
    n_valid: jax.Array,        # [B] int32
    *,
    kv_splits: int | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Fused paged decode attention; drop-in for
    :func:`repro.models.attention.attend_decode_paged` (same signature up
    to the keywords, same [B, 1, H, D] output).

    ``kv_splits`` and the kernel's pages per grid step default to the
    autotuner's choice for this (batch, kv_heads, table width, block
    size) — resolved here, outside any jit boundary, like
    ``cim_matmul``'s block resolution.

    ``n_valid`` is clamped to the table capacity ``W * BS`` (positions
    beyond the handed-in table do not exist); every backend applies the
    same clamp, so truncated-table callers agree across backends."""
    b, sq, h, d = q.shape
    assert sq == 1, "paged flash decoding serves single-token queries"
    k_q, k_s = _split_pages(k_pages)
    v_q, v_s = _split_pages(v_pages)
    kvh = k_q.shape[2]
    g = h // kvh
    width = block_tables.shape[1]
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "emulate"
    kv_splits, pages_per_step = decode_grouping(b, k_pages, width, g,
                                                kv_splits)
    qr = q.reshape(b, kvh, g, d)
    if backend == "emulate":
        out = flash_decode_jnp(qr, k_q, k_s, v_q, v_s, block_tables,
                               n_valid, kv_splits=kv_splits)
    elif backend in ("pallas", "interpret"):
        acc, m, l = paged_attention_kernel(
            qr, k_q, v_q, k_s, v_s,
            jnp.asarray(block_tables, jnp.int32),
            jnp.asarray(n_valid, jnp.int32),
            kv_splits=kv_splits, pages_per_step=pages_per_step,
            interpret=backend == "interpret")
        out = merge_splits(acc, m, l)
    else:
        raise ValueError(f"backend must be 'pallas', 'interpret', or "
                         f"'emulate', got {backend!r}")
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash prefill: causal chunk attention + paged KV writes
# ---------------------------------------------------------------------------

def flash_prefill_jnp(q, k_new, v_new, k_q, k_s, v_q, v_s, block_tables,
                      pos, n_tok, has_past: bool = True):
    """The prefill kernel's attention math as vectorized jnp.

    q [B,KVH,C,G,D]; k_new/v_new [B,C,KVH,D] (fp, post-RoPE); pages
    [NB,BS,KVH,D] (+ [NB,BS,KVH] scales for int8); block_tables [B,W];
    pos [B] past tokens; n_tok [B] valid chunk tokens.  Every chunk query
    attends all past positions < pos plus the causal (and ragged-tail
    masked) prefix of the in-hand chunk — the in-hand K/V stays fp, like
    the unchunked prefill's ``attend_full`` over in-hand projections.
    Returns the attention output only; page writes are a separate scatter
    (:func:`write_chunk_pages`).

    ``has_past=False`` (a STATIC hint: every row's pos is 0 — first
    chunks, the common case for short prompts) skips the past-page gather
    entirely; the math is unchanged because pos=0 masks every past
    position anyway."""
    b, kvh, c, g, d = q.shape
    bs = k_q.shape[1]
    w = block_tables.shape[1]
    sp = w * bs if has_past else 0

    def gather(pages, scale):
        gp = pages[block_tables].astype(jnp.float32)    # [B, W, BS, KVH, D]
        if scale is not None:
            gp = gp * scale[block_tables].astype(jnp.float32)[..., None]
        return gp.reshape(b, sp, kvh, d)

    if has_past:
        k_all = jnp.concatenate([gather(k_q, k_s),
                                 k_new.astype(jnp.float32)], axis=1)
        v_all = jnp.concatenate([gather(v_q, v_s),
                                 v_new.astype(jnp.float32)], axis=1)
    else:
        k_all = k_new.astype(jnp.float32)
        v_all = v_new.astype(jnp.float32)
    srs = jnp.einsum("bkcgd,bskd->bkcgs", q.astype(jnp.float32), k_all) \
        / np.sqrt(d)                                    # [B,KVH,C,G,Sp+C]
    kp = jnp.arange(sp + c)
    past_ok = (kp[None, :] < pos[:, None]) & (kp < sp)[None, :]   # [B, S]
    ci = jnp.arange(c)
    self_ok = ((kp[None, None, :] >= sp)
               & (kp[None, None, :] - sp <= ci[None, :, None])
               & ((kp[None, :] - sp < n_tok[:, None])[:, None, :]))
    valid = past_ok[:, None, :] | self_ok               # [B, C, Sp+C]
    valid = valid[:, None, :, None, :]                  # [B,1,C,1,S]
    srs = jnp.where(valid, srs, NEG_INF)
    m = srs.max(-1, keepdims=True)
    prob = jnp.where(valid, jnp.exp(srs - m), 0.0)
    l = prob.sum(-1, keepdims=True)
    acc = jnp.einsum("bkcgs,bskd->bkcgd", prob, v_all)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def write_chunk_pages(pages, new, block_tables, pos, n_tok, write_mask):
    """Scatter one chunk's K or V ([B, C, KVH, D] fp) into its pool pages.

    The same quantize-then-place semantics as the kernel's write phase
    (``attention.quantize_kv`` grid for int8 QTensor pools); chunk starts
    are page-aligned (C and pos are block_size multiples) so each chunk
    page maps to exactly one table slot.  Masked rows and ragged dead-tail
    pages land on the reserved null block 0."""
    bs = (pages.q if isinstance(pages, quant.QTensor) else pages).shape[1]
    b, c = new.shape[:2]
    assert c % bs == 0, f"chunk {c} must be a block_size {bs} multiple"
    cp = c // bs
    w = block_tables.shape[1]
    j = jnp.arange(cp)
    slots = pos[:, None] // bs + j[None, :]             # [B, CP]
    live = ((j[None, :] * bs < n_tok[:, None])
            & (slots < w))
    if write_mask is not None:
        live = live & write_mask[:, None]
    idx = jnp.where(
        live,
        jnp.take_along_axis(block_tables, jnp.minimum(slots, w - 1), axis=1),
        0)
    if isinstance(pages, quant.QTensor):
        from repro.models.attention import quantize_kv  # lazy: no cycle
        codes, scale = quantize_kv(new)
        chunk = quant.QTensor(
            codes.reshape(b, cp, bs, *codes.shape[2:]),
            scale[..., None].reshape(b, cp, bs, *scale.shape[2:], 1))
        return pages.at_set((idx,), chunk)
    dtype = pages.dtype
    return pages.at[idx].set(new.reshape(b, cp, bs, *new.shape[2:])
                             .astype(dtype))


def paged_prefill(
    q: jax.Array,              # [B, C, H, D]
    k_new: jax.Array,          # [B, C, KVH, D] (fp, post-RoPE)
    v_new: jax.Array,
    k_pages, v_pages,          # [NB, BS, KVH, D] arrays or QTensors
    block_tables: jax.Array,   # [B, W] int32
    pos: jax.Array,            # [B] int32 page-aligned chunk starts
    n_tok: jax.Array,          # [B] int32 valid tokens this chunk
    write_mask: jax.Array | None = None,   # [B] bool, None = all rows
    *,
    has_past: bool = True,
    backend: str | None = None,
):
    """Fused causal-chunk paged prefill: attention over (past pool pages +
    in-hand chunk) AND the chunk's K/V quantized + written into the pool,
    one kernel.  Drop-in for the model layer's chunked paged branch; the
    chunk K/V never exists as a dense cache and `pack_prompt` never runs.

    Returns ``(out [B, C, H, D], k_pages', v_pages')`` with the pages in
    their input form (QTensor for int8 pools).

    ``backend=None`` resolves to the compiled kernel on TPU and the
    same-math vectorized emulation elsewhere, like :func:`paged_attention`
    (``"interpret"`` runs the kernel through the Pallas interpreter for
    parity tests — the emulation's page writes are an out-of-kernel
    scatter of identically-quantized pages, not a ``pack_prompt``).

    ``has_past=False`` is a STATIC first-chunk hint (every row's pos is
    0): the emulation skips its past gather; the kernel needs no hint —
    its index-map clamp already elides every dead past-page DMA."""
    b, c, h, d = q.shape
    k_q, k_s = _split_pages(k_pages)
    v_q, v_s = _split_pages(v_pages)
    kvh = k_q.shape[2]
    g = h // kvh
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "emulate"
    wm = (jnp.ones((b,), jnp.int32) if write_mask is None
          else jnp.asarray(write_mask).astype(jnp.int32))
    pos = jnp.asarray(pos, jnp.int32)
    n_tok = jnp.asarray(n_tok, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    qr = q.reshape(b, c, kvh, g, d).transpose(0, 2, 1, 3, 4)
    if backend == "emulate":
        out = flash_prefill_jnp(qr, k_new, v_new, k_q, k_s, v_q, v_s,
                                bt, pos, n_tok, has_past=has_past)
        wm_b = wm.astype(bool)
        new_k = write_chunk_pages(k_pages, k_new, bt, pos, n_tok, wm_b)
        new_v = write_chunk_pages(v_pages, v_new, bt, pos, n_tok, wm_b)
    elif backend in ("pallas", "interpret"):
        res = flash_prefill_kernel(
            qr.reshape(b, kvh, c * g, d), k_new, v_new, k_q, v_q, k_s, v_s,
            bt, pos, n_tok, wm, interpret=backend == "interpret")
        if k_s is not None:
            out, ko, kso, vo, vso = res
            out = out.reshape(b, kvh, c, g, d)
            new_k = quant.QTensor(ko, kso[..., None])
            new_v = quant.QTensor(vo, vso[..., None])
        else:
            out, new_k, new_v = res
            out = out.reshape(b, kvh, c, g, d)
    else:
        raise ValueError(f"backend must be 'pallas', 'interpret', or "
                         f"'emulate', got {backend!r}")
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, c, h, d)
    return out.astype(q.dtype), new_k, new_v
