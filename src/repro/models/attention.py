"""Attention: GQA with RoPE/M-RoPE, qk-norm, sliding-window, cross-attention.

Three execution regimes, all numerically the same attention:

* ``attend_full``      — materialized scores; used for short sequences
                         (smoke tests, training at modest S).
* ``attend_chunked``   — double-chunked online-softmax (flash-style) scan:
                         outer scan over query chunks, inner scan over KV
                         chunks, O(chunk^2) live memory.  Used by training /
                         prefill at large S.  For sliding-window attention the
                         inner loop runs over a fixed-size KV *band* per query
                         chunk (O(S * window) FLOPs, not O(S^2)).
* ``attend_decode``    — single query position vs a KV cache.  Shardable on
                         the KV sequence axis: the softmax is expressed as
                         partial logsumexp + weighted-V partials so XLA SPMD
                         lowers it to small per-head collectives instead of
                         gathering the cache (see distributed/collectives.py
                         for the shard_map variant and the equivalence test).
* ``attend_decode_paged`` — decode over the continuous-batching paged KV
                         pool.  ``impl="reference"`` gathers the block-
                         table-referenced pages into a dense view and
                         reuses ``attend_decode``/``attend_decode_int8``;
                         ``impl="fused"`` (``DeploymentPlan(paged_attn=
                         True)``) runs the flash-decoding Pallas kernel in
                         kernels/paged_attention — no gathered cache, int8
                         pages dequantized in-registers, split-KV merge.

Score x value matmuls are activation x activation, so they stay in bf16 —
the CiM datapath applies to the projections only (DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init / pspecs
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qk_norm: bool = False,
                   dtype=jnp.bfloat16) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "q": layers.init_dense(k1, d_model, n_heads * head_dim, dtype),
        "k": layers.init_dense(k2, d_model, n_kv_heads * head_dim, dtype),
        "v": layers.init_dense(k3, d_model, n_kv_heads * head_dim, dtype),
        "o": layers.init_dense(k4, n_heads * head_dim, d_model, dtype,
                               scale=(n_heads * head_dim) ** -0.5),
    }
    if qk_norm:
        p["q_norm"] = layers.init_rmsnorm(head_dim)
        p["k_norm"] = layers.init_rmsnorm(head_dim)
    return p


def attention_pspec(qk_norm: bool = False, frozen: bool = False) -> dict:
    p = {
        "q": layers.dense_pspec("embed", "q_heads", frozen),
        "k": layers.dense_pspec("embed", "kv_heads", frozen),
        "v": layers.dense_pspec("embed", "kv_heads", frozen),
        "o": layers.dense_pspec("q_heads", "embed", frozen),
    }
    if qk_norm:
        p["q_norm"] = {"scale": (None,)}
        p["k_norm"] = {"scale": (None,)}
    return p


# ---------------------------------------------------------------------------
# Core math
# ---------------------------------------------------------------------------

def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """[B, S, KVH, D] -> [B, S, KVH*groups, D] for GQA."""
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)).reshape(
        b, s, h * groups, d
    )


def _mask_value(q_pos, k_pos, causal: bool, window: int | None):
    ok = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        ok = ok & (k_pos <= q_pos)
    if window is not None:
        ok = ok & (k_pos > q_pos - window)
    return ok


def attend_full(q, k, v, *, causal: bool, window: int | None = None,
                q_offset: int = 0) -> jax.Array:
    """q:[B,Sq,H,D] k,v:[B,Sk,KVH,D] -> [B,Sq,H,D].  Materialized scores."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    k = _repeat_kv(k, h // kvh)
    v = _repeat_kv(v, h // kvh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(d)
    q_pos = jnp.arange(sq)[:, None] + q_offset
    k_pos = jnp.arange(sk)[None, :]
    ok = _mask_value(q_pos, k_pos, causal, window)
    scores = jnp.where(ok[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend_chunked(q, k, v, *, causal: bool, window: int | None = None,
                   q_chunk: int = 512, kv_chunk: int = 1024) -> jax.Array:
    """Flash-style double-chunked attention; O(q_chunk*kv_chunk) live scores.

    For sliding-window attention each query chunk reads only the KV *band*
    [chunk_end - window - q_chunk, chunk_end), keeping FLOPs O(S * window).
    """
    b, s, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    groups = h // kvh
    assert s % q_chunk == 0, (s, q_chunk)
    n_q = s // q_chunk

    full_band = int(np.ceil(sk / kv_chunk)) * kv_chunk
    if window is not None:
        # Band width rounded up to a kv_chunk multiple for static shapes.
        band = int(np.ceil((window + q_chunk) / kv_chunk)) * kv_chunk
        band = min(band, full_band)
    else:
        band = full_band
    pad_k = band  # left-pad so every band slice is in range
    k_p = jnp.pad(k, ((0, 0), (pad_k, 0), (0, 0), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (pad_k, 0), (0, 0), (0, 0)))
    n_kv = band // kv_chunk

    q_r = q.reshape(b, n_q, q_chunk, h, d).transpose(1, 0, 2, 3, 4)

    from repro.distributed.sharding import constrain
    k_p = constrain(k_p, {0: "batch", 2: "model"})
    v_p = constrain(v_p, {0: "batch", 2: "model"})
    q_r = constrain(q_r, {1: "batch", 3: "model"})

    def q_step(_, qc_i):
        qc, i = qc_i  # qc: [B, qc, H, D]; i: chunk index
        q_end = (i + 1) * q_chunk           # exclusive end in unpadded coords
        if causal or window is not None:
            band_start = q_end - band       # trailing band (may start < 0)
        else:
            band_start = sk - band          # cross/bidirectional: cover all KV
        kb = jax.lax.dynamic_slice_in_dim(k_p, band_start + pad_k, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v_p, band_start + pad_k, band, axis=1)
        q_pos = band - q_chunk + jnp.arange(q_chunk)   # positions in band coords
        # (same offset math for mask: k band position j corresponds to
        #  absolute k_pos = band_start + j; q abs pos = q_end - q_chunk + t.)
        kb_r = kb.reshape(b, n_kv, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)
        vb_r = vb.reshape(b, n_kv, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)

        def kv_step(carry, kc_j):
            m, l, acc = carry
            kc, vc, j = kc_j
            kc = _repeat_kv(kc, groups)
            vc = _repeat_kv(vc, groups)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32)
            scores = scores / np.sqrt(d)
            k_band_pos = j * kv_chunk + jnp.arange(kv_chunk)
            abs_q = (band_start + q_pos)[:, None]
            abs_k = (band_start + k_band_pos)[None, :]
            ok = _mask_value(abs_q, abs_k, causal, window)
            ok = ok & (abs_k >= 0) & (abs_k < sk)  # padding bounds
            scores = jnp.where(ok[None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qc.dtype), vc
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, h, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((b, h, q_chunk), jnp.float32),
            jnp.zeros((b, h, q_chunk, d), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (kb_r, vb_r, jnp.arange(n_kv))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, qc, H, D]

    # Flash-style backward: recompute each query chunk's KV sweep instead of
    # saving [n_q, n_kv, B, H, qc, kc] score stacks for the layer backward.
    q_step = jax.checkpoint(
        q_step, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(q_step, None, (q_r, jnp.arange(n_q)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def attend_decode_int8(q, k_q, k_s, v_q, v_s, kv_len_mask=None) -> jax.Array:
    """Fully-integer decode attention over an int8 KV cache (KIVI-style).

    q: [B, 1, H, D] float; k_q/v_q: [B, S, KVH, D] int8 with per-token-head
    scales k_s/v_s: [B, S, KVH].  Both the QK^T and PV contractions run
    int8 x int8 -> int32, so the cache is read from HBM in int8 — half the
    bytes of bf16, a direct application of the paper's datapath to the
    serving cache.  v's scale is folded into the probabilities before the
    PV contraction (p' = p * v_s), keeping the math exact up to int8
    rounding of p'.
    """
    b, sq, h, d = q.shape
    kvh = k_q.shape[2]
    groups = h // kvh
    qh = q.reshape(b, sq, kvh, groups, d).astype(jnp.float32)
    q_scale = jnp.maximum(jnp.max(jnp.abs(qh), axis=-1), 1e-8) / 127.0
    qq = jnp.clip(jnp.round(qh / q_scale[..., None]), -127, 127).astype(jnp.int8)
    s_int = jax.lax.dot_general(
        qq.transpose(0, 2, 1, 3, 4).reshape(b, kvh, sq * groups, d),
        k_q.transpose(0, 2, 3, 1),               # [B, KVH, D, S]
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32,
    ).reshape(b, kvh, sq, groups, -1)            # [B, KVH, Sq, G, S]
    qs = q_scale.reshape(b, sq, kvh, groups).transpose(0, 2, 1, 3)
    scores = s_int.astype(jnp.float32) * qs[..., None] \
        * k_s.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    scores = scores / np.sqrt(d)
    if kv_len_mask is not None:
        scores = jnp.where(kv_len_mask[:, None, None, None, :], scores,
                           NEG_INF)
    m = scores.max(-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = p.sum(-1)
    # fold v scales into p, then quantize p' for the int8 PV contraction
    p_fold = p * v_s.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    p_scale = jnp.maximum(jnp.max(p_fold, axis=-1), 1e-8) / 127.0
    pq = jnp.clip(jnp.round(p_fold / p_scale[..., None]), 0, 127).astype(
        jnp.int8)
    o_int = jax.lax.dot_general(
        pq.reshape(b, kvh, sq * groups, -1),
        v_q.transpose(0, 2, 1, 3),               # [B, KVH, S, D]
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32,
    ).reshape(b, kvh, sq, groups, d)
    out = o_int.astype(jnp.float32) * p_scale[..., None]
    out = out / l[..., None]
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def gather_pages(pages, block_tables, n_valid=None):
    """pages [NB, BS, ...] (array or int8 QTensor), block_tables [B, NBR]
    -> each request's cache as a contiguous [B, W*BS, ...] view.

    Pure data movement: position p of request b lives at
    pages[block_tables[b, p // BS], p % BS], so the gathered view holds
    exactly the written tokens in order (padding-table entries point at the
    null block and are excluded by the caller's length mask).

    With ``n_valid`` ([B] live positions) *concretely* known, only the
    first ``ceil(max(n_valid) / BS)`` table columns are gathered — the
    tight upper bound, so the gathered view scales with live tokens
    instead of the table width.  Under a jit trace n_valid is abstract and
    the full table is gathered (shapes must be static); the serve loop
    gets the same effect by truncating the tables it dispatches to a
    bucketed live width (serve/server.py)."""
    from repro.core import quant
    if n_valid is not None:
        bs = (pages.q if isinstance(pages, quant.QTensor)
              else pages).shape[1]
        try:
            nmax = int(np.max(np.asarray(n_valid)))
        except (TypeError, jax.errors.ConcretizationTypeError):
            nmax = None                    # traced: full-width gather
        if nmax is not None:
            w = min(max(-(-nmax // bs), 1), block_tables.shape[1])
            block_tables = block_tables[:, :w]
    if isinstance(pages, quant.QTensor):
        g = pages[block_tables]
        b, nbr, bs = g.q.shape[:3]
        return quant.QTensor(
            g.q.reshape(b, nbr * bs, *g.q.shape[3:]),
            g.scale.reshape(b, nbr * bs, *g.scale.shape[3:]))
    g = pages[block_tables]
    b, nbr, bs = g.shape[:3]
    return g.reshape(b, nbr * bs, *g.shape[3:])


def attend_decode_paged(q, k_pages, v_pages, block_tables, n_valid, *,
                        impl: str = "reference", kv_splits: int | None = None
                        ) -> jax.Array:
    """Decode attention over a paged KV pool.

    q: [B, 1, H, D]; pages: [NB, BS, KVH, HD] arrays (fp cache) or int8
    QTensors (scale [NB, BS, KVH, 1]); block_tables: [B, NBR] int32;
    n_valid: [B] int32 live positions per request.

    ``impl="reference"`` (default) gathers the table-referenced pages into
    a dense cache view and attends over it — numerically identical to
    :func:`attend_decode` / :func:`attend_decode_int8` over a dense
    [B, W*BS] cache holding the same tokens: the gather is pure data
    movement and masked positions are forced to NEG_INF before the softmax
    in both paths.

    ``impl="fused"`` runs the flash-decoding kernel
    (:func:`repro.kernels.paged_attention.paged_attention`): no gathered
    cache, int8 pages dequantized in-registers, split-KV logsumexp merge.
    Selected by ``DeploymentPlan(paged_attn=True)`` in :func:`attention`.
    """
    if impl == "fused":
        from repro.kernels.paged_attention import ops as paged_ops
        return paged_ops.paged_attention(q, k_pages, v_pages, block_tables,
                                         n_valid, kv_splits=kv_splits)
    if impl != "reference":
        raise ValueError(f"impl must be 'reference' or 'fused', got "
                         f"{impl!r}")
    from repro.core import quant
    kg = gather_pages(k_pages, block_tables, n_valid)
    vg = gather_pages(v_pages, block_tables, n_valid)
    s = kg.shape[1]
    mask = jnp.arange(s)[None, :] < n_valid[:, None]
    if isinstance(kg, quant.QTensor):
        return attend_decode_int8(q, kg.q, kg.scale[..., 0], vg.q,
                                  vg.scale[..., 0], mask)
    return attend_decode(q, kg, vg, mask)


def attend_prefill_paged(q, k, v, k_pages, v_pages, block_tables, pos,
                         n_tok, write_mask=None, *, impl: str = "reference",
                         has_past: bool = True
                         ) -> tuple[jax.Array, Any, Any]:
    """Causal-chunk prefill attention over a paged KV pool.

    q: [B, C, H, D]; k/v: [B, C, KVH, D] the in-hand chunk projections
    (post-RoPE); pages as in :func:`attend_decode_paged`; pos: [B] int32
    page-aligned chunk starts (tokens already in the pool); n_tok: [B]
    valid tokens in this chunk (ragged tails).  Every chunk query attends
    all pool positions < pos plus the causal prefix of the in-hand chunk
    — the in-hand K/V stays fp exactly like the unchunked prefill's
    ``attend_full`` over in-hand projections, so chunked and one-shot
    prefill agree to fp rounding (int8 pools additionally read *past*
    chunks dequantized, the decode-identical approximation).

    The chunk's K/V is quantized (int8 pools, ``quantize_kv`` grid) and
    written into its pool pages: in-kernel for ``impl="fused"``
    (kernels/paged_attention flash prefill), as a paged scatter for the
    gather reference.  Rows with ``write_mask`` False attend garbage
    (discarded by the caller) and write only to the null block.

    Returns ``(out [B, C, H, D], k_pages', v_pages')``.
    """
    from repro.kernels.paged_attention import ops as paged_ops
    if impl == "fused":
        return paged_ops.paged_prefill(q, k, v, k_pages, v_pages,
                                       block_tables, pos, n_tok, write_mask,
                                       has_past=has_past)
    if impl != "reference":
        raise ValueError(f"impl must be 'reference' or 'fused', got "
                         f"{impl!r}")
    from repro.core import quant
    b, c, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    if has_past:
        kg = gather_pages(k_pages, block_tables)
        vg = gather_pages(v_pages, block_tables)
        if isinstance(kg, quant.QTensor):
            kg, vg = kg.dequant(), vg.dequant()
        sp = kg.shape[1]
        k_all = _repeat_kv(jnp.concatenate(
            [kg.astype(jnp.float32), k.astype(jnp.float32)], axis=1),
            groups)
        v_all = _repeat_kv(jnp.concatenate(
            [vg.astype(jnp.float32), v.astype(jnp.float32)], axis=1),
            groups)
    else:
        # STATIC first-chunk hint (every pos is 0): no past to gather.
        sp = 0
        k_all = _repeat_kv(k.astype(jnp.float32), groups)
        v_all = _repeat_kv(v.astype(jnp.float32), groups)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_all) / np.sqrt(d)
    kp = jnp.arange(sp + c)
    past_ok = (kp[None, :] < pos[:, None]) & (kp < sp)[None, :]
    ci = jnp.arange(c)
    self_ok = ((kp[None, None, :] >= sp)
               & (kp[None, None, :] - sp <= ci[None, :, None])
               & ((kp[None, :] - sp < n_tok[:, None])[:, None, :]))
    ok = past_ok[:, None, :] | self_ok                  # [B, C, Sp+C]
    scores = jnp.where(ok[:, None], scores, NEG_INF)
    m = scores.max(-1, keepdims=True)
    prob = jnp.where(ok[:, None], jnp.exp(scores - m), 0.0)
    l = prob.sum(-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", prob / jnp.maximum(l, 1e-30),
                     v_all).astype(q.dtype)
    wm = None if write_mask is None else jnp.asarray(write_mask, bool)
    pos = jnp.asarray(pos, jnp.int32)
    n_tok = jnp.asarray(n_tok, jnp.int32)
    k_pages = paged_ops.write_chunk_pages(k_pages, k, block_tables, pos,
                                          n_tok, wm)
    v_pages = paged_ops.write_chunk_pages(v_pages, v, block_tables, pos,
                                          n_tok, wm)
    return out, k_pages, v_pages


def attend_decode(q, k_cache, v_cache, kv_len_mask=None) -> jax.Array:
    """q: [B, Sq, H, D] vs given K/V [B, S, KVH, D]; no causal constraint
    (decode: Sq == 1; cross-attention: any Sq).

    Written as partial-softmax (logsumexp) algebra so a KV cache sharded on
    the sequence axis lowers to per-head collectives under SPMD.
    """
    b, sq, h, d = q.shape
    kvh = k_cache.shape[2]
    groups = h // kvh
    qh = q.reshape(b, sq, kvh, groups, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) / np.sqrt(d)
    if kv_len_mask is not None:
        scores = jnp.where(kv_len_mask[:, None, None, None, :], scores, NEG_INF)
    m = scores.max(-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = p.sum(-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p, v_cache.astype(jnp.float32))
    out = out / l[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full layer (projections + rope + attend + output)
# ---------------------------------------------------------------------------

def attention(
    p: dict,
    x: jax.Array,                    # [B, S, d_model]
    cfg,                             # ModelConfig
    *,
    positions: jax.Array | None = None,
    causal: bool = True,
    kv_cache: dict | None = None,    # {'k','v','len'} for decode
    xattn_kv: jax.Array | None = None,   # encoder output for cross-attn
    xattn_cache: dict | None = None,     # precomputed cross {'k','v'} (decode)
    mode: str | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    chunked_threshold: int = 2048,
) -> tuple[jax.Array, dict | None]:
    """Returns (output [B,S,d_model], updated kv_cache or None)."""
    mode = mode or cfg.linear_mode
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype

    from repro.core import backend as backend_lib
    from repro.distributed.sharding import constrain

    # int8 residency: q/k/v all consume the same normed activation — when
    # the plan asks for residency and all three are deployed int8, x is
    # converted ONCE and the int8 codes are shared (two elided HBM passes
    # per attention layer).  Self-attention only: cross-attention q and k/v
    # read different sources.
    x_in = x
    if (backend_lib.residency_enabled(mode) and xattn_kv is None
            and xattn_cache is None):
        x_in = backend_lib.shared_quant((p["q"], p["k"], p["v"]), x)

    # Named scopes (qkv, kv_write, flash_decode / flash_prefill, o_proj)
    # are profile metadata only: they leave every op and kernel name as is.
    with jax.named_scope("qkv"):
        q = layers.dense(p["q"], x_in, mode, dtype=dt,
                         path="attn/q").reshape(b, s, cfg.n_heads, hd)
        q = constrain(q, {0: "batch", 2: "model"})

    if xattn_cache is not None:
        # Cross-attention against precomputed (frozen) encoder K/V.
        kx, vx = xattn_cache["k"], xattn_cache["v"]
        if max(s, kx.shape[1]) <= chunked_threshold:
            out = attend_decode(q, kx, vx)
        else:
            out = attend_chunked(q, kx, vx, causal=False,
                                 q_chunk=min(q_chunk, s), kv_chunk=kv_chunk)
        y = layers.dense(p["o"], out.reshape(b, s, cfg.n_heads * hd), mode,
                         path="attn/o")
        return y.astype(dt), None

    with jax.named_scope("qkv"):
        kv_src = xattn_kv if xattn_kv is not None else x_in
        sk = kv_src.shape[1]
        k = layers.dense(p["k"], kv_src, mode, dtype=dt,
                         path="attn/k").reshape(b, sk, cfg.n_kv_heads, hd)
        v = layers.dense(p["v"], kv_src, mode, dtype=dt,
                         path="attn/v").reshape(b, sk, cfg.n_kv_heads, hd)
        k = constrain(k, {0: "batch", 2: "model"})
        v = constrain(v, {0: "batch", 2: "model"})

        if "q_norm" in p:
            q = layers.rmsnorm(p["q_norm"], q, cfg.norm_eps)
            k = layers.rmsnorm(p["k_norm"], k, cfg.norm_eps)

        if xattn_kv is None:  # self-attention: rotary
            if positions is None:
                # Keep batch dim 1: the angles are batch-invariant and XLA
                # then hoists a [1, S, hd/2] constant instead of a
                # replicated [B_global, S, hd/2] buffer.
                base = jnp.arange(s)[None, :]
                if kv_cache is not None:
                    if "lens" in kv_cache:
                        # Paged pool: per-request lengths -> per-row
                        # positions.
                        base = base + kv_cache["lens"][:, None]
                    else:
                        base = base + kv_cache["len"]
                positions = base
                if cfg.mrope_sections is not None:
                    positions = jnp.broadcast_to(positions[None], (3, 1, s))
            ang_q = layers.rope_angles(positions, hd, cfg.rope_theta,
                                       cfg.mrope_sections)
            q = layers.apply_rope(q, ang_q)
            k = layers.apply_rope(k, ang_q)

    new_cache = None
    if kv_cache is not None and "block_tables" in kv_cache:
        # Paged KV pool (continuous batching): per-request block tables and
        # lengths; single-token decode or causal prefill chunks.  The new
        # K/V is written into the page slot(s) holding positions
        # lens[b]..lens[b]+s-1; rows with write_mask False (finished /
        # idle) write into the reserved null block 0 instead so their
        # tables never overflow and all shapes stay static.
        assert xattn_kv is None, \
            "paged KV caches serve self-attention only"
        assert cfg.sliding_window is None, \
            "paged KV caches do not model sliding windows (no ring blocks)"
        assert cfg.mrope_sections is None, \
            "paged KV caches are single-axis-RoPE only (per-row lens " \
            "positions have no t/h/w M-RoPE layout)"
        from repro.core import quant as quant_lib
        bt = kv_cache["block_tables"]
        lens = kv_cache["lens"]
        wm = kv_cache.get("write_mask")
        if s > 1:
            # Chunked prefill: the chunk attends all pool positions < lens
            # plus its own causal prefix, and its K/V lands straight in the
            # pool pages (in-kernel for the fused plan) — no dense
            # intermediate cache, no pack_prompt.
            n_tok = kv_cache["chunk_len"]
            impl = ("fused" if backend_lib.paged_attn_enabled(mode)
                    else "reference")
            with jax.named_scope("flash_prefill"):
                out, k_pages, v_pages = attend_prefill_paged(
                    q, k, v, kv_cache["k"], kv_cache["v"], bt, lens, n_tok,
                    wm, impl=impl,
                    has_past=kv_cache.get("pf_has_past", True))
            with jax.named_scope("o_proj"):
                y = layers.dense(p["o"],
                                 out.reshape(b, s, cfg.n_heads * hd),
                                 mode, path="attn/o")
            return y.astype(dt), {"k": k_pages, "v": v_pages}
        k_pages, v_pages = kv_cache["k"], kv_cache["v"]
        int8_pool = isinstance(k_pages, quant_lib.QTensor)
        bs_blk = (k_pages.q if int8_pool else k_pages).shape[1]
        with jax.named_scope("kv_write"):
            slot = jnp.minimum(lens // bs_blk, bt.shape[1] - 1)
            page = jnp.take_along_axis(bt, slot[:, None], axis=1)[:, 0]
            off = lens % bs_blk
            if wm is not None:
                page = jnp.where(wm, page, 0)
            if int8_pool:
                k_q, k_s = quantize_kv(k)
                v_q, v_s = quantize_kv(v)
                k_pages = k_pages.at_set(
                    (page, off),
                    quant_lib.QTensor(k_q[:, 0], k_s[:, 0][..., None]))
                v_pages = v_pages.at_set(
                    (page, off),
                    quant_lib.QTensor(v_q[:, 0], v_s[:, 0][..., None]))
            else:
                k_pages = k_pages.at[page, off].set(
                    k[:, 0].astype(k_pages.dtype))
                v_pages = v_pages.at[page, off].set(
                    v[:, 0].astype(v_pages.dtype))
        wrote = (jnp.ones_like(lens) if wm is None
                 else wm.astype(jnp.int32))
        # DeploymentPlan(paged_attn=True) routes through the fused
        # flash-decoding kernel; default stays the gather reference.
        impl = ("fused" if backend_lib.paged_attn_enabled(mode)
                else "reference")
        with jax.named_scope("flash_decode"):
            out = attend_decode_paged(q, k_pages, v_pages, bt, lens + wrote,
                                      impl=impl)
        with jax.named_scope("o_proj"):
            y = layers.dense(p["o"], out.reshape(b, s, cfg.n_heads * hd),
                             mode, path="attn/o")
        return y.astype(dt), {"k": k_pages, "v": v_pages}
    if kv_cache is not None:
        s_cache = kv_cache["k"].shape[1]
        ring = (
            cfg.sliding_window is not None
            and xattn_kv is None
            and s_cache <= cfg.sliding_window
        )
        if s > 1:
            # Prefill: attend over the in-hand K/V (cache assumed empty),
            # then write the (tail of the) sequence into the cache.
            if s <= chunked_threshold:
                out = attend_full(q, k, v, causal=causal,
                                  window=cfg.sliding_window)
            else:
                out = attend_chunked(q, k, v, causal=causal,
                                     window=cfg.sliding_window,
                                     q_chunk=q_chunk, kv_chunk=kv_chunk)
            if ring:
                m = min(s, s_cache)
                idx = jnp.arange(s - m, s) % s_cache
                k_cache = kv_cache["k"].at[:, idx].set(
                    k[:, -m:].astype(kv_cache["k"].dtype))
                v_cache = kv_cache["v"].at[:, idx].set(
                    v[:, -m:].astype(kv_cache["v"].dtype))
                new_cache = {"k": k_cache, "v": v_cache,
                             "len": kv_cache["len"] + s}
            elif "k_scale" in kv_cache:
                k_q, k_s = quantize_kv(k)
                v_q, v_s = quantize_kv(v)
                start3 = (jnp.zeros((), jnp.int32),
                          jnp.asarray(kv_cache["len"], jnp.int32),
                          jnp.zeros((), jnp.int32))
                new_cache = {
                    "k": _update_cache(kv_cache["k"], k_q, kv_cache["len"]),
                    "v": _update_cache(kv_cache["v"], v_q, kv_cache["len"]),
                    "k_scale": jax.lax.dynamic_update_slice(
                        kv_cache["k_scale"],
                        k_s.astype(kv_cache["k_scale"].dtype), start3),
                    "v_scale": jax.lax.dynamic_update_slice(
                        kv_cache["v_scale"],
                        v_s.astype(kv_cache["v_scale"].dtype), start3),
                    "len": kv_cache["len"] + s,
                }
            else:
                k_cache = _update_cache(kv_cache["k"], k, kv_cache["len"])
                v_cache = _update_cache(kv_cache["v"], v, kv_cache["len"])
                new_cache = {"k": k_cache, "v": v_cache,
                             "len": kv_cache["len"] + s}
        elif "k_scale" in kv_cache:
            # int8 KV cache (per-token-head scales): insert quantized K/V,
            # attend with the fully-integer path.
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            k_cache = _update_cache(kv_cache["k"], k_q, kv_cache["len"])
            v_cache = _update_cache(kv_cache["v"], v_q, kv_cache["len"])
            start3 = (jnp.zeros((), jnp.int32),
                      jnp.asarray(kv_cache["len"], jnp.int32),
                      jnp.zeros((), jnp.int32))
            ks_cache = jax.lax.dynamic_update_slice(
                kv_cache["k_scale"], k_s.astype(kv_cache["k_scale"].dtype),
                start3)
            vs_cache = jax.lax.dynamic_update_slice(
                kv_cache["v_scale"], v_s.astype(kv_cache["v_scale"].dtype),
                start3)
            pos_mask = jnp.arange(s_cache)[None, :] < (kv_cache["len"] + s)
            out = attend_decode_int8(q, k_cache, ks_cache, v_cache, vs_cache,
                                     pos_mask)
            new_cache = {"k": k_cache, "v": v_cache, "k_scale": ks_cache,
                         "v_scale": vs_cache, "len": kv_cache["len"] + s}
        else:
            # Decode: insert one K/V, attend over the cache.
            if ring:
                # Ring buffer: O(window) memory even at 500k context.  Keys
                # are stored post-RoPE (absolute positions), so attention over
                # the rotated buffer is order-invariant given the mask.
                write_at = jnp.mod(kv_cache["len"], s_cache)
                k_cache = _update_cache(kv_cache["k"], k, write_at)
                v_cache = _update_cache(kv_cache["v"], v, write_at)
                n_valid = jnp.minimum(kv_cache["len"] + s, s_cache)
                pos_mask = jnp.arange(s_cache)[None, :] < n_valid
            else:
                k_cache = _update_cache(kv_cache["k"], k, kv_cache["len"])
                v_cache = _update_cache(kv_cache["v"], v, kv_cache["len"])
                pos_mask = jnp.arange(s_cache)[None, :] < (kv_cache["len"] + s)
                if cfg.sliding_window is not None and xattn_kv is None:
                    pos_mask = pos_mask & (
                        jnp.arange(s_cache)[None, :]
                        > kv_cache["len"] + s - 1 - cfg.sliding_window
                    )
            out = attend_decode(q, k_cache, v_cache, pos_mask)
            new_cache = {"k": k_cache, "v": v_cache,
                         "len": kv_cache["len"] + s}
    elif xattn_kv is not None:
        if max(s, sk) <= chunked_threshold:
            out = attend_full(q, k, v, causal=False)
        else:
            out = attend_chunked(q, k, v, causal=False,
                                 q_chunk=min(q_chunk, s), kv_chunk=kv_chunk)
    elif s <= chunked_threshold:
        out = attend_full(q, k, v, causal=causal, window=cfg.sliding_window)
    else:
        out = attend_chunked(q, k, v, causal=causal, window=cfg.sliding_window,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)

    y = layers.dense(p["o"], out.reshape(b, s, cfg.n_heads * hd), mode,
                     path="attn/o")
    return y.astype(dt), new_cache


def _update_cache(cache: jax.Array, new: jax.Array, length) -> jax.Array:
    """Insert [B, s, H, D] at position `length` (scalar) along axis 1."""
    start = (jnp.zeros((), jnp.int32), jnp.asarray(length, jnp.int32),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), start)


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[B, s, H, D] -> (int8 values, [B, s, H] per-token-head scales).

    The scale is rounded to its bf16 STORAGE precision before quantizing so
    quantize/dequantize use the identical value (error stays <= scale/2)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8).astype(jnp.bfloat16)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale.astype(jnp.float32)[..., None]),
        -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    c = {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }
    if dtype == jnp.int8:
        c["k_scale"] = jnp.zeros((batch, max_len, n_kv_heads), jnp.bfloat16)
        c["v_scale"] = jnp.zeros((batch, max_len, n_kv_heads), jnp.bfloat16)
    return c
