"""Block composition for all assigned architecture families.

All layer stacks are `lax.scan`-rolled over stacked parameters [L, ...]
(compact HLO => 80-layer 72B graphs compile on one CPU core) with optional
per-layer remat for training.  Families:

  dense   pre-norm attn + MLP residual blocks (stablelm/qwen3/danube/deepseek)
  moe     pre-norm attn + MoE FFN (moonshot, granite)
  ssm     Mamba-2 residual blocks (mamba2-1.3b)
  hybrid  Mamba-2 backbone + weight-SHARED attention block applied every
          `hybrid_attn_interval` layers (zamba2: shared weights, separate KV)
  encdec  bidirectional encoder + causal decoder with cross-attn (whisper)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import layers, mamba2, moe as moe_lib


# ---------------------------------------------------------------------------
# Per-family single blocks
# ---------------------------------------------------------------------------

def init_dense_block(key, cfg, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": layers.init_rmsnorm(cfg.d_model),
        "attn": attn_lib.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.qk_norm, dtype,
        ),
        "mlp_norm": layers.init_rmsnorm(cfg.d_model),
        "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def dense_block_pspec(cfg, frozen=False) -> dict:
    return {
        "attn_norm": {"scale": (None,)},
        "attn": attn_lib.attention_pspec(cfg.qk_norm, frozen),
        "mlp_norm": {"scale": (None,)},
        "mlp": layers.mlp_pspec(cfg.act, frozen),
    }


def dense_block(p, x, cfg, *, cache=None, positions=None, causal=True,
                mode=None):
    with jax.named_scope("attention"):
        h, new_cache = attn_lib.attention(
            p["attn"], layers.rmsnorm(p["attn_norm"], x, cfg.norm_eps), cfg,
            positions=positions, causal=causal, kv_cache=cache, mode=mode,
        )
    x = x + h
    with jax.named_scope("mlp"):
        x = x + layers.mlp(
            p["mlp"], layers.rmsnorm(p["mlp_norm"], x, cfg.norm_eps),
            cfg.act, mode or cfg.linear_mode)
    if getattr(cfg, "act_shard", False):
        from repro.distributed.sharding import constrain
        # residual stream stored d-sharded between blocks => remat carry
        # stacks shrink by the TP degree (one activation all-gather/layer)
        x = constrain(x, {0: "batch", 2: "model"})
    return x, new_cache


def init_moe_block(key, cfg, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": layers.init_rmsnorm(cfg.d_model),
        "attn": attn_lib.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.qk_norm, dtype,
        ),
        "moe_norm": layers.init_rmsnorm(cfg.d_model),
        "moe": moe_lib.init_moe(k2, cfg.d_model, cfg.moe, dtype),
    }


def moe_block_pspec(cfg, frozen=False) -> dict:
    return {
        "attn_norm": {"scale": (None,)},
        "attn": attn_lib.attention_pspec(cfg.qk_norm, frozen),
        "moe_norm": {"scale": (None,)},
        "moe": moe_lib.moe_pspec(cfg.moe),
    }


def moe_block(p, x, cfg, *, cache=None, positions=None, causal=True, mode=None):
    h, new_cache = attn_lib.attention(
        p["attn"], layers.rmsnorm(p["attn_norm"], x, cfg.norm_eps), cfg,
        positions=positions, causal=causal, kv_cache=cache, mode=mode,
    )
    x = x + h
    y, aux = moe_lib.moe(p["moe"], layers.rmsnorm(p["moe_norm"], x, cfg.norm_eps),
                         cfg.moe, mode or cfg.linear_mode)
    return x + y, new_cache, aux["aux_loss"]


def init_ssm_block(key, cfg, dtype) -> dict:
    return {
        "norm": layers.init_rmsnorm(cfg.d_model),
        "mamba": mamba2.init_mamba2(key, cfg.d_model, cfg.ssm, dtype),
    }


def ssm_block_pspec(cfg) -> dict:
    return {"norm": {"scale": (None,)}, "mamba": mamba2.mamba2_pspec()}


def ssm_block(p, x, cfg, *, state=None, mode=None):
    h, new_state = mamba2.mamba2_block(
        p["mamba"], layers.rmsnorm(p["norm"], x, cfg.norm_eps), cfg,
        state=state, mode=mode,
    )
    return x + h, new_state


# ---------------------------------------------------------------------------
# Stacks (scan over layers)
# ---------------------------------------------------------------------------

def _stack_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _add_layer_axis(pspec):
    return jax.tree.map(lambda t: ("layers",) + tuple(t), pspec,
                        is_leaf=lambda t: isinstance(t, tuple))


def init_stack(key, cfg, dtype=jnp.bfloat16) -> dict:
    at = cfg.arch_type
    if at in ("dense",):
        return {"blocks": _stack_init(
            lambda k: init_dense_block(k, cfg, dtype), key, cfg.n_layers)}
    if at == "moe":
        return {"blocks": _stack_init(
            lambda k: init_moe_block(k, cfg, dtype), key, cfg.n_layers)}
    if at == "ssm":
        return {"blocks": _stack_init(
            lambda k: init_ssm_block(k, cfg, dtype), key, cfg.n_layers)}
    if at == "hybrid":
        k1, k2 = jax.random.split(key)
        return {
            "blocks": _stack_init(
                lambda k: init_ssm_block(k, cfg, dtype), k1, cfg.n_layers),
            "shared_attn": init_dense_block(k2, cfg, dtype),
        }
    if at == "encdec":
        k1, k2 = jax.random.split(key)
        enc = _stack_init(lambda k: init_dense_block(k, cfg, dtype), k1,
                          cfg.n_enc_layers)

        def dec_init(k):
            ka, kb = jax.random.split(k)
            blk = init_dense_block(ka, cfg, dtype)
            blk["xattn_norm"] = layers.init_rmsnorm(cfg.d_model)
            blk["xattn"] = attn_lib.init_attention(
                kb, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.resolved_head_dim, cfg.qk_norm, dtype)
            return blk

        dec = _stack_init(dec_init, k2, cfg.n_layers)
        return {"encoder": enc, "decoder": dec}
    raise ValueError(f"unknown arch_type {at!r}")


def stack_pspec(cfg, frozen=False) -> dict:
    at = cfg.arch_type
    if at == "dense":
        return {"blocks": _add_layer_axis(dense_block_pspec(cfg, frozen))}
    if at == "moe":
        return {"blocks": _add_layer_axis(moe_block_pspec(cfg, frozen))}
    if at == "ssm":
        return {"blocks": _add_layer_axis(ssm_block_pspec(cfg))}
    if at == "hybrid":
        return {
            "blocks": _add_layer_axis(ssm_block_pspec(cfg)),
            "shared_attn": dense_block_pspec(cfg, frozen),
        }
    if at == "encdec":
        dec = dense_block_pspec(cfg, frozen)
        dec["xattn_norm"] = {"scale": (None,)}
        dec["xattn"] = attn_lib.attention_pspec(cfg.qk_norm, frozen)
        return {
            "encoder": _add_layer_axis(dense_block_pspec(cfg, frozen)),
            "decoder": _add_layer_axis(dec),
        }
    raise ValueError(at)


def _maybe_remat(fn, remat: bool, policy: str = "nothing"):
    if not remat:
        return fn
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    return jax.checkpoint(fn, policy=policies[policy], prevent_cse=False)


# -------------------------- forward (no caches) ----------------------------

def apply_stack(params, x, cfg, *, positions=None, remat=False,
                remat_policy="nothing", mode=None,
                enc_out=None) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (hidden, moe_aux_loss)."""
    at = cfg.arch_type

    if at in ("dense", "moe"):
        def body(carry, blk_p):
            h, aux = carry
            if at == "dense":
                h, _ = dense_block(blk_p, h, cfg, positions=positions, mode=mode)
                return (h, aux), None
            h, _, a = moe_block(blk_p, h, cfg, positions=positions, mode=mode)
            return (h, aux + a), None

        (x, aux), _ = jax.lax.scan(
            _maybe_remat(body, remat, remat_policy), (x, 0.0), params["blocks"])
        return x, aux

    if at == "ssm":
        def body(h, blk_p):
            h, _ = ssm_block(blk_p, h, cfg, mode=mode)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(body, remat, remat_policy), x,
                            params["blocks"])
        return x, 0.0

    if at == "hybrid":
        interval = cfg.hybrid_attn_interval
        n_groups = cfg.n_layers // interval
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, interval, *a.shape[1:]),
            params["blocks"])
        shared = params["shared_attn"]

        def group_body(h, grp_p):
            h2, _ = dense_block(shared, h, cfg, positions=positions, mode=mode)

            def inner(hh, blk_p):
                hh, _ = ssm_block(blk_p, hh, cfg, mode=mode)
                return hh, None

            # per-layer remat INSIDE the group: otherwise all `interval`
            # layers' SSD residuals are alive at once during group backward
            h3, _ = jax.lax.scan(_maybe_remat(inner, remat, remat_policy),
                                 h2, grp_p)
            return h3, None

        x, _ = jax.lax.scan(_maybe_remat(group_body, remat, remat_policy), x,
                            grouped)
        return x, 0.0

    if at == "encdec":
        assert enc_out is not None

        def dec_body(h, blk_p):
            hh, _ = attn_lib.attention(
                blk_p["attn"],
                layers.rmsnorm(blk_p["attn_norm"], h, cfg.norm_eps), cfg,
                positions=positions, causal=True, mode=mode)
            h = h + hh
            hx, _ = attn_lib.attention(
                blk_p["xattn"],
                layers.rmsnorm(blk_p["xattn_norm"], h, cfg.norm_eps), cfg,
                xattn_kv=enc_out, mode=mode)
            h = h + hx
            h = h + layers.mlp(
                blk_p["mlp"], layers.rmsnorm(blk_p["mlp_norm"], h, cfg.norm_eps),
                cfg.act, mode or cfg.linear_mode)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(dec_body, remat, remat_policy), x,
                            params["decoder"])
        return x, 0.0

    raise ValueError(at)


def apply_encoder(params, frames, cfg, *, remat=False, mode=None) -> jax.Array:
    """Bidirectional encoder over (stub) frame embeddings."""
    def body(h, blk_p):
        h, _ = dense_block(blk_p, h, cfg, causal=False, mode=mode)
        return h, None

    h, _ = jax.lax.scan(_maybe_remat(body, remat), frames, params["encoder"])
    return h


# ----------------------------- decode (caches) -----------------------------

def decode_stack(params, x, cfg, caches: dict, *, positions=None, mode=None):
    """Single-token decode through the stack.  caches is a dict of stacked
    per-layer states; returns (hidden, new_caches)."""
    at = cfg.arch_type

    if at in ("dense", "moe") and "block_tables" in caches:
        # Paged KV pool: caches["kv"] holds per-layer pages (leading L axis,
        # scanned like the dense cache); the block tables / per-request
        # lengths / write mask are layer-invariant and close over the scan.
        shared = {key: caches[key]
                  for key in ("block_tables", "lens", "write_mask",
                              "chunk_len", "pf_has_past")
                  if key in caches}

        def body(h, xs):
            blk_p, cache = xs
            kv = dict(cache, **shared)
            # Profile metadata only: ops of one layer read layer/...
            with jax.named_scope("layer"):
                if at == "dense":
                    h, nc = dense_block(blk_p, h, cfg, cache=kv,
                                        positions=positions, mode=mode)
                else:
                    h, nc, _ = moe_block(blk_p, h, cfg, cache=kv,
                                         positions=positions, mode=mode)
            return h, {key: nc[key] for key in cache}

        x, new_kv = jax.lax.scan(body, x, (params["blocks"], caches["kv"]))
        return x, dict(caches, kv=new_kv)

    if at in ("dense", "moe"):
        def body(h, xs):
            blk_p, cache = xs
            if at == "dense":
                h, nc = dense_block(blk_p, h, cfg, cache=cache,
                                    positions=positions, mode=mode)
            else:
                h, nc, _ = moe_block(blk_p, h, cfg, cache=cache,
                                     positions=positions, mode=mode)
            return h, nc

        x, new_kv = jax.lax.scan(body, x, (params["blocks"], caches["kv"]))
        return x, {"kv": new_kv}

    if at == "ssm":
        def body(h, xs):
            blk_p, st = xs
            h, ns = ssm_block(blk_p, h, cfg, state=st, mode=mode)
            return h, ns

        x, new_states = jax.lax.scan(body, x, (params["blocks"], caches["ssm"]))
        return x, {"ssm": new_states}

    if at == "hybrid":
        interval = cfg.hybrid_attn_interval
        n_groups = cfg.n_layers // interval
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, interval, *a.shape[1:]),
            params["blocks"])
        shared = params["shared_attn"]

        def group_body(h, xs):
            grp_p, grp_ssm, kv = xs
            h, new_kv = dense_block(shared, h, cfg, cache=kv,
                                    positions=positions, mode=mode)

            def inner(hh, ys):
                blk_p, st = ys
                hh, ns = ssm_block(blk_p, hh, cfg, state=st, mode=mode)
                return hh, ns

            h, new_ssm = jax.lax.scan(inner, h, (grp_p, grp_ssm))
            return h, (new_ssm, new_kv)

        grouped_ssm = jax.tree.map(
            lambda a: a.reshape(n_groups, interval, *a.shape[1:]),
            caches["ssm"])
        x, (new_ssm, new_kv) = jax.lax.scan(
            group_body, x, (grouped, grouped_ssm, caches["kv"]))
        new_ssm = jax.tree.map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), new_ssm)
        return x, {"ssm": new_ssm, "kv": new_kv}

    if at == "encdec":
        def body(h, xs):
            blk_p, kv, xk, xv = xs
            hh, new_kv = attn_lib.attention(
                blk_p["attn"],
                layers.rmsnorm(blk_p["attn_norm"], h, cfg.norm_eps), cfg,
                kv_cache=kv, mode=mode)
            h = h + hh
            # Cross-attention against precomputed per-layer encoder K/V.
            hx, _ = attn_lib.attention(
                blk_p["xattn"],
                layers.rmsnorm(blk_p["xattn_norm"], h, cfg.norm_eps), cfg,
                xattn_cache={"k": xk, "v": xv}, mode=mode)
            h = h + hx
            h = h + layers.mlp(
                blk_p["mlp"], layers.rmsnorm(blk_p["mlp_norm"], h, cfg.norm_eps),
                cfg.act, mode or cfg.linear_mode)
            return h, new_kv

        x, new_kv = jax.lax.scan(
            body, x,
            (params["decoder"], caches["kv"], caches["cross_k"],
             caches["cross_v"]))
        return x, {"kv": new_kv, "cross_k": caches["cross_k"],
                   "cross_v": caches["cross_v"]}

    raise ValueError(at)


def precompute_cross_kv(params, enc_out, cfg, mode=None) -> tuple[jax.Array, jax.Array]:
    """Per-decoder-layer cross K/V from the encoder output (done once at
    prefill).  Returns ([L,B,S,KVH,HD], [L,B,S,KVH,HD])."""
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim

    def body(_, blk_p):
        k = layers.dense(blk_p["xattn"]["k"], enc_out, mode or cfg.linear_mode,
                         path="xattn/k")
        v = layers.dense(blk_p["xattn"]["v"], enc_out, mode or cfg.linear_mode,
                         path="xattn/v")
        return None, (k.reshape(b, s, cfg.n_kv_heads, hd),
                      v.reshape(b, s, cfg.n_kv_heads, hd))

    _, (ks, vs) = jax.lax.scan(body, None, params["decoder"])
    return ks, vs


def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                enc_out: jax.Array | None = None) -> dict:
    """Zero caches for decode, shaped for the stack layout."""
    hd = cfg.resolved_head_dim
    at = cfg.arch_type
    L = cfg.n_layers

    def kv(n):
        kv_len = max_len
        if cfg.sliding_window is not None:
            # Ring buffer: O(window) memory regardless of context length.
            kv_len = min(max_len, cfg.sliding_window)
        int8_kv = (getattr(cfg, "kv_cache_dtype", "bf16") == "int8"
                   and cfg.sliding_window is None)
        store = jnp.int8 if int8_kv else dtype
        c = {
            "k": jnp.zeros((n, batch, kv_len, cfg.n_kv_heads, hd), store),
            "v": jnp.zeros((n, batch, kv_len, cfg.n_kv_heads, hd), store),
            "len": jnp.zeros((n,), jnp.int32),
        }
        if int8_kv:
            c["k_scale"] = jnp.zeros((n, batch, kv_len, cfg.n_kv_heads),
                                     jnp.bfloat16)
            c["v_scale"] = jnp.zeros((n, batch, kv_len, cfg.n_kv_heads),
                                     jnp.bfloat16)
        return c

    if at in ("dense", "moe"):
        return {"kv": kv(L)}
    if at == "ssm":
        st = mamba2.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
        return {"ssm": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L, *a.shape)), st)}
    if at == "hybrid":
        st = mamba2.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
        n_groups = L // cfg.hybrid_attn_interval
        return {
            "ssm": jax.tree.map(lambda a: jnp.broadcast_to(a, (L, *a.shape)), st),
            "kv": kv(n_groups),
        }
    if at == "encdec":
        c = kv(L)
        assert enc_out is not None, "encdec caches need encoder output shape"
        s_enc = enc_out.shape[1]
        return {
            "kv": c,
            "cross_k": jnp.zeros((L, batch, s_enc, cfg.n_kv_heads, hd), dtype),
            "cross_v": jnp.zeros((L, batch, s_enc, cfg.n_kv_heads, hd), dtype),
        }
    raise ValueError(at)
