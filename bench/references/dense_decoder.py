"""Plain reference of a dense decoder (Llama / Qwen3 family), as deployed.

Written from the published architecture (HF ``modeling_llama`` /
``modeling_qwen3``), in straightforward ``jax.numpy`` and float32 at the
highest matmul precision, with no kernel, cache, paging or batching.  It
imports nothing of the system under test.

Per layer: RMSNorm, q/k/v projections, (Qwen3) an RMSNorm per head of q
and k, rotary embedding on half-split pairs, causal softmax attention
with grouped KV heads, the output projection and a residual add; then
RMSNorm, SwiGLU MLP and a residual add.  A final RMSNorm and the output
head give the logits over the published vocabulary.

The precision the configuration states is part of the semantics and is
applied exactly, in float32 around it:

* every linear is W8A8: its input is quantized to int8 on the layer's
  static activation scale (round half to even, clipped to [-128, 127]),
  multiplied with the int8 weight codes in int32, and scaled back by the
  activation scale times the per-output-channel weight scale;
* the KV cache is int8: each token's K and V are stored per KV head as
  int8 codes with one scale, absmax / 127 rounded to bfloat16, and
  attention reads them back dequantized.

``weight_bits=4`` is the control: the same reference with every weight
requantized to int4 per output channel (codes in [-7, 7]), the step below
int8 that would halve the bytes a decode step streams.

Weights are a plain dict, named from the served tree by
``bench/adapters/dense_decoder.reference_weights``: ``embed`` [V_pad,
d], ``final_norm`` [d], ``lm_head`` {w, w_scale, a_scale}, and
``layers`` with a leading layer axis on every leaf: ``attn_norm``,
``mlp_norm``, ``q_norm``/``k_norm`` (Qwen3), and the linears ``q k v o
gate up down`` as {w [L, K, N] int8, w_scale [L, N], a_scale [L]}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256            # query rows per attention block (bounds memory)


def _rmsnorm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _requant4(w, w_scale):
    """int8 codes -> int4 codes (per output channel) and their scale."""
    w4 = jnp.clip(jnp.round(w.astype(jnp.float32) * (7.0 / 127.0)), -7, 7)
    return w4.astype(jnp.int8), w_scale * (127.0 / 7.0)


def _linear(x, p, weight_bits):
    w, w_scale, a_scale = p["w"], p["w_scale"], p["a_scale"]
    if weight_bits == 4:
        w, w_scale = _requant4(w, w_scale)
    xq = jnp.clip(jnp.round(x / a_scale), -128, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, w, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (a_scale * w_scale)


def _kv_int8(x):
    """Store-and-read of the int8 KV cache: [T, KVH, D] -> dequantized."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _rope(x, theta):
    """Half-split rotary embedding at positions 0..T-1: x [T, H, D]."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q [T, H, D], k/v [T, KVH, D] -> [T, H, D], in
    blocks of ``Q_BLOCK`` query rows."""
    t, h, d = q.shape
    groups = h // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    nb = t // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(t)[None, None, :] <= qpos[None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, jnp.arange(nb)).reshape(t, h, d)


def logits_at(weights: dict, model: dict, tokens, read_at, *,
              weight_bits: int = 8):
    """Logits [n, vocab] at positions `read_at` [n] of the sequence
    `tokens` [T] (T a multiple of ``Q_BLOCK``; positions past the end of
    the real tokens may hold anything, causality keeps them out).  `model`
    is the configuration file's dict (published keys)."""
    heads = int(model["num_attention_heads"])
    kv_heads = int(model["num_key_value_heads"])
    hd = int(model["head_dim"])
    eps = float(model["rms_norm_eps"])
    theta = float(model["rope_theta"])
    qk_norm = bool(model["qk_norm"])
    t = tokens.shape[0]

    def layer(x, p):
        h = _rmsnorm(x, p["attn_norm"], eps)
        q = _linear(h, p["q"], weight_bits).reshape(t, heads, hd)
        k = _linear(h, p["k"], weight_bits).reshape(t, kv_heads, hd)
        v = _linear(h, p["v"], weight_bits).reshape(t, kv_heads, hd)
        if qk_norm:
            q = _rmsnorm(q, p["q_norm"], eps)
            k = _rmsnorm(k, p["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        a = _attention(q, _kv_int8(k), _kv_int8(v))
        x = x + _linear(a.reshape(t, heads * hd), p["o"], weight_bits)
        h = _rmsnorm(x, p["mlp_norm"], eps)
        g = _linear(h, p["gate"], weight_bits)
        u = _linear(h, p["up"], weight_bits)
        x = x + _linear(jax.nn.silu(g) * u, p["down"], weight_bits)
        return x, None

    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, weights["layers"])
    h = _rmsnorm(x[read_at], weights["final_norm"], eps)
    logits = _linear(h, weights["lm_head"], weight_bits)
    return logits[:, :int(model["vocab_size"])]


# the configuration keys the forward reads (its jit cache keys on them)
MODEL_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
              "rms_norm_eps", "rope_theta", "qk_norm", "vocab_size")


@functools.lru_cache(maxsize=None)
def _jitted(model_items: tuple, weight_bits: int):
    model = dict(model_items)

    def fn(weights, tokens, read_at):
        with jax.default_matmul_precision("highest"):
            return logits_at(weights, model, tokens, read_at,
                             weight_bits=weight_bits)
    return jax.jit(fn)


def jitted(model: dict, weight_bits: int = 8):
    """The jitted forward ``fn(weights, tokens, read_at) -> logits`` of
    :func:`logits_at` for the configuration `model`, one per precision."""
    return _jitted(tuple((k, model[k]) for k in MODEL_KEYS), int(weight_bits))


def reference_logits(weights: dict, model: dict, tokens, read_at, *,
                     weight_bits: int = 8):
    """Jitted :func:`logits_at` (one program per shape and precision)."""
    return jitted(model, weight_bits)(weights, tokens, read_at)
