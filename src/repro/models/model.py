"""LM model wrapper: embeddings -> stack -> final norm -> head (+ loss).

Entry points (all pure, jit/pjit-ready):

  init(key, cfg)                          -> params
  pspec(cfg)                              -> logical-axes tree for params
  forward(params, batch, cfg, train=...)  -> (hidden, aux)
  loss_fn(params, batch, cfg)             -> (scalar loss, metrics)   [chunked CE]
  prefill(params, batch, cfg, max_len)    -> (last_logits, caches)
  decode_step(params, batch, caches, cfg) -> (logits, caches)

Batch layout (keys present depend on arch/frontend):
  tokens    [B, S] int32          labels [B, S] int32
  embeds    [B, S, d] (vision_stub: pre-merged token+patch embeddings)
  frames    [B, S, d] (audio_stub: encoder frame embeddings)
  positions [B, S] or [3, B, S] (M-RoPE) int32, optional
"""
from __future__ import annotations

import concurrent.futures
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import backend as backend_lib
from repro.models import layers, transformer


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init(key, cfg) -> dict:
    dt = _dtype(cfg)
    k_e, k_s, k_h = jax.random.split(key, 3)
    p = {
        "embed": layers.init_embedding(k_e, cfg.padded_vocab, cfg.d_model, dt),
        "stack": transformer.init_stack(k_s, cfg, dt),
        "final_norm": layers.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.init_lm_head(k_h, cfg.d_model, cfg.padded_vocab,
                                           dt)
    if cfg.arch_type == "encdec":
        p["enc_final_norm"] = layers.init_rmsnorm(cfg.d_model)
    return p


def pspec(cfg, frozen: bool = False) -> dict:
    p = {
        "embed": layers.embedding_pspec(),
        "stack": transformer.stack_pspec(cfg, frozen),
        "final_norm": {"scale": (None,)},
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.lm_head_pspec(frozen)
    if cfg.arch_type == "encdec":
        p["enc_final_norm"] = {"scale": (None,)}
    return p


# Routing quality is precision-sensitive: the default deployment keeps the
# router in float while every other weight-stationary linear goes int8
# (DESIGN.md §5: the CiM macro holds matmul weights; those are what
# quantize).  Kept as a plan so per-layer overrides compose with it.
DEFAULT_DEPLOY_PLAN = backend_lib.DeploymentPlan(
    rules=(("*router*", backend_lib.LayerRule("exact")),),
    default="w8a8",
)


def _as_deploy_plan(plan) -> backend_lib.DeploymentPlan:
    if plan is None:
        return DEFAULT_DEPLOY_PLAN
    return backend_lib.as_plan(plan, default="w8a8")


def freeze_params(params, a_scale: float = 1.0, plan=None):
    """Deploy transform: every weight-stationary linear (incl. stacked-layer
    and MoE expert banks) is frozen by its plan-resolved backend's own
    `freeze` — int8 with static per-channel scales for deployed backends,
    untouched master params for float ones.  Embedding gathers, norms, and
    depthwise conv are never linears and always stay in float.

    `plan` maps layer paths ('stack/blocks/attn/q', 'lm_head', ...) to
    backends + per-layer a_scale overrides; None -> DEFAULT_DEPLOY_PLAN
    (everything w8a8, router exact)."""
    plan = _as_deploy_plan(plan)

    def freeze_with(rule, node, n_mat_dims=2):
        backend = backend_lib.get_backend(rule.backend)
        if backend.needs_chip:
            raise NotImplementedError(
                f"backend {rule.backend!r} needs per-layer chip samples and "
                "macro configs, which the generic transformer freeze does "
                "not plumb; deploy it via executor.freeze / vgg.freeze_vgg8")
        w = node["w"]
        spec = backend_lib.LinearSpec(
            in_dim=int(w.shape[-2]), out_dim=int(w.shape[-1]),
            use_bias="b" in node, mode=rule.backend)
        a_s = a_scale if rule.a_scale is None else rule.a_scale
        return backend.freeze(node, spec, a_s, n_mat_dims=n_mat_dims)

    def walk(path, node):
        if isinstance(node, dict):
            if "w" in node and not isinstance(node["w"], dict):
                return freeze_with(plan.rule_for(path), node)
            if {"gate", "up", "down"} <= set(node.keys()) \
                    and not isinstance(node["gate"], dict):
                # MoE expert banks [.., E, d, ff].  One rule covers the
                # whole bank (the three matmuls share one dispatch buffer,
                # so per-matrix mixed precision is not representable).
                rule = plan.rule_for(path)
                if not backend_lib.get_backend(rule.backend).deploys_int8:
                    return {k: (v if k in ("gate", "up", "down")
                                else walk(f"{path}/{k}", v))
                            for k, v in node.items()}
                out = {}
                for k in ("gate", "up", "down"):
                    f = freeze_with(rule, {"w": node[k]}, n_mat_dims=3)
                    out[f"{k}_q"] = f["w_q"]
                    out[f"{k}_scale"] = f["w_scale"]
                out["a_scale"] = f["a_scale"]
                for k, v in node.items():
                    if k not in ("gate", "up", "down"):
                        out[k] = walk(f"{path}/{k}", v)
                return out
            return {k: walk(f"{path}/{k}" if path else k, v)
                    for k, v in node.items()}
        return node

    return walk("", params)


def frozen_groups(shapes) -> list[tuple[tuple, int]]:
    """``(path, bytes)`` of every parameter group of a (frozen) parameter
    tree: each dict whose values are all arrays, e.g. one stacked linear's
    ``w_q``/``w_scale``/``a_scale``."""
    groups: list[tuple[tuple, int]] = []

    def collect(path, node):
        if isinstance(node, dict) and any(isinstance(v, dict)
                                          for v in node.values()):
            for k, v in node.items():
                collect(path + (k,), v)
            return
        groups.append((path, sum(int(x.size) * x.dtype.itemsize
                                 for x in jax.tree.leaves(node))))

    collect((), shapes)
    return groups


def init_frozen(key, cfg, *, a_scale: float = 1.0, plan=None,
                shardings=None):
    """``freeze_params(init(key, cfg), a_scale, plan)`` without the float
    master ever existing whole on the device.

    One jitted program per parameter group (a dict of array leaves, e.g.
    one stacked linear's ``w_q``/``w_scale``/``a_scale``): XLA's dead-code
    elimination keeps only that group's slice of the seeded init, so the
    device peak is the frozen model built so far plus one group's float
    master.  Values equal the jitted two-step form.  Largest groups go
    first, while the device is still emptiest.  `shardings`
    (optional) is a tree of shardings matching the frozen structure."""

    def full(k):
        return freeze_params(init(k, cfg), a_scale=a_scale, plan=plan)

    shapes = jax.eval_shape(full, key)
    groups = frozen_groups(shapes)

    def get(tree, path):
        for k in path:
            tree = tree[k]
        return tree

    def program(path):
        sh = None if shardings is None else get(shardings, path)
        return jax.jit(lambda k: get(full(k), path),
                       out_shardings=sh).lower(key).compile()

    # Compiling is host work outside the GIL: compile every group's program
    # at once (one full-width group takes ~16 s alone), then run them.
    order = [path for path, _ in sorted(groups, key=lambda g: -g[1])]
    with concurrent.futures.ThreadPoolExecutor(
            min(8, os.cpu_count() or 1)) as pool:
        programs = list(pool.map(program, order))
    out: dict = {}
    for path, prog in zip(order, programs):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = prog(key)
    return out


def freeze_pspec(pspec_tree, plan=None):
    """Logical-axes tree matching freeze_params' output structure."""
    plan = _as_deploy_plan(plan)

    def is_frozen(path):
        # Match freeze_params: what matters is whether freeze() emits the
        # int8 layout (qat does, despite apply() consuming master params).
        return backend_lib.get_backend(plan.backend_for(path)).deploys_int8

    def walk(path, node):
        if isinstance(node, dict):
            if "w" in node and isinstance(node["w"], tuple):
                if not is_frozen(path):
                    return node
                spec = node["w"]
                out = {"w_q": spec, "w_scale": spec[:-2] + (spec[-1],),
                       "a_scale": spec[:-2]}
                if "b" in node:
                    out["b"] = node["b"]
                return out
            if {"gate", "up", "down"} <= set(node.keys()) \
                    and isinstance(node["gate"], tuple):
                if not is_frozen(path):
                    return {k: (v if k in ("gate", "up", "down")
                                else walk(f"{path}/{k}", v))
                            for k, v in node.items()}
                out = {}
                for k in ("gate", "up", "down"):
                    spec = node[k]
                    out[f"{k}_q"] = spec
                    out[f"{k}_scale"] = spec[:-2] + (spec[-1],)
                out["a_scale"] = node["gate"][:-3]
                for k, v in node.items():
                    if k not in ("gate", "up", "down"):
                        out[k] = walk(f"{path}/{k}", v)
                return out
            return {k: walk(f"{path}/{k}" if path else k, v)
                    for k, v in node.items()}
        return node

    return walk("", pspec_tree)


def _embed_inputs(params, batch, cfg):
    if "embeds" in batch:                       # vision_stub: pre-merged
        return batch["embeds"].astype(_dtype(cfg))
    return layers.embed(params["embed"], batch["tokens"])


def _encoder_out(params, batch, cfg, remat=False, mode=None):
    frames = batch["frames"].astype(_dtype(cfg))
    h = transformer.apply_encoder(params["stack"], frames, cfg, remat=remat,
                                  mode=mode)
    return layers.rmsnorm(params["enc_final_norm"], h, cfg.norm_eps)


def forward(params, batch, cfg, *, train: bool = False,
            remat: bool | None = None, remat_policy: str = "nothing",
            mode: str | None = None):
    """Full-sequence forward to final hidden states.  Returns (h, aux_loss)."""
    remat = train if remat is None else remat
    x = _embed_inputs(params, batch, cfg)
    positions = batch.get("positions")
    enc_out = None
    if cfg.arch_type == "encdec":
        enc_out = _encoder_out(params, batch, cfg, remat=remat, mode=mode)
    h, aux = transformer.apply_stack(
        params["stack"], x, cfg, positions=positions, remat=remat,
        remat_policy=remat_policy, mode=mode, enc_out=enc_out)
    h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return h, aux


def _head_weight(params, cfg):
    if cfg.tie_embeddings:
        return {"w": params["embed"]["table"].T}
    return params["lm_head"]


def logits_fn(params, h, cfg, mode=None):
    logits = layers.dense(_head_weight(params, cfg), h, mode or "exact",
                          dtype=jnp.float32, path="lm_head")
    if cfg.padded_vocab != cfg.vocab:
        # Mask the padding columns (kept in-shape so vocab stays shardable).
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def loss_fn(params, batch, cfg, *, loss_chunk: int = 256,
            remat_policy: str = "nothing", mode: str | None = None,
            aux_weight: float = 0.01):
    """Chunked-softmax LM loss: logits are materialized [B, chunk, V] at a
    time (a scan over the sequence), never [B, S, V] — mandatory for 150k+
    vocabs at S=4k."""
    h, aux = forward(params, batch, cfg, train=True, remat_policy=remat_policy,
                     mode=mode)
    labels = batch["labels"]
    b, s = labels.shape
    chunk = min(loss_chunk, s)
    assert s % chunk == 0
    n_chunks = s // chunk
    head = _head_weight(params, cfg)

    h_r = h.reshape(b, n_chunks, chunk, -1).transpose(1, 0, 2, 3)
    l_r = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab
                if cfg.padded_vocab != cfg.vocab else None)

    def body(carry, xs):
        tot, cnt = carry
        hc, lc = xs
        logits = layers.dense(head, hc, "exact", dtype=jnp.float32,
                              path="lm_head")
        if pad_mask is not None:
            logits = jnp.where(pad_mask, -1e30, logits)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        valid = (lc >= 0).astype(jnp.float32)
        nll = (logz - gold) * valid
        return (tot + nll.sum(), cnt + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (h_r, l_r))
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux, "tokens": cnt}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def prefill(params, batch, cfg, *, max_len: int, mode=None):
    """Process the prompt, build caches, return last-position logits.

    For attention archs the per-layer K/V caches are rebuilt from a full
    forward (projections recomputed per layer inside a scan so the HLO stays
    compact); SSM/hybrid carry their recurrent states.

    `batch['length']` (optional scalar int32) marks the true prompt length
    when `tokens` is right-padded to a bucketed shape (serve/engine.py):
    logits are taken at position length-1 and the KV write cursor is rewound
    past the pads so decode overwrites them.  Dense-attention archs only:
    SSM state would integrate the pads, and MoE capacity is computed from
    the padded token count (pads could displace real tokens).
    """
    dt = _dtype(cfg)
    at = cfg.arch_type
    x = _embed_inputs(params, batch, cfg)
    b, s = x.shape[:2]
    positions = batch.get("positions")

    enc_out = None
    if at == "encdec":
        enc_out = _encoder_out(params, batch, cfg, mode=mode)

    caches = transformer.init_caches(cfg, b, max_len, dt, enc_out=enc_out)

    if at == "encdec":
        ck, cv = transformer.precompute_cross_kv(params["stack"], enc_out, cfg,
                                                 mode=mode)
        caches["cross_k"], caches["cross_v"] = ck, cv

    # Run the full-sequence forward while filling the caches layer by layer.
    h, caches = _prefill_stack(params["stack"], x, cfg, caches,
                               positions=positions, mode=mode, enc_out=enc_out)
    length = batch.get("length")
    if length is None:
        h_last = h[:, -1:]
    else:
        assert at == "dense", \
            "bucketed prefill (batch['length']) is dense-attention only"
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        # Pads were written into the KV cache beyond `length`; rewind the
        # write cursor so decode overwrites them and the length masks
        # exclude them.
        kv = dict(caches["kv"], len=caches["kv"]["len"] - (s - length))
        caches = dict(caches, kv=kv)
    h = layers.rmsnorm(params["final_norm"], h_last, cfg.norm_eps)
    logits = logits_fn(params, h, cfg, mode)
    return logits, caches


def prefill_paged(params, batch, cfg, *, pages, block_table, max_len: int,
                  mode=None):
    """Prefill ONE request and pack its K/V into a paged pool.

    The dense per-request cache built by :func:`prefill` is a [1, max_len]
    scratch view that never leaves this function — the pool pages are the
    only cache that survives into decode (serve/kv_pool.py).  `batch` holds
    a single bucketed prompt ([1, S] tokens, optional scalar 'length');
    `block_table` is [max_len // block_size] int32 (tail entries past the
    allocated prompt blocks point at the null block).  Returns
    (last_logits, packed pages).  Dense-attention archs only, like bucketed
    prefill itself.
    """
    assert cfg.arch_type == "dense", \
        "paged KV pools serve dense-attention archs only"
    from repro.serve import kv_pool  # local import: serve layers on models
    logits, caches = prefill(params, batch, cfg, max_len=max_len, mode=mode)
    return logits, kv_pool.pack_prompt(pages, caches["kv"], block_table)


def prefill_chunk(params, tokens, cfg, *, pages, block_tables, pos, n_tok,
                  write_mask=None, has_past: bool = True, mode=None):
    """One causal chunk of paged prefill: advance each row's prompt by up
    to ``tokens.shape[1]`` positions, writing the chunk's K/V straight
    into the pool pages.

    ``tokens`` [B, C] holds each row's next prompt slice (right-padded for
    ragged tails); ``pos`` [B] is the page-aligned chunk start (tokens
    already in the pool — C must be a block_size multiple so chunks stay
    page-aligned); ``n_tok`` [B] the valid tokens in this slice;
    ``write_mask`` [B] bool marks rows actually prefilling (others attend
    garbage, discarded, and write only to the null block).  Unlike
    :func:`prefill_paged` there is NO dense intermediate cache and no
    ``pack_prompt`` scatter — the chunk attends past pool pages plus its
    own causal prefix and lands its K/V in the pool directly (in-kernel
    for ``DeploymentPlan(paged_attn=True)``).

    Returns ``(logits [B, V] at each row's last valid position, pages)``.
    Dense-attention archs only, like the paged pool itself.
    """
    assert cfg.arch_type == "dense", \
        "paged KV pools serve dense-attention archs only"
    x = _embed_inputs(params, {"tokens": tokens}, cfg)
    caches = {"kv": pages, "block_tables": block_tables,
              "lens": jnp.asarray(pos, jnp.int32),
              "chunk_len": jnp.asarray(n_tok, jnp.int32),
              "pf_has_past": bool(has_past)}
    if write_mask is not None:
        caches["write_mask"] = jnp.asarray(write_mask, bool)
    h, caches = transformer.decode_stack(params["stack"], x, cfg, caches,
                                         mode=mode)
    h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    idx = jnp.clip(jnp.asarray(n_tok, jnp.int32) - 1, 0,
                   tokens.shape[1] - 1)
    h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = logits_fn(params, h_last, cfg, mode)
    return logits[:, 0], caches["kv"]


def _prefill_stack(params, x, cfg, caches, *, positions, mode, enc_out):
    """Forward + cache fill.  Mirrors transformer.apply_stack but emits the
    K/V (or SSM state) of every layer."""
    at = cfg.arch_type
    dt = x.dtype
    b, s = x.shape[:2]
    hd = cfg.resolved_head_dim

    if at in ("dense", "moe"):
        def body(h, xs):
            blk_p, cache = xs
            # Fill the cache with this layer's K/V by running the block in
            # "prefill-as-decode" form: full-sequence attention, cache update.
            from repro.models import attention as attn_lib
            xin = layers.rmsnorm(blk_p["attn_norm"], h, cfg.norm_eps)
            hh, nc = attn_lib.attention(
                blk_p["attn"], xin, cfg, positions=positions, causal=True,
                kv_cache=cache, mode=mode)
            h = h + hh
            if at == "dense":
                h = h + layers.mlp(
                    blk_p["mlp"],
                    layers.rmsnorm(blk_p["mlp_norm"], h, cfg.norm_eps),
                    cfg.act, mode or cfg.linear_mode)
            else:
                from repro.models import moe as moe_lib
                y, _ = moe_lib.moe(
                    blk_p["moe"],
                    layers.rmsnorm(blk_p["moe_norm"], h, cfg.norm_eps),
                    cfg.moe, mode or cfg.linear_mode)
                h = h + y
            return h, nc

        h, new_kv = jax.lax.scan(body, x, (params["blocks"], caches["kv"]))
        caches = dict(caches, kv=new_kv)
        return h, caches

    if at == "ssm":
        def body(h, xs):
            blk_p, st = xs
            from repro.models import mamba2
            xin = layers.rmsnorm(blk_p["norm"], h, cfg.norm_eps)
            y, new_st = mamba2.mamba2_block(blk_p["mamba"], xin, cfg, mode=mode,
                                            return_final_state=True)
            return h + y, new_st

        h, new_states = jax.lax.scan(body, x, (params["blocks"], caches["ssm"]))
        return h, dict(caches, ssm=new_states)

    if at == "hybrid":
        interval = cfg.hybrid_attn_interval
        n_groups = cfg.n_layers // interval
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, interval, *a.shape[1:]),
            params["blocks"])
        grouped_ssm = jax.tree.map(
            lambda a: a.reshape(n_groups, interval, *a.shape[1:]),
            caches["ssm"])
        shared = params["shared_attn"]

        from repro.models import attention as attn_lib, mamba2

        def group_body(h, xs):
            grp_p, grp_ssm, kv = xs
            xin = layers.rmsnorm(shared["attn_norm"], h, cfg.norm_eps)
            hh, new_kv = attn_lib.attention(
                shared["attn"], xin, cfg, positions=positions, causal=True,
                kv_cache=kv, mode=mode)
            h = h + hh
            h = h + layers.mlp(
                shared["mlp"],
                layers.rmsnorm(shared["mlp_norm"], h, cfg.norm_eps),
                cfg.act, mode or cfg.linear_mode)

            def inner(hh2, ys):
                blk_p, st = ys
                xin2 = layers.rmsnorm(blk_p["norm"], hh2, cfg.norm_eps)
                y, new_st = mamba2.mamba2_block(blk_p["mamba"], xin2, cfg,
                                                mode=mode,
                                                return_final_state=True)
                return hh2 + y, new_st

            h, new_ssm = jax.lax.scan(inner, h, (grp_p, grp_ssm))
            return h, (new_ssm, new_kv)

        h, (new_ssm, new_kv) = jax.lax.scan(
            group_body, x, (grouped, grouped_ssm, caches["kv"]))
        new_ssm = jax.tree.map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), new_ssm)
        return h, dict(caches, ssm=new_ssm, kv=new_kv)

    if at == "encdec":
        from repro.models import attention as attn_lib

        def body(h, xs):
            blk_p, kv, xk, xv = xs
            xin = layers.rmsnorm(blk_p["attn_norm"], h, cfg.norm_eps)
            hh, nc = attn_lib.attention(
                blk_p["attn"], xin, cfg, positions=positions, causal=True,
                kv_cache=kv, mode=mode)
            h = h + hh
            hx, _ = attn_lib.attention(
                blk_p["xattn"],
                layers.rmsnorm(blk_p["xattn_norm"], h, cfg.norm_eps), cfg,
                xattn_cache={"k": xk, "v": xv}, mode=mode)
            h = h + hx
            h = h + layers.mlp(
                blk_p["mlp"], layers.rmsnorm(blk_p["mlp_norm"], h, cfg.norm_eps),
                cfg.act, mode or cfg.linear_mode)
            return h, nc

        h, new_kv = jax.lax.scan(
            body, x,
            (params["decoder"], caches["kv"], caches["cross_k"],
             caches["cross_v"]))
        return h, dict(caches, kv=new_kv)

    raise ValueError(at)


def decode_step(params, batch, caches, cfg, *, mode: str | None = None):
    """One token for every sequence in the batch.  Returns (logits, caches)."""
    x = _embed_inputs(params, batch, cfg)
    positions = batch.get("positions")
    h, caches = transformer.decode_stack(
        params["stack"], x, cfg, caches, positions=positions, mode=mode)
    h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return logits_fn(params, h, cfg, mode), caches
