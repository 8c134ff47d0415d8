"""AdamW with f32 master weights, cosine schedule, global-norm clipping.

ZeRO-1 posture: optimizer state (master, m, v) inherits the parameter
sharding, and parameters themselves are sharded over BOTH mesh axes by the
logical rules (FSDP x TP), so state bytes per chip are params_bytes * 12 /
(data * model).  No replicated optimizer state anywhere.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


def cosine_schedule(step, lr: float, warmup: int, total: int):
    step = jnp.asarray(step, jnp.float32)
    warm = lr * step / jnp.maximum(warmup, 1)
    progress = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0, 1)
    cos = 0.5 * lr * (1 + jnp.cos(jnp.pi * progress))
    return jnp.where(step < warmup, warm, cos)


def init_opt_state(params) -> dict:
    # A copy even for f32 params: the master must not alias the params,
    # which a train step donates alongside it.
    f32 = lambda p: jnp.array(p, jnp.float32, copy=True)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adamw_update(grads, opt_state, params, cfg) -> tuple[Any, dict, dict]:
    """Returns (new_params (compute dtype), new_opt_state, metrics)."""
    step = opt_state["step"] + 1
    lr = cosine_schedule(step, cfg.lr, cfg.warmup_steps, cfg.total_steps)

    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    b1, b2 = cfg.b1, cfg.b2

    def upd(g, m, v, master):
        g = g.astype(jnp.float32) * clip
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        mhat = m_new / (1 - b1 ** step.astype(jnp.float32))
        vhat = v_new / (1 - b2 ** step.astype(jnp.float32))
        delta = mhat / (jnp.sqrt(vhat) + 1e-8) + cfg.weight_decay * master
        return m_new, v_new, master - lr * delta

    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    flat_ma = treedef.flatten_up_to(opt_state["master"])
    new_m, new_v, new_master = [], [], []
    for g, m, v, ma in zip(flat_g, flat_m, flat_v, flat_ma):
        m2, v2, ma2 = upd(g, m, v, ma)
        new_m.append(m2)
        new_v.append(v2)
        new_master.append(ma2)
    new_opt = {
        "master": jax.tree.unflatten(treedef, new_master),
        "m": jax.tree.unflatten(treedef, new_m),
        "v": jax.tree.unflatten(treedef, new_v),
        "step": step,
    }
    flat_p = treedef.flatten_up_to(params)
    new_params = jax.tree.unflatten(
        treedef,
        [ma.astype(p.dtype) for ma, p in zip(new_master, flat_p)],
    )
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, new_opt, metrics
