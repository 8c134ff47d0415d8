"""The system under test, as a configuration file asks for it.

Maps ``configs/<config>.json`` onto the program: its registry config with
the published sizes checked and the file's settings applied, the
deployment plan, and the engine's settings.  Also makes the weights: one
jitted call from the seed, in the form they are served in (int8 codes
with per-channel scales), laid out as the program's frozen parameter tree
and, for the reference, as a plain dict of the same arrays.
"""
from __future__ import annotations

import dataclasses
import json

# published key -> program ModelConfig field
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "num_hidden_layers": "n_layers", "vocab_size": "vocab"}

CODE_STD = 73.61215932167728      # std of int8 codes uniform on [-127, 127]


def program_config(conf: dict):
    """The program's ``ModelConfig`` for the configuration file `conf`:
    the registry entry's architecture with the file's sizes and settings
    (the file holds the configuration as it is run)."""
    from repro import configs
    cfg = configs.get_config(conf["registry"])
    sizes = {field: int(conf[key]) for key, field in WIDTHS.items()}
    if int(conf["head_dim"]) != sizes["d_model"] // sizes["n_heads"]:
        sizes["head_dim"] = int(conf["head_dim"])
    return dataclasses.replace(
        cfg, **sizes, qk_norm=bool(conf["qk_norm"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        kv_cache_dtype=conf["engine"]["kv_cache_dtype"])


def deployment_plan(conf: dict):
    from repro.core import backend as backend_lib
    return backend_lib.DeploymentPlan.from_json(
        json.dumps(conf["engine"]["plan"]))


def frozen_shapes(cfg, plan, a_scale: float):
    """Shapes and dtypes of the program's frozen parameter tree."""
    import jax

    from repro.models import model as M
    return jax.eval_shape(
        lambda k: M.freeze_params(M.init(k, cfg), a_scale=a_scale,
                                  plan=plan), jax.random.PRNGKey(0))


def seed_words(seed: int):
    """`seed` (any whole number below 2**64) as two uint32 words."""
    import numpy as np
    seed = int(seed)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


CHUNK_ELEMS = 1 << 26    # a leaf is made in slices of at most this size


def _leaf(key, path: tuple, shape, dtype, sibling_k: int, a_scale: float):
    """One leaf, made slice by slice along its first axis so that the
    random bits of a large leaf never exist whole."""
    import math

    import jax
    import jax.numpy as jnp
    rest = math.prod(shape[1:]) if len(shape) > 1 else 1
    rows = max((r for r in range(1, (shape[0] if shape else 1) + 1)
                if shape and shape[0] % r == 0
                and r * rest <= CHUNK_ELEMS), default=None)
    if shape and rows is not None and rows < shape[0]:
        n = shape[0] // rows
        parts = jax.lax.map(
            lambda i: _slice(jax.random.fold_in(key, i), path,
                             (rows, *shape[1:]), dtype, sibling_k, a_scale),
            jnp.arange(n))
        return parts.reshape(shape)
    return _slice(key, path, shape, dtype, sibling_k, a_scale)


def _slice(key, path: tuple, shape, dtype, sibling_k: int, a_scale: float):
    import jax
    import jax.numpy as jnp
    name = path[-1]
    if name == "w_q":
        bits = jax.random.bits(key, shape, jnp.uint8)
        codes = bits.astype(jnp.int16) - 128
        return jnp.maximum(codes, -127).astype(jnp.int8)
    if name == "w_scale":
        jit = jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
        return jit * (sibling_k ** -0.5 / CODE_STD)
    if name == "a_scale":
        return jnp.full(shape, a_scale, dtype)
    if name == "scale":                      # RMSNorm gains
        return jax.random.uniform(key, shape, jnp.float32, 0.9, 1.1)
    if name == "table":                      # token embedding, std d^-1/2
        bits = jax.random.bits(key, shape, jnp.uint16).astype(jnp.float32)
        return ((bits - 32767.5) * (shape[-1] ** -0.5 / 18918.61)
                ).astype(dtype)
    raise ValueError(f"no weight rule for {'/'.join(path)}")


def weights_fn(shapes, a_scale: float):
    """The jitted ``make(lo, hi)`` that builds the frozen parameter tree
    `shapes` from the two words of a seed (:func:`seed_words`): every leaf
    made on the device in one call, in its served dtype."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in flat]
    by_path = dict(zip(paths, (x for _, x in flat)))

    def k_of(path):
        w = by_path.get(path[:-1] + ("w_q",))
        return int(w.shape[-2]) if w is not None else 1

    def make(lo, hi):
        base = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0x5EED), lo), hi)
        leaves = [_leaf(jax.random.fold_in(base, i), path, x.shape, x.dtype,
                        k_of(path), a_scale)
                  for i, (path, (_, x)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def make_weights(shapes, seed: int, a_scale: float):
    """The frozen parameter tree for `seed` (:func:`weights_fn`)."""
    return weights_fn(shapes, a_scale)(*seed_words(seed))


def reference_weights(params: dict, conf: dict) -> dict:
    """The arrays of the frozen tree `params` under the plain reference's
    own names (the same device arrays, no copy)."""
    blk = params["stack"]["blocks"]
    lin = {}
    for grp, names in (("attn", ("q", "k", "v", "o")),
                       ("mlp", ("gate", "up", "down"))):
        for n in names:
            p = blk[grp][n]
            lin[n] = {"w": p["w_q"], "w_scale": p["w_scale"],
                      "a_scale": p["a_scale"]}
    out = {"embed": params["embed"]["table"],
           "final_norm": params["final_norm"]["scale"],
           "lm_head": {"w": params["lm_head"]["w_q"],
                       "w_scale": params["lm_head"]["w_scale"],
                       "a_scale": params["lm_head"]["a_scale"]},
           "layers": {"attn_norm": blk["attn_norm"]["scale"],
                      "mlp_norm": blk["mlp_norm"]["scale"], **lin}}
    if conf["qk_norm"]:
        out["layers"]["q_norm"] = blk["attn"]["q_norm"]["scale"]
        out["layers"]["k_norm"] = blk["attn"]["k_norm"]["scale"]
    return out
