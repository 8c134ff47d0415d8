"""The system under test's weights and plan, for any architecture.

What is particular to an architecture (its configuration's mapping onto
the program, its extra leaves, its reference's names, its operations) is
in the adapter its configuration file names (``adapters/<name>.py``, see
``benchlib/spec.py``).  Here: the deployment plan, the shapes of the
program's frozen parameter tree, and the weights, made in one jitted call
from the seed in the form they are served in (int8 codes with
per-channel scales), leaf by leaf by the rule of each leaf's name.
"""
from __future__ import annotations

import json

CODE_STD = 73.61215932167728      # std of int8 codes uniform on [-127, 127]


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


def _w_q(key, shape, dtype, sibling_k, a_scale):
    """int8 weight codes, uniform on [-127, 127]."""
    import jax
    import jax.numpy as jnp
    bits = jax.random.bits(key, shape, jnp.uint8)
    codes = bits.astype(jnp.int16) - 128
    return jnp.maximum(codes, -127).astype(jnp.int8)


def _w_scale(key, shape, dtype, sibling_k, a_scale):
    """Per-output-channel weight scales: unit-variance outputs for unit
    inputs over the `sibling_k` rows of the codes, jittered by 20%."""
    import jax
    import jax.numpy as jnp
    jit = jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
    return jit * (sibling_k ** -0.5 / CODE_STD)


def _a_scale(key, shape, dtype, sibling_k, a_scale):
    import jax.numpy as jnp
    return jnp.full(shape, a_scale, dtype)


def _norm_scale(key, shape, dtype, sibling_k, a_scale):
    """RMSNorm gains."""
    import jax
    import jax.numpy as jnp
    return jax.random.uniform(key, shape, jnp.float32, 0.9, 1.1)


def _table(key, shape, dtype, sibling_k, a_scale):
    """Token embedding, std d^-1/2."""
    import jax
    import jax.numpy as jnp
    bits = jax.random.bits(key, shape, jnp.uint16).astype(jnp.float32)
    return ((bits - 32767.5) * (shape[-1] ** -0.5 / 18918.61)).astype(dtype)


# leaf name -> rule(key, shape, dtype, sibling_k, a_scale) -> array: how
# each leaf of that name is made.  `sibling_k` is the contraction size of
# the int8 matrix the leaf belongs to (``<x>_q`` for ``<x>_scale``, else
# ``w_q`` beside it; 1 where there is none).  An adapter's
# ``WEIGHT_RULES`` add rules for other names in the same form.
COMMON_RULES = {"w_q": _w_q, "w_scale": _w_scale, "a_scale": _a_scale,
                "scale": _norm_scale, "table": _table}


def _leaf(key, rule, shape, dtype, sibling_k: int, a_scale: float):
    """One leaf by `rule`, made slice by slice along its first axis so
    that the random bits of a large leaf never exist whole."""
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
            lambda i: rule(jax.random.fold_in(key, i), (rows, *shape[1:]),
                           dtype, sibling_k, a_scale),
            jnp.arange(n))
        return parts.reshape(shape)
    return rule(key, shape, dtype, sibling_k, a_scale)


def weights_fn(shapes, a_scale: float, rules: dict):
    """The jitted ``make(lo, hi)`` that builds the frozen parameter tree
    `shapes` from the two words of a seed (:func:`seed_words`): every leaf
    made on the device in one call, in its served dtype, by the rule of
    its name (``COMMON_RULES`` and the adapter's `rules`)."""
    import jax

    clash = sorted(COMMON_RULES.keys() & rules.keys())
    if clash:
        raise ValueError(f"adapter weight rules for {clash} would replace "
                         "the common rules")
    rules = {**COMMON_RULES, **rules}
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in flat]
    by_path = dict(zip(paths, (x for _, x in flat)))

    def rule_of(path):
        if path[-1] not in rules:
            raise ValueError(f"no weight rule for {'/'.join(path)}")
        return rules[path[-1]]

    def k_of(path):
        name = path[-1]
        mat = name[:-len("_scale")] + "_q" if name.endswith("_scale") \
            else "w_q"
        w = by_path.get(path[:-1] + (mat,))
        return int(w.shape[-2]) if w is not None else 1

    leaf_rules = [rule_of(path) for path in paths]

    def make(lo, hi):
        base = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0x5EED), lo), hi)
        leaves = [_leaf(jax.random.fold_in(base, i), rule, x.shape, x.dtype,
                        k_of(path), a_scale)
                  for i, (path, rule, (_, x)) in enumerate(
                      zip(paths, leaf_rules, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def make_weights(shapes, seed: int, a_scale: float, rules: dict):
    """The frozen parameter tree for `seed` (:func:`weights_fn`)."""
    return weights_fn(shapes, a_scale, rules)(*seed_words(seed))
