"""Whether what the window served is correct.

Once the window has closed and the engine is freed, a sample of the
requests the window finished, drawn from the seed and always holding the
longest, is run through the configuration's plain reference: one
forward over each prompt followed by its served tokens.  At every served
position the reading is how far the served token's logit lies below the
reference's best, in units of the rms of the reference's logits there
(0 where the served token is the reference's argmax).  The number
compared is the widest such gap over the sample; the cell's limit is in
``limits/<cell>.json``.

The control is the reference itself put in the program's place at the
next precision down (int4 weights): at each position of the same
sequences it reads the gap of the token the control puts first, and
those readings go through the same limits (``run.py --control 1``).
"""
from __future__ import annotations

import functools

import numpy as np


def pick(window, seed: int, min_tokens: int, max_reqs: int) -> list:
    """The requests to check: the longest finished in the window, then
    others in an order drawn from `seed`, until `min_tokens` served tokens
    or `max_reqs` requests."""
    done = [r for r in window.reqs.values()
            if r.status == "ok" and r.t_finish is not None
            and r.t_finish < window.t_close]
    pool = [r for r in done if r.t_finish >= window.t_open] or done
    if not pool:
        return []
    pool.sort(key=lambda r: r.rid)
    longest = max(pool, key=lambda r: (r.prompt_len + r.n_tokens, -r.rid))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xC4EC])
    out, n = [longest], longest.n_tokens
    for i in rng.permutation(len(pool)):
        if n >= min_tokens or len(out) >= max_reqs:
            break
        if pool[i] is not longest:
            out.append(pool[i])
            n += pool[i].n_tokens
    return out


@functools.lru_cache(maxsize=None)
def _gap_fn():
    import jax
    import jax.numpy as jnp

    def gaps(ref, tok):
        ref = ref.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1))
        got = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return (ref.max(-1) - got) / rms, jnp.argmax(ref, axis=-1)
    return jax.jit(gaps)


def sequence(prompt, tokens, seq_len: int, n_read: int):
    """The forward's input (prompt, then every served token but the last,
    zero-padded to `seq_len`) and the positions whose logits gave each
    served token (padded to `n_read`)."""
    p, n = len(prompt), len(tokens)
    seq = np.zeros(seq_len, np.int32)
    seq[:p] = prompt
    seq[p:p + n - 1] = tokens[:-1]
    at = np.zeros(n_read, np.int32)
    at[:n] = p - 1 + np.arange(n)
    return seq, at


def readings(ref_fn, weights, model: dict, items, seq_len: int, n_read: int,
             control: bool = False) -> dict:
    """Gaps of the served tokens under the reference over `items`
    (``(prompt, served tokens)`` pairs), and with `control`, under
    ``"control"``, the same numbers for the tokens the int4 control puts
    first at each of those positions."""
    import jax.numpy as jnp
    gap = _gap_fn()
    served, ctl, top1 = [], [], []
    for prompt, toks in items:
        seq, at = sequence(prompt, toks, seq_len, n_read)
        n = len(toks)
        seq, at = jnp.asarray(seq), jnp.asarray(at)
        ref = ref_fn(weights, model, seq, at)
        tok = np.zeros(n_read, np.int32)
        tok[:n] = toks
        g, arg = gap(ref, jnp.asarray(tok))
        g, arg = np.asarray(g)[:n], np.asarray(arg)[:n]
        served.append(g)
        top1.append(arg == np.asarray(toks))
        if control:
            low = ref_fn(weights, model, seq, at, weight_bits=4)
            ctl_tok = jnp.argmax(low, axis=-1).astype(jnp.int32)
            cg, _ = gap(ref, ctl_tok)
            ctl.append(np.asarray(cg)[:n])
            del low
        del ref
    out = _summary(served)
    out["top1_share"] = float(np.concatenate(top1).mean()) \
        if top1 else float("nan")
    if control:
        out["control"] = _summary(ctl)
    return out


def _summary(gaps: list) -> dict:
    """The numbers compared: the widest and the mean gap, and the count
    of positions read."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"widest_gap": float(g.max()) if g.size else float("nan"),
            "mean_gap": float(g.mean()) if g.size else float("nan"),
            "tokens": int(g.size)}
