"""Model-level invariants: decode==forward continuity, causality, MoE, SSD."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.models import model as M
from repro.models import moe as moe_lib


def tiny(arch, **kw):
    base = dict(name=f"tiny-{arch}", arch_type=arch, n_layers=4, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab=128, head_dim=16,
                dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


ARCHS = {
    "dense": tiny("dense"),
    "qknorm_swa": tiny("dense", qk_norm=True, sliding_window=12),
    "moe": tiny("moe", moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=64,
                                     n_shared_experts=1, capacity_factor=2.0)),
    "ssm": tiny("ssm", ssm=SSMConfig(d_state=16, headdim=16, chunk=8)),
    "hybrid": tiny("hybrid", ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                   hybrid_attn_interval=2),
    "encdec": tiny("encdec", n_enc_layers=2, frontend="audio_stub"),
}


def _batch(cfg, key, b=2, s=16):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (b, s), 0, cfg.vocab),
    }
    if cfg.frontend == "audio_stub":
        batch["frames"] = jax.random.normal(ks[2], (b, s, cfg.d_model))
    return batch


@pytest.mark.parametrize("name", list(ARCHS))
def test_prefill_decode_matches_forward(name, rng):
    """logits(prefill..decode t) == logits(full forward at t): the serving
    path and the training path are the same function."""
    cfg = ARCHS[name]
    params = M.init(rng, cfg)
    b, s = 2, 16
    batch = _batch(cfg, rng, b, s)

    h, _ = M.forward(params, batch, cfg, train=False)
    full_logits = M.logits_fn(params, h, cfg)         # [B, S, V]

    prompt = {k: (v[:, :8] if k != "frames" else v) for k, v in batch.items()}
    logits_p, caches = M.prefill(params, prompt, cfg, max_len=s)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0]), np.asarray(full_logits[:, 7]),
        rtol=5e-2, atol=5e-3,
    )
    # decode positions 8..11 feeding the *teacher-forced* tokens
    for t in range(8, 12):
        step = {"tokens": batch["tokens"][:, t:t + 1]}
        logits_d, caches = M.decode_step(params, step, caches, cfg)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(full_logits[:, t]),
            rtol=5e-2, atol=5e-3,
        )


def test_causality_dense(rng):
    """Future tokens must not affect past logits."""
    cfg = ARCHS["dense"]
    params = M.init(rng, cfg)
    batch = _batch(cfg, rng)
    h1, _ = M.forward(params, batch, cfg)
    l1 = M.logits_fn(params, h1, cfg)
    batch2 = dict(batch)
    batch2["tokens"] = batch["tokens"].at[:, 10:].set(0)
    h2, _ = M.forward(params, batch2, cfg)
    l2 = M.logits_fn(params, h2, cfg)
    np.testing.assert_allclose(np.asarray(l1[:, :10]), np.asarray(l2[:, :10]),
                               rtol=1e-4, atol=1e-5)


def test_ssm_is_causal(rng):
    cfg = ARCHS["ssm"]
    params = M.init(rng, cfg)
    batch = _batch(cfg, rng)
    h1, _ = M.forward(params, batch, cfg)
    batch2 = dict(batch)
    batch2["tokens"] = batch["tokens"].at[:, 10:].set(1)
    h2, _ = M.forward(params, batch2, cfg)
    np.testing.assert_allclose(np.asarray(h1[:, :10]), np.asarray(h2[:, :10]),
                               rtol=1e-4, atol=1e-5)


def test_swa_limits_receptive_field(rng):
    """With window w, logits at position t only see tokens in (t-w, t]."""
    cfg = tiny("dense", sliding_window=4, n_layers=1, dtype="float32")
    params = M.init(rng, cfg)
    batch = _batch(cfg, rng)
    h1, _ = M.forward(params, batch, cfg)
    batch2 = dict(batch)
    # Perturb token 0; positions >= 0+4 (single layer) must be unaffected.
    batch2["tokens"] = batch["tokens"].at[:, 0].set(
        (batch["tokens"][:, 0] + 1) % cfg.vocab)
    h2, _ = M.forward(params, batch2, cfg)
    np.testing.assert_allclose(np.asarray(h1[:, 4:]), np.asarray(h2[:, 4:]),
                               rtol=1e-4, atol=1e-5)
    assert not np.allclose(np.asarray(h1[:, 0]), np.asarray(h2[:, 0]))


def test_moe_routes_and_balances(rng):
    cfg = ARCHS["moe"]
    p = moe_lib.init_moe(rng, cfg.d_model, cfg.moe, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, cfg.d_model))
    y, aux = moe_lib.moe(p, x, cfg.moe)
    assert y.shape == x.shape
    assert np.isfinite(float(aux["aux_loss"]))
    assert float(aux["overflow_frac"]) <= 0.5
    # aux_loss >= 1 (it equals E * sum f_e P_e >= 1 by Cauchy-Schwarz).
    assert float(aux["aux_loss"]) >= 0.99


def test_moe_capacity_overflow_drops_gracefully(rng):
    moe_cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                        capacity_factor=0.25)
    p = moe_lib.init_moe(rng, 32, moe_cfg, jnp.float32)
    # tokens-per-group must exceed the dropless threshold (4*E) to see drops
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32))
    y, aux = moe_lib.moe(p, x, moe_cfg)
    assert np.all(np.isfinite(np.asarray(y)))
    assert float(aux["overflow_frac"]) > 0.2  # capacity deliberately tight


def test_mrope_positions_change_output(rng):
    cfg = tiny("dense", mrope_sections=(4, 2, 2), dtype="float32")
    params = M.init(rng, cfg)
    b, s = 2, 8
    emb = jax.random.normal(rng, (b, s, cfg.d_model))
    pos1 = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s)).astype(jnp.int32)
    pos2 = pos1.at[1].set(pos1[1] * 3)  # different spatial ids
    h1, _ = M.forward(params, {"embeds": emb, "positions": pos1}, cfg)
    h2, _ = M.forward(params, {"embeds": emb, "positions": pos2}, cfg)
    assert not np.allclose(np.asarray(h1), np.asarray(h2))


@pytest.mark.parametrize("arch", ["dense", "moe"])
def test_init_frozen_matches_frozen_init(arch):
    """Group-by-group frozen init == the jitted init-then-freeze program,
    leaf for leaf (dense linears and MoE expert banks)."""
    cfg = ARCHS[arch]
    key = jax.random.PRNGKey(3)
    plan = M.DEFAULT_DEPLOY_PLAN
    want = jax.jit(lambda k: M.freeze_params(M.init(k, cfg), a_scale=0.05,
                                             plan=plan))(key)
    got = M.init_frozen(key, cfg, a_scale=0.05, plan=plan)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
