"""Adapter of a dense decoder (Llama / Qwen3 family): grouped-query
attention with an optional RMSNorm per head of q and k, and a SwiGLU MLP.

Everything the harness needs to know of an architecture is in its
adapter, found by the ``adapter`` key of ``configs/<config>.json``:

* :func:`program_config` maps the file's published sizes and settings
  onto the program's ``ModelConfig``;
* ``WEIGHT_RULES`` makes the leaves of the frozen tree beyond the common
  ones of ``benchlib/model.py`` (a dense decoder has none);
* :func:`reference_weights` names the frozen tree's arrays as the plain
  reference (``references/dense_decoder.py``) reads them;
* :func:`model_ops` counts the operations of the work (the numerator of
  ``mfu``);
* :func:`kv_layout` gives the KV pool's layout that the engine's
  prefill-chunk choice reads;
* :func:`stand_in_config` shrinks every width to 1 for the host stand-in
  of the engine (``benchlib/engine.shadow_programs``).
"""
from __future__ import annotations

import dataclasses

# published key -> program ModelConfig field
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "num_hidden_layers": "n_layers", "vocab_size": "vocab"}

WEIGHT_RULES: dict = {}


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


def matmul_params(conf: dict) -> tuple[int, int]:
    """(weights of one token's pass through the layers, of the head)."""
    d, f = int(conf["hidden_size"]), int(conf["intermediate_size"])
    h, kvh = int(conf["num_attention_heads"]), \
        int(conf["num_key_value_heads"])
    hd = int(conf["head_dim"])
    per_layer = d * h * hd * 2 + d * kvh * hd * 2 + 3 * d * f
    return per_layer * int(conf["num_hidden_layers"]), \
        d * int(conf["vocab_size"])


def model_ops(conf: dict, tokens: int, heads_out: int, ctx_sum: int
              ) -> float:
    """Operations of `tokens` token positions through the model, `heads_out`
    of them through the output head, attending `ctx_sum` keys in all."""
    layers_w, head_w = matmul_params(conf)
    attn = 4.0 * int(conf["num_hidden_layers"]) \
        * int(conf["num_attention_heads"]) * int(conf["head_dim"]) * ctx_sum
    return 2.0 * layers_w * tokens + 2.0 * head_w * heads_out + attn


def kv_layout(cfg) -> tuple[int, int, int]:
    """(KV heads, head width, query heads per KV head) of the pool."""
    return cfg.n_kv_heads, cfg.resolved_head_dim, \
        cfg.n_heads // cfg.n_kv_heads


def stand_in_config(cfg):
    """`cfg` with every width 1: the engine's scheduler and bookkeeping
    as `cfg` has them, over a pool of one byte a block."""
    return dataclasses.replace(cfg, n_layers=1, n_heads=1, n_kv_heads=1,
                               head_dim=1, d_model=1, d_ff=1)
