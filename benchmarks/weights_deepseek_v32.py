"""Seeded weights of the DeepSeek-V3.2 decoder (`configs/deepseek-v3.2-*`),
made on the device, one function of (seed, leaf name) as in `weights.py`: the
program's model (a layer at a time, in the served dtype: the whole model
twice does not fit the chip) and the plain reference (a layer at a time,
float32) get the same numbers without either taking anything from the other.

Matrices are normal(0, `init.matrix_std`) rounded to bfloat16; RMSNorm and
LayerNorm weights ones, the LayerNorm bias zeros; the router's bias `b`
normal(0, `init.router_bias_std`) in float32 (assumed: the published one is
learned by the load balancer).  An expert's matrices are keyed by its index
among all `n_routed_experts`, so every share of the deployment draws the
experts the uncut model has at those indices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .weights import leaf, seed_key

MATRIX, ONES, ZEROS, ROUTER_BIAS, EXPERTS = "matrix", "ones", "zeros", "router_bias", "experts"


def model_cfg(cfg):
    """The configuration's file as the model's sizes.  In the file
    `n_routed_experts` counts the experts HELD here (listed in `reduced`), the
    router's width is under `published`, and the leading dense layers that
    are kept under `dense_layers_kept`; here `n_routed_experts` is the
    router's width, `experts_held` the share and `first_k_dense_replace` the
    dense layers there are."""
    if "experts_held" in cfg:
        return cfg
    out = dict(cfg)
    out["experts_held"] = cfg["n_routed_experts"]
    out["n_routed_experts"] = cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])
    out["first_k_dense_replace"] = cfg.get("dense_layers_kept", cfg["first_k_dense_replace"])
    return out


def _attn_leaves(cfg):
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, c, dn, dr, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    return [
        ("q_a_proj.weight", (h, ql), MATRIX), ("q_a_layernorm.weight", (ql,), ONES),
        ("q_b_proj.weight", (ql, H * (dn + dr)), MATRIX),
        ("kv_a_proj_with_mqa.weight", (h, c + dr), MATRIX), ("kv_a_layernorm.weight", (c,), ONES),
        ("kv_b_proj.weight", (c, H * (dn + dv)), MATRIX), ("o_proj.weight", (H * dv, h), MATRIX),
        ("indexer.wq_b.weight", (ql, Hi * Di), MATRIX), ("indexer.wk.weight", (h, Di), MATRIX),
        ("indexer.k_norm.weight", (Di,), ONES), ("indexer.k_norm.bias", (Di,), ZEROS),
        ("indexer.weights_proj.weight", (h, Hi), MATRIX),
    ]


def is_moe(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def layer_leaves(cfg, layer):
    """[(name, shape, kind)] of one decoder layer, the program's names and
    layout (a Linear's weight is [in, out]; the held experts are stacked)."""
    h = cfg["hidden_size"]
    pre = f"model.layers.{layer}."
    out = [(pre + "input_layernorm.weight", (h,), ONES),
           (pre + "post_attention_layernorm.weight", (h,), ONES)]
    out += [(pre + "self_attn." + n, s, k) for n, s, k in _attn_leaves(cfg)]
    if not is_moe(cfg, layer):
        i = cfg["intermediate_size"]
        return out + [(pre + "mlp.gate_proj.weight", (h, i), MATRIX),
                      (pre + "mlp.up_proj.weight", (h, i), MATRIX),
                      (pre + "mlp.down_proj.weight", (i, h), MATRIX)]
    im, held = cfg["moe_intermediate_size"], cfg["experts_held"]
    sh = im * cfg["n_shared_experts"]
    return out + [
        (pre + "mlp.gate.weight", (h, cfg["n_routed_experts"]), MATRIX),
        (pre + "mlp.gate.e_score_correction_bias", (cfg["n_routed_experts"],), ROUTER_BIAS),
        (pre + "mlp.experts.gate_proj", (held, h, im), EXPERTS),
        (pre + "mlp.experts.up_proj", (held, h, im), EXPERTS),
        (pre + "mlp.experts.down_proj", (held, im, h), EXPERTS),
        (pre + "mlp.shared_experts.gate_proj.weight", (h, sh), MATRIX),
        (pre + "mlp.shared_experts.up_proj.weight", (h, sh), MATRIX),
        (pre + "mlp.shared_experts.down_proj.weight", (sh, h), MATRIX),
    ]


def outer_leaves(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("model.embed_tokens.weight", (v, h), MATRIX), ("model.norm.weight", (h,), ONES),
            ("lm_head.weight", (h, v), MATRIX)]


def all_leaves(cfg):
    out = outer_leaves(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, layer)
    return out


def _one(key, cfg, name, shape, kind, dtype):
    init = cfg["init"]
    if kind == ONES:
        return jnp.ones(shape, jnp.float32)
    if kind == ZEROS:
        return jnp.zeros(shape, jnp.float32)
    if kind == ROUTER_BIAS:
        return leaf(key, name, shape, True, float(init["router_bias_std"]), jnp.float32)
    if kind == EXPERTS:
        first = int(cfg.get("expert_offset", 0))
        return jnp.stack([leaf(key, f"{name}.{first + e}", shape[1:], True,
                               float(init["matrix_std"]), dtype) for e in range(shape[0])])
    return leaf(key, name, shape, True, float(init["matrix_std"]), dtype)


def make(seed, cfg, leaves, matrix_dtype):
    """{name: array} for `leaves`, in one jitted call; norms and the
    router's bias in float32."""
    leaves = tuple(leaves)

    @jax.jit
    def f(key):
        return {n: _one(key, cfg, n, s, k, matrix_dtype) for n, s, k in leaves}

    return f(seed_key(seed))
