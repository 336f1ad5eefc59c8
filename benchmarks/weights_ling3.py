"""Seeded weights of the Ling-3.0-flash decoder (`configs/ling-3.0-flash-*`),
made on the device, one function of (seed, leaf name) as in `weights.py`: the
program's model (a layer at a time, in the served dtype) and the plain
reference (a layer at a time, float32) get the same numbers without either
taking anything from the other.

Matrices are normal(0, `init.matrix_std`) rounded to bfloat16; norm weights
ones; the router's bias normal(0, `init.router_bias_std`) in float32 (assumed:
the published one is learned).  A KDA layer's convolution taps are normal(0,
`init.conv_std`); its `A_log` is uniform(0, `init.kda_A_log_max`) a head and
its `f_proj.bias` normal(0, `init.kda_f_bias_std`) a channel, both float32, so
that `exp(A) (x W_f + b_f)` spreads over several units either side of 0 and
the decays `exp(kda_lower_bound * sigmoid(.))` over the whole of (e^-5, 1)
(assumed: the published ones are learned).  An expert's matrices are keyed by
its index among all `num_experts`, so every share of the deployment draws the
experts the uncut model has at those indices.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from .weights import leaf, seed_key

MATRIX, ONES, ROUTER_BIAS, EXPERTS, CONV, A_LOG, F_BIAS = (
    "matrix", "ones", "router_bias", "experts", "conv", "a_log", "f_bias")


def model_cfg(cfg):
    """The configuration's file as the model's sizes.  In the file
    `num_experts` counts the experts HELD here (listed in `reduced`) and the
    router's width is under `published`; here `num_experts` is the router's
    width and `experts_held` the share.  `first_k_dense_replace` stays as
    published; `dense_layers_kept` says how many of those layers are here."""
    if "experts_held" in cfg:
        return cfg
    out = dict(cfg)
    out["experts_held"] = cfg["num_experts"]
    out["num_experts"] = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    out.setdefault("dense_layers_kept", cfg["first_k_dense_replace"])
    out.setdefault("expert_offset", 0)
    return out


def published_index(cfg, layer):
    return layer + cfg["first_k_dense_replace"] - cfg["dense_layers_kept"]


def layer_kind(cfg, layer):
    """"mla" where the PUBLISHED index closes a group of `layer_group_size`."""
    return "mla" if (published_index(cfg, layer) + 1) % cfg["layer_group_size"] == 0 else "kda"


def is_moe(cfg, layer):
    return layer >= cfg["dense_layers_kept"]


def _attn_leaves(cfg, layer):
    h, H, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    if layer_kind(cfg, layer) == "mla":
        c, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                         cfg["v_head_dim"])
        return [("q_proj.weight", (h, H * (dn + dr)), MATRIX),
                ("kv_a_proj_with_mqa.weight", (h, c + dr), MATRIX),
                ("kv_a_layernorm.weight", (c,), ONES),
                ("kv_b_proj.weight", (c, H * (dn + dv)), MATRIX),
                ("g_proj.weight", (h, H), MATRIX), ("o_proj.weight", (H * dv, h), MATRIX)]
    return [("q_proj.weight", (h, H * d), MATRIX), ("k_proj.weight", (h, H * d), MATRIX),
            ("v_proj.weight", (h, H * d), MATRIX), ("f_proj.weight", (h, H * d), MATRIX),
            ("conv.weight", (cfg["short_conv_kernel_size"], 3 * H * d), CONV),
            ("f_proj.bias", (H * d,), F_BIAS), ("A_log", (H,), A_LOG),
            ("b_proj.weight", (h, H), MATRIX), ("g_proj.weight", (h, H), MATRIX),
            ("o_norm.weight", (d,), ONES), ("o_proj.weight", (H * d, h), MATRIX)]


def layer_leaves(cfg, layer):
    """[(name, shape, kind)] of one decoder layer, the program's names and
    layout (a Linear's weight is [in, out]; the held experts are stacked)."""
    h = cfg["hidden_size"]
    pre = f"model.layers.{layer}."
    out = [(pre + "input_layernorm.weight", (h,), ONES),
           (pre + "post_attention_layernorm.weight", (h,), ONES)]
    out += [(pre + "self_attn." + n, s, k) for n, s, k in _attn_leaves(cfg, layer)]
    if not is_moe(cfg, layer):
        i = cfg["intermediate_size"]
        return out + [(pre + "mlp.gate_proj.weight", (h, i), MATRIX),
                      (pre + "mlp.up_proj.weight", (h, i), MATRIX),
                      (pre + "mlp.down_proj.weight", (i, h), MATRIX)]
    im, held, sh = cfg["moe_intermediate_size"], cfg["experts_held"], cfg["moe_shared_expert_intermediate_size"]
    return out + [
        (pre + "mlp.gate.weight", (h, cfg["num_experts"]), MATRIX),
        (pre + "mlp.gate.e_score_correction_bias", (cfg["num_experts"],), ROUTER_BIAS),
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


def _leaf_key(key, name):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _one(key, cfg, name, shape, kind, dtype):
    init = cfg["init"]
    if kind == ONES:
        return jnp.ones(shape, jnp.float32)
    if kind == ROUTER_BIAS:
        return leaf(key, name, shape, True, float(init["router_bias_std"]), jnp.float32)
    if kind == F_BIAS:
        return leaf(key, name, shape, True, float(init["kda_f_bias_std"]), jnp.float32)
    if kind == A_LOG:
        return jax.random.uniform(_leaf_key(key, name), shape, jnp.float32, 0.0, float(init["kda_A_log_max"]))
    if kind == CONV:
        return leaf(key, name, shape, True, float(init["conv_std"]), dtype)
    if kind == EXPERTS:
        # one key an expert, from its index among ALL experts: 128 held ones in one draw
        k = _leaf_key(key, name)
        index = int(cfg.get("expert_offset", 0)) + jnp.arange(shape[0])
        x = jax.vmap(lambda e: jax.random.normal(jax.random.fold_in(k, e), shape[1:], jnp.float32))(index)
        x = x * float(init["matrix_std"])
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7).astype(dtype)
    return leaf(key, name, shape, True, float(init["matrix_std"]), dtype)


def make(seed, cfg, leaves, matrix_dtype):
    """{name: array} for `leaves`, in one jitted call; norms, the router's
    bias, `A_log` and `f_proj.bias` in float32."""
    leaves = tuple(leaves)

    @jax.jit
    def f(key):
        return {n: _one(key, cfg, n, s, k, matrix_dtype) for n, s, k in leaves}

    return f(seed_key(seed))
