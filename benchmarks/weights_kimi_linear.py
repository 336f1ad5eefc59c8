"""Seeded weights of the Kimi-Linear decoder (`configs/kimi-linear-*`), made on
the device, one function of (seed, leaf name) as in `weights.py`: the
program's model (a layer at a time, in the served dtype) and the plain
reference (a layer at a time, float32) get the same numbers without either
taking anything from the other.

As `weights_ling3.py`: matrices normal(0, `init.matrix_std`) rounded to
bfloat16, norm weights ones, the router's bias normal(0,
`init.router_bias_std`), convolution taps normal(0, `init.conv_std`), an
expert keyed by its index among all `num_experts`.  A KDA layer's decay
`-exp(A_log) * softplus(x W_fa W_fb + dt_bias)` takes `A_log` uniform(0,
`init.kda_A_log_max`) a head and `dt_bias` uniform(`init.kda_dt_bias_min`,
`init.kda_dt_bias_max`) a channel, both float32 (assumed: the published ones
are learned), so that the per-channel decays of a token spread from above
0.99 to below e^-8 (at ln 16 and (-6, 3): from e^-0.0025 to e^-49).

Two draws differ from Ling-3's so that the seeded router spreads its picks
as a trained one does (assumed: the published router is balanced by its
learned `e_score_correction_bias`).  `silu` gives q, k and v a positive
mean in every channel, so a KDA layer's output norm carries one vector
common to every token; through a drawn `o_proj` that vector is one
direction of the residual stream, and the router then sends most tokens to
the few experts it favours, a different few for each seed.  So a KDA
layer's `o_proj` is centred over its 4,096 inputs (each output column sums
to 0: the common vector maps to nothing), and the convolution's taps are
scaled to rms `init.conv_std` a channel (every channel the same gain, so
the common vector is one value in every channel).  With both, tokens share
0.09-0.12 of the router's input where they shared 0.52, and a decode batch
of 32 hits 77-83 of the 128 held experts a layer (uniform picks: 82), where
it hit about 55.

Layer i's kind is read from `linear_attn_config` (1-based lists), the dense
layers are the first `first_k_dense_replace`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights_ling3 as L
from .weights import seed_key

DT_BIAS, CENTRED, EVEN_CONV = "dt_bias", "centred_matrix", "even_conv"


def model_cfg(cfg):
    """The configuration's file as the model's sizes: `weights_ling3.model_cfg`'s
    share (`num_experts` the router's width, `experts_held` the share) and the
    published key names under the names `reference_ling3`'s router reads."""
    out = dict(L.model_cfg(cfg))
    out.setdefault("n_group", cfg["num_expert_group"])
    out.setdefault("num_experts_per_tok", cfg["num_experts_per_token"])
    out.setdefault("norm_topk_prob", cfg["moe_renormalize"])
    out.setdefault("moe_shared_expert_intermediate_size", cfg["moe_intermediate_size"] * cfg["num_shared_experts"])
    return out


def layer_kind(cfg, layer):
    return "mla" if layer + 1 in cfg["linear_attn_config"]["full_attn_layers"] else "kda"


def is_moe(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def _attn_leaves(cfg, layer):
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    if layer_kind(cfg, layer) == "mla":
        c, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                         cfg["v_head_dim"])
        return [("q_proj.weight", (h, H * (dn + dr)), L.MATRIX),
                ("kv_a_proj_with_mqa.weight", (h, c + dr), L.MATRIX),
                ("kv_a_layernorm.weight", (c,), L.ONES),
                ("kv_b_proj.weight", (c, H * (dn + dv)), L.MATRIX),
                ("o_proj.weight", (H * dv, h), L.MATRIX)]
    lin = cfg["linear_attn_config"]
    d = lin["head_dim"]
    return [("q_proj.weight", (h, H * d), L.MATRIX), ("k_proj.weight", (h, H * d), L.MATRIX),
            ("v_proj.weight", (h, H * d), L.MATRIX),
            ("conv.weight", (lin["short_conv_kernel_size"], 3 * H * d), EVEN_CONV),
            ("f_a_proj.weight", (h, d), L.MATRIX), ("f_b_proj.weight", (d, H * d), L.MATRIX),
            ("g_a_proj.weight", (h, d), L.MATRIX), ("g_b_proj.weight", (d, H * d), L.MATRIX),
            ("dt_bias", (H * d,), DT_BIAS), ("A_log", (H,), L.A_LOG),
            ("b_proj.weight", (h, H), L.MATRIX),
            ("o_norm.weight", (d,), L.ONES), ("o_proj.weight", (H * d, h), CENTRED)]


def layer_leaves(cfg, layer):
    """[(name, shape, kind)] of one decoder layer, the program's names and
    layout (a Linear's weight is [in, out]; the held experts are stacked)."""
    h = cfg["hidden_size"]
    pre = f"model.layers.{layer}."
    out = [(pre + "input_layernorm.weight", (h,), L.ONES),
           (pre + "post_attention_layernorm.weight", (h,), L.ONES)]
    out += [(pre + "self_attn." + n, s, k) for n, s, k in _attn_leaves(cfg, layer)]
    if not is_moe(cfg, layer):
        i = cfg["intermediate_size"]
        return out + [(pre + "mlp.gate_proj.weight", (h, i), L.MATRIX),
                      (pre + "mlp.up_proj.weight", (h, i), L.MATRIX),
                      (pre + "mlp.down_proj.weight", (i, h), L.MATRIX)]
    im, held, sh = cfg["moe_intermediate_size"], cfg["experts_held"], cfg["moe_shared_expert_intermediate_size"]
    E = cfg["num_experts"]
    return out + [
        (pre + "mlp.gate.weight", (h, E), L.MATRIX),
        (pre + "mlp.gate.e_score_correction_bias", (E,), L.ROUTER_BIAS),
        (pre + "mlp.experts.gate_proj", (held, h, im), L.EXPERTS),
        (pre + "mlp.experts.up_proj", (held, h, im), L.EXPERTS),
        (pre + "mlp.experts.down_proj", (held, im, h), L.EXPERTS),
        (pre + "mlp.shared_experts.gate_proj.weight", (h, sh), L.MATRIX),
        (pre + "mlp.shared_experts.up_proj.weight", (h, sh), L.MATRIX),
        (pre + "mlp.shared_experts.down_proj.weight", (sh, h), L.MATRIX),
    ]


outer_leaves = L.outer_leaves


def all_leaves(cfg):
    out = outer_leaves(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, layer)
    return out


def _one(key, cfg, name, shape, kind, dtype):
    init = cfg["init"]
    if kind == DT_BIAS:
        return jax.random.uniform(L._leaf_key(key, name), shape, jnp.float32,
                                  float(init["kda_dt_bias_min"]), float(init["kda_dt_bias_max"]))
    if kind in (CENTRED, EVEN_CONV):
        x = jax.random.normal(L._leaf_key(key, name), shape, jnp.float32)
        if kind == CENTRED:  # [in, out]: each output's weights sum to 0 over the inputs
            x = (x - jnp.mean(x, axis=0, keepdims=True)) * float(init["matrix_std"])
        else:  # [taps, channels]: every channel's taps at rms conv_std
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=0, keepdims=True)) * float(init["conv_std"])
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7).astype(dtype)
    return L._one(key, cfg, name, shape, kind, dtype)


def make(seed, cfg, leaves, matrix_dtype):
    """{name: array} for `leaves`, in one jitted call; norms, the router's
    bias, `A_log` and `dt_bias` in float32."""
    leaves = tuple(leaves)

    @jax.jit
    def f(key):
        return {n: _one(key, cfg, n, s, k, matrix_dtype) for n, s, k in leaves}

    return f(seed_key(seed))
