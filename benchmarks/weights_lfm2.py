"""Seeded weights of the LFM2-8B-A1B decoder (`configs/lfm2-*`), made on the
device, one function of (seed, leaf name) as in `weights.py`: the program's
model (a layer at a time, in the served dtype) and the plain reference (a
layer at a time, float32) get the same numbers without either taking
anything from the other.

As `weights_ling3.py`: matrices normal(0, `init.matrix_std`) rounded to
bfloat16, norm weights ones, the router's bias normal(0,
`init.router_bias_std`) in float32, convolution taps normal(0,
`init.conv_std`), an expert keyed by its index among all `num_experts`.  The
published bias is learned; a seeded one as large as the spread of the router's
scores would send every token to the same few experts, so it is drawn a
twentieth of that spread (scores `sigmoid(z W_g)`: logits of rms
`sqrt(hidden) * matrix_std` = 0.9, so scores spread by about 0.2) (assumed).
Nothing in these layers gives every token one common direction (no silu
before a projection that leaves the layer), so no draw is centred.

Layer l's kind is `layer_types[l]`; the dense layers are the first
`num_dense_layers`.  The head is the embedding (tied): there is no
`lm_head` leaf.
"""

from __future__ import annotations

from . import weights_ling3 as L

CONV, ATTN = "conv", "full_attention"
make = L.make


def model_cfg(cfg):
    """The configuration's file as the model's sizes, with the head size
    `reference_lfm2` reads (published: hidden / heads)."""
    out = dict(cfg)
    out.setdefault("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    return out


def layer_kind(cfg, layer):
    return cfg["layer_types"][layer]


def is_moe(cfg, layer):
    return layer >= cfg["num_dense_layers"]


def _op_leaves(cfg, layer):
    h = cfg["hidden_size"]
    if layer_kind(cfg, layer) == CONV:
        return [("conv.in_proj.weight", (h, 3 * h), L.MATRIX),
                ("conv.conv.weight", (cfg["conv_L_cache"], h), L.CONV),
                ("conv.out_proj.weight", (h, h), L.MATRIX)]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], h // cfg["num_attention_heads"]
    return [("self_attn.q_proj.weight", (h, H * d), L.MATRIX),
            ("self_attn.k_proj.weight", (h, KV * d), L.MATRIX),
            ("self_attn.v_proj.weight", (h, KV * d), L.MATRIX),
            ("self_attn.q_layernorm.weight", (d,), L.ONES),
            ("self_attn.k_layernorm.weight", (d,), L.ONES),
            ("self_attn.out_proj.weight", (H * d, h), L.MATRIX)]


def layer_leaves(cfg, layer):
    """[(name, shape, kind)] of one decoder layer, the program's names and
    layout (a Linear's weight is [in, out]; the experts are stacked)."""
    h = cfg["hidden_size"]
    pre = f"model.layers.{layer}."
    out = [(pre + "operator_norm.weight", (h,), L.ONES), (pre + "ffn_norm.weight", (h,), L.ONES)]
    out += [(pre + n, s, k) for n, s, k in _op_leaves(cfg, layer)]
    ff = pre + "feed_forward."
    if not is_moe(cfg, layer):
        i = cfg["intermediate_size"]
        return out + [(ff + "w1.weight", (h, i), L.MATRIX), (ff + "w3.weight", (h, i), L.MATRIX),
                      (ff + "w2.weight", (i, h), L.MATRIX)]
    E, im = cfg["num_experts"], cfg["moe_intermediate_size"]
    return out + [(ff + "gate.weight", (h, E), L.MATRIX), (ff + "expert_bias", (E,), L.ROUTER_BIAS),
                  (ff + "experts.w1", (E, h, im), L.EXPERTS), (ff + "experts.w3", (E, h, im), L.EXPERTS),
                  (ff + "experts.w2", (E, im, h), L.EXPERTS)]


def outer_leaves(cfg):
    h = cfg["hidden_size"]
    return [("model.embed_tokens.weight", (cfg["vocab_size"], h), L.MATRIX),
            ("model.embedding_norm.weight", (h,), L.ONES)]


def all_leaves(cfg):
    out = outer_leaves(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, layer)
    return out
