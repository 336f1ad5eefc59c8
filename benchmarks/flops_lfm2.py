"""Operations and bytes LFM2-8B-A1B needs (`configs/lfm2-*`), from the
configuration and the traffic alone, never from which kernel ran.  A
multiply-add counts two.  `cfg` is `weights_lfm2.model_cfg(file)`.

What is counted as needed:

- every projection and the router once a token: a convolution layer's
  `W_in` (hidden -> 3 hidden) and `W_out` and its `conv_L_cache` taps a
  channel, an attention layer's q, k, v and o, the dense SwiGLU of the
  leading layers; of the experts the `num_experts_per_tok` a token picks
  (every expert is held here); the head, which is the embedding's rows (tied);
- attention over every key at or before a query (`4 * heads * head_dim` a
  pair: QK^T and PV);
- in bytes, a decode step: the weights outside the experts once, each expert
  a step hit, the K/V rows in reach (a packed `[k | v]` row of `2 *
  head_dim` values a KV head, a layer), and the convolution tails read and
  written.

Padding, the zero half of the walk's padded query and the score's second 64
columns it multiplies are not needed work.
"""

from __future__ import annotations

from .weights_lfm2 import ATTN, CONV, is_moe


def param_counts(cfg):
    h, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, E = h // H, cfg["num_experts"]
    n = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"]
    moe = sum(is_moe(cfg, l) for l in range(n))
    p = {
        "conv": 4 * h * h + cfg["conv_L_cache"] * h,
        "attention": h * H * d + 2 * h * KV * d + H * d * h + 2 * d,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * E + E,
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "head": h * cfg["vocab_size"],
        "conv_layers": kinds.count(CONV),
        "attn_layers": kinds.count(ATTN),
        "moe_layers": moe,
        "dense_layers": n - moe,
    }
    # read by every decode step whatever it routes: all but the experts
    p["non_expert"] = (p["conv_layers"] * p["conv"] + p["attn_layers"] * p["attention"]
                       + p["dense_layers"] * p["dense_mlp"] + p["moe_layers"] * p["router"] + p["head"])
    # all the chip holds: those and every expert (the embedding is the head), the norms aside
    p["held"] = p["non_expert"] + p["moe_layers"] * E * p["expert"]
    return p


def kv_row_bytes(cfg, dtype_bytes=2):
    """A token's packed K/V rows in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * (cfg["hidden_size"] // cfg["num_attention_heads"]) * dtype_bytes


def token_flops(cfg):
    """One token through every layer but for attention over its context; the
    head not included."""
    p = param_counts(cfg)
    return 2 * (p["non_expert"] - p["head"] + p["moe_layers"] * cfg["num_experts_per_tok"] * p["expert"])


def _pair_flops(cfg):
    return 4 * cfg["hidden_size"]  # 4 * heads * head_dim


def forward_flops_decode(cfg, context):
    """One new token whose context is `context` tokens, itself included."""
    p = param_counts(cfg)
    return token_flops(cfg) + 2 * p["head"] + _pair_flops(cfg) * p["attn_layers"] * context


def forward_flops_prompt(cfg, n):
    """One prompt of n tokens; the head runs on the last position only."""
    p = param_counts(cfg)
    return n * token_flops(cfg) + 2 * p["head"] + _pair_flops(cfg) * p["attn_layers"] * n * (n + 1) // 2


def walk_bytes(cfg, rows, dtype_bytes=2):
    """Bytes the page walk must read for `rows` K/V rows in reach (summed over
    slots and layers): the rows themselves, not the pages copied."""
    return rows * kv_row_bytes(cfg, dtype_bytes)


def walk_flops(cfg, rows):
    """QK^T and PV over `rows` rows in reach (summed over slots and layers)."""
    return _pair_flops(cfg) * rows


def decode_bytes(cfg, steps, experts_hit, tail_bytes, kv_rows, dtype_bytes=2):
    """Bytes `steps` decode steps must move: the weights outside the experts
    once a step, each expert a step hit (`experts_hit`: hits summed over steps
    and layers), the tails the live slots read and wrote (`tail_bytes`: both
    ways, summed over steps), and the K/V rows in reach (`kv_rows`: summed
    over steps, slots and attention layers)."""
    p = param_counts(cfg)
    return (dtype_bytes * (steps * p["non_expert"] + experts_hit * p["expert"]) + tail_bytes
            + walk_bytes(cfg, kv_rows, dtype_bytes))
