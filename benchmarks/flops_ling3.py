"""Operations and bytes Ling-3.0-flash needs (`configs/ling-3.0-flash-*`), from
the configuration and the traffic alone, never from which kernel ran.  A
multiply-add counts two.  `cfg` is `weights_ling3.model_cfg(file)`.

What is counted as needed:
- every projection once a token (the absorbed MLA decode's `q W_uk` and `o
  W_uv` cost what one application of `W_ukv` costs);
- a KDA layer's recurrence, a token a head: the decay of the state (`d_k d_v`),
  `S'^T k`, the rank-one update and `S^T q` (two each a state entry): `7 d_k
  d_v`; the chunked form the program's prefill runs costs more a token and is
  not needed work; the convolution's `2 K` a channel;
- an MLA layer's attention over every key at or before the query: for a decode
  token in the latent space (`2 kv_lora_rank + qk_rope_head_dim` a head a
  pair), for a prompt's tokens with K and V expanded (`qk_nope_head_dim +
  qk_rope_head_dim + v_head_dim` a head a pair);
- of the routed experts the picks expected HERE: `num_experts_per_tok *
  experts_held / num_experts` a token; the shared expert in full.
Padding is not needed work.
"""

from __future__ import annotations

from .weights_ling3 import is_moe, layer_kind


def param_counts(cfg):
    h, H, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    c, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    im = cfg["moe_intermediate_size"]
    kinds = [layer_kind(cfg, l) for l in range(cfg["num_hidden_layers"])]
    moe = [is_moe(cfg, l) for l in range(cfg["num_hidden_layers"])]
    p = {
        # q, k, v, f, o; the beta and gate columns; the taps and the bias
        "kda": 5 * h * H * d + 2 * h * H + (3 * cfg["short_conv_kernel_size"] + 1) * H * d,
        "mla": h * H * (dn + dr) + h * (c + dr) + c * H * (dn + dv) + h * H + H * dv * h,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg["num_experts"],
        "shared": 3 * h * cfg["moe_shared_expert_intermediate_size"],
        "expert": 3 * h * im,
        "head": h * cfg["vocab_size"],
        "kda_layers": kinds.count("kda"),
        "mla_layers": kinds.count("mla"),
        "moe_layers": sum(moe),
        "dense_layers": len(moe) - sum(moe),
    }
    # read by every decode step whatever it routes: all but the routed experts
    p["non_expert"] = (p["kda_layers"] * p["kda"] + p["mla_layers"] * p["mla"]
                       + p["dense_layers"] * p["dense_mlp"]
                       + p["moe_layers"] * (p["router"] + p["shared"]) + p["head"])
    # all this chip holds: those, its experts, the embedding's slice, the norms aside
    p["held"] = (p["non_expert"] + p["moe_layers"] * cfg["experts_held"] * p["expert"]
                 + cfg["vocab_size"] * h)
    return p


def picks_here(cfg):
    """Routed picks a token is expected to land on the experts held here."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"] / cfg["num_experts"]


def state_bytes_per_slot(cfg, dtype_bytes=2):
    """A slot's KDA state (float32) and convolution tail, every KDA layer."""
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    tail = (cfg["short_conv_kernel_size"] - 1) * 3 * H * d * dtype_bytes
    return param_counts(cfg)["kda_layers"] * (H * d * d * 4 + tail)


def token_flops(cfg):
    """One token through every layer but for MLA's attention over its
    context; head not included."""
    p = param_counts(cfg)
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    recurrence = 7 * H * d * d
    moe = p["router"] + p["shared"] + picks_here(cfg) * p["expert"]
    return (2 * (p["kda_layers"] * p["kda"] + p["mla_layers"] * p["mla"]
                 + p["dense_layers"] * p["dense_mlp"] + p["moe_layers"] * moe)
            + p["kda_layers"] * recurrence)


def forward_flops_decode(cfg, context):
    """One new token whose context is `context` tokens, itself included."""
    p = param_counts(cfg)
    latent_pair = 2 * cfg["num_attention_heads"] * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return token_flops(cfg) + 2 * p["head"] + p["mla_layers"] * latent_pair * context


def forward_flops_prompt(cfg, n):
    """One prompt of n tokens; the head runs on the last position only."""
    p = param_counts(cfg)
    pair = 2 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                                            + cfg["v_head_dim"])
    return n * token_flops(cfg) + 2 * p["head"] + p["mla_layers"] * pair * (n * (n + 1) // 2)


def decode_bytes(cfg, steps, experts_hit, state_bytes, context_rows, dtype_bytes=2):
    """Bytes `steps` decode steps must move: the weights outside the routed
    experts once a step, each held expert a step hit (`experts_hit`: hits
    summed over steps and expert layers), the state the live slots read and
    wrote (`state_bytes`: both ways, summed over steps), and the latent rows
    in context (`context_rows`: summed over steps and slots, per MLA layer)."""
    p = param_counts(cfg)
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (dtype_bytes * (steps * p["non_expert"] + experts_hit * p["expert"]
                           + p["mla_layers"] * context_rows * latent) + state_bytes)
