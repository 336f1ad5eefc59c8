"""Operations and bytes DeepSeek-V3.2 needs (`configs/deepseek-v3.2-*`), from the
configuration and the traffic alone, never from which kernel ran.  A
multiply-add counts two.  `cfg` is `weights_deepseek_v32.model_cfg(file)`.

What is counted as needed:
- every projection once a token (the absorbed decode's `q W_uk` and `o W_uv`
  cost what one application of `W_ukv` costs, so the count is the same);
- the indexer's score of every key at or before the query: `index_n_heads *
  (index_head_dim + 1)` multiply-adds a pair;
- attention over the `min(index_topk, context)` selected keys only: for a
  decode token in the latent space (`2 * kv_lora_rank + qk_rope_head_dim` a
  head a pair), for a prompt's tokens with K and V expanded
  (`qk_nope_head_dim + qk_rope_head_dim + v_head_dim` a head a pair);
- of the routed experts the picks expected HERE: `num_experts_per_tok *
  experts_held / n_routed_experts` a token; the shared expert in full.
The dense-masked form the program's prefill runs (every key in context, not
only the selected) and padding are not needed work and do not count.
"""

from __future__ import annotations


def param_counts(cfg):
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, c, dn, dr, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    im = cfg["moe_intermediate_size"]
    dense_layers = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    p = {
        "attention": h * ql + ql * H * (dn + dr) + h * (c + dr) + c * H * (dn + dv) + H * dv * h,
        "indexer": ql * Hi * Di + h * Di + h * Hi,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg["n_routed_experts"],
        "shared": 3 * h * im * cfg["n_shared_experts"],
        "expert": 3 * h * im,
        "head": h * cfg["vocab_size"],
        "dense_layers": dense_layers,
        "moe_layers": cfg["num_hidden_layers"] - dense_layers,
    }
    # read by every decode step whatever it routes: all but the routed experts
    p["non_expert"] = (cfg["num_hidden_layers"] * (p["attention"] + p["indexer"])
                       + p["dense_layers"] * p["dense_mlp"]
                       + p["moe_layers"] * (p["router"] + p["shared"]) + p["head"])
    # all this chip holds: those, its experts, the embedding's slice, the norms aside
    p["held"] = (p["non_expert"] + p["moe_layers"] * cfg["experts_held"] * p["expert"]
                 + cfg["vocab_size"] * h)
    return p


def picks_here(cfg):
    """Routed picks a token is expected to land on the experts held here."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"] / cfg["n_routed_experts"]


def token_flops(cfg):
    """The token-wise matmuls of one token through every layer, head not
    included."""
    p = param_counts(cfg)
    per_layer = p["attention"] + p["indexer"]
    moe = p["router"] + p["shared"] + picks_here(cfg) * p["expert"]
    return 2 * (cfg["num_hidden_layers"] * per_layer + p["dense_layers"] * p["dense_mlp"]
                + p["moe_layers"] * moe)


def _index_pair(cfg):
    return 2 * cfg["index_n_heads"] * (cfg["index_head_dim"] + 1)


def forward_flops_decode(cfg, context):
    """One new token whose context is `context` tokens, itself included."""
    H = cfg["num_attention_heads"]
    latent_pair = 2 * H * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    selected = min(cfg["index_topk"], context)
    per_layer = _index_pair(cfg) * context + latent_pair * selected
    return token_flops(cfg) + 2 * param_counts(cfg)["head"] + cfg["num_hidden_layers"] * per_layer


def forward_flops_prompt(cfg, n):
    """One prompt of n tokens; the head runs on the last position only."""
    H, k = cfg["num_attention_heads"], cfg["index_topk"]
    pair = 2 * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    seen = n * (n + 1) // 2
    # sum over t of min(k, t + 1)
    selected = seen if n <= k else k * (k + 1) // 2 + (n - k) * k
    per_layer = _index_pair(cfg) * seen + pair * selected
    return n * token_flops(cfg) + 2 * param_counts(cfg)["head"] + cfg["num_hidden_layers"] * per_layer


def decode_bytes(cfg, steps, experts_hit, context_rows, selected_rows, dtype_bytes=2):
    """Bytes `steps` decode steps must read: the weights outside the routed
    experts once a step, each held expert a step hit (`experts_hit`: hits
    summed over steps and expert layers), the indexer's keys of every row in
    context and the selected latent rows (`context_rows`, `selected_rows`:
    summed over steps, per layer)."""
    p = param_counts(cfg)
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    rows = cfg["num_hidden_layers"] * (context_rows * cfg["index_head_dim"] + selected_rows * latent)
    return dtype_bytes * (steps * p["non_expert"] + experts_hit * p["expert"] + rows)
