"""Operations and bytes Mellum2 needs (`configs/mellum2-*`), from the
configuration and the traffic alone, never from which kernel ran.  A
multiply-add counts two.

What is counted as needed:
- every projection and the router once a token; of the experts the
  `num_experts_per_tok` a token picks (every expert is held here);
- attention over the keys a query may SEE: in a full layer every key at or
  before it, in a sliding layer the last `sliding_window` of those (`4 *
  heads * head_dim` a pair: QK^T and PV);
- in bytes, a decode step: the weights outside the experts once, each expert
  a step hit, and the K and V rows in reach of each slot (`2 * kv_heads *
  head_dim` values a row a layer).
Padding and keys behind a window are not needed work.
"""

from __future__ import annotations

from .weights_mellum2 import SLIDING


def param_counts(cfg):
    h, H, KV, d = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    sliding = sum(t == SLIDING for t in cfg["layer_types"])
    p = {
        "attention": h * H * d + 2 * h * KV * d + H * d * h,
        "router": h * cfg["num_experts"],
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "head": h * cfg["vocab_size"],
        "layers": len(cfg["layer_types"]),
        "sliding_layers": sliding,
        "full_layers": len(cfg["layer_types"]) - sliding,
    }
    # read by every decode step whatever it routes: all but the experts
    p["non_expert"] = p["layers"] * (p["attention"] + p["router"]) + p["head"]
    # all the chip holds: those, every expert, the embedding, the norms aside
    p["held"] = (p["non_expert"] + p["layers"] * cfg["num_experts"] * p["expert"]
                 + cfg["vocab_size"] * h)
    return p


def kv_row_bytes(cfg, dtype_bytes=2):
    """A token's K and V rows in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * dtype_bytes


def token_flops(cfg):
    """One token through every layer but for attention over its context; the
    head not included."""
    p = param_counts(cfg)
    return 2 * p["layers"] * (p["attention"] + p["router"] + cfg["num_experts_per_tok"] * p["expert"])


def rows_in_reach(cfg, context):
    """(rows a full layer's query reads, rows a sliding layer's reads) for a
    token whose context is `context` tokens, itself included."""
    return context, min(context, cfg["sliding_window"])


def _pair_flops(cfg):
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def forward_flops_decode(cfg, context):
    """One new token whose context is `context` tokens, itself included."""
    p = param_counts(cfg)
    full, sliding = rows_in_reach(cfg, context)
    return (token_flops(cfg) + 2 * p["head"]
            + _pair_flops(cfg) * (p["full_layers"] * full + p["sliding_layers"] * sliding))


def visible_pairs(n, window=None):
    """(query, key) pairs a causal layer attends over a prompt of n tokens;
    under a window a query sees at most `window` keys."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def forward_flops_prompt(cfg, n):
    """One prompt of n tokens; the head runs on the last position only."""
    p = param_counts(cfg)
    pairs = (p["full_layers"] * visible_pairs(n)
             + p["sliding_layers"] * visible_pairs(n, cfg["sliding_window"]))
    return n * token_flops(cfg) + 2 * p["head"] + _pair_flops(cfg) * pairs


def decode_bytes(cfg, steps, experts_hit, rows_full, rows_sliding, dtype_bytes=2):
    """Bytes `steps` decode steps must move: the weights outside the experts
    once a step, each expert a step hit (`experts_hit`: hits summed over steps
    and layers), and the K and V rows in reach (`rows_full`, `rows_sliding`:
    summed over steps, slots and the layers of each type)."""
    p = param_counts(cfg)
    return (dtype_bytes * (steps * p["non_expert"] + experts_hit * p["expert"])
            + walk_bytes(cfg, rows_full + rows_sliding, dtype_bytes))


def walk_bytes(cfg, rows, dtype_bytes=2):
    """Bytes the page walk must read for `rows` K/V rows in reach (summed over
    slots and layers): the rows themselves, not the pages copied."""
    return rows * kv_row_bytes(cfg, dtype_bytes)


def walk_flops(cfg, rows):
    """QK^T and PV over `rows` rows in reach (summed over slots and layers)."""
    return _pair_flops(cfg) * rows
