"""Operations and bytes the algorithm needs, from the configuration and the
traffic alone, never from which kernel ran.  A multiply-add counts two."""

from __future__ import annotations


def param_counts(cfg):
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = h * q + 2 * h * kv + q * h + 3 * h * i
    return {
        "layer_matmul": layer,
        "layers_matmul": layer * cfg["num_hidden_layers"],
        "head": h * v,
        "embedding": v * h,
        "norms": h * (2 * cfg["num_hidden_layers"] + 1),
        "total": layer * cfg["num_hidden_layers"] + 2 * h * v
        + h * (2 * cfg["num_hidden_layers"] + 1),
    }


def attention_flops(cfg, q_len, kv_len, causal):
    """QK^T and PV of one sequence over all layers: 4 * heads * head_dim per
    (query, key) pair that is attended; a causal square counts its lower
    triangle with the diagonal."""
    pairs = q_len * (q_len + 1) // 2 if causal else q_len * kv_len
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs * cfg["num_hidden_layers"]


def forward_flops_prompt(cfg, n):
    """One prompt of n tokens through every layer; the head runs on the last
    position only (the one whose logits are sampled)."""
    p = param_counts(cfg)
    return 2 * p["layers_matmul"] * n + 2 * p["head"] + attention_flops(cfg, n, n, True)


def forward_flops_decode(cfg, context):
    """One new token that attends to `context` tokens (itself included)."""
    p = param_counts(cfg)
    return 2 * (p["layers_matmul"] + p["head"]) + attention_flops(cfg, 1, context, False)


def train_flops_per_step(cfg, batch, seqlen):
    """Forward and backward of a step: 6 per matmul parameter (head included,
    embedding lookup not) per token, and causal attention three times its
    forward.  Recomputation is not counted."""
    p = param_counts(cfg)
    tokens = batch * seqlen
    return 6 * (p["layers_matmul"] + p["head"]) * tokens + 3 * batch * attention_flops(
        cfg, seqlen, seqlen, True
    )


def flash_train_flops(cfg, batch, seqlen):
    """Flash forward plus backward of one step, all layers (backward is
    twice the forward: dQ, dK, dV from recomputed scores)."""
    return 3 * batch * attention_flops(cfg, seqlen, seqlen, True)


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peak["flops_per_s"]["bfloat16"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
