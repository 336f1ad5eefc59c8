"""Operations and bytes Kimi-Linear-48B-A3B needs (`configs/kimi-linear-*`),
from the configuration and the traffic alone, never from which kernel ran.
A multiply-add counts two.  `cfg` is `weights_kimi_linear.model_cfg(file)`.

Parameters are counted as the program holds them: a KDA layer's q, k, v and
o, its two low-rank pairs (decay and gate), beta, the taps, `dt_bias` and
`A_log`; an MLA layer's q, `kv_a`, `kv_b` and o (no gate); the router, the
shared expert and the held experts of an expert layer.  The latent cache
holds a row `latent_width` wide (the 576 columns of `[ckv | k_pe]` padded to
whole lanes, 640), and a walk over it reads those bytes.
"""

from __future__ import annotations

from .weights_kimi_linear import is_moe, layer_kind

LATENT_WIDTH = 640  # `models/deepseek_v32.latent_width`: 512 + 64 padded to whole lanes


def param_counts(cfg):
    h, H, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["linear_attn_config"]["head_dim"]
    c, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    K = cfg["linear_attn_config"]["short_conv_kernel_size"]
    im = cfg["moe_intermediate_size"]
    n = cfg["num_hidden_layers"]
    kinds = [layer_kind(cfg, l) for l in range(n)]
    moe = [is_moe(cfg, l) for l in range(n)]
    p = {
        # q, k, v, o; the decay's and the gate's low-rank pairs; beta; taps, dt_bias, A_log
        "kda": 4 * h * H * d + 2 * (h * d + d * H * d) + h * H + (3 * K + 1) * H * d + H,
        "mla": h * H * (dn + dr) + h * (c + dr) + c * H * (dn + dv) + H * dv * h,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg["num_experts"],
        "shared": 3 * h * im * cfg["num_shared_experts"],
        "expert": 3 * h * im,
        "head": h * cfg["vocab_size"],
        "kda_layers": kinds.count("kda"),
        "mla_layers": kinds.count("mla"),
        "moe_layers": sum(moe),
        "dense_layers": n - sum(moe),
    }
    # read by every decode step whatever it routes: all but the routed experts
    p["non_expert"] = (p["kda_layers"] * p["kda"] + p["mla_layers"] * p["mla"]
                       + p["dense_layers"] * p["dense_mlp"]
                       + p["moe_layers"] * (p["router"] + p["shared"]) + p["head"])
    # all this chip holds: those, its experts, the embedding's slice, the norms aside
    p["held"] = (p["non_expert"] + p["moe_layers"] * cfg["experts_held"] * p["expert"]
                 + cfg["vocab_size"] * h)
    return p


def walk_bytes(cfg, rows, dtype_bytes=2):
    """Bytes a walk over `rows` latent rows reads: each row once, whole."""
    return rows * LATENT_WIDTH * dtype_bytes


def walk_flops(cfg, rows):
    """A decode query of every head against `rows` latent rows: the absorbed
    score over `kv_lora_rank + qk_rope_head_dim` and the weighted sum over
    `kv_lora_rank`."""
    return rows * 2 * cfg["num_attention_heads"] * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def decode_bytes(cfg, steps, experts_hit, state_bytes, latent_rows, dtype_bytes=2):
    """Bytes `steps` decode steps must move: the weights outside the routed
    experts once a step, each held expert a step hit (`experts_hit`: hits
    summed over steps and expert layers), the state the live slots read and
    wrote (`state_bytes`: both ways, summed over steps), and the latent rows
    in reach (`latent_rows`: summed over steps, slots and MLA layers)."""
    p = param_counts(cfg)
    return (dtype_bytes * (steps * p["non_expert"] + experts_hit * p["expert"]) + state_bytes
            + walk_bytes(cfg, latent_rows, dtype_bytes))
