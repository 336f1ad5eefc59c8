"""Kimi-Linear-48B-A3B for serving: Kimi Delta Attention (KDA) layers that
hold a FIXED STATE PER SLOT, multi-head latent attention (MLA) layers without
rope that hold a paged latent row per token, and the dropless expert layer
`deepseek_v32.py` has, told which experts it holds.

Config keys are the published ones (huggingface.co/moonshotai/
Kimi-Linear-48B-A3B-Instruct `config.json`) plus `experts_held` /
`expert_offset` (the chip's share of an expert-parallel deployment, as
`DeepseekV32Config`) and `dtype` (parameters are CREATED in it).  Layer i
(0-based) is MLA where `i + 1` is in `linear_attn_config["full_attn_layers"]`
and KDA where it is in `linear_attn_config["kda_layers"]`: the lists are
1-based, as the published model reads them, and a cut in depth cuts the
lists with it.  Pre-norm residual blocks, RMSNorm eps `rms_norm_eps`.

The layers are Ling-3's (`models/ling3.py`: one `_kda_scan`,
`_kda_recurrence`, `_mla_decode`, `_mla_prefill`), in the forms this config
switches on:

- KDA, `H = linear_attn_config["num_heads"]` heads of `d =
  linear_attn_config["head_dim"]`: `q = l2norm(silu(conv(x W_q)))`, `k =
  l2norm(silu(conv(x W_k)))`, `v = silu(conv(x W_v))` (`conv` causal and
  depthwise, kernel `short_conv_kernel_size`, no bias; `l2norm` eps 1e-6)
  (assumed: the `fla` library's KDA with `use_qk_l2norm_in_kernel`, as the
  published `modeling_kimi.py` calls it); the decay a channel `g = -exp(A_h)
  * softplus(x W_fa W_fb + dt_bias)` (`W_fa` [hidden, d], `W_fb` [d, H d]),
  `a = exp(g)`, unbounded below (`use_kda_lora`); `beta = sigmoid(x W_b)`;
  `S' = diag(a) S`, `S = S' + beta k (v - S'^T k)^T`, `o = S^T q d^-0.5`;
  `y = (rmsnorm_head(o) * w_norm * sigmoid(x W_ga W_gb)) W_o`, the gate one a
  CHANNEL through the low-rank pair (the gated norm's form assumed as `fla`'s
  `FusedRMSNormGated` with a sigmoid).
- MLA (`q_lora_rank` null, `mla_use_nope`): `q = x W_q` -> heads of `[q_nope
  | q_pe]`, `[ckv | k_pe] = x W_kva`, `ckv = rms(ckv)`, `[k_nope | v] = ckv
  W_kvb`; NO rope on `q_pe` or `k_pe` and so no rope tables; scores `(q_nope .
  k_nope + q_pe . k_pe) * (qk_nope_head_dim + qk_rope_head_dim)^-0.5` over every
  `s <= t`; no gate before `W_o`.
- Feed-forward: a dense SwiGLU in the first `first_k_dense_replace` layers,
  else `_route` as it stands (sigmoid scores, the correction bias for the
  choice only, `num_expert_group` groups of which `topk_group` stay, top
  `num_experts_per_token`, weights renormalised under `moe_renormalize`, times
  `routed_scaling_factor`), the held experts' part plus `num_shared_experts`
  shared experts of `moe_intermediate_size`.

The published keys no layer reads are not fields: `head_dim` (72),
`num_key_value_heads`, `rope_theta` and `rope_scaling` (no rope),
`use_grouped_topk` (over one group the grouped top-k is the plain one).  Not
built: the MTP module (`num_nextn_predict_layers` 0 as published).

The engine contract (`inference/engine.py`) is Ling-3's, and the decode
step's counters add the latent rows in reach (`profiler.latent_walk_summary()`).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from .ling3 import Ling3ForCausalLM, Ling3Model

PUBLISHED_LINEAR_ATTN = {
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
    "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4,
}


@dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    linear_attn_config: dict = field(default_factory=lambda: dict(PUBLISHED_LINEAR_ATTN))
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_token: int = 8
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_nextn_predict_layers: int = 0
    model_max_length: int = 1048576
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    # the chip's share of the routed experts; None holds them all
    experts_held: int | None = None
    expert_offset: int = 0
    dtype: str = "bfloat16"

    # the forms of the layers shared with `ling3.py`
    use_kda_lora = True  # the low-rank softplus decay and the gate a channel
    mla_rope = False
    mla_gate = False

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        lin = self.linear_attn_config
        if self.q_lora_rank is not None:
            raise ValueError("the MLA layer is written without the query's low-rank step")
        if not self.mla_use_nope:
            raise ValueError("the MLA layer is written in its published form, without rope (mla_use_nope)")
        if self.moe_router_activation_func != "sigmoid" or self.hidden_act != "silu":
            raise ValueError("only sigmoid routing and SwiGLU experts are written")
        if self.tie_word_embeddings or self.num_nextn_predict_layers or self.moe_layer_freq != 1:
            raise ValueError("tied embeddings, MTP layers and sparse expert placement are not built")
        if self.kda_heads != self.num_attention_heads:
            raise ValueError("KDA and MLA layers are written with one head count")
        full, kda = set(lin["full_attn_layers"]), set(lin["kda_layers"])
        if full & kda or full | kda != set(range(1, self.num_hidden_layers + 1)):
            raise ValueError("full_attn_layers and kda_layers must name each of layers 1 .. "
                             f"{self.num_hidden_layers} once")
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError("[expert_offset, expert_offset + experts_held) leaves the router's range")
        if self.num_experts % self.num_expert_group:
            raise ValueError("num_expert_group must divide num_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers")

    # what `deepseek_v32`'s router and expert layer and `ling3`'s layers read
    @property
    def n_routed_experts(self):
        return self.num_experts

    @property
    def n_shared_experts(self):
        return self.num_shared_experts

    @property
    def num_experts_per_tok(self):
        return self.num_experts_per_token

    @property
    def n_group(self):
        return self.num_expert_group

    @property
    def norm_topk_prob(self):
        return self.moe_renormalize

    @property
    def kda_heads(self):
        return self.linear_attn_config["num_heads"]

    @property
    def kda_head_dim(self):
        return self.linear_attn_config["head_dim"]

    @property
    def short_conv_kernel_size(self):
        return self.linear_attn_config["short_conv_kernel_size"]

    @property
    def max_position_embeddings(self):
        return self.model_max_length

    def layer_kind(self, layer):
        return "mla" if layer + 1 in self.linear_attn_config["full_attn_layers"] else "kda"

    def is_moe(self, layer):
        return layer >= self.first_k_dense_replace

    @staticmethod
    def tiny(**overrides):
        """Seven layers whose last period is short (MLA at 3 and 7, 1-based):
        KDA dense, KDA, MLA, KDA, KDA, KDA, MLA."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=7, first_k_dense_replace=1, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            linear_attn_config={"full_attn_layers": [3, 7], "kda_layers": [1, 2, 4, 5, 6],
                                "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
            num_experts=16, num_experts_per_token=4, model_max_length=256, dtype="float32",
        )
        base.update(overrides)
        return KimiLinearConfig(**base)


class KimiLinearModel(Ling3Model):
    def _step_counts(self, live, pos, moe_stats):
        """A decode step's counters, int32[6]: Ling-3's five, then the latent
        rows in reach of the live slots summed over the MLA layers (`pos + 1`
        a slot a layer: the row written this step among them)."""
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        cfg = self.config
        mla = sum(cfg.layer_kind(i) == "mla" for i in range(cfg.num_hidden_layers))
        first = super()._step_counts(live, pos, moe_stats)
        return apply(lambda c, lv, p: jnp.concatenate([c, (mla * jnp.sum(jnp.where(lv, p + 1, 0)))[None]]),
                     [first, live, pos], name="kimi_step_stats")


class KimiLinearForCausalLM(Ling3ForCausalLM):
    """The served model, through the contract Ling-3 keeps with the engine;
    what it refuses is Ling-3's (`engine_unsupported`), for the same reasons:
    most layers keep their past in a state per slot that no page holds."""

    model_class = KimiLinearModel

    def step_stats(self):
        """The last traced decode step's counters, int32[6] (a Tensor), or None."""
        return self.model.step_stats

    def record_step_stats(self, values):
        from .. import profiler

        super().record_step_stats(values)
        profiler.record_latent_walk_step(int(values[5]), int(values[4]))

    def forward(self, input_ids, labels=None, attn_mask=None):
        raise NotImplementedError(
            "KimiLinearForCausalLM is served through ContinuousBatchingEngine; it has no "
            "cache-free forward (benchmarks/reference_kimi_linear.py is the plain one)")
