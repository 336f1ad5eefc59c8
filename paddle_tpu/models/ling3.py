"""Ling-3.0-flash for serving (text only): Kimi Delta Attention (KDA) layers
that hold a FIXED STATE PER SLOT and no rows per token, a multi-head latent
attention (MLA) layer among every `layer_group_size` that holds a paged latent
row per token, and the dropless expert layer `deepseek_v32.py` has, told which
experts it holds.

Config keys are the published ones (huggingface.co/inclusionAI/Ling-3.0-flash-VL
`config.json`; the vision tower is not in it and is not built) plus
`experts_held` / `expert_offset` (the chip's share of an expert-parallel
deployment, as `DeepseekV32Config`), `dense_layers_kept` (how many of the
`first_k_dense_replace` leading dense layers a cut in depth keeps: layer i of
the model is published layer `i + first_k_dense_replace - dense_layers_kept`,
and its kind follows the PUBLISHED index) and `dtype` (parameters are CREATED
in it).  Layer p (published, 0-based) is MLA where `(p + 1) % layer_group_size
== 0`, KDA elsewhere.  Pre-norm residual blocks, RMSNorm eps `rms_norm_eps`.

KDA, per head h of H, `d_k = d_v = head_dim`, `x` the normed input.  Every
reading marked (assumed) is one the published config names and does not spell
out; program and reference (`benchmarks/reference_ling3.py`) read alike:

- `q = l2norm(silu(conv(x W_q)))`, `k = l2norm(silu(conv(x W_k)))`, `v =
  silu(conv(x W_v))`; `conv` a causal depthwise convolution of kernel
  `short_conv_kernel_size` over the sequence, per channel, no bias; `l2norm(z)
  = z * rsqrt(sum(z^2) + 1e-6)` over each head's d_k (`linear_silu` read as
  the silu after the convolution, `use_qk_norm` as this norm) (assumed).
- decay, per channel of the key dim: `g_t = kda_lower_bound * sigmoid(exp(A_h)
  * (x W_f + b_f))`, so `kda_lower_bound < g_t < 0`, `a_t = exp(g_t)`
  (`kda_safe_gate` read as this bounded gate, the `fla` library's KDA;
  `no_kda_lora` as a full-rank `W_f` [hidden, H * d_k]; `A` one scalar a head,
  `b_f` a bias) (assumed).
- `beta_t = sigmoid(x W_b)`, one a head.
- state `S` [d_k, d_v] float32: `S' = diag(a_t) S_{t-1}`; `S_t = S' + beta_t
  k_t (v_t - S'^T k_t)^T`; `o_t = S_t^T q_t * d_k^-0.5`.
- `y = (rmsnorm(o_t) * w_norm * sigmoid(x W_g)_h) W_o`: the norm over each
  head's d_v with one learned weight [d_v] (`group_norm_size` 1), the gate one
  scalar a head (`gated_attention_proj_granularity_type` head_wise) (assumed).

The KDA and MLA layers here are Kimi Linear's too (`models/kimi_linear.py`),
in the forms its config switches on: `use_kda_lora` (a low-rank softplus decay
unbounded below, a gate a channel), `mla_rope` and `mla_gate` off (no rope on
`q_pe` and `k_pe`, no gate before `W_o`); Ling-3's config keeps Ling-3's.

Decode is that step for every live slot at once (`_kda_decode`; on the TPU
the recurrence over the state is the `kda_state_step` kernel of
`ops/kda_decode.py`, one read and one write of the state); an idle slot's
state and convolution tail are left as they were.  Prefill is the
chunkwise form of the same recurrence (`_kda_scan`; Kimi Linear,
arXiv:2510.26692, section 3): with `G_i` the running sum of `g` inside a chunk
of C rows, `A[i, j] = beta_i sum_c k_i k_j exp(G_i - G_j)` (j < i), `Aqk[i, j]
= sum_c q_i k_j exp(G_i - G_j)` (j <= i), `[U | W] = (I + A)^-1 [beta v | beta k
exp(G)]` (a triangular solve), and per chunk `u = U - W S`, `o = (q exp(G)) S +
Aqk u`, `S <- diag(exp(G_C)) S + (k exp(G_C - G))^T u`.  The decays are formed
pairwise, `exp(G_i - G_j)` with `i >= j`, never as `exp(-G_j)`: a row decays by
up to e^5 here, and without a bound under Kimi Linear's gate (e^-49 a row as
the benchmark seeds it, `kimi_linear.py`), so a factored form leaves float32
after 17 rows or fewer.  Every exponent the scan takes is at most 0: `exp(G)`
and `exp(G_C)` fall to their true limit, 0, where a chunk's decays sum past
e^-104, and no quotient of two such values is formed.  Rows at or past
`true_len` take `g = 0`, `beta = 0` and leave state and tail as they were; a
fresh prefill starts its slot from zero, a later chunk from what the chunk
before left in the slot.

MLA (`q_lora_rank` null: no low-rank step on the query): `q = x W_q` -> heads
of `[q_nope | q_pe]`; `[ckv | k_pe] = x W_dkv`, `ckv = rms(ckv)`; plain rope
(`rope_theta`, element i paired with i + d/2 as `deepseek_v32.py` does)
(assumed) on `q_pe` and `k_pe`; `[k_nope | v] = ckv W_ukv`; scores `(q_nope .
k_nope + q_pe . k_pe) * (qk_nope_head_dim + qk_rope_head_dim)^-0.5` over every
`s <= t`; the same head-wise sigmoid gate before `W_o` (assumed).  The cache
holds `[ckv | k_pe]` padded to whole lanes.  Decode absorbs `W_uk` / `W_uv`
and WALKS the slot's pages (`paged_walk_decode` of `ops/flash_attention.py`:
one grid step a slot, the slot's own pages in a loop inside it, 8 pages a
copy; the latent arena is the keys AND the values, said by `arena_v=None`, so
a page is copied once and both dots read it: one KV head, H query rows, the
output's first `kv_lora_rank` columns kept); prefill expands K and V block by
block.

Feed-forward: a dense SwiGLU in the leading layers, else `_route` /
`_routed_experts` / `_moe` of `deepseek_v32.py` as they stand.  Not built: the
vision tower, the MTP module, and the SwiGLU clamp
(`expert_swiglu_limit_list`, `share_expert_swiglu_limit_list`: 0, no clamp,
for every published layer below 34; a config that keeps a clamped layer is
refused).

The engine contract (`inference/engine.py`): `backbone`, `lm_head`,
`cache_rows()`, `cache_layers()`, `engine_unsupported`, `step_stats` /
`record_step_stats`.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .. import nn
from ..tensor import Tensor
from .deepseek_v32 import (LATENT, DeepseekV32MLP, _attend_expanded, _block_rows, _gather_context,
                           _Head, _Leaves, _RMSNorm, _rms, _rope, latent_width)
from .llama import PagedDecodeView, PagedPrefillView, _kv_store

KDA_STATE, CONV_TAIL = "kda_state", "conv_tail"
KDA_CHUNK = 64


@dataclass
class Ling3Config:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    no_kda_lora: bool = True
    use_qk_norm: bool = True
    linear_silu: bool = True
    group_norm_size: int = 1
    gated_attention_proj_granularity_type: str = "head_wise"
    num_experts: int = 512
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    expert_swiglu_limit_list: list = field(default_factory=list)
    share_expert_swiglu_limit_list: list = field(default_factory=list)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    initializer_range: float = 0.02
    # the chip's share of the routed experts; None holds them all
    experts_held: int | None = None
    expert_offset: int = 0
    # how many of the leading dense layers a cut in depth keeps; None: all
    dense_layers_kept: int | None = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.dense_layers_kept is None:
            self.dense_layers_kept = self.first_k_dense_replace
        if self.score_function != "sigmoid" or not self.moe_router_enable_expert_bias:
            raise ValueError("only sigmoid scores with a learned bias are written")
        if self.q_lora_rank is not None:
            raise ValueError("the MLA layer is written without the query's low-rank step")
        if not (self.kda_safe_gate and self.no_kda_lora and self.use_qk_norm and self.linear_silu
                and self.group_norm_size == 1
                and self.gated_attention_proj_granularity_type == "head_wise"):
            raise ValueError("the KDA layer is written for the published switches only")
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError("[expert_offset, expert_offset + experts_held) leaves the router's range")
        if self.num_experts % self.n_group:
            raise ValueError("n_group must divide num_experts")
        if not 0 <= self.dense_layers_kept <= self.first_k_dense_replace:
            raise ValueError("dense_layers_kept counts some of the first_k_dense_replace layers")
        if self.moe_shared_expert_intermediate_size != self.moe_intermediate_size * self.num_shared_experts:
            raise ValueError("the shared expert is num_shared_experts experts wide")
        last = self.published_index(self.num_hidden_layers - 1)
        for limits in (self.expert_swiglu_limit_list, self.share_expert_swiglu_limit_list):
            if any(limits[:last + 1]):
                raise ValueError("the SwiGLU clamp is not built: keep layers whose limit is 0")

    # what `deepseek_v32`'s router and expert layer read under DeepSeek's names
    @property
    def n_routed_experts(self):
        return self.num_experts

    @property
    def n_shared_experts(self):
        return self.num_shared_experts

    # the forms the layers shared with `kimi_linear.py` read: Ling-3's published
    # switches (`use_kda_lora` and `use_mla_nope` off in its config)
    use_kda_lora = False  # a full-rank bounded decay and one output gate a head
    mla_rope = True
    mla_gate = True

    @property
    def kda_heads(self):
        return self.num_attention_heads

    @property
    def kda_head_dim(self):
        return self.head_dim

    def published_index(self, layer):
        return layer + self.first_k_dense_replace - self.dense_layers_kept

    def layer_kind(self, layer):
        return "mla" if (self.published_index(layer) + 1) % self.layer_group_size == 0 else "kda"

    def is_moe(self, layer):
        return layer >= self.dense_layers_kept

    @staticmethod
    def tiny(**overrides):
        """Seven layers with every kind: published 1-7 of a model whose period
        is 6 (KDA dense, KDA, KDA, KDA, MLA, KDA, KDA)."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_hidden_layers=7, first_k_dense_replace=2,
            dense_layers_kept=1, layer_group_size=6, num_attention_heads=4, num_key_value_heads=4,
            head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
            max_position_embeddings=256, dtype="float32",
        )
        base.update(overrides)
        return Ling3Config(**base)


def _rope_tables(cfg):
    if not cfg.mla_rope:
        return None
    d = cfg.qk_rope_head_dim
    inv = 1.0 / (float(cfg.rope_theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    f = np.outer(np.arange(cfg.max_position_embeddings, dtype=np.float64), inv)
    return Tensor(np.cos(f).astype(np.float32)), Tensor(np.sin(f).astype(np.float32))


# -- KDA on arrays ------------------------------------------------------------------

def _kda_inputs(cfg, w, x, conv_in):
    """x [n, hidden] (normed) and the convolution's input rows `conv_in` [n,
    K, 3 * H * dk] (each token's own projection last, the K - 1 before it
    first) -> q, k, v [n, H, dk] f32 (q and k l2-normed), g [n, H, dk] f32
    (log decay, below 0), beta [n, H] f32.  The decay is Ling-3's bounded one
    (`kda_lower_bound * sigmoid(exp(A) (x W_f + b_f))`) or, under
    `cfg.use_kda_lora`, Kimi Linear's low-rank one (`-exp(A) * softplus(x W_fa
    W_fb + dt_bias)`, unbounded below)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    conv = jnp.sum(conv_in.astype(jnp.float32) * w["conv.weight"].astype(jnp.float32)[None], axis=1)
    q, k, v = (a.reshape(n, H, dk) for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    if cfg.use_kda_lora:
        f = ((x @ w["f_a_proj.weight"]) @ w["f_b_proj.weight"]).astype(jnp.float32) + w["dt_bias"]
        g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(f.reshape(n, H, dk))
    else:
        f = (x @ w["f_proj.weight"]).astype(jnp.float32) + w["f_proj.bias"]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(jnp.exp(w["A_log"])[None, :, None] * f.reshape(n, H, dk))
    beta = jax.nn.sigmoid((x @ w["b_proj.weight"]).astype(jnp.float32))
    return q, k, v, g, beta


def _kda_project(w, x):
    """The convolution's input of each token: [x W_q | x W_k | x W_v]."""
    import jax.numpy as jnp

    return jnp.concatenate([x @ w["q_proj.weight"], x @ w["k_proj.weight"], x @ w["v_proj.weight"]], -1)


def _kda_output(cfg, w, x, o):
    """o [n, H, dv] f32 -> the layer's output [n, hidden]: the per-head norm,
    the gate (one a head, or under `cfg.use_kda_lora` one a channel through
    the low-rank pair `W_ga W_gb`), W_o."""
    import jax
    import jax.numpy as jnp

    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps) * w["o_norm.weight"]
    if cfg.use_kda_lora:
        gate = jax.nn.sigmoid(((x @ w["g_a_proj.weight"]) @ w["g_b_proj.weight"]).astype(jnp.float32))
        gate = gate.reshape(o.shape)
    else:
        gate = jax.nn.sigmoid((x @ w["g_proj.weight"]).astype(jnp.float32))[..., None]
    return (y * gate).reshape(x.shape[0], -1).astype(x.dtype) @ w["o_proj.weight"]


def _kda_recurrence(q, k, v, g, beta, state, live):
    """The decode step's recurrence for every slot: q, k, g [S, H, dk] f32, v
    [S, H, dv] f32, beta [S, H] f32, state [S, H, dk, dv] f32, live [S] bool
    -> (o [S, H, dv] f32, unscaled; the state, a slot that is not live
    keeping its own).  Elementwise and reductions in float32, no matmul that
    would round it.

    XLA reads the state once for both `S'^T k` and `S'^T q` (`S_t^T q = S'^T
    q + u (k . q)`) and once more for the update.  On the TPU the
    `kda_state_step` kernel (`ops/kda_decode.py`) does the same arithmetic in
    one read and one write of the state, in place; off the TPU (and for a
    shape the kernel refuses, which counts as a Pallas fallback) the step
    takes the XLA form, the one form."""
    import jax.numpy as jnp

    from ..ops import flash_attention as fa

    interpret = fa._FORCE_INTERPRET
    if interpret or fa._on_tpu():
        from ..ops import kda_decode as kd

        reason = None if interpret else kd.refusal(state)  # the interpreter takes any shape
        if reason is None:
            fa._log_pallas_call("kda_state_step")
            return kd.kda_state_step(q, k, g, v, beta, live, state, interpret)
        fa._log_pallas_fallback("kda_state_step: " + reason, shape=state.shape)
    sp = jnp.exp(g)[..., None] * state
    pred = jnp.sum(sp * k[..., None], axis=2)
    u = beta[..., None] * (v - pred)
    o = jnp.sum(sp * q[..., None], axis=2) + u * jnp.sum(q * k, axis=-1, keepdims=True)
    new = sp + k[..., None] * u[:, :, None, :]
    return o, jnp.where(live[:, None, None, None], new, state)


def _kda_decode(cfg, w, x, state, tail, live):
    """One token a slot: x [S, hidden], state [S, H, dk, dv] f32, tail [S, K -
    1, 3 H dk], live [S] bool.  Returns (out [S, hidden], state, tail); a slot
    that is not live keeps its state and tail."""
    import jax.numpy as jnp

    proj = _kda_project(w, x)
    conv_in = jnp.concatenate([tail, proj[:, None].astype(tail.dtype)], axis=1)
    q, k, v, g, beta = _kda_inputs(cfg, w, x, conv_in)
    o, state = _kda_recurrence(q, k, v, g, beta, state, live)
    out = _kda_output(cfg, w, x, o * cfg.kda_head_dim ** -0.5)
    return out, state, jnp.where(live[:, None, None], conv_in[:, 1:], tail)


def _kda_scan(q, k, v, g, beta, s0, chunk=KDA_CHUNK):
    """The chunkwise form of the recurrence over n rows of one sequence: q,
    k, v, g [n, H, d] f32, beta [n, H] f32, s0 [H, dk, dv] f32 -> (o [n, H,
    dv] f32, unscaled; the state after the last row).  A row with g = 0 and
    beta = 0 changes nothing.  `g` may lie anywhere below 0 (Ling-3's bounded
    gate, Kimi Linear's unbounded one): the module's docstring says why the
    pairwise form holds."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    n, H, dk = q.shape
    C = _block_rows(n, chunk)
    m = n // C
    # [m, H, C, ...]: a chunk's rows of one head lie together
    cut = lambda a: jnp.moveaxis(a.reshape((m, C) + a.shape[1:]), 1, 2)
    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    G = jnp.cumsum(g, axis=2)
    low = jnp.tril(jnp.ones((C, C), bool))

    def pairs(args):
        # one chunk: the [H, C, C, dk] pairwise decays live here and nowhere else
        qc, kc, Gc = args
        d = jnp.exp(jnp.where(low[None, :, :, None], Gc[:, :, None] - Gc[:, None, :], -jnp.inf))
        kd = kc[:, None, :, :] * d
        return jnp.sum(kc[:, :, None] * kd, axis=-1), jnp.sum(qc[:, :, None] * kd, axis=-1)

    kk, qk = lax.map(pairs, (q, k, G))  # [m, H, C, C] each, [i, j] with j <= i
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), kk * beta[..., None], 0.0)
    rhs = jnp.concatenate([v * beta[..., None], k * jnp.exp(G) * beta[..., None]], axis=-1)
    UW = jax.scipy.linalg.solve_triangular(A + jnp.eye(C, dtype=A.dtype), rhs, lower=True,
                                           unit_diagonal=True)
    U, W = UW[..., :v.shape[-1]], UW[..., v.shape[-1]:]
    Qt = q * jnp.exp(G)
    Gend = G[:, :, -1]                        # [m, H, dk]
    Kh = k * jnp.exp(Gend[:, :, None] - G)    # each row's key as the chunk's end sees it

    def step(S, xs):
        Uc, Wc, Qc, qkc, Khc, ge = xs
        u = Uc - jnp.einsum("hck,hkv->hcv", Wc, S, precision=hi)
        o = jnp.einsum("hck,hkv->hcv", Qc, S, precision=hi) + jnp.einsum("hij,hjv->hiv", qkc, u, precision=hi)
        return jnp.exp(ge)[..., None] * S + jnp.einsum("hck,hcv->hkv", Khc, u, precision=hi), o

    S, o = lax.scan(step, s0, (U, W, Qt, qk, Kh, Gend))
    return jnp.moveaxis(o, 2, 1).reshape(n, H, -1), S


def _kda_prefill(cfg, w, x, state, tail, slot, true_len, fresh):
    """A chunk of one sequence: x [s, hidden], seated in `slot` (int32
    scalar).  `fresh`: the slot starts from zero state and a zero tail; else
    from what the chunk before left there.  Returns (out [s, hidden], state,
    tail) with the slot's entries as row `true_len - 1` leaves them."""
    import jax.numpy as jnp
    from jax import lax

    s = x.shape[0]
    K = cfg.short_conv_kernel_size
    slot = jnp.reshape(slot, ())
    n_valid = jnp.reshape(true_len, ())
    s0 = lax.dynamic_index_in_dim(state, slot, 0, False)
    t0 = lax.dynamic_index_in_dim(tail, slot, 0, False)
    if fresh:
        s0, t0 = jnp.zeros_like(s0), jnp.zeros_like(t0)
    rows = jnp.concatenate([t0, _kda_project(w, x).astype(tail.dtype)], axis=0)  # [K - 1 + s, ch]
    conv_in = jnp.stack([rows[i:i + s] for i in range(K)], axis=1)
    q, k, v, g, beta = _kda_inputs(cfg, w, x, conv_in)
    valid = jnp.arange(s, dtype=jnp.int32) < n_valid
    g = jnp.where(valid[:, None, None], g, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    o, s1 = _kda_scan(q, k, v, g, beta, s0)
    t1 = lax.dynamic_slice_in_dim(rows, n_valid, K - 1, 0)  # the last K - 1 real rows
    out = _kda_output(cfg, w, x, o * cfg.kda_head_dim ** -0.5)
    return (out, lax.dynamic_update_index_in_dim(state, s1, slot, 0),
            lax.dynamic_update_index_in_dim(tail, t1, slot, 0))


# -- MLA on arrays ------------------------------------------------------------------

def _mla_scale(cfg):
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _mla_project(cfg, w, x, cos, sin):
    """x [n, hidden], cos/sin [n, rope/2] (None without rope) -> q_nope [n,
    H, dn], q_pe [n, H, dr], the cache's latent row [n, latent_width].  Under
    `cfg.mla_rope` false (Kimi Linear's `mla_use_nope`) `q_pe` and `k_pe` are
    taken as they are projected."""
    import jax.numpy as jnp

    n = x.shape[0]
    H, dn, dr, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q = (x @ w["q_proj.weight"]).reshape(n, H, dn + dr)
    kv = x @ w["kv_a_proj_with_mqa.weight"]
    row = jnp.concatenate([_rms(kv[:, :c], w["kv_a_layernorm.weight"], cfg.rms_norm_eps),
                           _rope(kv[:, c:], cos, sin) if cfg.mla_rope else kv[:, c:],
                           jnp.zeros((n, latent_width(cfg) - c - dr), x.dtype)], -1)
    if not cfg.mla_rope:
        return q[..., :dn], q[..., dn:], row
    return q[..., :dn], _rope(q[..., dn:], cos[:, None], sin[:, None]), row


def _mla_output(cfg, w, x, o):
    """o [n, H, dv] -> [n, hidden]: the head-wise gate (under `cfg.mla_gate`),
    then W_o."""
    import jax
    import jax.numpy as jnp

    if not cfg.mla_gate:
        return o.reshape(x.shape[0], -1) @ w["o_proj.weight"]
    gate = jax.nn.sigmoid((x @ w["g_proj.weight"]).astype(jnp.float32))
    return (o * gate[..., None].astype(o.dtype)).reshape(x.shape[0], -1) @ w["o_proj.weight"]


def _mla_decode(cfg, w, x, cos, sin, lat, tables, pos, max_len):
    """One token a slot: x [S, hidden], pos [S].  Stores the token's latent
    row, then attends the slot's whole context in the latent space by a WALK
    over its pages with an online softmax: the absorbed query `[q_nope W_uk |
    q_pe | 0]` against the one arena, whose rows are the keys and the values
    (`arena_v=None`: a page is copied once), one KV head, H query rows; the
    output's first `kv_lora_rank` columns are `sum p ckv`."""
    import jax.numpy as jnp

    from ..ops import flash_attention as fa

    S = x.shape[0]
    H, dn, dv, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, row = _mla_project(cfg, w, x, cos, sin)
    lat = _kv_store(lat, row[:, None, None, :], tables, pos)
    w_ukv = w["kv_b_proj.weight"].reshape(c, H, dn + dv)
    q_abs = jnp.einsum("shd,chd->shc", q_nope, w_ukv[..., :dn])
    pad = jnp.zeros((S, H, lat.shape[3] - c - q_pe.shape[-1]), x.dtype)
    q = jnp.concatenate([q_abs, q_pe, pad], -1)[:, None].astype(lat.dtype)
    if fa._on_tpu() or fa._FORCE_INTERPRET:
        # straight to the walk: the dispatcher's head_dim <= 256 rule is for K/V
        # heads, and a latent row's 640 columns of ONE head fit VMEM with room
        fa._log_pallas_call("paged_decode_fused")
        o = fa._fused_paged_decode(q, lat, None, tables, pos, max_len, _mla_scale(cfg), fa._FORCE_INTERPRET)
    else:
        ctx = fa.paged_gather_kv(lat, tables, max_len)
        o = fa.decode_attention_array(q, ctx, ctx, pos, _mla_scale(cfg))
    o = jnp.einsum("shc,chd->shd", o[:, 0, :, :c], w_ukv[..., dn:])
    return _mla_output(cfg, w, x, o), lat


def _mla_prefill(cfg, w, x, cos, sin, lat, table, start, true_len):
    """A chunk of one sequence: x [s, hidden] at positions start .. start + s.
    Stores the chunk's rows, then attends the sequence through the page table
    causally, K and V expanded from the latent rows a block of keys at a time
    (`deepseek_v32._attend_expanded`: the `mla_prefill` kernel on the TPU);
    key blocks past the context are never visited."""
    import jax.numpy as jnp

    s = x.shape[0]
    q_nope, q_pe, row = _mla_project(cfg, w, x, cos, sin)
    st = jnp.reshape(start, (1,))
    lat = _kv_store(lat, row[None, :, None, :], table[None], st, true_len)
    ctx = _gather_context(lat, table)
    kb, qb = _block_rows(ctx.shape[0], 1024), _block_rows(s, 512)
    n_blocks = (st[0] + jnp.reshape(true_len, ()) + kb - 1) // kb
    o = _attend_expanded(cfg, w["kv_b_proj.weight"], q_nope, q_pe, ctx, n_blocks, kb, qb, _mla_scale(cfg), st[0])
    return _mla_output(cfg, w, x, o.reshape(s, cfg.num_attention_heads, cfg.v_head_dim)), lat


# -- the layers, as the program's modules -------------------------------------------

def _is_decode(cache):
    if isinstance(cache, PagedDecodeView):
        return True
    if isinstance(cache, PagedPrefillView):
        return False
    raise TypeError(f"Ling3 is served through the paged engine; got {type(cache).__name__}")


class Ling3KDA(_Leaves):
    def __init__(self, cfg):
        super().__init__(cfg)
        h, H, dk = cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim
        if cfg.use_kda_lora:  # Kimi Linear: low-rank decay and gate, a decay bias a channel
            for n in ("q_proj", "k_proj", "v_proj"):
                self._matrix(n + ".weight", h, H * dk)
            self._matrix("conv.weight", cfg.short_conv_kernel_size, 3 * H * dk)
            for n in ("f", "g"):
                self._matrix(n + "_a_proj.weight", h, dk)
                self._matrix(n + "_b_proj.weight", dk, H * dk)
            self._norm("dt_bias", H * dk, 0.0)
            self._norm("A_log", H, 0.0)
            self._matrix("b_proj.weight", h, H)
        else:
            for n in ("q_proj", "k_proj", "v_proj", "f_proj"):
                self._matrix(n + ".weight", h, H * dk)
            self._matrix("conv.weight", cfg.short_conv_kernel_size, 3 * H * dk)
            self._norm("f_proj.bias", H * dk, 0.0)
            self._norm("A_log", H, 0.0)
            self._matrix("b_proj.weight", h, H)
            self._matrix("g_proj.weight", h, H)
        self._norm("o_norm.weight", dk)
        self._matrix("o_proj.weight", H * dk, h)

    def forward(self, x, cache, pos=None):
        """x [b, s, hidden] Tensor (already normed); `cache` a paged view whose
        arena holds this layer's state and tail per slot."""
        from ..ops.dispatch import apply

        cfg = self.cfg
        named = self.leaves()
        names, leaves = list(named), list(named.values())
        state_t, tail_t = getattr(cache.arena, KDA_STATE), getattr(cache.arena, CONV_TAIL)
        if _is_decode(cache):
            if x.shape[1] != 1:
                raise ValueError("the KDA decode step takes one token a slot")

            def f(xa, st, tl, lv, *ws):
                out, st, tl = _kda_decode(cfg, dict(zip(names, ws)), xa[:, 0], st, tl, lv)
                return out[:, None], st, tl

            out, st, tl = apply(f, [x, state_t, tail_t, cache.live] + leaves, multi=True,
                                name="kda_decode")
        else:
            if x.shape[0] != 1:
                raise ValueError("the KDA prefill takes one sequence")
            fresh = cache.start is None

            def f(xa, st, tl, sl, n, *ws):
                out, st, tl = _kda_prefill(cfg, dict(zip(names, ws)), xa[0], st, tl, sl, n, fresh)
                return out[None], st, tl

            out, st, tl = apply(f, [x, state_t, tail_t, cache.slot, cache.true_len] + leaves,
                                multi=True, name="kda_prefill")
        state_t._data, tail_t._data = st._data, tl._data
        return out


def _rows_at(rope, at):
    """The rope tables' rows at positions `at`: (cos, sin), or (None, None)
    for a model without rope."""
    return tuple(t[at] for t in rope) if rope else (None, None)


class Ling3MLA(_Leaves):
    def __init__(self, cfg, rope):
        super().__init__(cfg)
        h, H = cfg.hidden_size, cfg.num_attention_heads
        dn, dr, dv, c = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
        self._matrix("q_proj.weight", h, H * (dn + dr))
        self._matrix("kv_a_proj_with_mqa.weight", h, c + dr)
        self._norm("kv_a_layernorm.weight", c)
        self._matrix("kv_b_proj.weight", c, H * (dn + dv))
        if cfg.mla_gate:
            self._matrix("g_proj.weight", h, H)
        self._matrix("o_proj.weight", H * dv, h)
        self.rope_cos, self.rope_sin = rope or (None, None)

    def forward(self, x, cache, pos=None):
        from ..ops.dispatch import apply

        cfg = self.cfg
        named = self.leaves()
        names, leaves = list(named), list(named.values())
        lat_t = getattr(cache.arena, LATENT)
        max_len = cache.max_len
        rope = [self.rope_cos, self.rope_sin] if cfg.mla_rope else []
        r = len(rope)
        if _is_decode(cache):
            if x.shape[1] != 1:
                raise ValueError("the latent decode path takes one token a slot")

            def f(xa, *rest):
                lat, tables, p, *ws = rest[r:]
                x0 = xa[:, 0]
                cos, sin = _rows_at(rest[:r], p)
                out, lat = _mla_decode(cfg, dict(zip(names, ws)), x0, cos, sin, lat, tables, p, max_len)
                return out[:, None], lat

            out, lat = apply(f, [x] + rope + [lat_t, cache.tables, pos] + leaves,
                             multi=True, name="mla_walk_decode")
        else:
            if x.shape[0] != 1:
                raise ValueError("the latent prefill path takes one sequence")
            has_start = cache.start is not None

            def f(xa, *rest):
                import jax.numpy as jnp
                lat, table, tl, *ws = rest[r:]
                st = ws.pop(0) if has_start else jnp.zeros((1,), jnp.int32)
                at = st[0] + jnp.arange(xa.shape[1], dtype=jnp.int32)
                x0 = xa[0]
                cos, sin = _rows_at(rest[:r], at)
                out, lat = _mla_prefill(cfg, dict(zip(names, ws)), x0, cos, sin, lat, table, st, tl)
                return out[None], lat

            ins = [x] + rope + [lat_t, cache.table, cache.true_len]
            out, lat = apply(f, ins + ([cache.start] if has_start else []) + leaves, multi=True,
                             name="mla_prefill")
        lat_t._data = lat._data
        return out


class Ling3DecoderLayer(nn.Layer):
    def __init__(self, cfg, rope, index):
        super().__init__()
        self.input_layernorm = _RMSNorm(cfg, cfg.hidden_size)
        self.self_attn = Ling3MLA(cfg, rope) if cfg.layer_kind(index) == "mla" else Ling3KDA(cfg)
        self.post_attention_layernorm = _RMSNorm(cfg, cfg.hidden_size)
        self.mlp = DeepseekV32MLP(cfg, moe=cfg.is_moe(index))

    def forward(self, x, cache, pos, live):
        x = x + self.self_attn(self.input_layernorm(x), cache, pos)
        m, stats = self.mlp(self.post_attention_layernorm(x), live)
        return x + m, stats


class Ling3Model(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        rope = _rope_tables(cfg)
        self.embed_tokens = _Leaves(cfg)
        self.embed_tokens._matrix("weight", cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [Ling3DecoderLayer(cfg, rope, i) for i in range(cfg.num_hidden_layers)])
        self.norm = _RMSNorm(cfg, cfg.hidden_size)
        self.step_stats = None

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None, lora=None):
        """The engine's call: `caches` one paged view a layer, `pos` [slots]
        for a decode step.  Returns (hidden, caches); the decode step's
        counters are left in `step_stats`."""
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        if caches is None or lora is not None or attn_mask is not None:
            raise ValueError("Ling3 runs through the paged serving engine only, without LoRA")
        x = apply(lambda ids, e: e[ids], [input_ids, self.embed_tokens.weight], name="embedding")
        view = caches[0]
        decode = _is_decode(view)
        if decode:
            live = view.live
        else:
            n = input_ids.shape[1]
            live = apply(lambda tl: jnp.arange(n, dtype=jnp.int32) < jnp.reshape(tl, ()),
                         [view.true_len], name="ling3_live")
        stats = []
        for layer, cache in zip(self.layers, caches):
            x, st = layer(x, cache, pos, live)
            if st is not None:
                stats.append(st)
        self.step_stats = self._step_counts(live, pos, stats) if decode else None
        return self.norm(x), caches

    def _step_counts(self, live, pos, moe_stats):
        """A decode step's counters, int32[5]: the four of
        `profiler.record_moe_step` summed over the expert layers, then the
        live slots."""
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        def count(lv, *moe):
            m = jnp.stack(moe) if moe else jnp.zeros((1, 4), jnp.int32)
            return jnp.concatenate([jnp.sum(m[:, :3], axis=0), jnp.max(m[:, 3:], axis=0),
                                    jnp.sum(lv, dtype=jnp.int32)[None]])

        return apply(count, [live] + moe_stats, name="ling3_step_stats")


class Ling3ForCausalLM(nn.Layer):
    """The served model.  What the serving engine asks of a model: `backbone`
    (called with `caches=` / `pos=`), `lm_head`, `cache_rows()`, for a model
    whose layers differ `cache_layers()`, and for one that cannot do all the
    engine offers, `engine_unsupported`."""

    # as DeepseekV32 (no int8 latent rows, no tp/cp layout, no handoff format,
    # one token a slot, no LoRA delta), and no prefix cache: a hit resumes
    # from pages alone, and 35 of 42 layers keep their past in a state per
    # slot that no page holds (a snapshot at page boundaries would: ROADMAP A.7)
    engine_unsupported = frozenset({"tp", "cp", "kv_quant", "lora", "spec_k", "role", "prefix_cache"})
    model_class = Ling3Model

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = self.model_class(config)
        self.lm_head = _Head(config)
        self.eval()

    @property
    def backbone(self):
        return self.model

    def cache_rows(self):
        """A token's rows in a layer that has rows (the MLA layers): (name,
        heads, width, dtype)."""
        return [(LATENT, 1, latent_width(self.config), self.config.dtype)]

    def cache_state(self):
        """A slot's state in a layer that has state (the KDA layers): (name,
        shape, dtype)."""
        c = self.config
        H, d = c.kda_heads, c.kda_head_dim
        return [(KDA_STATE, (H, d, d), "float32"),
                (CONV_TAIL, (c.short_conv_kernel_size - 1, 3 * H * d), c.dtype)]

    def cache_layers(self):
        """Each layer's cache, (rows, state): an MLA layer has rows and no
        state, a KDA layer state and no rows."""
        c = self.config
        return [(self.cache_rows(), []) if c.layer_kind(i) == "mla" else ([], self.cache_state())
                for i in range(c.num_hidden_layers)]

    def state_bytes_per_slot(self):
        from ..framework import core as _fcore

        return sum(int(np.prod(shape)) * np.dtype(_fcore.to_jax_dtype(dt)).itemsize
                   for _, state in self.cache_layers() for _, shape, dt in state)

    def step_stats(self):
        """The last traced decode step's counters, int32[5]: the four of
        `profiler.record_moe_step`, then the live slots (a Tensor), or None."""
        return self.model.step_stats

    def record_step_stats(self, values):
        from .. import profiler

        profiler.record_moe_step(*(int(v) for v in values[:4]))
        profiler.record_linear_attn_step(int(values[4]), int(values[4]) * self.state_bytes_per_slot())

    def forward(self, input_ids, labels=None, attn_mask=None):
        raise NotImplementedError(
            "Ling3ForCausalLM is served through ContinuousBatchingEngine; it has no "
            "cache-free forward (benchmarks/reference_ling3.py is the plain one)")
