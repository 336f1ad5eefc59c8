"""DeepSeek-V3.2 for serving: multi-head latent attention (MLA) over a paged
latent cache, DeepSeek Sparse Attention (the lightning indexer picks the
`index_topk` keys each query attends) with its own paged key cache, and a
dropless expert layer that is told which experts it holds.

Config keys are the published ones (huggingface.co/deepseek-ai/DeepSeek-V3.2
`config.json`) plus `experts_held` / `expert_offset` (the chip's share of an
expert-parallel deployment: the router scores all `n_routed_experts`, this
chip computes the picks that land in `[expert_offset, expert_offset +
experts_held)` with their published weights, adds the shared expert in full,
and passes that partial sum on) and `dtype` (parameters are CREATED in it).

Per token x, pre-norm residual blocks, RMSNorm eps `rms_norm_eps`:

- MLA: `cq = rms(x W_dq)`; `q = cq W_uq` -> heads of `[q_nope | q_pe]`, rope
  on `q_pe`; `[ckv | k_pe] = x W_dkv`, `ckv = rms(ckv)`, rope on `k_pe` (one
  per token, shared by every head); per head `[k_nope | v] = ckv W_ukv`;
  scores `(q_nope.k_nope + q_pe.k_pe) * qk_head_dim^-0.5 * m^2`, `m = 0.1 *
  mscale_all_dim * ln(factor) + 1`; YaRN-corrected rope frequencies.  The
  cache holds `[ckv | k_pe]` only.  Decode absorbs `W_uk` into the query and
  `W_uv` into the output (attention in the latent space, all heads sharing
  one row); prefill expands K and V block by block.
- Indexer: `qi = cq W_qb` (rope on the first `qk_rope_head_dim` of each
  head); `ki = LayerNorm(x W_k)` (one per token, same rope), cached; `w = x
  W_w * index_n_heads^-0.5 * index_head_dim^-0.5`; `I[t, s] = sum_h w[t, h]
  relu(qi[t, h] . ki[s])`, `s <= t`; the query attends exactly the
  `min(index_topk, t + 1)` keys of largest `I`, ties to the lower position.
- Router: `s = sigmoid(x W_g)` in float32; choice scores `s + b`; a group's
  score is the sum of its top 2; the best `topk_group` groups stay; top
  `num_experts_per_tok` among them; weights `s` of the chosen over their sum
  times `routed_scaling_factor`.  The first `first_k_dense_replace` layers
  use a dense SwiGLU.

Rope pairs element i with i + d/2 (halves), in MLA and in the indexer alike.
Not built: the MTP module, FP8 weights and caches with block scales (and so
the indexer's Hadamard rotation, which leaves a dot product unchanged).

The engine contract (`inference/engine.py`): `backbone`, `lm_head`,
`cache_rows()`, `engine_unsupported`, `step_stats` / `record_step_stats`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from .. import nn
from ..nn import initializer as I
from ..nn.layer import ParamAttr
from ..tensor import Tensor
from .llama import PagedDecodeView, PagedPrefillView, _kv_store

LATENT, INDEX_KEY = "latent", "index_key"
LANES = 128  # a cache row is padded to whole lanes, or the chip lays the arena out rows-minor
             # and copies it whole around every store (PERF.md, PR 29)


@dataclass
class DeepseekV32Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    num_nextn_predict_layers: int = 0
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # the chip's share of the routed experts; None holds them all
    experts_held: int | None = None
    expert_offset: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError("only sigmoid scores with noaux_tc group-limited choice are written")
        if self.num_nextn_predict_layers:
            raise ValueError("the MTP module is not built: num_nextn_predict_layers must be 0")
        if not 0 <= self.expert_offset <= self.n_routed_experts - self.experts_held:
            raise ValueError("[expert_offset, expert_offset + experts_held) leaves the router's range")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")

    @staticmethod
    def tiny(**overrides):
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2, index_head_dim=16,
            index_topk=16, n_routed_experts=16, num_experts_per_tok=4, n_group=4,
            topk_group=2, max_position_embeddings=256, dtype="float32",
            rope_scaling={"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        )
        base.update(overrides)
        return DeepseekV32Config(**base)


# -- rope ------------------------------------------------------------------------

def yarn_inv_freq(cfg):
    """Rope frequencies with YaRN's correction: dimensions that turn fewer
    than `beta_slow` times over the original context are interpolated by
    `factor`, those that turn more than `beta_fast` times are kept, a linear
    ramp between."""
    d, base, rs = cfg.qk_rope_head_dim, float(cfg.rope_theta), cfg.rope_scaling
    freqs = 1.0 / (base ** (np.arange(0, d, 2, dtype=np.float64) / d))
    orig = rs["original_max_position_embeddings"]
    if cfg.max_position_embeddings <= orig:
        return freqs

    def turns_dim(n):
        return d * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return freqs / rs["factor"] * ramp + freqs * (1 - ramp)


def softmax_scale(cfg):
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if cfg.max_position_embeddings > rs["original_max_position_embeddings"]:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def _rope_tables(cfg):
    f = np.outer(np.arange(cfg.max_position_embeddings, dtype=np.float64), yarn_inv_freq(cfg))
    return Tensor(np.cos(f).astype(np.float32)), Tensor(np.sin(f).astype(np.float32))


# -- the layers' mathematics, on arrays -----------------------------------------

def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rope(x, cos, sin):
    """x [..., d] turned by cos/sin [..., d/2] (broadcast over heads): element
    i pairs with i + d/2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def _rope_head(x, r, cos, sin):
    """Rope on the first r values of each row of x, the rest kept."""
    import jax.numpy as jnp

    return jnp.concatenate([_rope(x[..., :r], cos, sin), x[..., r:]], -1)


def latent_width(cfg):
    """The cache's latent row `[ckv | k_pe]`, padded with zeros to whole lanes."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // LANES) * LANES


def _attn_project(cfg, w, x, cos, sin):
    """x [n, hidden], cos/sin [n, rope/2] of each token's position ->
    q_nope [n, H, dn], q_pe [n, H, dr], the cache's latent row [n, latent_width],
    indexer query [n, Hi, Di], indexer key [n, Di], head weights [n, Hi] f32."""
    import jax.numpy as jnp

    n = x.shape[0]
    H, dn, dr, c = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.kv_lora_rank)
    Hi, Di, eps = cfg.index_n_heads, cfg.index_head_dim, cfg.rms_norm_eps
    cq = _rms(x @ w["q_a_proj"], w["q_a_layernorm"], eps)
    q = (cq @ w["q_b_proj"]).reshape(n, H, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos[:, None], sin[:, None])
    kv = x @ w["kv_a_proj_with_mqa"]
    row = jnp.concatenate([_rms(kv[:, :c], w["kv_a_layernorm"], eps), _rope(kv[:, c:], cos, sin),
                           jnp.zeros((n, latent_width(cfg) - c - dr), x.dtype)], -1)
    qi = _rope_head((cq @ w["indexer.wq_b"]).reshape(n, Hi, Di), dr, cos[:, None], sin[:, None])
    ki = _rope_head(_layer_norm(x @ w["indexer.wk"], w["indexer.k_norm.weight"],
                                w["indexer.k_norm.bias"], eps), dr, cos, sin)
    wi = (x @ w["indexer.weights_proj"]).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return q_nope, q_pe, row, qi, ki, wi


def _gather_context(arena, table):
    """The rows of one sequence in position order: arena [pages, 1, ps, w]
    through table [P] -> [P * ps, w]."""
    g = arena[table]
    return g.reshape(g.shape[0] * g.shape[2], g.shape[3])


def _decode_attention(cfg, w, x, cos, sin, lat, idx_arena, tables, pos):
    """One token per slot: x [S, hidden], pos [S].  Stores the token's rows,
    scores every indexer key of its context, takes the top `index_topk` and
    attends those latent rows with `W_uk` and `W_uv` absorbed.  Returns
    (out [S, hidden], latent arena, index arena, selected [S] int32)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = x.shape[0]
    H, dn, dv, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dr = cfg.qk_rope_head_dim
    q_nope, q_pe, row, qi, ki, wi = _attn_project(cfg, w, x, cos, sin)
    lat = _kv_store(lat, row[:, None, None, :], tables, pos)
    idx_arena = _kv_store(idx_arena, ki[:, None, None, :], tables, pos)
    ps = lat.shape[2]
    keys = jax.vmap(_gather_context, in_axes=(None, 0))(idx_arena, tables)  # [S, L, Di]
    L = keys.shape[1]
    s = jnp.einsum("shd,sld->shl", qi, keys, preferred_element_type=jnp.float32)
    score = jnp.einsum("shl,sh->sl", jax.nn.relu(s), wi)
    live = jnp.arange(L, dtype=jnp.int32)[None, :] <= pos[:, None]
    k = min(cfg.index_topk, L)
    _, sel = lax.top_k(jnp.where(live, score, -jnp.inf), k)  # ties: the lower position first
    sel = sel.astype(jnp.int32)
    sel_live = sel <= pos[:, None]
    page = jnp.take_along_axis(tables, sel // ps, axis=1)
    rows = lat[page, 0, sel % ps]  # [S, k, latent_width]
    ckv, kpe = rows[..., :c], rows[..., c:c + dr]
    w_ukv = w["kv_b_proj"].reshape(c, H, dn + dv)
    q_abs = jnp.einsum("shd,chd->shc", q_nope, w_ukv[..., :dn])
    logits = (jnp.einsum("shc,skc->shk", q_abs, ckv, preferred_element_type=jnp.float32)
              + jnp.einsum("shr,skr->shk", q_pe, kpe, preferred_element_type=jnp.float32))
    logits = jnp.where(sel_live[:, None, :], logits * softmax_scale(cfg), -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("shk,skc->shc", p, ckv)
    o = jnp.einsum("shc,chd->shd", o_lat, w_ukv[..., dn:]).reshape(S, H * dv)
    return o @ w["o_proj"], lat, idx_arena, jnp.sum(sel_live, axis=1, dtype=jnp.int32)


def _block_rows(n, want):
    """The largest block of at most `want` rows that divides n."""
    b = min(n, want)
    while n % b:
        b -= 1
    return b


def _select_mask(score, k):
    """score [q, L] (-inf where a key may not be seen) -> bool [q, L]: the k
    largest of each row, ties to the lower position; every finite one where
    fewer than k are finite.

    The k-th largest value comes from a bisection over the floats' ordered
    integer image, 32 counting passes, exact: a sort of [2048, 24576] took 53
    ms a layer on the chip, a fifth to a half of a prefill chunk (PERF.md)."""
    import jax.numpy as jnp
    from jax import lax

    k = min(k, score.shape[1])
    bits = jnp.where(score == 0, 0, lax.bitcast_convert_type(score, jnp.int32))  # -0.0 is 0.0 (XLA drops `+ 0.0`)
    # larger float <-> larger unsigned code (negative floats: magnitude reversed)
    code = lax.bitcast_convert_type(jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits), jnp.uint32) ^ jnp.uint32(1 << 31)

    def bit(i, kth):  # the largest t with at least k codes >= t, a bit at a time from the top
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(code >= cand, axis=1, keepdims=True, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros((score.shape[0], 1), jnp.uint32))
    above = code > kth
    tie = (code == kth) & (score > -jnp.inf)
    room = k - jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
    # nearly always every tie fits (one key at the k-th value): no prefix count needed
    return lax.cond(
        jnp.all(jnp.sum(tie, axis=1, keepdims=True, dtype=jnp.int32) <= room),
        lambda: above | tie,
        lambda: above | (tie & (jnp.cumsum(tie, axis=1, dtype=jnp.int32) <= room)))


def _attend_expanded(cfg, w_ukv, q_nope, q_pe, ctx_lat, n_blocks, kb, qb, scale, start, mask=None):
    """s queries (q_nope [s, H, dn], q_pe [s, H, dr]) over one sequence's latent rows `ctx_lat` [L,
    latent_width], K and V expanded `kb` keys at a time, online softmax, `qb` queries a tile, the first
    `n_blocks` (data) blocks; `mask` None is causal from `start` (data), else bool [s, L].  On the TPU one
    Pallas kernel (`ops/mla_prefill.py`) keeps the score tiles in VMEM; elsewhere, or for a shape it
    refuses, this loop, whose tiles go through memory.  Returns [s, H * dv] in q's dtype."""
    import jax.numpy as jnp
    from jax import lax

    from ..ops import flash_attention as fa, mla_prefill as mp

    if (it := fa._FORCE_INTERPRET) or fa._on_tpu():  # the backend and static shapes choose
        reason = mp.refusal(q_nope, q_pe, ctx_lat, w_ukv, kb, qb, mask, it)
        if reason is None:
            fa._log_pallas_call("mla_prefill")
            return mp.mla_prefill(q_nope, q_pe, ctx_lat, w_ukv, n_blocks, kb, qb, scale, start, mask, it)
        fa._log_pallas_fallback("mla_prefill: " + reason, shape=q_nope.shape)
    s = q_nope.shape[0]
    H, dn, dv, c, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
                        cfg.qk_rope_head_dim)
    q_full = jnp.concatenate([q_nope, q_pe], -1)  # against [k_nope | k_pe]: one matmul a score tile
    q_pos = jnp.reshape(start, ()) + jnp.arange(s, dtype=jnp.int32)

    def attend_block(j, carry):
        m, l, acc = carry
        rows = lax.dynamic_slice_in_dim(ctx_lat, j * kb, kb, 0)
        kv = (rows[:, :c] @ w_ukv).reshape(kb, H, dn + dv)
        v = kv[..., dn:]
        k_full = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(rows[:, None, c:c + dr], (kb, H, dr))], -1)
        mask_j = (lax.dynamic_slice_in_dim(mask, j * kb, kb, 1) if mask is not None
                  else (j * kb + jnp.arange(kb, dtype=jnp.int32))[None, :] <= q_pos[:, None])
        ms, ls, accs = [], [], []
        for i in range(0, s, qb):
            lg = jnp.einsum("thd,lhd->thl", q_full[i:i + qb], k_full, preferred_element_type=jnp.float32)
            lg = jnp.where(mask_j[i:i + qb, None, :], lg * scale, -jnp.inf)
            m_new = jnp.maximum(m[i:i + qb], jnp.max(lg, axis=-1))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)  # no key chosen yet
            p = jnp.exp(lg - m_safe[..., None])
            fade = jnp.exp(jnp.where(m[i:i + qb] == -jnp.inf, -jnp.inf, m[i:i + qb] - m_safe))
            ms.append(m_new)
            ls.append(l[i:i + qb] * fade + jnp.sum(p, axis=-1))
            accs.append(acc[i:i + qb] * fade[..., None]
                        + jnp.einsum("thl,lhd->thd", p.astype(v.dtype), v, preferred_element_type=jnp.float32))
        return jnp.concatenate(ms, 0), jnp.concatenate(ls, 0), jnp.concatenate(accs, 0)

    init = (jnp.full((s, H), -jnp.inf, jnp.float32), jnp.zeros((s, H), jnp.float32), jnp.zeros((s, H, dv), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, attend_block, init)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_nope.dtype).reshape(s, H * dv)


def _prefill_attention(cfg, w, x, cos, sin, lat, idx_arena, table, start, true_len):
    """A chunk of one sequence: x [s, hidden] at positions start .. start + s.
    Stores the chunk's rows, then attends the sequence through the page
    table: the indexer's scores over the context so far, the exact top-k
    mask, and attention under that mask with K and V expanded from the
    latent rows a block of keys at a time (online softmax).  Key blocks past
    the context are never visited (the loops' trip counts are data)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = x.shape[0]
    H, dn, dv, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dr = cfg.qk_rope_head_dim
    scale = softmax_scale(cfg)
    q_nope, q_pe, row, qi, ki, wi = _attn_project(cfg, w, x, cos, sin)
    st = jnp.reshape(start, (1,))
    lat = _kv_store(lat, row[None, :, None, :], table[None], st, true_len)
    idx_arena = _kv_store(idx_arena, ki[None, :, None, :], table[None], st, true_len)
    ctx_lat, ctx_key = _gather_context(lat, table), _gather_context(idx_arena, table)
    L = ctx_lat.shape[0]
    kb, qb = _block_rows(L, 1024), _block_rows(s, 512)
    n_ctx = st[0] + jnp.reshape(true_len, ())
    n_blocks = (n_ctx + kb - 1) // kb
    q_pos = st[0] + jnp.arange(s, dtype=jnp.int32)

    def seen(j):  # [s, kb]: key block j's positions against each query's
        return (j * kb + jnp.arange(kb, dtype=jnp.int32))[None, :] <= q_pos[:, None]

    def score_block(j, score):
        keys = lax.dynamic_slice_in_dim(ctx_key, j * kb, kb, 0)
        cols = []
        for i in range(0, s, qb):
            a = jnp.einsum("thd,ld->thl", qi[i:i + qb], keys, preferred_element_type=jnp.float32)
            cols.append(jnp.einsum("thl,th->tl", jax.nn.relu(a), wi[i:i + qb]))
        blk = jnp.where(seen(j), jnp.concatenate(cols, 0), -jnp.inf)
        return lax.dynamic_update_slice_in_dim(score, blk, j * kb, 1)

    score = lax.fori_loop(0, n_blocks, score_block, jnp.full((s, L), -jnp.inf, jnp.float32))
    chosen = _select_topk(score, cfg.index_topk, n_blocks, kb)
    o = _attend_expanded(cfg, w["kv_b_proj"], q_nope, q_pe, ctx_lat, n_blocks, kb, qb, scale, st[0], chosen)
    return o @ w["o_proj"], lat, idx_arena


def indexer_selection(cfg, w, x, cos, sin, rows):
    """The positions the indexer selects for the queries at `rows` (int32
    [r]) of one sequence: x [n, hidden] (normed), cos/sin [n, rope/2] -> int32
    [r, k], position order, then -1 where fewer than k keys are in context.  The serving paths select inside their own steps; this is for
    probes and tests of the selection alone."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    _, _, _, qi, ki, wi = _attn_project(cfg, w, x, cos, sin)
    at = jnp.asarray(rows, jnp.int32)
    s = jnp.einsum("thd,ld->thl", qi[at], ki, preferred_element_type=jnp.float32)
    score = jnp.einsum("thl,th->tl", jax.nn.relu(s), wi[at])
    seen = jnp.arange(x.shape[0], dtype=jnp.int32)[None, :] <= at[:, None]
    k = min(cfg.index_topk, x.shape[0])
    _, sel = lax.top_k(jnp.where(seen, score, -jnp.inf), k)
    n = x.shape[0]
    sel = jnp.sort(jnp.where(sel <= at[:, None], sel, n).astype(jnp.int32), axis=1)
    return jnp.where(sel < n, sel, -1)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _route(cfg, x, gate_w, bias):
    """x [T, hidden] -> (experts [T, K] int32, weights [T, K] f32), the
    router in float32 as published."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, E, G = x.shape[0], cfg.n_routed_experts, cfg.n_group
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    choice = s + bias.astype(jnp.float32)
    group = jnp.sum(lax.top_k(choice.reshape(T, G, E // G), 2)[0], axis=-1)
    _, best = lax.top_k(group, cfg.topk_group)
    keep = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], best].set(True)
    choice = jnp.where(jnp.repeat(keep, E // G, axis=1), choice, -jnp.inf)
    _, experts = lax.top_k(choice, cfg.num_experts_per_tok)
    wts = jnp.take_along_axis(s, experts, axis=1)
    if cfg.norm_topk_prob:
        total = jnp.sum(wts, axis=1, keepdims=True)
        eps = getattr(cfg, "topk_norm_eps", 0.0)  # LFM2's published form adds one; no other does
        wts = wts / (total + eps if eps else total)
    return experts.astype(jnp.int32), wts * cfg.routed_scaling_factor


def _route_softmax(cfg, x, gate_w):
    """The router's other published form (no bias, groups or scaling): x [T,
    hidden] -> (experts [T, K] int32, weights [T, K] f32) with `p = softmax(x
    W_g)` in float32 over every expert, the K largest, and, under
    `norm_topk_prob`, their weights over their sum."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    wts, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        wts = wts / jnp.sum(wts, axis=1, keepdims=True)
    return experts.astype(jnp.int32), wts


def _routed_experts(cfg, x, experts, wts, live, w1, w3, w2):
    """The held experts' part of the routed sum, dropless.  x [T, hidden],
    experts/wts [T, K], live [T] bool (padding rows and idle slots route
    nowhere), w1/w3 [held, hidden, I], w2 [held, I, hidden].

    Picks that land on a held expert are sorted by expert and cut into
    blocks of B rows, each block of one expert; a loop whose trip count is
    the number of blocks in use (data) runs one expert's SwiGLU per block
    and adds the weighted rows back to their tokens.  An expert nobody picked
    costs nothing, no pick is ever left out, and there is no capacity.

    A step of at most B tokens whose picks number the router's width or more
    (`T * K >= n_routed_experts`: every held expert expects a token or more,
    which is a deployment's decode batch) takes the `grouped_experts` kernel
    (`ops/grouped_experts.py`) instead, on the TPU: nearly every expert would
    be a block of its own here, each costing the loop's fixed work (32 us a
    hit against 14 us of an expert's bytes, PERF.md, PR 33) on top of the
    expert's bytes.  The kernel walks the same hit experts, pays a copy's
    bytes a hit and nothing else, and overlaps one expert's dots with the
    next one's copy; the router's weight goes into the middle product's rows
    and the sum over experts stays in float32.  Off the TPU (and for a shape
    the kernel refuses, which counts as a Pallas fallback) such a step takes
    the loop as well: it is the one XLA form.
    Returns (y [T, hidden] f32, [tokens, picks held, experts hit, max load])."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, K = experts.shape
    held = w1.shape[0]
    B = min(128, -(-max(T, 8) // 8) * 8)
    local = experts - cfg.expert_offset
    mine = (local >= 0) & (local < held) & live[:, None]
    key = jnp.where(mine, local, held).reshape(-1)
    hot = key[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]  # [T * K, held]: a pick, its held expert
    counts = jnp.sum(hot, axis=0, dtype=jnp.int32)
    stats = jnp.stack([jnp.sum(live, dtype=jnp.int32), jnp.sum(counts), jnp.sum(counts > 0, dtype=jnp.int32),
                       jnp.max(counts)])
    if T <= B and T * K >= cfg.n_routed_experts:
        from ..ops import flash_attention as fa
        from ..ops import grouped_experts as ge

        interpret = fa._FORCE_INTERPRET
        if interpret or fa._on_tpu():
            reason = None if interpret else ge.refusal(x, w1)  # the interpreter takes any shape
            if reason is None:
                fa._log_pallas_call("grouped_experts")
                weight = jnp.sum(jnp.where(hot, wts.reshape(-1, 1), 0.0).reshape(T, K, held), axis=1)
                return ge.grouped_experts(x, weight, *ge.hit_list(counts), w1, w3, w2, interpret), stats
            fa._log_pallas_fallback("grouped_experts: " + reason, shape=x.shape)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    blocks = (counts + B - 1) // B
    blk_end = jnp.cumsum(blocks)
    first_pick = jnp.cumsum(counts) - counts
    flat_w = wts.reshape(-1)

    def body(i, y):
        e = jnp.sum(blk_end <= i, dtype=jnp.int32)  # the expert block i belongs to
        j = (i - (blk_end[e] - blocks[e])) * B + jnp.arange(B, dtype=jnp.int32)
        ok = j < counts[e]
        pick = order[jnp.where(ok, first_pick[e] + j, 0)]
        tok = pick // K
        wt = jnp.where(ok, flat_w[pick], 0.0)
        out = _swiglu(x[tok], lax.dynamic_index_in_dim(w1, e, 0, False),
                      lax.dynamic_index_in_dim(w3, e, 0, False),
                      lax.dynamic_index_in_dim(w2, e, 0, False))
        return y.at[tok].add(out.astype(jnp.float32) * wt[:, None])

    return lax.fori_loop(0, blk_end[-1], body, jnp.zeros(x.shape, jnp.float32)), stats


def _moe(cfg, w, x, live):
    experts, wts = _route(cfg, x, w["gate.weight"], w["gate.e_score_correction_bias"])
    y, stats = _routed_experts(cfg, x, experts, wts, live, w["experts.gate_proj"],
                               w["experts.up_proj"], w["experts.down_proj"])
    shared = _swiglu(x, w["shared_experts.gate_proj"], w["shared_experts.up_proj"],
                     w["shared_experts.down_proj"])
    return (y + shared.astype(y.dtype)).astype(x.dtype), stats


# -- the layers, as the program's modules ---------------------------------------

ATTN_MATRICES = (
    ("q_a_proj", "hidden_size", "q_lora_rank"),
    ("q_b_proj", "q_lora_rank", "q_out"),
    ("kv_a_proj_with_mqa", "hidden_size", "kv_a_out"),
    ("kv_b_proj", "kv_lora_rank", "kv_b_out"),
    ("o_proj", "o_in", "hidden_size"),
    ("indexer.wq_b", "q_lora_rank", "index_q_out"),
    ("indexer.wk", "hidden_size", "index_head_dim"),
    ("indexer.weights_proj", "hidden_size", "index_n_heads"),
)


def _dims(cfg):
    d = dict(vars(cfg))
    H = cfg.num_attention_heads
    d.update(q_out=H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
             kv_a_out=cfg.kv_lora_rank + cfg.qk_rope_head_dim,
             kv_b_out=H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
             o_in=H * cfg.v_head_dim, index_q_out=cfg.index_n_heads * cfg.index_head_dim)
    return d


class _Leaves(nn.Layer):
    """A layer whose parameters carry dotted names (`indexer.k_norm.weight`)
    and are created in the configuration's dtype, never in float32 first.
    Served, not trained: no parameter takes a gradient, so a compiled step
    traces no backward."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg

    def _leaf(self, name, shape, init, dtype=None):
        p = self.create_parameter(list(shape), attr=ParamAttr(trainable=False),
                                  dtype=dtype or self.cfg.dtype, default_initializer=init)
        self.add_parameter(name, p)

    def _matrix(self, name, *shape):
        self._leaf(name, shape, I.Normal(0.0, self.cfg.initializer_range))

    def _norm(self, name, n, value=1.0):
        self._leaf(name, (n,), I.Constant(value), "float32")

    def leaves(self):
        return dict(self._parameters)


class _RMSNorm(_Leaves):
    def __init__(self, cfg, n):
        super().__init__(cfg)
        self._norm("weight", n)

    def forward(self, x):
        from ..nn import functional as F

        return F.rms_norm(x, self.weight, self.cfg.rms_norm_eps)


class DeepseekV32Attention(_Leaves):
    def __init__(self, cfg, rope):
        super().__init__(cfg)
        d = _dims(cfg)
        for name, a, b in ATTN_MATRICES:
            self._matrix(name + ".weight", d[a], d[b])
        self._norm("q_a_layernorm.weight", cfg.q_lora_rank)
        self._norm("kv_a_layernorm.weight", cfg.kv_lora_rank)
        self._norm("indexer.k_norm.weight", cfg.index_head_dim)
        self._norm("indexer.k_norm.bias", cfg.index_head_dim, 0.0)
        self.rope_cos, self.rope_sin = rope

    def _weights(self):
        named = self.leaves()
        return [n[:-len(".weight")] if n.endswith(".weight") and "k_norm" not in n else n
                for n in named], list(named.values())

    def forward(self, x, cache, pos=None):
        """x [b, s, hidden] Tensor (already normed); `cache` a paged view over
        this layer's arena.  Returns (out, selected-rows Tensor or None)."""
        from ..ops.dispatch import apply

        cfg = self.cfg
        names, leaves = self._weights()
        arena = cache.arena
        lat_t, idx_t = getattr(arena, LATENT), getattr(arena, INDEX_KEY)

        if isinstance(cache, PagedDecodeView):
            if x.shape[1] != 1:
                raise ValueError("the latent decode path takes one token a slot")

            def f(xa, cos, sin, lat, idx, tables, p, *ws):
                c, s = cos[p], sin[p]
                out, lat, idx, sel = _decode_attention(
                    cfg, dict(zip(names, ws)), xa[:, 0], c, s, lat, idx, tables, p)
                return out[:, None], lat, idx, sel

            out, lat, idx, sel = apply(
                f, [x, self.rope_cos, self.rope_sin, lat_t, idx_t, cache.tables, pos] + leaves,
                multi=True, name="mla_sparse_decode")
        elif isinstance(cache, PagedPrefillView):
            if x.shape[0] != 1:
                raise ValueError("the latent prefill path takes one sequence")
            sel = None
            has_start = cache.start is not None

            def f(xa, cos, sin, lat, idx, table, tl, *rest):
                import jax.numpy as jnp
                st = rest[0] if has_start else jnp.zeros((1,), jnp.int32)
                ws = rest[1:] if has_start else rest
                # a gather clamps each position alone: rows past the tables
                # are padding rows only, and no real row's position shifts
                at = st[0] + jnp.arange(xa.shape[1], dtype=jnp.int32)
                c, si = cos[at], sin[at]
                out, lat, idx = _prefill_attention(
                    cfg, dict(zip(names, ws)), xa[0], c, si, lat, idx, table, st, tl)
                return out[None], lat, idx

            ins = [x, self.rope_cos, self.rope_sin, lat_t, idx_t, cache.table, cache.true_len]
            out, lat, idx = apply(f, ins + ([cache.start] if has_start else []) + leaves,
                                  multi=True, name="mla_sparse_prefill")
        else:
            raise TypeError(f"DeepseekV32 is served through the paged engine; got {type(cache).__name__}")
        lat_t._data, idx_t._data = lat._data, idx._data
        return out, sel


class DeepseekV32MLP(_Leaves):
    """Dense SwiGLU (the leading layers) or the expert layer."""

    def __init__(self, cfg, moe):
        super().__init__(cfg)
        self.moe = moe
        h = cfg.hidden_size
        if not moe:
            for n, a, b in (("gate_proj", h, cfg.intermediate_size), ("up_proj", h, cfg.intermediate_size),
                            ("down_proj", cfg.intermediate_size, h)):
                self._matrix(n + ".weight", a, b)
            return
        held, im = cfg.experts_held, cfg.moe_intermediate_size
        sh = im * cfg.n_shared_experts
        self._matrix("gate.weight", h, cfg.n_routed_experts)
        self._leaf("gate.e_score_correction_bias", (cfg.n_routed_experts,), I.Constant(0.0), "float32")
        self._matrix("experts.gate_proj", held, h, im)
        self._matrix("experts.up_proj", held, h, im)
        self._matrix("experts.down_proj", held, im, h)
        for n, a, b in (("gate_proj", h, sh), ("up_proj", h, sh), ("down_proj", sh, h)):
            self._matrix(f"shared_experts.{n}.weight", a, b)

    def forward(self, x, live):
        """x [b, s, hidden], live [b * s] bool Tensor -> (out, stats or None)."""
        from ..ops.dispatch import apply

        cfg = self.cfg
        named = self.leaves()
        leaves = list(named.values())
        if not self.moe:
            return apply(_swiglu, [x] + leaves, name="dsv32_mlp"), None
        names = [n[:-len(".weight")] if n.startswith("shared_experts") else n for n in named]

        def f(xa, lv, *ws):
            b, s, h = xa.shape
            y, stats = _moe(cfg, dict(zip(names, ws)), xa.reshape(b * s, h), lv)
            return y.reshape(b, s, h), stats

        return apply(f, [x, live] + leaves, multi=True, name="dsv32_moe")


class DeepseekV32DecoderLayer(nn.Layer):
    def __init__(self, cfg, rope, index):
        super().__init__()
        self.input_layernorm = _RMSNorm(cfg, cfg.hidden_size)
        self.self_attn = DeepseekV32Attention(cfg, rope)
        self.post_attention_layernorm = _RMSNorm(cfg, cfg.hidden_size)
        self.mlp = DeepseekV32MLP(cfg, moe=index >= cfg.first_k_dense_replace)

    def forward(self, x, cache, pos, live):
        a, sel = self.self_attn(self.input_layernorm(x), cache, pos)
        x = x + a
        m, stats = self.mlp(self.post_attention_layernorm(x), live)
        return x + m, sel, stats


class DeepseekV32Model(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        rope = _rope_tables(cfg)
        self.embed_tokens = _Leaves(cfg)
        self.embed_tokens._matrix("weight", cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [DeepseekV32DecoderLayer(cfg, rope, i) for i in range(cfg.num_hidden_layers)])
        self.norm = _RMSNorm(cfg, cfg.hidden_size)
        self.step_stats = None

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None, lora=None):
        """The engine's call: `caches` one paged view a layer, `pos` [slots]
        for a decode step.  Returns (hidden, caches); the decode step's
        counters are left in `step_stats` for the engine to fetch with the
        step's tokens."""
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        if caches is None or lora is not None or attn_mask is not None:
            raise ValueError("DeepseekV32 runs through the paged serving engine only, without LoRA")
        x = apply(lambda ids, e: e[ids], [input_ids, self.embed_tokens.weight], name="embedding")
        view = caches[0]
        decode = isinstance(view, PagedDecodeView)
        if decode:
            # an idle slot's table row is all scratch (page 0)
            live = apply(lambda t: t[:, 0] > 0, [view.tables], name="dsv32_live")
        else:
            n = input_ids.shape[1]
            live = apply(lambda tl: jnp.arange(n, dtype=jnp.int32) < jnp.reshape(tl, ()),
                         [view.true_len], name="dsv32_live")
        sels, stats = [], []
        for layer, cache in zip(self.layers, caches):
            x, sel, st = layer(x, cache, pos, live)
            sels.append(sel)
            if st is not None:
                stats.append(st)
        self.step_stats = None
        if decode:
            k = self.config.index_topk

            def count(lv, p, *parts):
                sel, moe = parts[:len(sels)], parts[len(sels):]
                m = jnp.stack(moe) if moe else jnp.zeros((1, 4), jnp.int32)
                ctx = jnp.where(lv, p + 1, 0)
                sparse = [jnp.sum(lv, dtype=jnp.int32), jnp.sum(lv & (ctx > k), dtype=jnp.int32),
                          jnp.sum(jnp.where(lv, sel[0], 0)), jnp.sum(ctx)]
                return jnp.concatenate([jnp.sum(m[:, :3], axis=0), jnp.max(m[:, 3:], axis=0),
                                        jnp.stack(sparse).astype(jnp.int32)])

            self.step_stats = apply(count, [live, pos] + sels + stats, name="dsv32_step_stats")
        return self.norm(x), caches


class _Head(_Leaves):
    def __init__(self, cfg):
        super().__init__(cfg)
        self._matrix("weight", cfg.hidden_size, cfg.vocab_size)

    def forward(self, x):
        from ..ops.dispatch import apply

        return apply(lambda a, w: a @ w, [x, self.weight], name="lm_head")


class DeepseekV32ForCausalLM(nn.Layer):
    """The served model.  What the serving engine asks of a model: `backbone`
    (called with `caches=` / `pos=`), `lm_head`, `cache_rows()`, and for a
    model that cannot do all the engine offers, `engine_unsupported`."""

    # the latent rows have no int8 form, no tensor/context-parallel layout and
    # no handoff format; the decode path takes one token a slot (no verify
    # window) and the projections take no LoRA delta
    engine_unsupported = frozenset({"tp", "cp", "kv_quant", "lora", "spec_k", "role"})

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = DeepseekV32Model(config)
        self.lm_head = _Head(config)
        self.eval()

    @property
    def backbone(self):
        return self.model

    def cache_rows(self):
        """A token's rows in each layer's cache: (name, heads, width, dtype)."""
        c = self.config
        return [(LATENT, 1, latent_width(c), c.dtype),
                (INDEX_KEY, 1, c.index_head_dim, c.dtype)]

    def step_stats(self):
        """The last traced decode step's counters, int32[8]: the four of
        `profiler.record_moe_step`, then the four of `record_sparse_attn_step`
        (a Tensor), or None."""
        return self.model.step_stats

    @staticmethod
    def record_step_stats(values):
        from .. import profiler

        profiler.record_moe_step(*(int(v) for v in values[:4]))
        profiler.record_sparse_attn_step(*(int(v) for v in values[4:8]))

    def forward(self, input_ids, labels=None, attn_mask=None):
        raise NotImplementedError(
            "DeepseekV32ForCausalLM is served through ContinuousBatchingEngine; it has no "
            "cache-free forward (benchmarks/reference_deepseek_v32.py is the plain one)")


def _select_topk(score, k, n_blocks, kb):
    """`_select_mask`'s mask of a prefill chunk whose context fills the first
    `n_blocks` (data) key blocks of `kb` (the columns past them are -inf).  On
    the TPU one Pallas kernel (`ops/topk_select.py`) reads only those blocks,
    once, into VMEM and counts there; elsewhere, or for a shape it refuses,
    `_select_mask`'s 32 passes over every column."""
    from ..ops import flash_attention as fa, topk_select as ts

    if (it := fa._FORCE_INTERPRET) or fa._on_tpu():  # the backend and static shapes choose
        reason = ts.refusal(score, kb, it)
        if reason is None:
            fa._log_pallas_call("topk_select")
            return ts.topk_select(score, k, n_blocks, kb, it)
        fa._log_pallas_fallback("topk_select: " + reason, shape=score.shape)
    return _select_mask(score, k)
