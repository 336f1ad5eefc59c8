"""LFM2-8B-A1B (LiquidAI `lfm2_moe`) for serving: gated short-convolution
layers that hold a FIXED STATE PER SLOT (the last `conv_L_cache - 1` rows of
the convolution's input) and no rows per token, grouped-query attention layers
with a per-head RMSNorm on q and k, and in every layer after the first
`num_dense_layers` a dropless expert sum under a sigmoid router whose learned
bias chooses the experts and does not weigh them.

Config keys are the published ones (huggingface.co/LiquidAI/LFM2-8B-A1B
`config.json`) plus `dtype` (parameters are CREATED in it).  Layer l is a
convolution or an attention layer by `layer_types[l]` (`conv`,
`full_attention`); a cut in depth cuts the list with it.  Every reading
marked (assumed) is one the published config names and does not spell out;
program and reference (`benchmarks/reference_lfm2.py`) read alike.

- Block: `h = x + op(rms(x))`, `out = h + ff(rms(h))`, RMSNorm eps `norm_eps`
  (`operator_norm`, `ffn_norm`); `op` is the layer's kind, `ff` a dense SwiGLU
  `W2 (silu(W1 z) * W3 z)` of width `intermediate_size` for `l <
  num_dense_layers`, else the expert layer.
- Convolution (`conv_L_cache` L, no bias; `conv_bias` true is refused): `[B
  | C | u] = z W_in` (hidden -> 3 hidden, split in that order as published),
  `v = B * u`, `w_t = sum_{j<L} k_j * v_{t-L+1+j}` a channel (causal and
  depthwise, zeros before the first token), `y = (C * w) W_out`.  No
  activation.  The taps are held as `conv.weight` [L, hidden] (the published
  `Conv1d` weight [hidden, 1, L], transposed).  The state of a slot is `v` of
  its last L - 1 positions, in the served dtype (`B * u` is rounded to it
  before the convolution, as the published bf16 model rounds it).
- Attention: `q = rms_q(z W_q)`, `k = rms_k(z W_k)` (per head over
  `hidden / heads` dims, one learned weight a layer each), rope on both
  (rotate-half, element i paired with i + d/2, `rope_theta`) (assumed),
  `v = z W_v`; H query heads on KV heads; scores `q . k * d^-0.5` over every
  `s <= t`, softmax in float32; `o W_o` (`out_proj`).
- Experts: `s = sigmoid(z W_g)` in float32 over `num_experts`; the
  `num_experts_per_tok` largest of `s + expert_bias` are chosen; their
  weights are the chosen `s` WITHOUT the bias, over their sum plus 1e-6
  (`norm_topk_prob`), times `routed_scaling_factor`; `sum_e w_e W_down,e
  (silu(W_gate,e z) * W_up,e z)` over experts of width
  `moe_intermediate_size`, no shared expert.  `deepseek_v32._route` at one
  group with `topk_norm_eps` 1e-6, and `_routed_experts` (a decode step
  through the `grouped_experts` kernel), every expert held.
- Output: a final RMSNorm (`embedding_norm`), then the head TIED to
  `embed_tokens` (assumed: the row's config omits `tie_word_embeddings`, and
  only a tied head gives the model card's 8.3B parameters).

The cache.  A convolution layer declares a state and no rows: `conv_tail`
[L - 1, hidden] a slot.  Decode sums the tail and the new row's `v` with the
taps and writes the tail back for live slots only; a prefill chunk convolves
`[tail | chunk]`, a fresh prefill from a zero tail, a later chunk from what the
chunk before left, and leaves the last L - 1 real rows.  An attention layer
declares rows and no state: ONE row kind `kv` a KV head, `[k_h | v_h]` (2 x
64 = 128 wide: whole lanes, which the engine asks of a row on the TPU; XLA
would copy an arena of 64-wide rows around every store and call).  Decode stores the
packed row and walks the slot's pages with the single-operand walk
(`paged_walk_decode`, `arena_v=None`): the query padded to `[q | 0]` scores
`[q | 0] . [k | v] = q . k` (scale `d^-0.5`, not the row's width), and of the
output `p . [k | v]` the second d columns are the attention's.  Prefill
attends the sequence through the page table a block of pages at a time in
XLA (`mellum2._attend_pages`, K and V from the halves of the packed rows).

The engine contract (`inference/engine.py`): `backbone`, `lm_head`,
`cache_rows()`, `cache_layers()`, `engine_unsupported`, `step_stats` /
`record_step_stats`, and `record_prefill_stats` for the state layers'
prefill counters (`profiler.short_conv_summary()`).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .. import nn
from ..tensor import Tensor
from .deepseek_v32 import _Leaves, _rms, _RMSNorm, _rope, _route, _routed_experts, _swiglu
from .llama import PagedDecodeView, PagedPrefillView, _kv_store
from .mellum2 import _attend_dense, _attend_pages

CONV, ATTN = "conv", "full_attention"
CONV_TAIL, KV = "conv_tail", "kv"

PUBLISHED_LAYER_TYPES = [
    CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV,
    CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV, CONV, ATTN, CONV, CONV,
]


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: list = field(default_factory=lambda: list(PUBLISHED_LAYER_TYPES))
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every layer")
        if any(t not in (CONV, ATTN) for t in self.layer_types):
            raise ValueError(f"a layer is {CONV} or {ATTN}")
        if self.conv_bias:
            raise ValueError("conv_bias: the convolution is written without a bias")
        if not self.use_expert_bias:
            raise ValueError("use_expert_bias: only the router with its learned bias is written")
        if not self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings: only the head tied to the embedding is written")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache: a convolution of one tap keeps no state")
        if self.hidden_size % self.num_attention_heads or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide hidden_size, and KV heads the heads")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers counts leading layers")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    # what `deepseek_v32`'s router and expert layer read under DeepSeek's
    # names: one group, every expert held, the published 1e-6 in the normaliser
    @property
    def n_routed_experts(self):
        return self.num_experts

    n_group = topk_group = 1
    topk_norm_eps = 1e-6

    @property
    def experts_held(self):
        return self.num_experts

    @property
    def expert_offset(self):
        return 0

    @property
    def rms_norm_eps(self):  # the name `deepseek_v32._RMSNorm` reads
        return self.norm_eps

    def is_moe(self, layer):
        return layer >= self.num_dense_layers

    @staticmethod
    def tiny(**overrides):
        """Published layers 1-9 in kind (conv dense, then attention and three
        convolutions, twice), head size 16 (packed rows 32 wide)."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=9, layer_types=PUBLISHED_LAYER_TYPES[1:10], num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8, num_experts_per_tok=4,
            max_position_embeddings=256, dtype="float32",
        )
        base.update(overrides)
        return Lfm2MoeConfig(**base)


def _rope_tables(cfg):
    d = cfg.head_dim
    inv = 1.0 / (float(cfg.rope_theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    f = np.outer(np.arange(cfg.max_position_embeddings, dtype=np.float64), inv)
    return Tensor(np.cos(f).astype(np.float32)), Tensor(np.sin(f).astype(np.float32))


# -- the short convolution on arrays --------------------------------------------------

def _conv_gates(w, x):
    """x [n, hidden] (normed) -> (C, v = B * u), each [n, hidden]."""
    import jax.numpy as jnp

    b, c, u = jnp.split(x @ w["in_proj.weight"], 3, axis=-1)
    return c, b * u


def _conv_out(w, c, rows):
    """rows [n, L, hidden] (each position's own `v` last) -> the layer's
    output [n, hidden]: the taps' sum in float32, gated by C, W_out."""
    import jax.numpy as jnp

    taps = w["conv.weight"].astype(jnp.float32)
    conv = jnp.sum(rows.astype(jnp.float32) * taps[None], axis=1)
    return (c * conv.astype(c.dtype)) @ w["out_proj.weight"]


def _conv_decode(w, x, tail, live):
    """One token a slot: x [S, hidden], tail [S, L - 1, hidden], live [S]
    bool.  Returns (out [S, hidden], tail); a slot that is not live keeps
    its tail."""
    import jax.numpy as jnp

    c, v = _conv_gates(w, x)
    rows = jnp.concatenate([tail, v[:, None].astype(tail.dtype)], axis=1)
    return _conv_out(w, c, rows), jnp.where(live[:, None, None], rows[:, 1:], tail)


def _conv_prefill(cfg, w, x, tail, slot, true_len, fresh):
    """A chunk of one sequence: x [s, hidden], seated in `slot` (int32
    scalar).  `fresh`: the slot's tail starts from zeros; else from what the
    chunk before left.  Returns (out [s, hidden], tail) with the slot's tail
    the last L - 1 rows of `v` before `true_len`."""
    import jax.numpy as jnp
    from jax import lax

    s, L = x.shape[0], cfg.conv_L_cache
    slot = jnp.reshape(slot, ())
    t0 = lax.dynamic_index_in_dim(tail, slot, 0, False)
    if fresh:
        t0 = jnp.zeros_like(t0)
    c, v = _conv_gates(w, x)
    rows = jnp.concatenate([t0, v.astype(tail.dtype)], axis=0)  # [L - 1 + s, hidden]
    out = _conv_out(w, c, jnp.stack([rows[j:j + s] for j in range(L)], axis=1))
    t1 = lax.dynamic_slice_in_dim(rows, jnp.reshape(true_len, ()), L - 1, 0)
    return out, lax.dynamic_update_index_in_dim(tail, t1, slot, 0)


# -- attention on arrays --------------------------------------------------------------

def _project(cfg, w, x, cos, sin):
    """x [n, hidden], cos/sin [n, d / 2] -> q [n, H, d], k, v [n, KV, d]:
    q and k normed a head, then turned."""
    n = x.shape[0]
    H, KV, d, eps = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.norm_eps
    turn = lambda a: _rope(a, cos[:, None], sin[:, None])
    q = turn(_rms((x @ w["q_proj.weight"]).reshape(n, H, d), w["q_layernorm.weight"], eps))
    k = turn(_rms((x @ w["k_proj.weight"]).reshape(n, KV, d), w["k_layernorm.weight"], eps))
    return q, k, (x @ w["v_proj.weight"]).reshape(n, KV, d)


def _attn_decode(cfg, w, x, cos, sin, kv, tables, pos, max_len):
    """One token a slot: x [S, hidden], pos [S].  Stores the token's packed
    row, then walks the slot's pages (`arena_v=None`: a page is copied once,
    both dots read it).  Returns (out [S, hidden], the arena)."""
    import jax.numpy as jnp

    from ..ops import flash_attention as fa

    S, d = x.shape[0], cfg.head_dim
    scale = d ** -0.5
    q, k, v = _project(cfg, w, x, cos, sin)
    kv = _kv_store(kv, jnp.concatenate([k, v], -1)[:, None], tables, pos)
    if fa._on_tpu() or fa._FORCE_INTERPRET:
        fa._log_pallas_call("paged_decode_fused")
        qa = jnp.concatenate([q, jnp.zeros_like(q)], -1)[:, None].astype(kv.dtype)
        o = fa._fused_paged_decode(qa, kv, None, tables, pos, max_len, scale, fa._FORCE_INTERPRET)[:, 0, :, d:]
    else:
        ctx = fa.paged_gather_kv(kv, tables, max_len)
        o = _attend_dense(q, ctx[..., :d], ctx[..., d:], pos, jnp.zeros_like(pos), scale)
    return o.reshape(S, -1).astype(x.dtype) @ w["out_proj.weight"], kv


def _attn_prefill(cfg, w, x, cos, sin, kv, table, start, true_len):
    """A chunk of one sequence: x [s, hidden] at positions start .. start + s.
    Stores the chunk's packed rows, then attends the sequence through the
    page table (`_attend_pages` over the packed arena)."""
    import jax.numpy as jnp

    s = x.shape[0]
    q, k, v = _project(cfg, w, x, cos, sin)
    st = jnp.reshape(start, (1,))
    kv = _kv_store(kv, jnp.concatenate([k, v], -1)[None], table[None], st, true_len)
    q_pos = st[0] + jnp.arange(s, dtype=jnp.int32)
    o = _attend_pages(q.astype(kv.dtype), kv, None, table, q_pos, st[0] + jnp.reshape(true_len, ()),
                      None, cfg.head_dim ** -0.5)
    return o.astype(x.dtype) @ w["out_proj.weight"], kv


def _moe(cfg, w, x, live):
    """x [T, hidden] -> (the routed sum [T, hidden], the four counters of
    `_routed_experts`)."""
    experts, wts = _route(cfg, x, w["gate.weight"], w["expert_bias"])
    y, stats = _routed_experts(cfg, x, experts, wts, live, w["experts.w1"], w["experts.w3"], w["experts.w2"])
    return y.astype(x.dtype), stats


# -- the layers, as the program's modules ---------------------------------------------

def _is_decode(cache):
    if isinstance(cache, PagedDecodeView):
        return True
    if isinstance(cache, PagedPrefillView):
        return False
    raise TypeError(f"Lfm2Moe is served through the paged engine; got {type(cache).__name__}")


def _split(layer):
    named = layer.leaves()
    return list(named), list(named.values())


class Lfm2ShortConv(_Leaves):
    def __init__(self, cfg):
        super().__init__(cfg)
        h = cfg.hidden_size
        self._matrix("in_proj.weight", h, 3 * h)
        self._matrix("conv.weight", cfg.conv_L_cache, h)
        self._matrix("out_proj.weight", h, h)

    def forward(self, x, cache, pos=None):
        """x [b, s, hidden] Tensor (already normed); `cache` a paged view whose
        arena holds this layer's tail per slot and no rows."""
        import jax

        from ..ops.dispatch import apply

        cfg = self.cfg
        names, leaves = _split(self)
        tail_t = getattr(cache.arena, CONV_TAIL)
        if _is_decode(cache):
            if x.shape[1] != 1:
                raise ValueError("the convolution's decode step takes one token a slot")

            def f(xa, tl, lv, *ws):
                with jax.named_scope("short_conv"):
                    out, tl = _conv_decode(dict(zip(names, ws)), xa[:, 0], tl, lv)
                return out[:, None], tl

            out, tl = apply(f, [x, tail_t, cache.live] + leaves, multi=True, name="short_conv_decode")
        else:
            if x.shape[0] != 1:
                raise ValueError("the convolution's prefill takes one sequence")
            fresh = cache.start is None

            def f(xa, tl, sl, n, *ws):
                with jax.named_scope("short_conv"):
                    out, tl = _conv_prefill(cfg, dict(zip(names, ws)), xa[0], tl, sl, n, fresh)
                return out[None], tl

            out, tl = apply(f, [x, tail_t, cache.slot, cache.true_len] + leaves, multi=True,
                            name="short_conv_prefill")
        tail_t._data = tl._data
        return out


class Lfm2MoeAttention(_Leaves):
    def __init__(self, cfg, rope):
        super().__init__(cfg)
        h, H, KV, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        self._matrix("q_proj.weight", h, H * d)
        self._matrix("k_proj.weight", h, KV * d)
        self._matrix("v_proj.weight", h, KV * d)
        self._norm("q_layernorm.weight", d)
        self._norm("k_layernorm.weight", d)
        self._matrix("out_proj.weight", H * d, h)
        self.rope_cos, self.rope_sin = rope

    def forward(self, x, cache, pos=None):
        """x [b, s, hidden] Tensor (already normed); `cache` a paged view over
        this layer's packed arena."""
        from ..ops.dispatch import apply

        cfg = self.cfg
        names, leaves = _split(self)
        kv_t = getattr(cache.arena, KV)
        max_len = cache.max_len
        if _is_decode(cache):
            if x.shape[1] != 1:
                raise ValueError("the decode path takes one token a slot")

            def f(xa, cos, sin, kv, tables, p, *ws):
                out, kv = _attn_decode(cfg, dict(zip(names, ws)), xa[:, 0], cos[p], sin[p], kv, tables, p,
                                       max_len)
                return out[:, None], kv

            out, kv = apply(f, [x, self.rope_cos, self.rope_sin, kv_t, cache.tables, pos] + leaves,
                            multi=True, name="lfm2_walk_decode")
        else:
            if x.shape[0] != 1:
                raise ValueError("the prefill path takes one sequence")
            has_start = cache.start is not None

            def f(xa, cos, sin, kv, table, tl, *rest):
                import jax.numpy as jnp
                st = rest[0] if has_start else jnp.zeros((1,), jnp.int32)
                ws = rest[1:] if has_start else rest
                at = st[0] + jnp.arange(xa.shape[1], dtype=jnp.int32)
                out, kv = _attn_prefill(cfg, dict(zip(names, ws)), xa[0], cos[at], sin[at], kv, table, st, tl)
                return out[None], kv

            ins = [x, self.rope_cos, self.rope_sin, kv_t, cache.table, cache.true_len]
            out, kv = apply(f, ins + ([cache.start] if has_start else []) + leaves, multi=True,
                            name="lfm2_prefill")
        kv_t._data = kv._data
        return out


class Lfm2MoeFeedForward(_Leaves):
    """The dense SwiGLU (the leading layers) or the expert layer."""

    def __init__(self, cfg, moe):
        super().__init__(cfg)
        self.moe = moe
        h = cfg.hidden_size
        if not moe:
            i = cfg.intermediate_size
            for n, a, b in (("w1", h, i), ("w3", h, i), ("w2", i, h)):
                self._matrix(n + ".weight", a, b)
            return
        E, im = cfg.num_experts, cfg.moe_intermediate_size
        self._matrix("gate.weight", h, E)
        self._norm("expert_bias", E, 0.0)
        self._matrix("experts.w1", E, h, im)
        self._matrix("experts.w3", E, h, im)
        self._matrix("experts.w2", E, im, h)

    def forward(self, x, live):
        """x [b, s, hidden], live [b * s] bool Tensor -> (out, stats or None)."""
        from ..ops.dispatch import apply

        cfg = self.cfg
        names, leaves = _split(self)
        if not self.moe:
            return apply(_swiglu, [x] + leaves, name="lfm2_mlp"), None

        def f(xa, lv, *ws):
            b, s, h = xa.shape
            y, stats = _moe(cfg, dict(zip(names, ws)), xa.reshape(b * s, h), lv)
            return y.reshape(b, s, h), stats

        return apply(f, [x, live] + leaves, multi=True, name="lfm2_moe")


class Lfm2MoeDecoderLayer(nn.Layer):
    def __init__(self, cfg, rope, index):
        super().__init__()
        self.kind = cfg.layer_types[index]
        self.operator_norm = _RMSNorm(cfg, cfg.hidden_size)
        if self.kind == ATTN:
            self.self_attn = Lfm2MoeAttention(cfg, rope)
        else:
            self.conv = Lfm2ShortConv(cfg)
        self.ffn_norm = _RMSNorm(cfg, cfg.hidden_size)
        self.feed_forward = Lfm2MoeFeedForward(cfg, moe=cfg.is_moe(index))

    def forward(self, x, cache, pos, live):
        op = self.self_attn if self.kind == ATTN else self.conv
        x = x + op(self.operator_norm(x), cache, pos)
        m, stats = self.feed_forward(self.ffn_norm(x), live)
        return x + m, stats


class Lfm2MoeModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        rope = _rope_tables(cfg) if ATTN in cfg.layer_types else None
        self.embed_tokens = _Leaves(cfg)
        self.embed_tokens._matrix("weight", cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([Lfm2MoeDecoderLayer(cfg, rope, i) for i in range(cfg.num_hidden_layers)])
        self.embedding_norm = _RMSNorm(cfg, cfg.hidden_size)
        self.step_stats = None

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None, lora=None):
        """The engine's call: `caches` one paged view a layer, `pos` [slots]
        for a decode step.  Returns (hidden, caches); the decode step's
        counters are left in `step_stats`."""
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        if caches is None or lora is not None or attn_mask is not None:
            raise ValueError("Lfm2Moe runs through the paged serving engine only, without LoRA")
        cfg = self.config
        x = apply(lambda ids, e: e[ids], [input_ids, self.embed_tokens.weight], name="embedding")
        view = caches[0]
        decode = _is_decode(view)
        if decode:
            live = view.live
        else:
            n = input_ids.shape[1]
            live = apply(lambda tl: jnp.arange(n, dtype=jnp.int32) < jnp.reshape(tl, ()),
                         [view.true_len], name="lfm2_live")
        stats = []
        for layer, cache in zip(self.layers, caches):
            x, st = layer(x, cache, pos, live)
            if st is not None:
                stats.append(st)
        self.step_stats = None
        if decode:
            attn = cfg.layer_types.count(ATTN)

            def count(lv, p, *moe):
                m = jnp.stack(moe) if moe else jnp.zeros((1, 4), jnp.int32)
                rows = attn * jnp.sum(jnp.where(lv, p + 1, 0))
                return jnp.concatenate([jnp.sum(m[:, :3], axis=0), jnp.max(m[:, 3:], axis=0),
                                        jnp.stack([jnp.sum(lv, dtype=jnp.int32), rows]).astype(jnp.int32)])

            self.step_stats = apply(count, [live, pos] + stats, name="lfm2_step_stats")
        return self.embedding_norm(x), caches


class _TiedHead(nn.Layer):
    """The head that reads the embedding's rows: logits `x E^T`."""

    def __init__(self, embed):
        super().__init__()
        self._embed = (embed,)  # a tuple: not a sublayer, so the leaf is named once

    def forward(self, x):
        from ..ops.dispatch import apply

        return apply(lambda a, e: a @ e.T, [x, self._embed[0].weight], name="lm_head")


class Lfm2MoeForCausalLM(nn.Layer):
    """The served model.  What the serving engine asks of a model: `backbone`
    (called with `caches=` / `pos=`), `lm_head`, `cache_rows()`,
    `cache_layers()`, and for one that cannot do all the engine offers,
    `engine_unsupported`."""

    # as Ling-3 and Kimi Linear: no int8 form, no tp/cp layout, no LoRA delta,
    # one token a slot (a verify window would need the tails rolled back), no
    # handoff format for a slot's state; and no prefix cache or session: a hit
    # resumes from pages alone, and 7 of 9 layers keep their past in a tail
    # per slot that no page holds
    engine_unsupported = frozenset({"tp", "cp", "kv_quant", "lora", "spec_k", "role", "prefix_cache"})

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)
        self.lm_head = _TiedHead(self.model.embed_tokens)
        self.eval()

    @property
    def backbone(self):
        return self.model

    def cache_rows(self):
        """A token's rows in an attention layer: (name, heads, width, dtype),
        one packed `[k | v]` row a KV head."""
        c = self.config
        return [(KV, c.num_key_value_heads, 2 * c.head_dim, c.dtype)]

    def cache_state(self):
        """A slot's state in a convolution layer: (name, shape, dtype)."""
        c = self.config
        return [(CONV_TAIL, (c.conv_L_cache - 1, c.hidden_size), c.dtype)]

    def cache_layers(self):
        """Each layer's cache, (rows, state): an attention layer has rows and
        no state, a convolution layer state and no rows."""
        c = self.config
        return [(self.cache_rows(), []) if t == ATTN else ([], self.cache_state()) for t in c.layer_types]

    def tail_bytes_per_slot(self):
        """A slot's tails over every convolution layer, in bytes."""
        from ..framework import core as _fcore

        c = self.config
        (_, shape, dt), = self.cache_state()
        one = int(np.prod(shape)) * np.dtype(_fcore.to_jax_dtype(dt)).itemsize
        return one * c.layer_types.count(CONV)

    def step_stats(self):
        """The last traced decode step's counters, int32[6]: the four of
        `profiler.record_moe_step`, the live slots, and the K/V rows in reach
        of the live slots summed over the attention layers (a Tensor), or
        None."""
        return self.model.step_stats

    def record_step_stats(self, values):
        from .. import profiler

        live = int(values[4])
        profiler.record_moe_step(*(int(v) for v in values[:4]))
        profiler.record_short_conv_step(live, live * self.tail_bytes_per_slot())
        profiler.record_kv_walk_step(int(values[5]), live)

    @staticmethod
    def record_prefill_stats(rows, resumed):
        from .. import profiler

        profiler.record_short_conv_prefill(rows, resumed)

    def forward(self, input_ids, labels=None, attn_mask=None):
        raise NotImplementedError(
            "Lfm2MoeForCausalLM is served through ContinuousBatchingEngine; it has no "
            "cache-free forward (benchmarks/reference_lfm2.py is the plain one)")
