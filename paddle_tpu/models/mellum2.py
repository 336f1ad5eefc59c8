"""Mellum2 (JetBrains Mellum2-12B-A2.5B-Instruct) for serving: grouped-query
attention whose layers are SLIDING-WINDOW or FULL by `layer_types`, a rope
table per layer type, and in every layer a dropless expert sum under a softmax
top-k router (no shared expert, bias, groups or scaling).

Config keys are the published ones
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct `config.json`) plus
`dtype` (parameters are CREATED in it).  Pre-norm residual blocks, RMSNorm eps
`rms_norm_eps`, a final norm, an untied head.  Per layer l of type `t =
layer_types[l]`:

- `q = x W_q` (H heads of `head_dim`), `k = x W_k`, `v = x W_v` (`num_key_
  value_heads` each), no bias, no q/k norm (assumed: the config names none);
  rope of type `t` on q and k over the whole head, element i paired with i +
  d/2 (rotate-half) (assumed); scores `q . k * head_dim^-0.5`; key j is
  visible to query i iff `j <= i` and, for `t = sliding_attention`, `i - j <
  sliding_window` (the window counts the query's own position) (assumed);
  softmax in float32; `o = concat W_o`.
- rope, `rope_parameters[t]`: `default` is `inv_freq_i = theta^(-2i/d)`;
  `yarn` is `deepseek_v32.yarn_inv_freq` (the blend of interpolated and
  extrapolated frequencies by the linear ramp between the dimensions that
  `beta_fast` and `beta_slow` give at the original length) with cos and sin
  multiplied by `attention_factor`.  A cut `max_position_embeddings` cuts the
  tables off; it does not rescale them.
- experts: `p = softmax(x W_g)` in float32 over `num_experts`; the
  `num_experts_per_tok` largest (softmax before top-k) (assumed); `w = p_sel /
  sum(p_sel)` (`norm_topk_prob`); `sum_e w_e W_down,e (silu(W_gate,e x) *
  W_up,e x)`.  `_route_softmax` and `_routed_experts` (a decode step through
  the `grouped_experts` kernel) are `deepseek_v32.py`'s, every expert held
  (`expert_offset` 0).
- `intermediate_size` is read by no layer (every `mlp_layer_types` entry is
  `sparse`; another is refused).  Not built: the MTP head the model card
  mentions (no key of the config describes it).

The cache: a K and a V row a token in every layer.  A sliding layer's rows are
WINDOWED: `cache_layers()` says so with a reach of `sliding_window` tokens
beside the rows, and the engine (not this file) keeps those layers' pages in a
second page group whose pages are released behind the window.  Decode walks a
slot's pages (`paged_walk_decode` of `ops/flash_attention.py`), a sliding
layer's walk from the page of its first visible position (`first = pos -
sliding_window + 1`); prefill attends the sequence through the page table a
block of key pages at a time in XLA, a sliding layer's loop from the block of
its first visible key, so its time does not grow with the offset.

The engine contract (`inference/engine.py`): `backbone`, `lm_head`,
`cache_rows()`, `cache_layers()`, `engine_unsupported`, `step_stats` /
`record_step_stats`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import SimpleNamespace

import numpy as np

from .. import nn
from ..tensor import Tensor
from .deepseek_v32 import (_block_rows, _Head, _Leaves, _RMSNorm, _rope, _route_softmax,
                           _routed_experts, yarn_inv_freq)
from .llama import PagedDecodeView, PagedPrefillView, _kv_store

SLIDING, FULL = "sliding_attention", "full_attention"


def _published_rope():
    return {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
               "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000},
    }


@dataclass
class Mellum2Config:
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: list = field(default_factory=list)      # empty: three sliding then one full
    mlp_layer_types: list = field(default_factory=list)  # empty: every layer sparse
    sliding_window: int = 1024
    use_sliding_window: bool = True
    max_window_layers: int = 0
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rope_parameters: dict = field(default_factory=_published_rope)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.layer_types:
            self.layer_types = [FULL if (i + 1) % 4 == 0 else SLIDING for i in range(n)]
        if not self.mlp_layer_types:
            self.mlp_layer_types = ["sparse"] * n
        if len(self.layer_types) != n or len(self.mlp_layer_types) != n:
            raise ValueError("layer_types and mlp_layer_types name every layer")
        if any(t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(f"a layer is {SLIDING} or {FULL}")
        if any(t != "sparse" for t in self.mlp_layer_types):
            raise ValueError("only sparse (expert) feed-forward layers are written")
        if not self.use_sliding_window and SLIDING in self.layer_types:
            raise ValueError("use_sliding_window is off but a layer is sliding")
        if self.attention_bias or self.tie_word_embeddings or self.hidden_act != "silu":
            raise ValueError("written for the published switches: no bias, untied head, silu")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")
        for t in set(self.layer_types):
            kind = self.rope_parameters[t].get("rope_type", "default")
            if kind not in ("default", "yarn"):
                raise ValueError(f"rope_type {kind!r} is not written (default, yarn)")

    # what `deepseek_v32`'s expert sum reads under DeepSeek's names: every
    # expert is held here
    @property
    def n_routed_experts(self):
        return self.num_experts

    @property
    def experts_held(self):
        return self.num_experts

    @property
    def expert_offset(self):
        return 0

    def window(self, layer):
        """The reach of layer `layer`'s attention in tokens, None for a full
        layer."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else None

    @staticmethod
    def tiny(**overrides):
        """Two whole periods (sliding, sliding, sliding, full, twice), a
        window of 16 and a YaRN table that bites (original 64 of 512)."""
        rope = _published_rope()
        rope[FULL].update(original_max_position_embeddings=64, factor=8)
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=16, num_experts=8, num_experts_per_tok=2, rope_parameters=rope,
            max_position_embeddings=512, dtype="float32",
        )
        base.update(overrides)
        return Mellum2Config(**base)


def rope_inv_freq(cfg, layer_type):
    """(inv_freq [head_dim / 2] float64, the factor on cos and sin) of one
    layer type."""
    rp, d = cfg.rope_parameters[layer_type], cfg.head_dim
    if rp.get("rope_type", "default") == "default":
        return 1.0 / (float(rp["rope_theta"]) ** (np.arange(0, d, 2, dtype=np.float64) / d)), 1.0
    # YaRN as published, whatever the context was cut to: the correction is a
    # property of the original length and the factor
    shim = SimpleNamespace(
        qk_rope_head_dim=d, rope_theta=rp["rope_theta"], rope_scaling=rp,
        max_position_embeddings=int(rp["original_max_position_embeddings"] * rp["factor"]))
    return yarn_inv_freq(shim), float(rp.get("attention_factor", 1.0))


def _rope_tables(cfg, layer_type):
    inv, factor = rope_inv_freq(cfg, layer_type)
    f = np.outer(np.arange(cfg.max_position_embeddings, dtype=np.float64), inv)
    return (Tensor((np.cos(f) * factor).astype(np.float32)),
            Tensor((np.sin(f) * factor).astype(np.float32)))


# -- attention on arrays ------------------------------------------------------------

def _project(cfg, w, x, cos, sin):
    """x [n, hidden], cos/sin [n, head_dim / 2] -> q [n, H, d], k, v [n, KV,
    d], q and k turned."""
    n = x.shape[0]
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _rope((x @ w["q_proj.weight"]).reshape(n, H, d), cos[:, None], sin[:, None])
    k = _rope((x @ w["k_proj.weight"]).reshape(n, KV, d), cos[:, None], sin[:, None])
    return q, k, (x @ w["v_proj.weight"]).reshape(n, KV, d)


def first_visible(pos, window):
    """The first key position a query at `pos` sees under `window` (None: 0)."""
    import jax.numpy as jnp

    return jnp.zeros_like(pos) if window is None else jnp.maximum(pos - (window - 1), 0)


def _attend_dense(q, k, v, pos, first, scale):
    """q [S, H, d] against each slot's gathered rows k, v [S, L, KV, d]: keys
    `first <= j <= pos`, softmax in float32.  The plain path, for a machine
    without the page walk."""
    import jax
    import jax.numpy as jnp

    S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(S, KV, H // KV, d)
    lg = jnp.einsum("sgrd,slgd->sgrl", qg, k, preferred_element_type=jnp.float32) * scale
    j = jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    seen = (j <= pos[:, None]) & (j >= first[:, None])
    p = jax.nn.softmax(jnp.where(seen[:, None, None, :], lg, -jnp.inf), axis=-1)
    return jnp.einsum("sgrl,slgd->sgrd", p.astype(v.dtype), v).reshape(S, H, d)


def _attn_decode(cfg, w, x, cos, sin, ak, av, tables, pos, max_len, window):
    """One token a slot: x [S, hidden], pos [S].  Stores the token's K and V
    rows, then walks the slot's pages from the page of its first visible
    position.  Returns (out [S, hidden], K arena, V arena)."""
    import jax.numpy as jnp

    from ..ops import flash_attention as fa

    S = x.shape[0]
    scale = cfg.head_dim ** -0.5
    q, k, v = _project(cfg, w, x, cos, sin)
    ak = _kv_store(ak, k[:, None], tables, pos)
    av = _kv_store(av, v[:, None], tables, pos)
    if fa._on_tpu() or fa._FORCE_INTERPRET:
        fa._log_pallas_call("paged_decode_fused")
        qa = q[:, None].astype(ak.dtype)
        if window is None:
            o = fa._fused_paged_decode(qa, ak, av, tables, pos, max_len, scale, fa._FORCE_INTERPRET)
        else:
            o = fa._fused_paged_decode_window(qa, ak, av, tables, pos, first_visible(pos, window),
                                              max_len, scale, fa._FORCE_INTERPRET)
        o = o[:, 0]
    else:
        o = _attend_dense(q, fa.paged_gather_kv(ak, tables, max_len),
                          fa.paged_gather_kv(av, tables, max_len), pos, first_visible(pos, window), scale)
    return o.reshape(S, -1).astype(x.dtype) @ w["o_proj.weight"], ak, av


def _attend_pages(q, ak, av, table, q_pos, n_ctx, window, scale, key_rows=1024, q_rows=512):
    """Attention of s queries (q [s, H, d] at positions q_pos [s]) over one
    sequence's K and V rows (`av` None: `ak`'s rows are `[k | v]`, 2 d wide),
    read THROUGH its page table a block of whole
    pages at a time (`key_rows` keys, `q_rows` queries to a score tile),
    online softmax in float32.  Blocks from the one that holds the first
    query's first visible key to the one that holds key `n_ctx - 1` are
    visited, no other: under a window the trip count does not grow with the
    offset, and the table's entries outside it are never read.  Returns [s,
    H * d] in q's dtype."""
    import jax.numpy as jnp
    from jax import lax

    s, H, d = q.shape
    KV, ps = ak.shape[1], ak.shape[2]
    pb = _block_rows(table.shape[0], max(1, key_rows // ps))
    kb, qb = pb * ps, _block_rows(s, q_rows)
    qg = jnp.moveaxis(q.reshape(s, KV, H // KV, d), 0, 2)  # [KV, rep, s, d]
    lo = first_visible(q_pos, window)

    def attend_block(j, carry):
        m, l, acc = carry
        pages = lax.dynamic_slice_in_dim(table, j * pb, pb, 0)
        # [pb, KV, ps, d] -> [KV, kb, d]: a block's rows of one head lie together
        k = jnp.moveaxis(ak[pages], 1, 0).reshape(KV, kb, -1)
        if av is None:  # packed rows, [k | v] of a head each
            k, v = k[..., :d], k[..., d:]
        else:
            v = jnp.moveaxis(av[pages], 1, 0).reshape(KV, kb, d)
        at = j * kb + jnp.arange(kb, dtype=jnp.int32)
        mask = (at[None, :] <= q_pos[:, None]) & (at[None, :] >= lo[:, None])
        ms, ls, accs = [], [], []
        for i in range(0, s, qb):
            rows = slice(i, i + qb)
            lg = jnp.einsum("grtd,gld->grtl", qg[:, :, rows], k, preferred_element_type=jnp.float32)
            lg = jnp.where(mask[None, None, rows], lg * scale, -jnp.inf)
            m_new = jnp.maximum(m[:, :, rows], jnp.max(lg, axis=-1))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)  # no key seen yet
            p = jnp.exp(lg - m_safe[..., None])
            fade = jnp.exp(jnp.where(m[:, :, rows] == -jnp.inf, -jnp.inf, m[:, :, rows] - m_safe))
            ms.append(m_new)
            ls.append(l[:, :, rows] * fade + jnp.sum(p, axis=-1))
            accs.append(acc[:, :, rows] * fade[..., None]
                        + jnp.einsum("grtl,gld->grtd", p.astype(v.dtype), v,
                                     preferred_element_type=jnp.float32))
        return jnp.concatenate(ms, 2), jnp.concatenate(ls, 2), jnp.concatenate(accs, 2)

    shape = (KV, H // KV, s)
    _, l, acc = lax.fori_loop(
        lo[0] // kb, (n_ctx + kb - 1) // kb, attend_block,
        (jnp.full(shape, -jnp.inf, jnp.float32), jnp.zeros(shape, jnp.float32),
         jnp.zeros(shape + (d,), jnp.float32)))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return jnp.moveaxis(o, 2, 0).reshape(s, H * d)


def _attn_prefill(cfg, w, x, cos, sin, ak, av, table, start, true_len, window):
    """A chunk of one sequence: x [s, hidden] at positions start .. start + s.
    Stores the chunk's rows, then attends the sequence through the page table
    (`_attend_pages`)."""
    import jax.numpy as jnp

    s = x.shape[0]
    q, k, v = _project(cfg, w, x, cos, sin)
    st = jnp.reshape(start, (1,))
    ak = _kv_store(ak, k[None], table[None], st, true_len)
    av = _kv_store(av, v[None], table[None], st, true_len)
    q_pos = st[0] + jnp.arange(s, dtype=jnp.int32)
    o = _attend_pages(q.astype(ak.dtype), ak, av, table, q_pos, st[0] + jnp.reshape(true_len, ()),
                      window, cfg.head_dim ** -0.5)
    return o.astype(x.dtype) @ w["o_proj.weight"], ak, av


def _moe(cfg, w, x, live):
    """x [T, hidden] -> (the routed sum [T, hidden], the four counters of
    `_routed_experts`)."""
    experts, wts = _route_softmax(cfg, x, w["gate.weight"])
    y, stats = _routed_experts(cfg, x, experts, wts, live, w["experts.gate_proj"],
                               w["experts.up_proj"], w["experts.down_proj"])
    return y.astype(x.dtype), stats


# -- the layers, as the program's modules -------------------------------------------

def _is_decode(cache):
    if isinstance(cache, PagedDecodeView):
        return True
    if isinstance(cache, PagedPrefillView):
        return False
    raise TypeError(f"Mellum2 is served through the paged engine; got {type(cache).__name__}")


class Mellum2Attention(_Leaves):
    def __init__(self, cfg, rope, window):
        super().__init__(cfg)
        h, H, KV, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        self._matrix("q_proj.weight", h, H * d)
        self._matrix("k_proj.weight", h, KV * d)
        self._matrix("v_proj.weight", h, KV * d)
        self._matrix("o_proj.weight", H * d, h)
        self.rope_cos, self.rope_sin = rope
        self.window = window

    def forward(self, x, cache, pos=None):
        """x [b, s, hidden] Tensor (already normed); `cache` a paged view over
        this layer's K and V arenas (a sliding layer's: the window group's
        tables)."""
        from ..ops.dispatch import apply

        cfg, window = self.cfg, self.window
        named = self.leaves()
        names, leaves = list(named), list(named.values())
        k_t, v_t = cache.arena.k, cache.arena.v
        max_len = cache.max_len
        if _is_decode(cache):
            if x.shape[1] != 1:
                raise ValueError("the decode path takes one token a slot")

            def f(xa, cos, sin, ak, av, tables, p, *ws):
                out, ak, av = _attn_decode(cfg, dict(zip(names, ws)), xa[:, 0], cos[p], sin[p],
                                           ak, av, tables, p, max_len, window)
                return out[:, None], ak, av

            out, ak, av = apply(f, [x, self.rope_cos, self.rope_sin, k_t, v_t, cache.tables, pos] + leaves,
                                multi=True, name="mellum2_walk_decode")
        else:
            if x.shape[0] != 1:
                raise ValueError("the prefill path takes one sequence")
            has_start = cache.start is not None

            def f(xa, cos, sin, ak, av, table, tl, *rest):
                import jax.numpy as jnp
                st = rest[0] if has_start else jnp.zeros((1,), jnp.int32)
                ws = rest[1:] if has_start else rest
                at = st[0] + jnp.arange(xa.shape[1], dtype=jnp.int32)
                out, ak, av = _attn_prefill(cfg, dict(zip(names, ws)), xa[0], cos[at], sin[at],
                                            ak, av, table, st, tl, window)
                return out[None], ak, av

            ins = [x, self.rope_cos, self.rope_sin, k_t, v_t, cache.table, cache.true_len]
            out, ak, av = apply(f, ins + ([cache.start] if has_start else []) + leaves, multi=True,
                                name="mellum2_prefill")
        k_t._data, v_t._data = ak._data, av._data
        return out


class Mellum2MoE(_Leaves):
    def __init__(self, cfg):
        super().__init__(cfg)
        h, E, im = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
        self._matrix("gate.weight", h, E)
        self._matrix("experts.gate_proj", E, h, im)
        self._matrix("experts.up_proj", E, h, im)
        self._matrix("experts.down_proj", E, im, h)

    def forward(self, x, live):
        """x [b, s, hidden], live [b * s] bool Tensor -> (out, stats)."""
        from ..ops.dispatch import apply

        cfg = self.cfg
        named = self.leaves()
        names, leaves = list(named), list(named.values())

        def f(xa, lv, *ws):
            b, s, h = xa.shape
            y, stats = _moe(cfg, dict(zip(names, ws)), xa.reshape(b * s, h), lv)
            return y.reshape(b, s, h), stats

        return apply(f, [x, live] + leaves, multi=True, name="mellum2_moe")


class Mellum2DecoderLayer(nn.Layer):
    def __init__(self, cfg, rope, index):
        super().__init__()
        self.input_layernorm = _RMSNorm(cfg, cfg.hidden_size)
        self.self_attn = Mellum2Attention(cfg, rope[cfg.layer_types[index]], cfg.window(index))
        self.post_attention_layernorm = _RMSNorm(cfg, cfg.hidden_size)
        self.mlp = Mellum2MoE(cfg)

    def forward(self, x, cache, pos, live):
        x = x + self.self_attn(self.input_layernorm(x), cache, pos)
        m, stats = self.mlp(self.post_attention_layernorm(x), live)
        return x + m, stats


class Mellum2Model(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        rope = {t: _rope_tables(cfg, t) for t in sorted(set(cfg.layer_types))}
        self.embed_tokens = _Leaves(cfg)
        self.embed_tokens._matrix("weight", cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [Mellum2DecoderLayer(cfg, rope, i) for i in range(cfg.num_hidden_layers)])
        self.norm = _RMSNorm(cfg, cfg.hidden_size)
        self.step_stats = None

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None, lora=None):
        """The engine's call: `caches` one paged view a layer, `pos` [slots]
        for a decode step.  Returns (hidden, caches); the decode step's
        counters are left in `step_stats`."""
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        if caches is None or lora is not None or attn_mask is not None:
            raise ValueError("Mellum2 runs through the paged serving engine only, without LoRA")
        cfg = self.config
        x = apply(lambda ids, e: e[ids], [input_ids, self.embed_tokens.weight], name="embedding")
        view = caches[0]
        decode = _is_decode(view)
        if decode:
            live = view.live
        else:
            n = input_ids.shape[1]
            live = apply(lambda tl: jnp.arange(n, dtype=jnp.int32) < jnp.reshape(tl, ()),
                         [view.true_len], name="mellum2_live")
        stats = []
        for layer, cache in zip(self.layers, caches):
            x, st = layer(x, cache, pos, live)
            stats.append(st)
        self.step_stats = None
        if decode:
            sliding = sum(t == SLIDING for t in cfg.layer_types)
            full, W = len(cfg.layer_types) - sliding, cfg.sliding_window

            def count(lv, p, *moe):
                m = jnp.stack(moe)
                ctx = jnp.where(lv, p + 1, 0)
                reach = [full * jnp.sum(ctx), sliding * jnp.sum(jnp.minimum(ctx, W)),
                         jnp.sum(lv, dtype=jnp.int32)]
                return jnp.concatenate([jnp.sum(m[:, :3], axis=0), jnp.max(m[:, 3:], axis=0),
                                        jnp.stack(reach).astype(jnp.int32)])

            self.step_stats = apply(count, [live, pos] + stats, name="mellum2_step_stats")
        return self.norm(x), caches


class Mellum2ForCausalLM(nn.Layer):
    """The served model.  What the serving engine asks of a model: `backbone`
    (called with `caches=` / `pos=`), `lm_head`, `cache_rows()`, for a model
    whose layers differ `cache_layers()`, and for one that cannot do all the
    engine offers, `engine_unsupported`."""

    # K and V rows, but no int8 form here, no tp/cp layout, no LoRA delta, one
    # token a slot (a verify window over a windowed walk is not written), no
    # handoff format for two page groups; and no prefix cache: a hit resumes
    # from pages, and a sliding layer's pages behind the window are gone
    engine_unsupported = frozenset({"tp", "cp", "kv_quant", "lora", "spec_k", "role", "prefix_cache"})

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = Mellum2Model(config)
        self.lm_head = _Head(config)
        self.eval()

    @property
    def backbone(self):
        return self.model

    def cache_rows(self):
        """A token's rows in a layer: (name, heads, width, dtype)."""
        c = self.config
        return [("k", c.num_key_value_heads, c.head_dim, c.dtype),
                ("v", c.num_key_value_heads, c.head_dim, c.dtype)]

    def cache_layers(self):
        """Each layer's cache, (rows, state, reach): K and V rows, no state;
        a sliding layer's rows are windowed, `reach` tokens of them visible
        (the query's own among them), a full layer's reach is None."""
        c = self.config
        return [(self.cache_rows(), [], c.window(i)) for i in range(c.num_hidden_layers)]

    def step_stats(self):
        """The last traced decode step's counters, int32[7]: the four of
        `profiler.record_moe_step`, the K/V rows in reach over the full and
        over the sliding layers, the live slots (a Tensor), or None."""
        return self.model.step_stats

    @staticmethod
    def record_step_stats(values):
        from .. import profiler

        profiler.record_moe_step(*(int(v) for v in values[:4]))
        profiler.record_window_rows(int(values[4]), int(values[5]), int(values[6]))

    def forward(self, input_ids, labels=None, attn_mask=None):
        raise NotImplementedError(
            "Mellum2ForCausalLM is served through ContinuousBatchingEngine; it has no "
            "cache-free forward (benchmarks/reference_mellum2.py is the plain one)")
