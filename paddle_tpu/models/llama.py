"""Llama family (benchmark configs 4: Llama-2-7B TP=8 — BASELINE.json).

Reference capability: PaddleNLP's LlamaForCausalLM with fleet TP wiring
(ColumnParallelLinear/RowParallelLinear fused paths).  TPU-native build:
- attention → Pallas flash kernel (ops/flash_attention.py), GQA supported
- rotary embeddings precomputed as state, applied in fp32
- TP via mp-sharded parallel layers (degrade to plain layers at mp=1)
- sequence parallel via sharding constraints, recompute via jax.checkpoint
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from .. import nn, ops
from ..distributed import mesh as _mesh
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..nn import functional as F
from ._utils import sequence_ce
from ..tensor import Tensor


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tensor_parallel_degree: int = 1
    sequence_parallel: bool = False
    # long-context parallelism over the 'sep' mesh axis (SURVEY.md §5.7):
    # sep_degree routes attention through Ulysses (all-to-all seq<->head),
    # context_parallel_degree through ring attention (ppermute KV rotation).
    sep_degree: int = 1
    context_parallel_degree: int = 1
    use_recompute: bool = False
    tie_word_embeddings: bool = False

    @staticmethod
    def llama2_7b(**overrides):
        return LlamaConfig(**overrides)

    @staticmethod
    def tiny(**overrides):
        base = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=4,
            max_position_embeddings=256,
        )
        base.update(overrides)
        return LlamaConfig(**base)


def _use_tp(config):
    return config.tensor_parallel_degree > 1 or _mesh.axis_size("mp") > 1


def _rope_cache(config):
    """cos/sin tables duplicated to full head_dim (rotate-half convention —
    no interleave/stack temps on the hot path; HBM-friendly)."""
    dim = config.hidden_size // config.num_attention_heads
    inv_freq = 1.0 / (
        config.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    )
    t = np.arange(config.max_position_embeddings, dtype=np.float64)
    freqs = np.outer(t, inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)  # [max_pos, dim]
    return (
        Tensor(np.cos(emb).astype(np.float32)),
        Tensor(np.sin(emb).astype(np.float32)),
    )


def apply_rotary_pos_emb(q, k, cos, sin, position_offset=0):
    """q,k: [b, s, h, d]; cos/sin: [max_pos, d] state tensors (rotate-half).
    position_offset may be a python int, a scalar int Tensor (the compiled
    decode step passes the position as data so one executable serves every
    token), or a [b] int Tensor of PER-ROW offsets (the continuous-batching
    engine's slots: every slot sits at its own position, still one
    executable)."""
    import jax.numpy as jnp
    from jax import lax

    from ..ops.dispatch import apply

    s = q.shape[1]
    dyn = isinstance(position_offset, Tensor)
    per_row = dyn and len(position_offset.shape) == 1

    def f(qa, ka, c, si, *off_in):
        if off_in and per_row:
            # per-slot offsets: gather each row's cos/sin window (jax gather
            # clamps out-of-range, matching the cache-bounds contract)
            idx = off_in[0][:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            c = c[idx][:, :, None, :].astype(qa.dtype)        # [b, s, 1, d]
            si_ = si[idx][:, :, None, :].astype(qa.dtype)
        elif off_in:
            # traced offset (compiled decode): cache bounds guarantee
            # off + s <= max_pos, so the dynamic slice never clamps
            c = lax.dynamic_slice_in_dim(c, off_in[0], s, 0)
            si_ = lax.dynamic_slice_in_dim(si, off_in[0], s, 0)
        else:
            # static offset: plain slicing keeps the out-of-range case loud
            c = c[position_offset : position_offset + s]
            si_ = si[position_offset : position_offset + s]
        if not (off_in and per_row):
            c = c[None, :, None, :].astype(qa.dtype)
            si_ = si_[None, :, None, :].astype(qa.dtype)

        def rot(x):
            half = x.shape[-1] // 2
            rh = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
            return x * c + rh * si_

        return rot(qa), rot(ka)

    ins = [q, k, cos, sin] + ([position_offset] if dyn else [])
    return apply(f, ins, multi=True, name="rope")


class StaticKVCache:
    """Preallocated [b, max_len, kv_heads, head_dim] K/V buffers updated in
    place with dynamic_update_slice at the current position — shapes never
    change, so ONE compiled decode step serves every generated token
    (reference: the inference runtime's flash-decode KV cache, SURVEY §2.1
    L8; the growing-concat Cache forced a recompile per token)."""

    def __init__(self, b, max_len, kv_heads, head_dim, dtype="float32"):
        from ..framework import core as _fcore

        self.max_len = max_len
        zeros = np.zeros((b, max_len, kv_heads, head_dim), _fcore.to_jax_dtype(dtype))
        self.k = Tensor(zeros)
        self.v = Tensor(zeros.copy())
        self.k.stop_gradient = True
        self.v.stop_gradient = True


def _cache_write(cache_t, new_t, pos_t):
    """dynamic_update_slice of this chunk's K or V at the absolute position
    `pos` (a scalar: lock-step decode has the whole batch at one position)."""
    from jax import lax

    from ..ops.dispatch import apply

    def f(c, n, p):
        return lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), p, 1)

    return apply(f, [cache_t, new_t, pos_t], name="kv_cache_write")


class PagedKVCache:
    """One layer's paged K/V arena: `[num_pages, kv_heads, page_size,
    head_dim]` buffers addressed through per-slot page tables (traced data).
    (page_size, head_dim) are the minor dims because the fused page-walk
    kernels stream one (page, kv head) tile per grid step and the TPU
    lowering only takes a K/V block whose last two dims are whole (or
    8x128-divisible) dims of the array.  Page 0 is scratch — inactive
    slots' all-zero table rows and every masked scatter land there (see
    inference/paging.py).

    quant="int8" (ISSUE 18) stores the K/V buffers as int8 and adds
    `k_scale`/`v_scale` float32 buffers `[num_pages, kv_heads, 1,
    page_size]`: one symmetric scale per (token row, kv head), written by
    the same scatters, addressed by the same tables, shared/copied by the
    same refcount/COW machinery.  Per-ROW scales (not per-page) mean a
    decode write never requantizes the rest of its page; the rows lie along
    the minor (lane) dim so a page's scales are one dense [1, page_size]
    tile — a trailing unit dim is lane-padded 128x on the TPU."""

    def __init__(self, num_pages, page_size, kv_heads=None, head_dim=None,
                 dtype="float32", quant="none", rows=None, state=(), slots=0):
        """`rows` is what a served model declares for this layer, a token's
        rows: [(name, heads, width, dtype)].  Each becomes a buffer
        `[num_pages, heads, page_size, width]` under its name, all on the
        one page table; an empty list gives a layer no arena at all.
        Without it: a K and a V row of `kv_heads` heads of `head_dim` in
        `dtype`.  `state` is the layer's fixed state per slot, [(name,
        shape, dtype)]: each a buffer `[slots, *shape]` under its name,
        zeros, which no page table reaches (a layer of linear attention
        holds its running state there and no rows per token)."""
        from ..framework import core as _fcore

        self.page_size = int(page_size)
        self.quant = str(quant)
        if rows is None:
            rows = [("k", kv_heads, head_dim, dtype), ("v", kv_heads, head_dim, dtype)]
        self.row_names = tuple(r[0] for r in rows)
        self.state_names = tuple(st[0] for st in state)
        self.k_scale = None
        self.v_scale = None
        if self.quant == "int8":
            if self.row_names != ("k", "v"):
                raise ValueError(f"an int8 arena holds K and V rows, not {self.row_names}")
            scales = np.zeros((num_pages, rows[0][1], 1, page_size), np.float32)
            self.k_scale = Tensor(scales)
            self.v_scale = Tensor(scales.copy())
        for name, heads, width, dt in rows:
            elem = np.int8 if self.quant == "int8" else _fcore.to_jax_dtype(dt)
            setattr(self, name, Tensor(np.zeros((num_pages, heads, page_size, width), elem)))
        for name, shape, dt in state:
            setattr(self, name, Tensor(np.zeros((int(slots),) + tuple(shape), _fcore.to_jax_dtype(dt))))
        for t in self.buffers() + self.state_buffers():
            t.stop_gradient = True

    def buffers(self):
        """Every buffer a page owns a slice of, scale buffers included: what
        a page copy has to move together."""
        out = [getattr(self, n) for n in self.row_names]
        return out + [t for t in (self.k_scale, self.v_scale) if t is not None]

    def state_buffers(self):
        """The buffers a SLOT owns a slice of; no page copy moves them."""
        return [getattr(self, n) for n in self.state_names]


class PagedPrefillView:
    """Prefill into a paged arena.  Fresh prefill (`start is None`): the
    prompt attends to itself causally (rope offset 0, plain causal SDPA)
    while its K/V scatter into the pages of `table` ([max_pages_per_seq]
    int32, data).  Chunk prefill
    (`start` an int32[1] Tensor): a prefix-cache hit prefills only the
    unshared suffix at rope offset `start`, attending the shared pages
    through a table gather.  Rows past `true_len` (bucket padding) and rows
    whose page index overruns the table never touch a mapped page
    (`_kv_store`).  `slot` (int32 scalar Tensor, data) is the slot the
    prompt is seated in, for a layer that keeps state per slot: a fresh
    prefill starts that slot's state from zero, a chunk resumes from what
    the chunk before left there."""

    def __init__(self, arena, table, true_len, max_len, start=None,
                 kernel="auto", slot=None):
        self.arena = arena
        self.table = table
        self.true_len = true_len
        self.max_len = max_len
        self.start = start
        self.kernel = kernel  # paged attention dispatch: auto|fused|gather
        self.slot = slot


class PagedDecodeView:
    """Compiled decode over the paged arena: `tables` is the full
    [slots, max_pages_per_seq] int32 page table (data), each slot writes
    its token at page `tables[s, pos//page_size]` row `pos % page_size`
    and attends the gathered pages sliced back to [slots, max_len].

    Multi-query verify (speculative decoding): the same view serves a
    [slots, k+1] token window — row i writes page entry (pos+i)//page_size
    (overruns redirected to scratch, see `_page_decode_write`) and attends
    positions j <= pos+i through the per-row-pos decode kernel.  Row 0 of
    a k+1 window is therefore the exact single-token decode step.  `live`
    ([slots] bool Tensor, data) is the step's mask, for a layer that keeps
    state per slot: an idle slot's state is left as it was."""

    def __init__(self, arena, tables, max_len, kernel="auto", live=None):
        self.arena = arena
        self.tables = tables
        self.max_len = max_len
        self.kernel = kernel  # paged attention dispatch: auto|fused|gather
        self.live = live


def _kv_store(arena, rows, tables, start=None, true_len=None):
    """The one way K/V rows enter a paged arena (traced: the five writers
    below call it inside their ops).  `arena` is a value arena `[pages,
    kv_heads, page_size, head_dim]` taking `rows` `[b, s, kv_heads,
    head_dim]`, or an int8 arena's scale buffer `[pages, kv_heads, 1,
    page_size]` taking `rows` `[b, s, kv_heads]`.  Row (n, i) has global
    index `start[n] + i` (`start` None: i) and lands on page `tables[n,
    idx // page_size]`, row `idx % page_size`.  Rows with `i >= true_len`
    and rows whose page entry overruns the table never touch a mapped page:
    they fall on scratch page 0 (content unspecified) or are dropped.

    Arena and rows go through `stop_gradient`: under `dispatch.apply`'s
    `jax.vjp` a scatter whose updates carry a tangent selects over a u32
    twin of the whole arena, and no gradient is ever taken through a cache.

    The static row count per sequence picks the form, because XLA:TPU
    copies the whole arena around a scatter that does not run in the
    arena's own layout (chip readings: PERF.md, PR 28):

    - `s < page_size` (decode, the verify window): (page, head, row) are all
      indexed, so the only window dim is the minor one;
    - else (prefill buckets), by whole pages: gather the pages the chunk
      touches, merge the new rows into them on the row axis (rows before
      `start` and rows at or past `true_len` keep what they held), and put
      the pages back with a scatter whose only indexed dim is `pages`.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    arena = lax.stop_gradient(arena)
    rows = lax.stop_gradient(rows).astype(arena.dtype)
    scales = rows.ndim == 3
    ps = arena.shape[3] if scales else arena.shape[2]
    b, s, kvh = rows.shape[:3]
    P = tables.shape[1]
    # a chunk with a traced start may begin inside a page: one page more
    n_pg = -(-s // ps) + (0 if start is None else 1)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    n_valid = s if true_len is None else jnp.reshape(true_len, ())

    def pages_of(entry, live):
        # [b, n] table entries -> page ids; dead and overrunning -> scratch
        pg = jnp.take_along_axis(tables, jnp.minimum(entry, P - 1), axis=1)
        return jnp.where(live & (entry < P), pg, 0)

    if s < ps:
        i = jnp.arange(s, dtype=jnp.int32)[None, :]
        idx = start[:, None] + i
        pg = pages_of(idx // ps, i < n_valid)[:, :, None]
        row = (idx % ps)[:, :, None]
        head = jnp.arange(kvh, dtype=jnp.int32)[None, None, :]
        return arena.at[(pg, head, 0, row) if scales else (pg, head, row)].set(rows)

    # the chunk on the grid of its pages: grid row j is index first * ps + j
    first, off = start // ps, start % ps
    grid = jax.vmap(
        lambda r, o: lax.dynamic_update_slice_in_dim(
            jnp.zeros((n_pg * ps,) + r.shape[1:], r.dtype), r, o, 0
        )
    )(rows, off)
    j = jnp.arange(n_pg * ps, dtype=jnp.int32)[None, :]
    new = (j >= off[:, None]) & (j < off[:, None] + jnp.minimum(n_valid, s))
    new = new.reshape(b, n_pg, ps)
    entry = first[:, None] + jnp.arange(n_pg, dtype=jnp.int32)[None, :]
    pgs = pages_of(entry, new.any(axis=2))
    # [b, n_pg * ps, kv_heads, ...] -> pages shaped as the arena holds them
    grid = jnp.moveaxis(grid.reshape((b, n_pg, ps) + grid.shape[2:]), 2, 3)
    if scales:
        grid, new = grid[:, :, :, None, :], new[:, :, None, None, :]
    else:
        new = new[:, :, None, :, None]
    return arena.at[pgs].set(jnp.where(new, grid, arena[pgs]))


def _page_scatter(arena_t, new_t, table_t, true_len_t, start_t=None):
    """Store a [1, s, kv_heads, d] prefill chunk into the pages of `table_t`
    ([max_pages_per_seq] int32) from global index `start_t` (int32[1];
    None: 0).  Rows i >= true_len (bucket padding) never touch a page a
    reader could share: see `_kv_store`."""
    from ..ops.dispatch import apply

    def f(c, n, t, tl, *st):
        return _kv_store(c, n, t[None], *st, true_len=tl)

    ins = [arena_t, new_t, table_t, true_len_t] + ([start_t] if start_t is not None else [])
    return apply(f, ins, name="kv_page_scatter")


def _rope_page_scatter(arena_k_t, arena_v_t, q, k, v, cos, sin, table_t,
                       true_len_t, start_t=None):
    """Fused prefill cache-write: RoPE on q/k AND the k/v page stores in
    ONE traced op — the unfused form round-trips the rotated k (and raw v)
    through HBM between the rope op and each store op; fusing them keeps
    the activations in registers/VMEM within one XLA computation.  The rope
    math is operation-for-operation identical to `apply_rotary_pos_emb`
    (static offset 0 without `start_t`, the per-row cos/sin gather with it),
    so q and k are bit-identical to the unfused op's.  Returns (q_rot,
    k_rot, new_arena_k, new_arena_v)."""
    import jax.numpy as jnp

    from ..ops.dispatch import apply

    s = q.shape[1]

    def f(ak, av, qa, ka, va, c, si, t, tl, *st):
        if st:
            # start is int32[1]: the same per-row cos/sin gather the rope op
            # takes for a 1-d offset (jax gather clamps out-of-range)
            idx = st[0][:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            cc = c[idx][:, :, None, :].astype(qa.dtype)
            si_ = si[idx][:, :, None, :].astype(qa.dtype)
        else:
            cc = c[0:s][None, :, None, :].astype(qa.dtype)
            si_ = si[0:s][None, :, None, :].astype(qa.dtype)

        def rot(x):
            half = x.shape[-1] // 2
            rh = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
            return x * cc + rh * si_

        def store(arena, rows):
            return _kv_store(arena, rows, t[None], *st, true_len=tl)

        q_rot, k_rot = rot(qa), rot(ka)
        return q_rot, k_rot, store(ak, k_rot), store(av, va)

    ins = [arena_k_t, arena_v_t, q, k, v, cos, sin, table_t, true_len_t]
    if start_t is not None:
        ins.append(start_t)
    return apply(f, ins, multi=True, name="rope_page_scatter")


def _quantize_kv_rows(x):
    """Symmetric per-row int8 quantization of KV rows `[..., head_dim]`:
    scale = max|x| / 127 over the head dim (float32), zero rows pinned to
    scale 1 so their dequant is exactly zero.  Returns (int8 values,
    float32 scales [..., 1]).  Traced inline inside the store ops, so
    the rotated K (and raw V) quantize in-register — no full-precision
    round trip through HBM on the way into the arena."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _rope_page_scatter_quant(arena_k_t, arena_v_t, ks_t, vs_t, q, k, v, cos,
                             sin, table_t, true_len_t, start_t=None):
    """`_rope_page_scatter` for an int8 arena (ISSUE 18): identical RoPE,
    but the K/V rows quantize per (row, kv head) before landing and the
    scales go into the parallel scale buffers through the SAME addresses —
    one traced op still, so rope, quantize and all four stores fuse.
    Returns (q_rot, k_rot, new_ak, new_av, new_ks, new_vs) — q_rot/k_rot
    stay full precision for the prefill's own causal attention."""
    import jax.numpy as jnp

    from ..ops.dispatch import apply

    s = q.shape[1]

    def f(ak, av, aks, avs, qa, ka, va, c, si, t, tl, *st):
        if st:
            idx = st[0][:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            cc = c[idx][:, :, None, :].astype(qa.dtype)
            si_ = si[idx][:, :, None, :].astype(qa.dtype)
        else:
            cc = c[0:s][None, :, None, :].astype(qa.dtype)
            si_ = si[0:s][None, :, None, :].astype(qa.dtype)

        def rot(x):
            half = x.shape[-1] // 2
            rh = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
            return x * cc + rh * si_

        def store(arena, rows):
            return _kv_store(arena, rows, t[None], *st, true_len=tl)

        q_rot, k_rot = rot(qa), rot(ka)
        kq, ksc = _quantize_kv_rows(k_rot)
        vq, vsc = _quantize_kv_rows(va)
        return (q_rot, k_rot, store(ak, kq), store(av, vq),
                store(aks, ksc[..., 0]), store(avs, vsc[..., 0]))

    ins = [arena_k_t, arena_v_t, ks_t, vs_t, q, k, v, cos, sin, table_t,
           true_len_t]
    if start_t is not None:
        ins.append(start_t)
    return apply(f, ins, multi=True, name="rope_page_scatter_q8")


def _page_decode_write(arena_t, new_t, tables_t, pos_t):
    """Per-slot decode write: slot s's [s_q, kv_heads, d] token K/V rows land
    at page tables[s, (pos[s]+i)//page_size] row (pos[s]+i) % page_size for
    i < s_q (`_kv_store`).  Inactive slots run at pos 0 over an all-zero
    table row — scratch page 0.

    s_q == 1 is the plain decode step; s_q > 1 is the speculative VERIFY
    step writing the whole draft window at once.  Rows whose page entry
    overruns the table — drafts past a slot's mapped coverage, or the window
    tail of a slot about to hit its length bound — are redirected to scratch
    page 0, the same rollback-by-redirect contract prefill padding gets: a
    rejected draft's K/V is either overwritten before any reader can attend
    it (positions >= the advanced pos are rewritten by the next step's own
    window, writes precede attention within every layer) or never lands in
    a mapped page at all."""
    from ..ops.dispatch import apply

    return apply(_kv_store, [arena_t, new_t, tables_t, pos_t],
                 name="kv_page_decode_write")


def _page_decode_write_quant(arena_t, scale_t, new_t, tables_t, pos_t):
    """`_page_decode_write` for an int8 arena: the full-precision decode (or
    verify-window) rows quantize per (row, kv head) in-register, then the
    int8 values and their float32 scales go through the SAME addresses —
    one traced op.  Returns (new_arena, new_scales)."""
    from ..ops.dispatch import apply

    def f(c, sc, n, t, p):
        nq, ns = _quantize_kv_rows(n)
        return _kv_store(c, nq, t, p), _kv_store(sc, ns[..., 0], t, p)

    return apply(
        f, [arena_t, scale_t, new_t, tables_t, pos_t], multi=True,
        name="kv_page_decode_write_q8",
    )


def _lora_add(lora, target, y, x):
    """Base projection output `y` (computed from `x`) plus the batched-
    gather LoRA delta for `target` (ISSUE 12).  `lora` is a per-layer
    arena view carrying this dispatch's `[b]` int32 adapter-slot ids as
    traced data; None (training, non-LoRA serving) is an exact
    passthrough — the traced program is byte-identical to pre-LoRA."""
    return y if lora is None else lora.add(target, y, x)


class LlamaMLP(nn.Layer):
    def __init__(self, config):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        if _use_tp(config):
            self.gate_proj = ColumnParallelLinear(h, i, has_bias=False, gather_output=False)
            self.up_proj = ColumnParallelLinear(h, i, has_bias=False, gather_output=False)
            self.down_proj = RowParallelLinear(i, h, has_bias=False, input_is_parallel=True)
        else:
            self.gate_proj = nn.Linear(h, i, bias_attr=False)
            self.up_proj = nn.Linear(h, i, bias_attr=False)
            self.down_proj = nn.Linear(i, h, bias_attr=False)

    def forward(self, x, lora=None):
        if lora is None:
            return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))
        h = F.silu(_lora_add(lora, "gate_proj", self.gate_proj(x), x)) * _lora_add(
            lora, "up_proj", self.up_proj(x), x
        )
        return _lora_add(lora, "down_proj", self.down_proj(h), h)


class LlamaAttention(nn.Layer):
    def __init__(self, config, rope):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        kv_out = self.num_kv_heads * self.head_dim
        if _use_tp(config):
            self.q_proj = ColumnParallelLinear(h, h, has_bias=False, gather_output=False)
            self.k_proj = ColumnParallelLinear(h, kv_out, has_bias=False, gather_output=False)
            self.v_proj = ColumnParallelLinear(h, kv_out, has_bias=False, gather_output=False)
            self.o_proj = RowParallelLinear(h, h, has_bias=False, input_is_parallel=True)
        else:
            self.q_proj = nn.Linear(h, h, bias_attr=False)
            self.k_proj = nn.Linear(h, kv_out, bias_attr=False)
            self.v_proj = nn.Linear(h, kv_out, bias_attr=False)
            self.o_proj = nn.Linear(h, h, bias_attr=False)
        self.rope_cos, self.rope_sin = rope

    def forward(self, x, attn_mask=None, cache=None, pos=None, lora=None):
        b, s = x.shape[0], x.shape[1]
        q = _lora_add(lora, "q_proj", self.q_proj(x), x).reshape(
            [b, s, self.num_heads, self.head_dim]
        )
        k = _lora_add(lora, "k_proj", self.k_proj(x), x).reshape(
            [b, s, self.num_kv_heads, self.head_dim]
        )
        v = _lora_add(lora, "v_proj", self.v_proj(x), x).reshape(
            [b, s, self.num_kv_heads, self.head_dim]
        )
        if isinstance(cache, PagedPrefillView):
            quant = getattr(cache.arena, "quant", "none") == "int8"
            if cache.start is None:
                # fresh paged prefill: rope offset 0, causal SDPA over the
                # prompt, its K/V rows landing in the table's pages.  RoPE +
                # both page scatters run as ONE fused op (no activation
                # round-trip).
                # Under an int8 arena the scatter quantizes on write, but
                # the prompt's own attention below still runs on the full-
                # precision k/v in register — first tokens stay exact
                if quant:
                    q, k, new_ak, new_av, new_ks, new_vs = \
                        _rope_page_scatter_quant(
                            cache.arena.k, cache.arena.v,
                            cache.arena.k_scale, cache.arena.v_scale,
                            q, k, v, self.rope_cos, self.rope_sin,
                            cache.table, cache.true_len,
                        )
                    cache.arena.k_scale._data = new_ks._data
                    cache.arena.v_scale._data = new_vs._data
                else:
                    q, k, new_ak, new_av = _rope_page_scatter(
                        cache.arena.k, cache.arena.v, q, k, v,
                        self.rope_cos, self.rope_sin, cache.table,
                        cache.true_len,
                    )
                cache.arena.k._data = new_ak._data
                cache.arena.v._data = new_av._data
                out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            else:
                # chunk prefill (prefix-cache hit): suffix rows at rope
                # offset `start` scatter into their pages, then attend the
                # whole sequence — shared prefix included — through the
                # table gather; row i sees j <= start + i
                if quant:
                    q, k, new_ak, new_av, new_ks, new_vs = \
                        _rope_page_scatter_quant(
                            cache.arena.k, cache.arena.v,
                            cache.arena.k_scale, cache.arena.v_scale,
                            q, k, v, self.rope_cos, self.rope_sin,
                            cache.table, cache.true_len, cache.start,
                        )
                    cache.arena.k_scale._data = new_ks._data
                    cache.arena.v_scale._data = new_vs._data
                else:
                    q, k, new_ak, new_av = _rope_page_scatter(
                        cache.arena.k, cache.arena.v, q, k, v,
                        self.rope_cos, self.rope_sin, cache.table,
                        cache.true_len, cache.start,
                    )
                cache.arena.k._data = new_ak._data
                cache.arena.v._data = new_av._data
                out = F.paged_flash_decode(
                    q, cache.arena.k, cache.arena.v,
                    cache.table.reshape([1, -1]), cache.start, cache.max_len,
                    kernel=getattr(cache, "kernel", "auto"),
                    k_scale=cache.arena.k_scale if quant else None,
                    v_scale=cache.arena.v_scale if quant else None,
                )
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return _lora_add(lora, "o_proj", self.o_proj(out), out), cache
        if isinstance(cache, PagedDecodeView):
            # paged compiled decode: per-row rope; the page-table indirection
            # happens inside the compiled step (tables are data) — fused
            # in-kernel on the Pallas path, gather-then-dense otherwise
            quant = getattr(cache.arena, "quant", "none") == "int8"
            q, k = apply_rotary_pos_emb(q, k, self.rope_cos, self.rope_sin, pos)
            if quant:
                new_ak, new_ks = _page_decode_write_quant(
                    cache.arena.k, cache.arena.k_scale, k, cache.tables, pos
                )
                new_av, new_vs = _page_decode_write_quant(
                    cache.arena.v, cache.arena.v_scale, v, cache.tables, pos
                )
                cache.arena.k._data = new_ak._data
                cache.arena.v._data = new_av._data
                cache.arena.k_scale._data = new_ks._data
                cache.arena.v_scale._data = new_vs._data
            else:
                cache.arena.k._data = _page_decode_write(
                    cache.arena.k, k, cache.tables, pos
                )._data
                cache.arena.v._data = _page_decode_write(
                    cache.arena.v, v, cache.tables, pos
                )._data
            out = F.paged_flash_decode(
                q, cache.arena.k, cache.arena.v, cache.tables, pos,
                cache.max_len, kernel=getattr(cache, "kernel", "auto"),
                k_scale=cache.arena.k_scale if quant else None,
                v_scale=cache.arena.v_scale if quant else None,
            )
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return _lora_add(lora, "o_proj", self.o_proj(out), out), cache
        if isinstance(cache, StaticKVCache):
            # compiled decode path: fixed-shape cache, position as data;
            # cache validity rides the flash_decode kernel (in-kernel
            # comparison against pos), never an additive mask — the mask
            # was exactly what forced the XLA fallback (round-4 verdict)
            q, k = apply_rotary_pos_emb(q, k, self.rope_cos, self.rope_sin, pos)
            cache.k._data = _cache_write(cache.k, k, pos)._data
            cache.v._data = _cache_write(cache.v, v, pos)._data
            out = F.flash_decode(q, cache.k, cache.v, pos)
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return _lora_add(lora, "o_proj", self.o_proj(out), out), cache
        offset = 0
        if cache is not None:
            offset = cache[0].shape[1]
        q, k = apply_rotary_pos_emb(q, k, self.rope_cos, self.rope_sin, offset)
        if cache is not None:
            k = ops.concat([cache[0], k], axis=1)
            v = ops.concat([cache[1], v], axis=1)
            new_cache = (k, v)
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, is_causal=s > 1)
        else:
            new_cache = None
            out = self._dispatch_attention(q, k, v, attn_mask)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if new_cache is not None:
            return out, new_cache
        return out

    def _dispatch_attention(self, q, k, v, attn_mask):
        """Route by config: ring (context parallel) > Ulysses (sep) > flash.
        Both long-context paths ride the 'sep' mesh axis and degrade to
        plain flash attention when the mesh doesn't provide it."""
        cfg = self.config
        sep_n = _mesh.axis_size("sep")
        want_ring = sep_n > 1 and cfg.context_parallel_degree > 1 and attn_mask is None
        want_ulysses = sep_n > 1 and cfg.sep_degree > 1 and attn_mask is None
        if want_ring or want_ulysses:
            # ring/Ulysses operate on equal q/k head counts: expand GQA kv
            # heads first (same repeat sdpa_array does internally)
            if self.num_kv_heads != self.num_heads:
                rep = self.num_heads // self.num_kv_heads
                k = ops.repeat_interleave(k, rep, axis=2)
                v = ops.repeat_interleave(v, rep, axis=2)
            if want_ring:
                from ..distributed.fleet.meta_parallel.ring_attention import (
                    ring_flash_attention,
                )

                return ring_flash_attention(q, k, v, causal=True)
            if self.num_heads % sep_n == 0:
                from ..distributed.fleet.meta_parallel.ring_attention import (
                    ulysses_attention,
                )

                return ulysses_attention(q, k, v, causal=True)
            import warnings

            warnings.warn(
                f"sep_degree set but num_attention_heads ({self.num_heads}) is "
                f"not divisible by the sep mesh axis ({sep_n}); falling back "
                "to flash attention"
            )
        return F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, is_causal=True)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config, rope):
        super().__init__()
        self.config = config
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config, rope)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def _block(self, x, attn_mask=None):
        h = x + self.self_attn(self.input_layernorm(x), attn_mask)
        return h + self.mlp(self.post_attention_layernorm(h))

    def forward(self, x, attn_mask=None, cache=None, pos=None, lora=None):
        if cache is not None:
            residual = x
            attn_out, new_cache = self.self_attn(
                self.input_layernorm(x), attn_mask, cache, pos, lora=lora
            )
            h = residual + attn_out
            out = h + self.mlp(self.post_attention_layernorm(h), lora=lora)
            return out, new_cache
        if self.config.use_recompute and self.training:
            from ..incubate.recompute import recompute

            return recompute(self._block, x)
        return self._block(x, attn_mask)


class LlamaModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        rope = _rope_cache(config)
        if _use_tp(config):
            self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config, rope) for _ in range(config.num_hidden_layers)]
        )
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None, lora=None):
        x = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            from ..distributed.fleet.meta_parallel.sp_utils import ScatterOp

            x = ScatterOp.apply(x)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(
                    x, attn_mask, caches[i], pos,
                    lora=lora.layer(i) if lora is not None else None,
                )
                new_caches.append(c)
            else:
                x = layer(x, attn_mask)
        x = self.norm(x)
        if self.config.sequence_parallel:
            from ..distributed.fleet.meta_parallel.sp_utils import GatherOp

            x = GatherOp.apply(x)
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if _use_tp(config):
            # vocab-sharded head + sharded-logsumexp CE: the full replicated
            # [B*S, vocab] logits never materialize (reference:
            # mp_ops._c_softmax_with_cross_entropy's fused NCCL op)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False, gather_output=False
            )
            self.parallel_ce = ParallelCrossEntropy(ignore_index=-100)
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)
            self.parallel_ce = None

    @property
    def backbone(self):
        """What the serving engine calls with `caches=` / `pos=`."""
        return self.llama

    def cache_rows(self):
        """A token's rows in each layer's cache, for the serving engine:
        (name, heads, width, dtype)."""
        c = self.config
        d = c.hidden_size // c.num_attention_heads
        dt = self.lm_head.weight.dtype  # bf16 under AMP-O2 decorate
        return [("k", c.num_key_value_heads, d, dt), ("v", c.num_key_value_heads, d, dt)]

    def forward(self, input_ids, labels=None, attn_mask=None):
        hidden = self.llama(input_ids, attn_mask)
        logits = self.lm_head(hidden)
        if labels is not None:
            loss = sequence_ce(self, logits, labels)
            return loss, logits
        return logits

    def generate(self, input_ids, max_new_tokens=16, temperature=0.0, top_k=0, top_p=1.0,
                 decode_strategy=None, num_beams=1, seed=None, eos_token_id=None,
                 length_penalty=0.0):
        """Greedy / compiled-sampling / beam search over the shared compiled
        static-KV decode step (models/_utils.compiled_generate): one
        executable dispatch per token for every strategy (reference:
        PaddleNLP generation_utils decode_strategy)."""
        from ._utils import compiled_generate

        def forward_step(toks, caches, pos):
            hidden, _ = self.llama(toks, caches=caches, pos=pos)
            return self.lm_head(hidden)[:, -1]

        return compiled_generate(
            self, input_ids, max_new_tokens, temperature, forward_step,
            kv_heads=self.config.num_key_value_heads, top_k=top_k, top_p=top_p,
            decode_strategy=decode_strategy, num_beams=num_beams, seed=seed,
            eos_token_id=eos_token_id, length_penalty=length_penalty,
        )


def shard_llama_for_tp(model):
    """Re-place an already-constructed TP Llama's weights onto the installed
    'mp' mesh.  The parallel layers shard themselves at construction, but a
    serving model is usually built BEFORE the engine installs its mesh (so
    those `shard_tensor_` calls were no-ops); this walks the module tree and
    applies the canonical layout eagerly:

      ColumnParallelLinear   weight P(None, 'mp')   bias P('mp')
      RowParallelLinear      weight P('mp', None)   bias replicated
      VocabParallelEmbedding weight P('mp', None)
      everything else        replicated

    Idempotent (device_put to the same sharding is a no-op) and safe on a
    non-TP model (plain Linears all fall in the replicate bucket).
    """
    from jax.sharding import PartitionSpec as P

    if _mesh.get_mesh() is None or _mesh.axis_size("mp") <= 1:
        return model
    placed = set()

    def _put(t, spec):
        if t is None:
            return
        _mesh.shard_tensor_(t, spec)
        placed.add(id(t))

    for _, layer in model.named_sublayers(include_self=True):
        if isinstance(layer, ColumnParallelLinear):
            _put(layer.weight, P(None, "mp"))
            _put(layer.bias, P("mp"))
        elif isinstance(layer, RowParallelLinear):
            _put(layer.weight, P("mp", None))
            _put(layer.bias, P())
        elif isinstance(layer, VocabParallelEmbedding):
            _put(layer.weight, P("mp", None))
        elif isinstance(layer, LlamaAttention):
            # rope cos/sin are plain Tensors (shared across layers), not
            # registered parameters — replicate them explicitly
            _put(layer.rope_cos, P())
            _put(layer.rope_sin, P())
    for _, p in model.named_parameters():
        if id(p) not in placed:
            _put(p, P())
    return model
