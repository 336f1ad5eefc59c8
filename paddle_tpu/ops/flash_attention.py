"""Flash attention — TPU-native (reference capability:
paddle/phi/kernels/gpu/flash_attn_kernel.cu wrapping the FlashAttention CUDA
library; here a Pallas TPU kernel + an XLA blockwise fallback).

Layout convention follows the reference API: [batch, seq, num_heads, head_dim].

Design (see /opt/skills/guides/pallas_guide.md):
- forward: online-softmax kernel; grid (batch*heads, q blocks, k blocks)
  with k innermost — each step DMAs ONE [block_k, d] K/V tile through VMEM
  and carries (m, l, acc) in VMEM scratch across the sequential grid, so
  sequence length is bounded by HBM, not VMEM (32k+ works).
- backward: hand-written FA-2 kernels — dkdv (grid over k blocks, q
  streamed) and dq (grid over q blocks, k streamed) — recomputing p from
  (q, k, lse); delta = rowsum(g*out) precomputed outside.  An XLA blockwise
  path remains as fallback for masks/odd shapes and as the parity oracle.
- varlen: packed sequences with SEGMENT IDS (the static-shape TPU encoding
  of the reference's flash_attn_varlen cu_seqlens API): attention is masked
  to seg_q == seg_k in the kernels; `flash_attn_varlen` converts cu_seqlens
  to segment ids.
"""

from __future__ import annotations

import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ..framework import core as _core
from ..tensor import Tensor
from .dispatch import apply, coerce

_NEG_INF = -1e30

# Scoped-VMEM budget declared to Mosaic for the dense flash kernels.  Their
# 1024x1024 blocks keep ~16 MiB of f32 score/prob temporaries live; the
# causal variants fit the compiler's default 16 MiB scope, the non-causal
# key-bias forward overruns it by ~1 MiB (compile-time RESOURCE_EXHAUSTED
# on v5e, libtpu 0.0.34).  A v5e core has 128 MiB of VMEM.
_DENSE_VMEM_LIMIT = 32 * 1024 * 1024


def _on_tpu():
    # a backend that fails to initialise raises here: answering False would
    # send a TPU program down the XLA fallback without a word
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _blk_mask(s, q_start, k_start, block_q, block_k, causal, sq=None, sk=None):
    """Apply causal and/or segment masking to a [block_q, block_k] score
    block.  sq/sk: per-row/col segment ids (or None).  q_start may carry a
    global offset (context-parallel rectangular causal blocks)."""
    masked = s
    if causal:
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        masked = jnp.where(q_ids >= k_ids, masked, _NEG_INF)
    if sq is not None:
        masked = jnp.where(sq[:, None] == sk[None, :], masked, _NEG_INF)
    return masked


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, causal, scale, block_q, block_k, seg_refs=(), carry_refs=(),
    off_ref=None, kb_ref=None,
):
    """Grid (bh blocks, q blocks, k blocks), k innermost: one K/V tile per
    step, (m, l, acc) carried in VMEM scratch across the sequential grid.
    All refs carry a leading block_bh dim — batching several (batch, head)
    rows per grid step amortizes the per-step overhead that dominates at
    short seq / many heads (BERT-384 measured ~10% MXU eff at bb=1)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    if off_ref is not None:
        # per-q-block ABSOLUTE start positions (context-parallel
        # rectangular causal blocks; zig-zag q halves have different
        # global offsets, so each block carries its own)
        q_start = off_ref[qi]
    else:
        q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        if carry_refs:
            # continuation: previous partial (out, lse) is algebraically a
            # pseudo-block with m=lse, l=1, acc=out — the ring-attention
            # hop merge happens IN-KERNEL instead of as a separate
            # elementwise chain per hop
            m_scr[...] = carry_refs[1][...].astype(jnp.float32)
            l_scr[...] = jnp.ones_like(l_scr)
            acc_scr[...] = carry_refs[0][...].astype(jnp.float32)
        else:
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: blocks strictly above the diagonal contribute nothing
    needed = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # [bb, block_q, d] — half precision operands for the MXU
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [bb, block_q, block_k]
        if kb_ref is not None:
            # additive key bias (lowered key-padding attn_mask): one value
            # per key column, a [1, block_k] row broadcast over the q rows
            # exactly as the XLA fallback's `s + mask`
            s = s + kb_ref[...][None]
        sq = sk = None
        if seg_refs:
            sq = seg_refs[0][:, 0]
            sk = seg_refs[1][:, 0]
        s = _blk_mask(s, q_start, k_start, block_q, block_k, causal, sq, sk)
        m = m_scr[..., 0]  # [bb, block_q]
        l = l_scr[..., 0]
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new[..., None]
        l_scr[...] = (alpha * l + p.sum(-1))[..., None]
        acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[..., 0], 1e-30)
        o_ref[...] = (acc_scr[...] / l_safe[..., None]).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[..., 0] + jnp.log(l_safe))[..., None]


def q_block_starts(offsets_and_lens, bq):
    """Per-q-block absolute start positions for a q tensor formed by
    concatenating chunks: [(global_offset, rows), ...] -> int32 array.
    `bq` must divide every chunk's row count (blocks may not straddle
    chunks — rows within a block share one contiguous global range)."""
    starts = []
    for off, n in offsets_and_lens:
        assert n % bq == 0, (n, bq)
        for r in range(0, n, bq):
            starts.append(off + r)
    return jnp.stack([jnp.asarray(o, jnp.int32) for o in starts])


def _pick_block(seq_len, pref):
    """Largest multiple-of-128 divisor of seq_len that is <= pref: big
    blocks amortize the per-grid-step q reload (seq 384 must pick 384, not
    128 — a 3x3 grid of tiny programs measurably regressed BERT)."""
    best = 128
    b = 128
    while b <= min(seq_len, pref):
        if seq_len % b == 0:
            best = b
        b += 128
    return best


def _pick_bh_block(bh, n_heads, block_q, block_k, d, has_segments):
    """How many (batch, head) rows to process per grid step.  Budgeted by
    the [bb, block_q, block_k] fp32 score/prob temporaries (~2 live copies)
    against ~8MB of the ~16MB VMEM; long sequences naturally get bb=1.
    With segment ids the bh block must stay within one batch row, so bb
    must divide n_heads."""
    per_bb = block_q * block_k * 4 * 2 + 4 * block_q * d * 4
    limit = max(1, (8 * 1024 * 1024) // max(per_bb, 1))
    cand = n_heads if has_segments else bh
    best = 1
    for bb in range(1, min(limit, cand) + 1):
        if cand % bb == 0 and bh % bb == 0:
            best = bb
    return best


_PAGED_WALK_VMEM_BUDGET = 4 * 1024 * 1024
# a q block of at most this many rows a KV head (a decode step, a verify
# window) makes the page walk a matter of copies; more rows (a chunk
# prefill) make it a matter of compute, a page a grid step as before
_PAGED_WALK_LOOP_ROWS = 128
_PAGED_WALK_MAX_PAGES = 8


def _pick_kv_heads_block(hk, qr, page_size, d, itemsize):
    """How many KV heads of a page one grid step of the page walk takes:
    the largest divisor of the (local) `hk` whose VMEM estimate fits
    `_PAGED_WALK_VMEM_BUDGET`, a quarter of the 16 MiB a kernel may scope.
    Per head: a K and a V page tile and the q and out blocks, each
    double-buffered; the f32 accumulator and the m and l
    columns (a [qr, 1] f32 column takes whole 128-lane tiles); two live
    [qr, page_size] f32 score tiles.  Decode and a verify window (a few q
    rows) take every head of the page, one contiguous block of the arena,
    and walk the slot's pages in a loop, `_pick_pages_per_step` pages a
    copy; a chunk prefill (hundreds of q rows a head) gets 1 head and a
    page a grid step."""
    per_head = (
        2 * 2 * page_size * d * itemsize
        + 2 * 2 * qr * d * itemsize
        + qr * d * 4 + 2 * qr * 128 * 4
        + 2 * qr * page_size * 4
    )
    best = 1
    for hb in range(1, hk + 1):
        if hk % hb == 0 and hb * per_head <= _PAGED_WALK_VMEM_BUDGET:
            best = hb
    return best


def _pick_pages_per_step(hb, qr, page_size, d, itemsize, kv_operands, n_cols):
    """How many pages one copy block of the looping page walk holds: as many
    as keep the blocks (`hb` heads of a page, one buffer per arena, two
    blocks in flight) inside `_PAGED_WALK_VMEM_BUDGET`, and two live
    [qr, pages * page_size] f32 score tiles a head inside another; at most
    `_PAGED_WALK_MAX_PAGES` (more gained nothing on the chip, PR 34) and no
    more than the table has columns.  Mistral's decode (8 heads of 128 x 128
    bf16, K and V): 4 pages, 4 MiB.  Ling-3's latent walk (one head of
    128 x 640 bf16, one arena): 8 pages, 2.5 MiB."""
    block = 2 * kv_operands * hb * page_size * d * itemsize
    scores = 2 * hb * qr * page_size * 4
    return max(1, min(_PAGED_WALK_MAX_PAGES, n_cols, _PAGED_WALK_VMEM_BUDGET // max(block, scores)))


def _pallas_flash_forward(q, k, v, causal, scale, segments=None, n_heads=1,
                          block_q=1024, block_k=1024, interpret=False,
                          carry=None, out_dtype=None, q_offset=None,
                          kbias=None):
    """q,k,v: [bh, seq, d]; segments: optional [b, seq, 1] int32 (shared
    across the head dim via the index map); carry: optional
    (out_prev [bh, seq, d], lse_prev [bh, seq, 1]) continuation state —
    this call merges its blocks ONTO the carry (ring-attention hops);
    q_offset: optional int32 [seq/block_q] (may be traced) — ABSOLUTE
    global start position of each q block, for rectangular causal blocks
    whose rows are not contiguous in global positions (zig-zag context
    parallelism); build with q_block_starts().
    kbias: optional [b, 1, k_len] f32 additive per-key bias (a lowered
    key-padding attn_mask), shared across heads via the index map; keys lie
    along lanes — a [k_len, 1] column is lane-padded 128x in VMEM and tips
    the 1024x1024 forward block over the 16 MiB scoped limit.
    Returns (out [bh, seq, d], lse [bh, seq, 1] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_len, d = q.shape
    k_len = k.shape[1]
    # block sizes must divide the sequence (the caller guarantees s % 128
    # == 0, so 128 always works)
    block_q = _pick_block(seq_len, block_q)
    block_k = _pick_block(k_len, block_k)
    per_batch = segments is not None or kbias is not None
    bb = _pick_bh_block(bh, n_heads, block_q, block_k, d, per_batch)
    grid = (bh // bb, seq_len // block_q, k_len // block_k)

    in_specs = [
        pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, j, 0)),
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, j, 0)),
    ]
    args = [q, k, v]
    if segments is not None:
        # bb divides n_heads, so one bh block maps to exactly one batch row
        in_specs += [
            pl.BlockSpec((None, block_q, 1), lambda b, i, j, *_: ((b * bb) // n_heads, i, 0)),
            pl.BlockSpec((None, block_k, 1), lambda b, i, j, *_: ((b * bb) // n_heads, j, 0)),
        ]
        args += [segments, segments]
    if kbias is not None:
        in_specs += [
            pl.BlockSpec((None, 1, block_k), lambda b, i, j, *_: ((b * bb) // n_heads, 0, j)),
        ]
        args += [kbias]
    if carry is not None:
        in_specs += [
            pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((bb, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
        ]
        args += [carry[0], carry[1]]

    def kernel(*refs):
        if q_offset is not None:
            off_ref, refs = refs[0], refs[1:]
        else:
            off_ref = None
        q_ref, k_ref, v_ref, *rest = refs
        if segments is not None:
            seg_refs, rest = rest[:2], rest[2:]
        else:
            seg_refs = ()
        if kbias is not None:
            kb_ref, rest = rest[0], rest[1:]
        else:
            kb_ref = None
        if carry is not None:
            carry_refs, rest = rest[:2], rest[2:]
        else:
            carry_refs = ()
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        _flash_fwd_kernel(
            q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            seg_refs=seg_refs, carry_refs=carry_refs, off_ref=off_ref,
            kb_ref=kb_ref,
        )

    out_specs = [
        pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),
        # [bh, seq, 1] — a trailing unit dim keeps the block TPU-tileable
        pl.BlockSpec((bb, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
        jax.ShapeDtypeStruct((bh, seq_len, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bb, block_q, 1), jnp.float32),
        pltpu.VMEM((bb, block_q, 1), jnp.float32),
        pltpu.VMEM((bb, block_q, d), jnp.float32),
    ]
    params = pltpu.CompilerParams(vmem_limit_bytes=_DENSE_VMEM_LIMIT)
    if q_offset is not None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
            compiler_params=params,
        )(jnp.asarray(q_offset, jnp.int32).reshape(-1), *args)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=params,
    )(*args)


# ---------------------------------------------------------------------------
# Pallas backward kernels (FA-2: recompute p from q,k,lse; delta precomputed)
# ---------------------------------------------------------------------------


def _flash_bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, causal, scale, block_q, block_k, seg_refs=(),
    off_ref=None, kb_ref=None,
):
    """Grid (bh, k blocks, q blocks), q innermost; dk/dv accumulate in
    scratch across the q sweep."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)
    k_start = ki * block_k
    q_start = off_ref[qi] if off_ref is not None else qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    needed = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # [bb, block_q, d]
        k = k_ref[...]
        v = v_ref[...]
        g = g_ref[...]
        lse = lse_ref[..., 0]  # [bb, block_q]
        delta = delta_ref[..., 0]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [bb, bq, bk]
        if kb_ref is not None:
            s = s + kb_ref[...][None]
        sq = sk = None
        if seg_refs:
            sq = seg_refs[0][:, 0]
            sk = seg_refs[1][:, 0]
        s = _blk_mask(s, q_start, k_start, block_q, block_k, causal, sq, sk)
        p = jnp.exp(s - lse[..., None])  # [bb, bq, bk] f32
        pb = p.astype(g.dtype)
        dv_scr[...] += jax.lax.dot_general(
            pb, g, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # [bb, bk, d]
        dp = jax.lax.dot_general(
            g, v, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # [bb, bq, bk]
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # [bb, bk, d]

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, causal, scale, block_q, block_k, seg_refs=(), off_ref=None,
    kb_ref=None,
):
    """Grid (bh, q blocks, k blocks), k innermost; dq accumulates in
    scratch across the k sweep."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_start = off_ref[qi] if off_ref is not None else qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    needed = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # [bb, block_q, d]
        k = k_ref[...]
        v = v_ref[...]
        g = g_ref[...]
        lse = lse_ref[..., 0]
        delta = delta_ref[..., 0]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale
        if kb_ref is not None:
            s = s + kb_ref[...][None]
        sq = sk = None
        if seg_refs:
            sq = seg_refs[0][:, 0]
            sk = seg_refs[1][:, 0]
        s = _blk_mask(s, q_start, k_start, block_q, block_k, causal, sq, sk)
        p = jnp.exp(s - lse[..., None])
        dp = jax.lax.dot_general(
            g, v, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _pallas_flash_backward(q, k, v, g, out, lse, causal, scale, segments=None,
                           n_heads=1, block_q=1024, block_k=1024, interpret=False,
                           delta=None, q_offset=None, kbias=None):
    """q/g/out/lse: [bh, sq, ...]; k/v: [bh, sk, d] — rectangular k is
    allowed (causal with sq != sk requires q_offset: absolute per-q-block
    start positions; without q_offset, causal assumes sq == sk).
    delta: optional precomputed rowsum(g*out) [bh, sq, 1] — the ring path
    computes it ONCE for all hops instead of once per hop.
    kbias: optional [b, 1, sk] f32 additive per-key bias (same operand as
    the forward pass — s must be recomputed identically for p to match).
    Returns (dq, dk, dv)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(sk, block_k)
    per_batch = segments is not None or kbias is not None
    bb = _pick_bh_block(bh, n_heads, block_q, block_k, d, per_batch)
    if delta is None:
        delta = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
        )  # [bh, s, 1]

    common = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k)
    params = pltpu.CompilerParams(vmem_limit_bytes=_DENSE_VMEM_LIMIT)

    # -- dk/dv: grid over k blocks, stream q --------------------------------
    in_specs = [
        pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, j, 0)),  # q
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, i, 0)),  # k
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, i, 0)),  # v
        pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, j, 0)),  # g
        pl.BlockSpec((bb, block_q, 1), lambda b, i, j, *_: (b, j, 0)),  # lse
        pl.BlockSpec((bb, block_q, 1), lambda b, i, j, *_: (b, j, 0)),  # delta
    ]
    args = [q, k, v, g, lse, delta]
    if segments is not None:
        in_specs += [
            pl.BlockSpec((None, block_q, 1), lambda b, i, j, *_: ((b * bb) // n_heads, j, 0)),
            pl.BlockSpec((None, block_k, 1), lambda b, i, j, *_: ((b * bb) // n_heads, i, 0)),
        ]
        args += [segments, segments]
    if kbias is not None:
        in_specs += [
            pl.BlockSpec((None, 1, block_k), lambda b, i, j, *_: ((b * bb) // n_heads, 0, i)),
        ]
        args += [kbias]

    def dkdv_kernel(*refs):
        if q_offset is not None:
            off_ref, refs = refs[0], refs[1:]
        else:
            off_ref = None
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest = refs
        if segments is not None:
            seg_refs, rest = rest[:2], rest[2:]
        else:
            seg_refs = ()
        kb_ref = rest[0] if kbias is not None else None
        dk_ref, dv_ref, dk_scr, dv_scr = rest[-4:]
        _flash_bwd_dkdv_kernel(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
            dk_scr, dv_scr, seg_refs=seg_refs, off_ref=off_ref, kb_ref=kb_ref,
            **common,
        )

    dkdv_grid = (bh // bb, sk // block_k, s // block_q)
    dkdv_out_specs = [
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, i, 0)),
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, i, 0)),
    ]
    dkdv_out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    dkdv_scratch = [
        pltpu.VMEM((bb, block_k, d), jnp.float32),
        pltpu.VMEM((bb, block_k, d), jnp.float32),
    ]
    if q_offset is not None:
        off_arr = jnp.asarray(q_offset, jnp.int32).reshape(-1)
        dk, dv = pl.pallas_call(
            dkdv_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=dkdv_grid, in_specs=in_specs,
                out_specs=dkdv_out_specs, scratch_shapes=dkdv_scratch,
            ),
            out_shape=dkdv_out_shape,
            interpret=interpret,
            compiler_params=params,
        )(off_arr, *args)
    else:
        dk, dv = pl.pallas_call(
            dkdv_kernel,
            grid=dkdv_grid,
            in_specs=in_specs,
            out_specs=dkdv_out_specs,
            out_shape=dkdv_out_shape,
            scratch_shapes=dkdv_scratch,
            interpret=interpret,
            compiler_params=params,
        )(*args)

    # -- dq: grid over q blocks, stream k -----------------------------------
    in_specs = [
        pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),  # q
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, j, 0)),  # k
        pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, j, 0)),  # v
        pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),  # g
        pl.BlockSpec((bb, block_q, 1), lambda b, i, j, *_: (b, i, 0)),  # lse
        pl.BlockSpec((bb, block_q, 1), lambda b, i, j, *_: (b, i, 0)),  # delta
    ]
    args = [q, k, v, g, lse, delta]
    if segments is not None:
        in_specs += [
            pl.BlockSpec((None, block_q, 1), lambda b, i, j, *_: ((b * bb) // n_heads, i, 0)),
            pl.BlockSpec((None, block_k, 1), lambda b, i, j, *_: ((b * bb) // n_heads, j, 0)),
        ]
        args += [segments, segments]
    if kbias is not None:
        in_specs += [
            pl.BlockSpec((None, 1, block_k), lambda b, i, j, *_: ((b * bb) // n_heads, 0, j)),
        ]
        args += [kbias]

    def dq_kernel(*refs):
        if q_offset is not None:
            off_ref, refs = refs[0], refs[1:]
        else:
            off_ref = None
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest = refs
        if segments is not None:
            seg_refs, rest = rest[:2], rest[2:]
        else:
            seg_refs = ()
        kb_ref = rest[0] if kbias is not None else None
        dq_ref, dq_scr = rest[-2:]
        _flash_bwd_dq_kernel(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dq_scr,
            seg_refs=seg_refs, off_ref=off_ref, kb_ref=kb_ref, **common,
        )

    dq_grid = (bh // bb, s // block_q, sk // block_k)
    dq_out_spec = pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0))
    dq_out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dq_scratch = [pltpu.VMEM((bb, block_q, d), jnp.float32)]
    if q_offset is not None:
        dq = pl.pallas_call(
            dq_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=dq_grid, in_specs=in_specs,
                out_specs=dq_out_spec, scratch_shapes=dq_scratch,
            ),
            out_shape=dq_out_shape,
            interpret=interpret,
            compiler_params=params,
        )(off_arr, *args)
    else:
        dq = pl.pallas_call(
            dq_kernel,
            grid=dq_grid,
            in_specs=in_specs,
            out_specs=dq_out_spec,
            out_shape=dq_out_shape,
            scratch_shapes=dq_scratch,
            interpret=interpret,
            compiler_params=params,
        )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Pallas decode kernel — q [sq small] vs a static KV cache [L], cache
# validity expressed IN-KERNEL from the write position (passed as a scalar)
# instead of an additive mask, so cached/serving attention never drops to
# the XLA fallback (reference: the inference runtime's flash-decode path,
# SURVEY §2.1 L8; round-4 verdict "flash-kernel decode attention").
# ---------------------------------------------------------------------------


def _decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, block_q, block_k,
):
    """Grid (bh blocks, q blocks, k blocks), k innermost.  Query row i of
    q-block qi sits at absolute position pos + qi*block_q + i and may attend
    cache slots j <= that position — which by construction covers exactly
    the written slots, so no separate validity mask exists anywhere."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    pos = pos_ref[0]
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # blocks entirely beyond the last valid slot contribute nothing
    needed = k_start <= pos + q_start + block_q - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[...]  # [bb, block_q, d]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale  # [bb, block_q, block_k]
        q_ids = pos + q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_ids >= k_ids, s, _NEG_INF)
        m = m_scr[..., 0]
        l = l_scr[..., 0]
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new[..., None]
        l_scr[...] = (alpha * l + p.sum(-1))[..., None]
        acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[..., 0], 1e-30)
        o_ref[...] = (acc_scr[...] / l_safe[..., None]).astype(o_ref.dtype)


def _pallas_decode_forward(q, k, v, pos, scale, interpret=False):
    """q: [bh, sq, d] (sq pre-padded to the q block); k,v: [bh, L, d] cache
    buffers; pos: int32[1] scalar-prefetch.  Returns out [bh, sq, d]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    L = k.shape[1]
    block_q = sq if sq <= 256 else 128  # padded to 8/128 multiples by caller
    block_k = _pick_block(L, 512)
    # VMEM budget: score/prob temporaries + one K/V tile per bh row
    per_bb = block_q * block_k * 4 * 2 + 2 * block_k * d * 2 + 4 * block_q * d * 4
    limit = max(1, (8 * 1024 * 1024) // max(per_bb, 1))
    bb = 1
    for c in range(1, min(limit, bh) + 1):
        if bh % c == 0:
            bb = c
    grid = (bh // bb, sq // block_q, L // block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((bb, block_k, d), lambda b, i, j, *_: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((bb, block_q, d), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bb, block_q, 1), jnp.float32),
            pltpu.VMEM((bb, block_q, 1), jnp.float32),
            pltpu.VMEM((bb, block_q, d), jnp.float32),
        ],
    )

    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        _decode_kernel(
            pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            scale=scale, block_q=block_q, block_k=block_k,
        )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, k, v)


def decode_attention_array(q, k, v, pos, scale=None):
    """Cached-attention for the static-KV decode path.

    q: [b, sq, h, d] (the fresh chunk); k,v: [b, L, kv_h, d] cache buffers
    (every slot, written or not); pos: scalar int32 — absolute position of
    q row 0 — or int32[b] PER-BATCH-ROW positions (each row decodes at its
    own length, still one executable).
    Row i attends cache slots j <= pos + i.  Pallas on TPU (or under
    interpret); a fused dense XLA path elsewhere — both take validity from
    `pos`, never from a mask array.  Vector pos always takes the dense path
    (single-token decode is its domain and the dense matvec is the optimal
    lowering there anyway).

    Per-row pos composes with sq > 1: this is the speculative-decoding
    VERIFY contract (ISSUE 11).  A [b, k+1] draft window at per-slot
    positions runs one dense pass where window row i of slot s attends
    j <= pos[s] + i — row 0 reproduces the single-token decode step exactly
    (same reduction geometry per row), and the extra k rows are the
    near-free FLOPs speculation converts into accepted tokens.  Garbage
    cache rows beyond a slot's true length sit at j > pos + i and carry
    zero weight, so rejected-draft leftovers from a previous verify step
    are never attended before the next window overwrites them.
    """
    b, sq, h, d = q.shape
    per_row_pos = jnp.ndim(pos) == 1
    L = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qt = jnp.transpose(q, (0, 2, 1, 3))  # [b, h, sq, d]
    kt = jnp.transpose(k, (0, 2, 1, 3))  # [b, hk, L, d] — NEVER repeated:
    vt = jnp.transpose(v, (0, 2, 1, 3))  # GQA groups share the cache as-is
    hk = kt.shape[1]
    rep = h // hk
    interpret = _FORCE_INTERPRET
    # kernel choice by q-chunk size: single-token (and small-chunk) decode
    # is a matvec per head — the dense XLA lowering fuses it into the
    # surrounding program with zero launch overhead and IS the optimal
    # flash-decode for q=1 (measured: Pallas per-layer launches cost ~30%
    # of decode tok/s).  The Pallas kernel wins for prefill-with-cache,
    # where it avoids materializing the [sq, L] score block.
    use_pallas = (
        (_on_tpu() or interpret)
        and not per_row_pos
        and d <= 256
        and L % 128 == 0
        and sq >= 64
    )
    if use_pallas:
        from ..distributed import mesh as _mesh

        if _mesh.axis_size("mp") > 1:
            # the kernel flattens (batch, kv heads) into one grid dim, which
            # a heads-sharded mesh cannot map per device, and GSPMD cannot
            # partition a Mosaic call; it can partition the dense path below
            _log_pallas_fallback("decode kernel under an mp mesh", shape=q.shape)
            use_pallas = False
    if use_pallas:
        # pad q rows up to the TPU sublane tile; padded rows attend slot 0+
        # legitimately (their q_ids exceed the real rows') and are sliced off.
        # The common serving shapes are already 8/128-aligned — hoist the
        # check so they take a zero-copy path (no per-group pad OR slice)
        sq_pad = -(-sq // 8) * 8 if sq <= 256 else -(-sq // 128) * 128
        needs_pad = sq_pad != sq
        _log_pallas_call("decode")
        kf = kt.reshape(b * hk, L, d)
        vf = vt.reshape(b * hk, L, d)
        # one kernel call per GQA group: q heads of group r run against the
        # UN-duplicated cache (a jnp.repeat would materialize rep copies of
        # the whole cache per layer per step)
        qg = qt.reshape(b, hk, rep, sq, d)
        outs = []
        for r in range(rep):
            qf = qg[:, :, r].reshape(b * hk, sq, d)
            if needs_pad:
                qf = jnp.pad(qf, ((0, 0), (0, sq_pad - sq), (0, 0)))
            o = _pallas_decode_forward(qf, kf, vf, pos, scale, interpret=interpret)
            if needs_pad:
                o = o[:, :sq]
            outs.append(o.reshape(b, hk, 1, sq, d))
        out = outs[0] if rep == 1 else jnp.concatenate(outs, axis=2)
        return jnp.transpose(out.reshape(b, h, sq, d), (0, 2, 1, 3))
    # dense path: grouped einsum chain (kv heads stay un-repeated; the GQA
    # broadcast happens inside the contraction), validity from pos
    q5 = qt.reshape(b, hk, rep, sq, d)
    s = jnp.einsum(
        "bgrqd,bgkd->bgrqk", q5, kt, preferred_element_type=jnp.float32
    ) * scale
    iota_q = jax.lax.broadcasted_iota(jnp.int32, (sq, L), 0)
    if per_row_pos:
        # [b, 1, 1, sq, L] broadcast against s [b, g, r, sq, L]
        q_ids = pos.reshape(b, 1, 1, 1, 1) + iota_q
    else:
        q_ids = pos + iota_q
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, L), 1)
    s = jnp.where(q_ids >= k_ids, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bgrqk,bgkd->bgrqd", p.astype(vt.dtype), vt, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    return jnp.transpose(out.reshape(b, h, sq, d), (0, 2, 1, 3))


def flash_decode(query, key, value, pos, scale=None):
    """Tensor-level cached-decode attention (see decode_attention_array)."""
    query, key, value, pos = coerce(query), coerce(key), coerce(value), coerce(pos)

    def f(q, k, v, p):
        return decode_attention_array(q, k, v, p, scale)

    return apply(f, [query, key, value, pos], name="flash_decode")


def paged_gather_kv(arena, tables, max_len):
    """Gather a paged arena [num_pages, kv_h, page_size, d] back into dense
    per-sequence buffers [b, max_len, kv_h, d] through the page tables
    ([b, P] int32).  The reshape-then-slice gives each sequence a dense
    [max_len] cache (P * page_size >= max_len; the slack
    rows come from the sequence's own trailing page and are masked by pos
    downstream anyway)."""
    b = tables.shape[0]
    g = arena[tables]  # [b, P, kv_h, page_size, d]
    g = jnp.swapaxes(g, 2, 3)  # [b, P, page_size, kv_h, d]
    g = g.reshape(b, -1, arena.shape[1], arena.shape[3])
    return g[:, :max_len]


def paged_gather_scale(scale, tables, max_len):
    """`paged_gather_kv` for a scale arena [num_pages, kv_h, 1, page_size]:
    returns [b, max_len, kv_h, 1], the per-(row, kv head) factors aligned
    with the gathered K/V rows."""
    b = tables.shape[0]
    g = scale[tables]  # [b, P, kv_h, 1, page_size]
    g = jnp.transpose(g, (0, 1, 4, 2, 3))  # [b, P, page_size, kv_h, 1]
    g = g.reshape(b, -1, scale.shape[1], 1)
    return g[:, :max_len]


def _fused_paged_decode_forward(q, arena_k, arena_v, tables, pos, max_len,
                                scale, interpret=False, first=None):
    """Fused paged-decode attention: read the arena THROUGH the page tables
    in-kernel instead of materializing the gather (`paged_gather_kv` writes
    a dense [b, max_len, kv_h, d] copy of every sequence's KV to HBM each
    step — the single biggest HBM tax on the serving hot path; ROADMAP 4).

    q: [b, sq, h, d] (sq == 1 plain decode, sq == k+1 speculative verify);
    arena_k/v: [num_pages, kv_h, page_size, d] — (page_size, d) minor, so a
    (page, kv head) tile is a whole trailing [page_size, d] block of the
    array and all heads of a page are one contiguous block of HBM;
    `arena_v=None` says the values ARE the keys' rows (MLA's absorbed
    decode over its latent arena): the page is copied once and both dots
    read the one buffer; tables: [b, P] int32 page ids (traced DATA, fed as
    scalar-prefetch); pos: int32 scalar or [b] per-slot positions.

    The walk is bound by the COUNT of its grid steps, not by their bytes
    (PR 30, one v5e: 0.2-0.3 us a step whether it moves 64 KB or nothing),
    so a q block of few rows (`_PAGED_WALK_LOOP_ROWS`: decode, the verify
    window) takes the walk OFF the grid.  The grid is (slot, kv-head block)
    alone, `hb` as many heads as the static shape leaves VMEM for
    (`_pick_kv_heads_block`: all of a page's).  The arenas stay in HBM
    (`pl.ANY`); inside a grid step the kernel loops over the slot's OWN
    pages, `(pos + sq - 1) // page_size + 1` of them (a traced bound: no
    step, copy or compute exists for a column past the slot's newest
    visible page, whatever the table holds there), `pages_per_step`
    (`_pick_pages_per_step`) at a time by its own `make_async_copy` from
    `arena.at[page]` into one of two VMEM blocks, the next block in flight
    while this one is computed, and the next grid step's first block
    started before this step's last compute, so a slot's start waits for
    no copy of its own.  Online softmax (m, l, acc) carries in scratch block
    by block (one dot pair a block: a dot pair a page, each under its own
    guard, kept the scheduler from overlapping them and read 2.0 ms on the
    chip where this reads 0.9, PR 34) — the same recurrence as
    `_flash_fwd_kernel`, the head a batch dim of both dots, walking pages
    in table order.  The room of a page the last block does not hold is
    zeroed, not copied.

    A q block of many rows (a chunk prefill, whose rows fill VMEM: `hb` 1)
    keeps the grid (slot, kv-head block, page), one page tile a step through
    the BlockSpec pipeline, the table clamped in XLA at the slot's newest
    visible page so later steps copy nothing and `needed` skips their
    compute.

    Each slot's q rows for one kv head pack the whole GQA group x verify
    window ([rep * sq, d], row r = group member r // sq at window offset
    r % sq), so the un-duplicated cache tile is read ONCE per group.
    In-kernel masks reproduce the gather path bit-for-bit: `jid <= pos + w`
    is the per-row causal/validity fence (also inert for inactive slots
    parked on scratch page 0 at pos 0, which walk exactly that page) and
    `jid < max_len` reproduces the gather's `[:max_len]` slice of the
    trailing page's slack rows.

    `first` ([b] int32, data; the looping walk only): each slot's FIRST
    visible position, for a layer whose attention is windowed.  The walk then
    starts at page `first // page_size` (no copy, step or compute exists for
    a page before it, whatever the table holds there: the page manager has
    released it), rows of that page below `first` are masked, and the block
    count follows the pages in reach.  A third scalar-prefetch operand and
    one more compare a block; without it the traced kernel is the one above,
    to the instruction.

    Returns [b, sq, h, d]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import profiler as _prof

    b, sq, h, d = q.shape
    hk = arena_k.shape[1]
    ps = arena_k.shape[2]
    itemsize = arena_k.dtype.itemsize
    rep = h // hk
    P = tables.shape[1]
    R = rep * sq
    qr = -(-R // 8) * 8  # f32 sublane tile; pad rows are sliced off
    hb = _pick_kv_heads_block(hk, qr, ps, d, itemsize)
    looped = qr <= _PAGED_WALK_LOOP_ROWS
    windowed = first is not None
    if windowed and not looped:
        raise ValueError("a windowed page walk takes a decode step's rows, not a chunk's")
    if arena_v is None and not looped:
        arena_v = arena_k  # the pipeline of the grid brings a tile an operand
    arenas = (arena_k,) if arena_v is None else (arena_k, arena_v)
    pp = _pick_pages_per_step(hb, qr, ps, d, itemsize, len(arenas), P) if looped else 1
    _prof.record_paged_walk(
        b=b, sq=sq, heads_per_step=hb,
        grid_steps=b * (hk // hb) * (1 if looped else P),
        kv_bytes_per_step=len(arenas) * pp * hb * ps * d * itemsize,
        pages_per_step=pp, kv_operands=len(arenas),
    )
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(b, hk, rep, sq, d)
    qg = qt.reshape(b, hk, R, d)
    if qr != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, qr - R), (0, 0)))
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    tab = jnp.asarray(tables, jnp.int32)

    def _init(m_scr, l_scr, acc_scr):
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(qb, kb, vb, first_row, p0, m_scr, l_scr, acc_scr, lo=None):
        """qb [hb, qr, d] against kb and vb [hb, n, d], the slot's rows
        `first_row ..`: a page, or a block of pages in table order.  `lo`:
        the slot's first visible position (a windowed walk)."""
        n = kb.shape[1]
        s = jax.lax.dot_general(
            qb, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [hb, qr, n]
        w = jax.lax.broadcasted_iota(jnp.int32, (qr, n), 0) % sq if sq > 1 else 0
        jid = first_row + jax.lax.broadcasted_iota(jnp.int32, (qr, n), 1)
        seen = (jid <= p0 + w) & (jid < max_len)
        if lo is not None:
            seen &= jid >= lo
        s = jnp.where(seen, s, _NEG_INF)
        m = m_scr[...]  # [hb, qr, 1]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    def _finish(o_ref, l_scr, acc_scr):
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)

    def grid_kernel(t_ref, p_ref, q_ref, k_ref, v_ref, o_ref, *scr):
        j = pl.program_id(2)
        p0 = p_ref[pl.program_id(0)]
        pl.when(j == 0)(lambda: _init(*scr))
        # pages entirely beyond the newest visible position (window row
        # sq-1 sees up to pos + sq - 1) contribute nothing
        pl.when(j * ps <= p0 + sq - 1)(
            lambda: _compute(q_ref[...], k_ref[...], v_ref[...], j * ps, p0, *scr))
        pl.when(j == pl.num_programs(2) - 1)(lambda: _finish(o_ref, *scr[1:]))

    n_groups = hk // hb

    def loop_kernel(t_ref, p_ref, *rest):
        f_ref, (q_ref, *rest) = (rest[0], rest[1:]) if windowed else (None, rest)
        n = len(arenas)  # the arenas, the output, m l acc, a buffer an arena
        hbm, (o_ref, *scr), bufs, (sem, first) = rest[:n], rest[n:n + 4], rest[n + 4:-2], rest[-2:]
        slot, g = pl.program_id(0), pl.program_id(1)

        def page0(s):  # the table column the slot's walk starts at
            return f_ref[s] // ps if windowed else 0

        def n_pages(s):  # every slot holds one: an idle one is parked on page 0
            return jnp.minimum((p_ref[s] + sq - 1) // ps + 1, P) - page0(s)

        def block(s, grp, blk, buf, wait):
            """Start, or wait for, the copies of block `blk` of slot `s`, head
            group `grp`, into buffer `buf`: a copy a page the slot holds.
            Waiting, the room of a page it does not hold (the last block's
            tail) is zeroed in the values' buffer: what an earlier block or a
            new kernel left there is multiplied by a weight of 0, and may not
            be a NaN."""
            held = n_pages(s)
            for i in range(pp):
                rows_i = pl.ds(i * ps, ps)

                def page(i=i, rows_i=rows_i):
                    src = t_ref[s * P + page0(s) + blk * pp + i]
                    for a in range(n):
                        tile = hbm[a].at[src] if hb == hk else hbm[a].at[src, pl.ds(grp * hb, hb)]
                        copy = pltpu.make_async_copy(
                            tile, bufs[a].at[buf, :, rows_i], sem.at[a, buf])
                        copy.wait() if wait else copy.start()

                def no_page(rows_i=rows_i):
                    bufs[-1][buf, :, rows_i] = jnp.zeros((hb, ps, d), arena_k.dtype)

                pl.when(blk * pp + i < held)(page)
                if wait and i:  # a block that exists holds its first page
                    pl.when(blk * pp + i >= held)(no_page)

        @pl.when((slot == 0) & (g == 0))
        def _first():
            first[0] = 0
            block(slot, g, 0, 0, wait=False)

        _init(*scr)
        p0 = p_ref[slot]
        n_blocks = (n_pages(slot) + pp - 1) // pp
        buf0 = first[0]  # where the step before put this step's first block
        g_next = jnp.where(g == n_groups - 1, 0, g + 1)
        s_next = jnp.where(g == n_groups - 1, slot + 1, slot)

        def body(blk, carry):
            buf = (buf0 + blk) % 2
            pl.when(blk + 1 < n_blocks)(
                lambda: block(slot, g, blk + 1, 1 - buf, wait=False))
            # the next grid step's first block, ahead of this step's last compute
            pl.when((blk + 1 == n_blocks) & (s_next < b))(
                lambda: block(s_next, g_next, 0, 1 - buf, wait=False))
            block(slot, g, blk, buf, wait=True)
            _compute(q_ref[...], bufs[0][buf], bufs[-1][buf], (page0(slot) + blk * pp) * ps, p0, *scr,
                     lo=f_ref[slot] if windowed else None)
            return carry

        jax.lax.fori_loop(0, n_blocks, body, 0)
        first[0] = (buf0 + n_blocks) % 2
        _finish(o_ref, *scr[1:])

    softmax_state = [
        pltpu.VMEM((hb, qr, 1), jnp.float32),
        pltpu.VMEM((hb, qr, 1), jnp.float32),
        pltpu.VMEM((hb, qr, d), jnp.float32),
    ]
    if looped:
        rows = pl.BlockSpec((None, hb, qr, d), lambda s, g, *scalars: (s, g, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 if windowed else 2,
            grid=(b, n_groups),
            in_specs=[rows] + [pl.BlockSpec(memory_space=pl.ANY)] * len(arenas),
            out_specs=rows,
            scratch_shapes=softmax_state
            + [pltpu.VMEM((2, hb, pp * ps, d), arena_k.dtype)] * len(arenas)
            + [pltpu.SemaphoreType.DMA((len(arenas), 2)), pltpu.SMEM((1,), jnp.int32)],
        )
        kernel = loop_kernel
    else:
        # columns past a slot's newest visible page repeat that page's entry.
        # Clamped here and not in the index map: there the divide and the min
        # run for every grid step and operand (PR 30, on the chip: 0.267 ms a
        # call against 0.248 with this, 0.246 with no clamp at all)
        col = jnp.minimum(jnp.arange(P, dtype=jnp.int32), ((pos_v + sq - 1) // ps)[:, None])
        tab = jnp.take_along_axis(tab, col, axis=1)
        rows = pl.BlockSpec((None, hb, qr, d), lambda s, g, j, t, p: (s, g, 0, 0))
        page_tile = pl.BlockSpec(
            (None, hb, ps, d), lambda s, g, j, t, p: (t[s * P + j], g, 0, 0)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_groups, P),
            in_specs=[rows, page_tile, page_tile],
            out_specs=rows,
            scratch_shapes=softmax_state,
        )
        kernel = grid_kernel
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, qr, d), q.dtype),
        interpret=interpret,
        name="paged_walk_decode",
    )(tab.reshape(-1), pos_v, *([jnp.asarray(first, jnp.int32).reshape(b)] if windowed else []),
      qg, *arenas)
    out = out[:, :, :R].reshape(b, hk, rep, sq, d).reshape(b, h, sq, d)
    return jnp.transpose(out, (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_paged_decode(q, arena_k, arena_v, tables, pos, max_len, scale,
                        interpret):
    """Differentiation-opaque wrapper: the dispatch layer's eager path
    computes a vjp over every op, and scalar-prefetch pallas_call has no JVP
    rule — decode is inference-only, so the vjp is declared (never pulled)
    via custom_vjp instead of traced through the kernel."""
    return _fused_paged_decode_forward(
        q, arena_k, arena_v, tables, pos, max_len, scale, interpret=interpret
    )


def _fused_paged_decode_fwd(q, arena_k, arena_v, tables, pos, max_len, scale,
                            interpret):
    out = _fused_paged_decode_forward(
        q, arena_k, arena_v, tables, pos, max_len, scale, interpret=interpret
    )
    return out, None


def _fused_paged_decode_bwd(max_len, scale, interpret, res, g):
    raise NotImplementedError(
        "fused paged decode attention is inference-only (no backward); "
        "differentiate through kernel='gather' instead"
    )


_fused_paged_decode.defvjp(_fused_paged_decode_fwd, _fused_paged_decode_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused_paged_decode_window(q, arena_k, arena_v, tables, pos, first, max_len, scale, interpret):
    """`_fused_paged_decode` for a windowed layer: `first` [b] int32 is each
    slot's first visible position (`_fused_paged_decode_forward`)."""
    return _fused_paged_decode_forward(
        q, arena_k, arena_v, tables, pos, max_len, scale, interpret=interpret, first=first)


_fused_paged_decode_window.defvjp(
    lambda q, ak, av, t, p, f, max_len, scale, interpret: (
        _fused_paged_decode_forward(q, ak, av, t, p, max_len, scale, interpret=interpret, first=f), None),
    lambda max_len, scale, interpret, res, g: _fused_paged_decode_bwd(max_len, scale, interpret, res, g))


def _fused_paged_decode_quant_forward(q, arena_k, arena_v, k_scale, v_scale,
                                      tables, pos, max_len, scale,
                                      interpret=False):
    """`_fused_paged_decode_forward` over an int8 arena (ISSUE 18), one
    (page, kv head) tile a grid step (ROADMAP 3.3): the K/V
    page tiles arrive as int8 and their per-row scales ([1, page_size]
    float32 tiles from the parallel scale arenas `[num_pages, kv_h, 1,
    page_size]`, addressed by the SAME `t[s*P+j]` table lookup in their
    BlockSpec index maps) ride into VMEM with them.  The scale rows lie
    along LANES: a trailing unit dim would make XLA relayout the whole
    scale arena into a 128x lane-padded copy in front of every call.
    Dequantization happens per page tile inside the online-softmax loop,
    so the arena's HBM footprint is what streams: 1 byte per element plus
    4 bytes per (row, head) instead of 2.  q is cast to f32 in-kernel so
    the dots run at the dequantized precision the gather oracle uses —
    fused and gather agree to float reassociation under quantization too.
    Masks and the softmax recurrence are those of the unquantized kernel:
    scratch-page garbage scales are finite by construction and fenced by
    `jid <= pos + w` before they could reach a softmax."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    hk = arena_k.shape[1]
    ps = arena_k.shape[2]
    rep = h // hk
    P = tables.shape[1]
    R = rep * sq
    qr = -(-R // 8) * 8  # f32 sublane tile; pad rows are sliced off
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(b, hk, rep, sq, d)
    qg = qt.reshape(b, hk, R, d)
    if qr != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, qr - R), (0, 0)))
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    tab = jnp.asarray(tables, jnp.int32).reshape(-1)

    def kernel(t_ref, p_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
               m_scr, l_scr, acc_scr):
        j = pl.program_id(2)
        n_p = pl.num_programs(2)
        p0 = p_ref[pl.program_id(0)]

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        needed = j * ps <= p0 + sq - 1

        @pl.when(needed)
        def _compute():
            qb = q_ref[...].astype(jnp.float32)  # [qr, d]
            kb = k_ref[...].astype(jnp.float32)  # [ps, d] raw int8 values
            vb = v_ref[...].astype(jnp.float32)
            # in-VMEM dequant, factored out of the contractions: a row's
            # scale is constant over head_dim, so q.(k*ks) == (q.k)*ks and
            # p.(v*vs) == (p*vs).v — the [1, ps] scale rows multiply score
            # COLUMNS instead of [ps, d] tiles
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * ks_ref[...] * scale  # [qr, ps]
            w = jax.lax.broadcasted_iota(jnp.int32, (qr, ps), 0) % sq
            jid = j * ps + jax.lax.broadcasted_iota(jnp.int32, (qr, ps), 1)
            s = jnp.where((jid <= p0 + w) & (jid < max_len), s, _NEG_INF)
            m = m_scr[..., 0]
            l = l_scr[..., 0]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            m_scr[...] = m_new[..., None]
            l_scr[...] = (alpha * l + p.sum(-1))[..., None]
            acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
                p * vs_ref[...], vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(j == n_p - 1)
        def _finish():
            l_safe = jnp.maximum(l_scr[..., 0], 1e-30)
            o_ref[...] = (acc_scr[...] / l_safe[..., None]).astype(o_ref.dtype)

    page_tile = pl.BlockSpec(
        (None, None, ps, d), lambda s, g, j, t, p: (t[s * P + j], g, 0, 0)
    )
    scale_tile = pl.BlockSpec(
        (None, None, 1, ps), lambda s, g, j, t, p: (t[s * P + j], g, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, P),
        in_specs=[
            pl.BlockSpec((None, None, qr, d), lambda s, g, j, t, p: (s, g, 0, 0)),
            page_tile,
            page_tile,
            scale_tile,
            scale_tile,
        ],
        out_specs=pl.BlockSpec(
            (None, None, qr, d), lambda s, g, j, t, p: (s, g, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((qr, 1), jnp.float32),
            pltpu.VMEM((qr, 1), jnp.float32),
            pltpu.VMEM((qr, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, qr, d), q.dtype),
        interpret=interpret,
    )(tab, pos_v, qg, arena_k, arena_v, k_scale, v_scale)
    out = out[:, :, :R].reshape(b, hk, rep, sq, d).reshape(b, h, sq, d)
    return jnp.transpose(out, (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _fused_paged_decode_quant(q, arena_k, arena_v, k_scale, v_scale, tables,
                              pos, max_len, scale, interpret):
    """Differentiation-opaque wrapper over the quantized fused kernel —
    same contract as `_fused_paged_decode` (decode is inference-only)."""
    return _fused_paged_decode_quant_forward(
        q, arena_k, arena_v, k_scale, v_scale, tables, pos, max_len, scale,
        interpret=interpret,
    )


def _fused_paged_decode_quant_fwd(q, arena_k, arena_v, k_scale, v_scale,
                                  tables, pos, max_len, scale, interpret):
    out = _fused_paged_decode_quant_forward(
        q, arena_k, arena_v, k_scale, v_scale, tables, pos, max_len, scale,
        interpret=interpret,
    )
    return out, None


def _fused_paged_decode_quant_bwd(max_len, scale, interpret, res, g):
    raise NotImplementedError(
        "quantized fused paged decode attention is inference-only (no "
        "backward); differentiate through kernel='gather' instead"
    )


_fused_paged_decode_quant.defvjp(
    _fused_paged_decode_quant_fwd, _fused_paged_decode_quant_bwd
)


def _fused_paged_viable(q, page_size):
    """Static eligibility for the fused paged kernel.  The arena page IS
    the kernel's K/V block, so page_size must be a sublane multiple; head
    dim is bounded by the same VMEM budget as the dense kernels."""
    if q.shape[3] > 256:
        return False, "paged head_dim > 256"
    if page_size % 8 != 0:
        return False, "paged page_size not 8-aligned"
    return True, None


def _fused_paged_decode_tp(q, arena_k, arena_v, tables, pos, max_len, scale,
                           interpret, k_scale=None, v_scale=None):
    """Tensor-parallel dispatch of the fused kernels: `shard_map` over the
    'mp' mesh axis, q/output split on their HEADS dim (axis 2), the arenas
    (and, quantized, their scale arenas) on their kv-heads dim (axis 1),
    tables/pos replicated, so each device's `pallas_call` streams only its
    local kv heads' pages.  GSPMD cannot partition a custom call — without
    the shard_map it would all-gather the whole arena onto every device.

    The GQA head packing keeps locality exact: q head `hk*rep + r` belongs
    to kv head `hk`, and contiguous 'mp' sharding of both head axes gives
    device d q heads [d*h/mp, (d+1)*h/mp) == the rep-block of its kv heads
    [d*hk/mp, (d+1)*hk/mp) — each local kernel is byte-identical to a
    single-device kernel over a model with h/mp heads.  check_vma=False:
    tables/pos stay replicated but the output is genuinely sharded."""
    from jax.sharding import PartitionSpec as P

    from ..distributed import mesh as _mesh

    q_heads = P(None, None, "mp", None)
    kv_heads = P(None, "mp", None, None)
    if k_scale is None:
        def body(qq, ak, av, t, p):
            return _fused_paged_decode(
                qq, ak, av, t, p, max_len, scale, interpret
            )
        ins, specs = (), ()
    else:
        def body(qq, ak, av, ks, vs, t, p):
            return _fused_paged_decode_quant(
                qq, ak, av, ks, vs, t, p, max_len, scale, interpret
            )
        ins, specs = (k_scale, v_scale), (kv_heads, kv_heads)
    fn = jax.shard_map(
        body,
        mesh=_mesh.get_mesh(),
        in_specs=(q_heads, kv_heads, kv_heads) + specs + (P(None, None), P(None)),
        out_specs=q_heads,
        check_vma=False,
    )
    return fn(q, arena_k, arena_v, *ins, tables, pos)


def _fused_paged_decode_partials_forward(q, arena_k, arena_v, tables,
                                         page_base, pos, max_len, scale,
                                         interpret=False, k_scale=None,
                                         v_scale=None):
    """The fused paged-decode kernel in PARTIALS form, for context-parallel
    decode (ISSUE 20): identical page-walk, GQA/verify packing, and online-
    softmax recurrence to `_fused_paged_decode_forward` (but one (page, kv
    head) tile a grid step, that kernel's grid before PR 30; ROADMAP 3.3),
    with two changes.

    (1) Table columns no longer imply token positions.  Under cp, shard s
    holds sequence pages {s, s+cp, ...} as LOCAL table columns 0..P_l-1, so
    the caller passes `page_base` (int32 [P_l], scalar-prefetch): column j's
    first token position.  The masks become `page_base[j] + lane` where the
    single-device kernel uses `j*ps + lane` — at cp=1 with
    page_base[j] = j*ps they are the same arithmetic.

    (2) No `_finish` divide.  The kernel emits its raw online-softmax state
    — acc [b, hk, qr, d], m [b, hk, qr, 1], l [b, hk, qr, 1], all float32 —
    so shards can merge exactly:

        m*   = max_s m_s
        l*   = sum_s l_s * exp(m_s - m*)
        acc* = sum_s acc_s * exp(m_s - m*)
        out  = acc* / max(l*, eps)

    which is the SAME two-term merge the kernel itself applies page by page,
    just reassociated across shards (see `cp_softmax_combine`).  A shard
    whose every key is masked reports m = -inf, l = 0, acc = 0 and drops out
    of the sums; the round-robin layout puts sequence page 0 (token 0) on
    shard 0, so every active row has a finite global m.

    Passing `k_scale`/`v_scale` selects the int8 arena variant: page tiles
    dequantize in VMEM exactly as in `_fused_paged_decode_quant_forward`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quant = k_scale is not None
    b, sq, h, d = q.shape
    hk = arena_k.shape[1]
    ps = arena_k.shape[2]
    rep = h // hk
    P = tables.shape[1]
    R = rep * sq
    qr = -(-R // 8) * 8  # f32 sublane tile; pad rows are sliced off
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(b, hk, rep, sq, d)
    qg = qt.reshape(b, hk, R, d)
    if qr != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, qr - R), (0, 0)))
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    tab = jnp.asarray(tables, jnp.int32).reshape(-1)
    base = jnp.asarray(page_base, jnp.int32).reshape(-1)

    def kernel(t_ref, base_ref, p_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, oa_ref, om_ref, ol_ref, m_scr, l_scr, acc_scr = rest
        else:
            oa_ref, om_ref, ol_ref, m_scr, l_scr, acc_scr = rest
        j = pl.program_id(2)
        n_p = pl.num_programs(2)
        p0 = p_ref[pl.program_id(0)]
        j0 = base_ref[j]

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # pages entirely beyond the newest visible position (window row
        # sq-1 sees up to pos + sq - 1) contribute nothing
        needed = j0 <= p0 + sq - 1

        @pl.when(needed)
        def _compute():
            if quant:
                qb = q_ref[...].astype(jnp.float32)
                kb = k_ref[...].astype(jnp.float32)
                vb = v_ref[...].astype(jnp.float32)
            else:
                qb = q_ref[...]  # [qr, d]
                kb = k_ref[...]  # [ps, d] — the page this table entry names
                vb = v_ref[...]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [qr, ps]
            if quant:
                s = s * ks_ref[...]  # [1, ps] row scales on score columns
            s = s * scale
            w = jax.lax.broadcasted_iota(jnp.int32, (qr, ps), 0) % sq
            jid = j0 + jax.lax.broadcasted_iota(jnp.int32, (qr, ps), 1)
            s = jnp.where((jid <= p0 + w) & (jid < max_len), s, _NEG_INF)
            m = m_scr[..., 0]
            l = l_scr[..., 0]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            m_scr[...] = m_new[..., None]
            l_scr[...] = (alpha * l + p.sum(-1))[..., None]
            pv = p * vs_ref[...] if quant else p.astype(vb.dtype)
            acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
                pv, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(j == n_p - 1)
        def _emit():
            # partials out, UN-normalized: the cross-shard combine divides.
            # exp(m) can overflow where m is the -inf init of a fully masked
            # row; the combine's exp(m - m*) handles that, not us.
            oa_ref[...] = acc_scr[...]
            om_ref[...] = m_scr[...]
            ol_ref[...] = l_scr[...]

    page_tile = pl.BlockSpec(
        (None, None, ps, d), lambda s, g, j, t, bb, p: (t[s * P + j], g, 0, 0)
    )
    scale_tile = pl.BlockSpec(
        (None, None, 1, ps), lambda s, g, j, t, bb, p: (t[s * P + j], g, 0, 0)
    )
    q_tile = pl.BlockSpec(
        (None, None, qr, d), lambda s, g, j, t, bb, p: (s, g, 0, 0)
    )
    ml_tile = pl.BlockSpec(
        (None, None, qr, 1), lambda s, g, j, t, bb, p: (s, g, 0, 0)
    )
    in_specs = [q_tile, page_tile, page_tile]
    ins = [tab, base, pos_v, qg, arena_k, arena_v]
    if quant:
        in_specs += [scale_tile, scale_tile]
        ins += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hk, P),
        in_specs=in_specs,
        out_specs=[q_tile, ml_tile, ml_tile],
        scratch_shapes=[
            pltpu.VMEM((qr, 1), jnp.float32),
            pltpu.VMEM((qr, 1), jnp.float32),
            pltpu.VMEM((qr, d), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, qr, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hk, qr, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hk, qr, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*ins)
    return acc, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused_paged_decode_partials(q, arena_k, arena_v, tables, page_base, pos,
                                 max_len, scale, interpret):
    """Differentiation-opaque wrapper over the partials kernel — same
    contract as `_fused_paged_decode` (decode is inference-only)."""
    return _fused_paged_decode_partials_forward(
        q, arena_k, arena_v, tables, page_base, pos, max_len, scale,
        interpret=interpret,
    )


def _fused_paged_decode_partials_fwd(q, arena_k, arena_v, tables, page_base,
                                     pos, max_len, scale, interpret):
    out = _fused_paged_decode_partials_forward(
        q, arena_k, arena_v, tables, page_base, pos, max_len, scale,
        interpret=interpret,
    )
    return out, None


def _fused_paged_decode_partials_bwd(max_len, scale, interpret, res, g):
    raise NotImplementedError(
        "context-parallel fused paged decode is inference-only (no backward)"
    )


_fused_paged_decode_partials.defvjp(
    _fused_paged_decode_partials_fwd, _fused_paged_decode_partials_bwd
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _fused_paged_decode_partials_q8(q, arena_k, arena_v, k_scale, v_scale,
                                    tables, page_base, pos, max_len, scale,
                                    interpret):
    """Quantized partials kernel, differentiation-opaque (see above)."""
    return _fused_paged_decode_partials_forward(
        q, arena_k, arena_v, tables, page_base, pos, max_len, scale,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )


def _fused_paged_decode_partials_q8_fwd(q, arena_k, arena_v, k_scale, v_scale,
                                        tables, page_base, pos, max_len,
                                        scale, interpret):
    out = _fused_paged_decode_partials_forward(
        q, arena_k, arena_v, tables, page_base, pos, max_len, scale,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )
    return out, None


def _fused_paged_decode_partials_q8_bwd(max_len, scale, interpret, res, g):
    raise NotImplementedError(
        "context-parallel quantized fused paged decode is inference-only"
    )


_fused_paged_decode_partials_q8.defvjp(
    _fused_paged_decode_partials_q8_fwd, _fused_paged_decode_partials_q8_bwd
)


def cp_softmax_combine(acc, m, l, eps=1e-30):
    """Merge per-shard online-softmax partials into finished attention.

    Given shard partials acc_s = sum_j e^{s_j - m_s} v_j, m_s = max_j s_j,
    l_s = sum_j e^{s_j - m_s} over DISJOINT key sets (stacked on a leading
    shard axis, or pre-reduced by the caller):

        m*   = max_s m_s
        out  = (sum_s acc_s e^{m_s - m*}) / max(sum_s l_s e^{m_s - m*}, eps)

    — the flash-attention two-term merge reassociated across shards, so the
    result equals running one online softmax over the union of keys (up to
    float reassociation).  Fully masked shards (m_s = -inf, l_s = 0) drop
    out: e^{-inf - m*} = 0 for finite m*; the engine's round-robin page
    layout guarantees shard 0 sees token 0, keeping m* finite for every
    active row.  Pure jnp — usable both inside shard_map (after psum/pmax,
    pass the already-reduced sums with the max) and on stacked arrays in
    tests."""
    m_star = jnp.max(m, axis=0)
    corr = jnp.exp(m - m_star[None])
    l_star = jnp.sum(l * corr, axis=0)
    acc_star = jnp.sum(acc * corr, axis=0)
    return acc_star / jnp.maximum(l_star, eps)


def _fused_paged_decode_cp_impl(q, arena_k, arena_v, tables, pos, max_len,
                                scale, interpret, cp, mp, k_scale=None,
                                v_scale=None):
    """Context-parallel dispatch of the fused paged-decode kernel (ISSUE
    20): `shard_map` over ('cp', 'mp') with the ARENA PAGE axis block-split
    over 'cp' (shard s physically holds global pages [s*per_shard,
    (s+1)*per_shard)) and kv heads split over 'mp' exactly as in
    `_fused_paged_decode_tp`.  q, tables, and pos stay replicated across
    'cp'.

    Each shard derives its LOCAL view in-jit from the replicated global
    table: sequence page k lives on shard k % cp (the engine's round-robin
    allocator invariant), so shard s's columns are k = j*cp + s; a mapped
    global id g in its range becomes local row g - s*per_shard, anything
    else (unmapped 0-sentinel columns, other shards' pages never appear)
    redirects to local row 0 — that shard's own scratch page, whose garbage
    the position fence masks exactly as on one device.  `page_base[j] =
    (j*cp + s) * page_size` carries the true token positions into the
    kernel masks.  The per-shard partials then merge with ONE
    pmax + two psums over 'cp' (`cp_softmax_combine` math) — the only
    cross-device traffic the whole decode step adds."""
    from jax.sharding import PartitionSpec as P

    from ..distributed import mesh as _mesh

    quant = k_scale is not None
    num_pages = arena_k.shape[0]
    per_shard = num_pages // cp
    hk = arena_k.shape[1]
    ps = arena_k.shape[2]
    b, sq, h, d = q.shape
    rep = h // hk
    R = rep * sq

    mp_ax = "mp" if mp > 1 else None
    heads = P(None, None, mp_ax, None)
    pages = P("cp", mp_ax, None, None)

    def body(qq, ak, av, ks, vs, t, p):
        s = jax.lax.axis_index("cp")
        Pl = t.shape[1] // cp
        cols = (s + cp * jnp.arange(Pl, dtype=jnp.int32)).astype(jnp.int32)
        g = jnp.take(t, cols, axis=1)  # [b, Pl] global page ids
        loc = g - s * per_shard
        loc = jnp.where((loc > 0) & (loc < per_shard), loc, 0).astype(jnp.int32)
        base = (cols * ps).astype(jnp.int32)
        if quant:
            acc, m, l = _fused_paged_decode_partials_q8(
                qq, ak, av, ks, vs, loc, base, p, max_len, scale, interpret
            )
        else:
            acc, m, l = _fused_paged_decode_partials(
                qq, ak, av, loc, base, p, max_len, scale, interpret
            )
        m_star = jax.lax.pmax(m, "cp")
        corr = jnp.exp(m - m_star)
        l_star = jax.lax.psum(l * corr, "cp")
        acc_star = jax.lax.psum(acc * corr, "cp")
        out = acc_star / jnp.maximum(l_star, 1e-30)  # [b, hk_l, qr, d] f32
        hk_l = out.shape[1]
        out = out[:, :, :R].reshape(b, hk_l, rep, sq, d)
        out = out.reshape(b, hk_l * rep, sq, d).astype(qq.dtype)
        return jnp.transpose(out, (0, 2, 1, 3))

    if not quant:
        # dummy replicated scalars keep ONE body signature for both modes
        k_scale = jnp.zeros((), jnp.float32)
        v_scale = jnp.zeros((), jnp.float32)
        scale_spec = P()
    else:
        scale_spec = pages
    fn = jax.shard_map(
        body,
        mesh=_mesh.get_mesh(),
        in_specs=(heads, pages, pages, scale_spec, scale_spec,
                  P(None, None), P(None)),
        out_specs=heads,
        check_vma=False,
    )
    return fn(q, arena_k, arena_v, k_scale, v_scale, tables, pos)


# custom_vjp opacity, same contract as the single-device fused kernels: the
# cp combine's pmax/psum have no JAX differentiation rules, and decode is
# inference-only anyway — dispatch.apply's eager jax.vjp must be able to
# trace the forward without ever building a backward.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_paged_decode_cp(q, arena_k, arena_v, tables, pos, max_len, scale,
                           interpret, cp, mp):
    return _fused_paged_decode_cp_impl(
        q, arena_k, arena_v, tables, pos, max_len, scale, interpret, cp, mp
    )


def _fused_paged_decode_cp_fwd(q, arena_k, arena_v, tables, pos, max_len,
                               scale, interpret, cp, mp):
    return _fused_paged_decode_cp(
        q, arena_k, arena_v, tables, pos, max_len, scale, interpret, cp, mp
    ), None


def _fused_paged_decode_cp_bwd(max_len, scale, interpret, cp, mp, res, g):
    raise NotImplementedError(
        "context-parallel fused paged decode is inference-only"
    )


_fused_paged_decode_cp.defvjp(
    _fused_paged_decode_cp_fwd, _fused_paged_decode_cp_bwd
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _fused_paged_decode_cp_q8(q, arena_k, arena_v, k_scale, v_scale, tables,
                              pos, max_len, scale, interpret, cp, mp):
    return _fused_paged_decode_cp_impl(
        q, arena_k, arena_v, tables, pos, max_len, scale, interpret, cp, mp,
        k_scale=k_scale, v_scale=v_scale,
    )


def _fused_paged_decode_cp_q8_fwd(q, arena_k, arena_v, k_scale, v_scale,
                                  tables, pos, max_len, scale, interpret, cp,
                                  mp):
    return _fused_paged_decode_cp_q8(
        q, arena_k, arena_v, k_scale, v_scale, tables, pos, max_len, scale,
        interpret, cp, mp,
    ), None


def _fused_paged_decode_cp_q8_bwd(max_len, scale, interpret, cp, mp, res, g):
    raise NotImplementedError(
        "context-parallel quantized fused paged decode is inference-only"
    )


_fused_paged_decode_cp_q8.defvjp(
    _fused_paged_decode_cp_q8_fwd, _fused_paged_decode_cp_q8_bwd
)


def paged_decode_attention_array(q, arena_k, arena_v, tables, pos, max_len,
                                 scale=None, kernel="auto", k_scale=None,
                                 v_scale=None):
    """Paged-decode attention dispatcher.

    kernel="auto": the fused Pallas kernel when on TPU (or under interpret)
    and the shape is eligible, else gather-then-dense.  kernel="fused":
    require the fused kernel — raises ValueError when it cannot run (the
    engine surfaces this at construction, not mid-traffic).
    kernel="gather": force the gather-then-dense oracle (`paged_gather_kv`
    materializes each sequence's KV densely, then the exact dense-cache
    decode math runs on the result) — the bit-parity baseline the fused
    kernel is tested against.

    Under a tensor-parallel 'mp' mesh the fused kernel goes through
    `shard_map` (kv_heads axis sharded; see `_fused_paged_decode_tp`) and
    the gather oracle relies on GSPMD propagating the arena's heads
    sharding through the gather + dense einsums.

    k_scale/v_scale non-None selects the QUANTIZED paths (ISSUE 18): the
    arena holds int8 rows and the scale arenas hold their per-(row, kv
    head) float32 scales.  The fused kernel dequantizes per page tile in
    VMEM ('paged_decode_fused_q8'); the gather oracle gathers values and
    scales through the same tables and applies the identical
    `int8 * scale` dequant before the dense math, staying the parity
    baseline under quantization too."""
    if kernel not in ("auto", "fused", "gather"):
        raise ValueError(
            f"paged decode kernel must be auto|fused|gather, got {kernel!r}"
        )
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = _FORCE_INTERPRET
    if kernel != "gather":
        from ..distributed import mesh as _mesh

        ok, reason = _fused_paged_viable(q, arena_k.shape[2])
        mp = _mesh.axis_size("mp")
        cp = _mesh.axis_size("cp")
        if ok and mp > 1 and (q.shape[2] % mp or arena_k.shape[1] % mp):
            # engine construction validates this for serving; direct callers
            # (or a q-head count that packs unevenly) fall back to the
            # GSPMD-sharded gather path instead of a shard_map shape error
            ok, reason = False, "paged heads not divisible by mp"
        if ok and cp > 1 and (tables.shape[1] % cp or arena_k.shape[0] % cp):
            # the engine pads pages_per_seq and the pool to cp multiples;
            # direct callers fall back to the GSPMD gather path
            ok, reason = False, "paged tables/pool not divisible by cp"
        on_path = _on_tpu() or interpret
        if ok and on_path:
            if cp > 1:
                _log_pallas_call("paged_decode_fused_cp_q8" if quant else
                                 "paged_decode_fused_cp")
                if quant:
                    return _fused_paged_decode_cp_q8(
                        q, arena_k, arena_v, k_scale, v_scale, tables, pos,
                        max_len, scale, interpret, cp, mp,
                    )
                return _fused_paged_decode_cp(
                    q, arena_k, arena_v, tables, pos, max_len, scale,
                    interpret, cp, mp,
                )
            _log_pallas_call("paged_decode_fused_q8" if quant else
                             "paged_decode_fused")
            if mp > 1:
                return _fused_paged_decode_tp(
                    q, arena_k, arena_v, tables, pos, max_len, scale,
                    interpret, k_scale, v_scale,
                )
            if quant:
                return _fused_paged_decode_quant(
                    q, arena_k, arena_v, k_scale, v_scale, tables, pos,
                    max_len, scale, interpret,
                )
            return _fused_paged_decode(
                q, arena_k, arena_v, tables, pos, max_len, scale, interpret
            )
        if kernel == "fused":
            raise ValueError(
                "paged decode kernel 'fused' unavailable: "
                + (reason or "not on TPU (tests set _FORCE_INTERPRET)")
            )
        if on_path:
            _log_pallas_fallback(reason, shape=q.shape)
    k = paged_gather_kv(arena_k, tables, max_len)
    v = paged_gather_kv(arena_v, tables, max_len)
    if quant:
        # the oracle's dequant is the same math the kernel runs in VMEM:
        # int8 rows * their gathered scale rows, q upcast to f32 so both
        # paths reduce at the same precision
        k = k.astype(jnp.float32) * paged_gather_scale(k_scale, tables, max_len)
        v = v.astype(jnp.float32) * paged_gather_scale(v_scale, tables, max_len)
        out = decode_attention_array(q.astype(jnp.float32), k, v, pos, scale)
        return out.astype(q.dtype)
    return decode_attention_array(q, k, v, pos, scale)


def paged_flash_decode(query, arena_k, arena_v, tables, pos, max_len, scale=None,
                       kernel="auto", k_scale=None, v_scale=None):
    """Tensor-level paged cached-decode attention.  `k_scale`/`v_scale`
    (the int8 arena's parallel scale buffers) select the quantized
    dispatch; the kv-quant mode string is deliberately a closure constant
    of the traced fn — ops.dispatch._code_key and the AOT snapshot
    fingerprint freeze closure values, so an executable cached under one
    quant mode can never serve the other even if avals were ever to
    coincide."""
    query, arena_k, arena_v = coerce(query), coerce(arena_k), coerce(arena_v)
    tables, pos = coerce(tables), coerce(pos)
    max_len = int(max_len)
    kernel = str(kernel)
    kv_quant = "int8" if k_scale is not None else "none"

    if kv_quant == "int8":
        k_scale, v_scale = coerce(k_scale), coerce(v_scale)

        def fq(q, ak, av, ks, vs, t, p):
            assert kv_quant == "int8"  # closure cell -> eager-cache key
            return paged_decode_attention_array(
                q, ak, av, t, p, max_len, scale, kernel=kernel,
                k_scale=ks, v_scale=vs,
            )

        return apply(
            fq, [query, arena_k, arena_v, k_scale, v_scale, tables, pos],
            name="paged_flash_decode_q8",
        )

    def f(q, ak, av, t, p):
        assert kv_quant == "none"  # closure cell -> eager-cache key
        return paged_decode_attention_array(
            q, ak, av, t, p, max_len, scale, kernel=kernel
        )

    return apply(f, [query, arena_k, arena_v, tables, pos], name="paged_flash_decode")


# ---------------------------------------------------------------------------
# Blockwise XLA fallback (O(seq) memory via scan + checkpoint)
# ---------------------------------------------------------------------------


def _blockwise_attention(q, k, v, mask, causal, scale, block_k=512):
    """q: [b, h, sq, d]; k,v: [b, h, sk, d]; mask broadcastable [b, h, sq, sk].

    Returns (out [b,h,sq,d] in q.dtype, lse [b,h,sq] f32)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if mask is not None or sk <= block_k or sk % block_k != 0:
        return _dense_attention(q, k, v, mask, causal, scale)

    nblocks = sk // block_k

    def body(carry, ki):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, ki * block_k, block_k, axis=2)
        vs = lax.dynamic_slice_in_dim(v, ki * block_k, block_k, axis=2)
        # bf16 operands, fp32 accumulation — full-rate MXU; scale applied to
        # the fp32 scores, not the half-precision operands
        s = jnp.einsum("bhqd,bhkd->bhqk", q, ks, preferred_element_type=jnp.float32) * scale
        if causal:
            q_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (sq, block_k), 1)
            s = jnp.where(q_ids >= k_ids - (sk - sq), s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), vs, preferred_element_type=jnp.float32
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, h, sq), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, d), jnp.float32),
    )
    (m, l, acc), _ = lax.scan(jax.checkpoint(body), init, jnp.arange(nblocks))
    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe[..., None]).astype(q.dtype), m + jnp.log(l_safe)


def _dense_attention(q, k, v, mask, causal, scale):
    # half-precision operands with fp32 accumulation (full-rate MXU); softmax
    # and masking in fp32.  Returns (out, lse).
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    sq, sk = q.shape[2], k.shape[2]
    if causal:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(q_ids >= k_ids - (sk - sq), s, _NEG_INF)
    if mask is not None:
        s = s + mask.astype(s.dtype)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    return out, lse


def _flash_backward(q, k, v, mask, out, lse, g, causal, scale, block_k=512):
    """Explicit flash-attention-2 backward (dq, dk, dv), expressed for XLA.

    Matmul operands stay in the input (half) precision with fp32 accumulation
    — jax.vjp over the forward would instead produce fp32-operand matmuls
    (p and ds are fp32), halving MXU throughput and doubling HBM traffic
    (the round-1 AMP audit finding).  Reference capability:
    paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [b,h,sq]

    if mask is not None or sk <= block_k or sk % block_k != 0:
        bk, nblocks = sk, 1
    else:
        bk, nblocks = block_k, sk // block_k

    def body(dq_acc, ki):
        k0 = ki * bk
        ks = lax.dynamic_slice_in_dim(k, k0, bk, axis=2)
        vs = lax.dynamic_slice_in_dim(v, k0, bk, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, ks, preferred_element_type=jnp.float32) * scale
        if causal:
            q_ids = jax.lax.broadcasted_iota(jnp.int32, (sq, bk), 0)
            k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, (sq, bk), 1)
            s = jnp.where(q_ids >= k_ids - (sk - sq), s, _NEG_INF)
        if mask is not None:
            s = s + mask.astype(s.dtype)
        p = jnp.exp(s - lse[..., None])  # [b,h,sq,bk] f32
        pb = p.astype(q.dtype)
        dv_i = jnp.einsum(
            "bhqk,bhqd->bhkd", pb, g, preferred_element_type=jnp.float32
        ).astype(v.dtype)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g, vs, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, ks, preferred_element_type=jnp.float32
        )
        dk_i = jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q, preferred_element_type=jnp.float32
        ).astype(k.dtype)
        return dq_acc, (dk_i, dv_i)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    if nblocks == 1:
        dq, (dk, dv) = body(dq0, 0)
    else:
        dq, (dks, dvs) = lax.scan(jax.checkpoint(body), dq0, jnp.arange(nblocks))
        dk = jnp.moveaxis(dks, 0, 2).reshape(k.shape)
        dv = jnp.moveaxis(dvs, 0, 2).reshape(v.shape)
    return dq.astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# public entry — jax-level (arrays in, arrays out; custom_vjp around pallas)
# ---------------------------------------------------------------------------

# Every Pallas kernel this module can dispatch, and every fallback reason it
# can emit — obs/metrics.py zero-renders both families so a fallback
# regression shows up as a counter MOVING, not a series appearing.  The two
# retired reasons ("seq not a 128-multiple", "attn_mask given") stay listed:
# their permanent zeros are the proof the gaps are closed.
_PALLAS_KERNELS = (
    "flash_fwd", "flash_bwd", "decode", "paged_decode_fused",
    "paged_decode_fused_q8", "paged_decode_fused_cp",
    "paged_decode_fused_cp_q8",
)
_FALLBACK_REASONS = (
    "attn_mask not key-padding",
    "q/k shapes differ",
    "head_dim > 256",
    "heads not divisible by mp",
    "decode kernel under an mp mesh",
    "paged head_dim > 256",
    "paged page_size not 8-aligned",
    "paged heads not divisible by mp",
    "paged tables/pool not divisible by cp",
    "seq not a 128-multiple",  # retired (pad-and-mask) — must stay 0
    "attn_mask given",         # retired (key-bias lowering) — must stay 0
)

_fallback_lock = threading.Lock()
_fallback_logged = set()  # (reason, shape) pairs already warned about
_FALLBACK_LOG_BOUND = 512  # serving emits few distinct shapes; cap leaks


def _log_pallas_call(kernel):
    """Count a Pallas kernel dispatch (the positive counterpart to
    `_log_pallas_fallback`): benches and /metrics prove the fast path ran
    by this counter moving, not by the absence of fallbacks."""
    from .. import profiler as _prof

    _prof.record_flash_pallas_call(kernel)


def _log_pallas_fallback(reason, shape=None):
    """Gate honesty (round-1 finding): never silently run the slow path on a
    TPU — benches must be able to see which kernel they measured.  Counts
    every fallback into the profiler's `flash_fallbacks` gauge and warns
    once per (reason, q-shape) so a new shape hitting the slow path is
    visible even late in a long run."""
    from .. import profiler as _prof

    _prof.record_flash_fallback(reason)
    key = (reason, tuple(shape) if shape is not None else None)
    warn = False
    global _fallback_logged
    with _fallback_lock:
        if not isinstance(_fallback_logged, set):
            # tests plant falsy sentinels here to detect logging; keep their
            # `assert not fa._fallback_logged` semantics by replacing the
            # sentinel with a real (truthy) set instead of crashing
            _fallback_logged = set()
        if key not in _fallback_logged:
            if len(_fallback_logged) >= _FALLBACK_LOG_BOUND:
                _fallback_logged.clear()
            _fallback_logged.add(key)
            warn = True
    if warn:
        import logging

        logging.getLogger("paddle_tpu").warning(
            "flash_attention: Pallas kernel unavailable (%s) for q shape %s; "
            "using XLA blockwise fallback",
            reason, key[1],
        )


# tests set this to exercise the Pallas kernels off-TPU via interpret mode
_FORCE_INTERPRET = False


def _per_device(local, bh_arrays, b_arrays, out_ndims):
    """Run `local(*bh_arrays, *b_arrays)` once per device shard.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh the dense kernels are
    `shard_map`ped over every mesh axis that is still automatic: batch
    (dim 0) split over 'dp', heads (dim 1 of `bh_arrays`) over 'mp' —
    attention is independent per (batch, head) — and everything else
    replicated.  `b_arrays` are per-batch-row operands (segment ids, key
    bias; None entries allowed).  `local` returns a tuple of arrays laid out
    [batch, heads, ...] of ranks `out_ndims`.  Without a mesh, or inside a
    `shard_map` that has already made every axis manual, the call is direct.
    `_pallas_viable` has checked that the heads divide 'mp'."""
    from jax.sharding import PartitionSpec as P

    from ..distributed import mesh as _mesh

    mesh = _mesh.get_mesh()
    if mesh is None or mesh.size == 1:
        return local(*bh_arrays, *b_arrays)
    ctx = jax.sharding.get_abstract_mesh()
    manual = set() if ctx.empty else set(ctx.manual_axes)
    auto = set(mesh.axis_names) - manual
    if not auto:
        return local(*bh_arrays, *b_arrays)
    b, h = bh_arrays[0].shape[:2]

    def axis(name, n):
        ok = name in auto and mesh.shape[name] > 1 and n % mesh.shape[name] == 0
        return name if ok else None

    dp, mp = axis("dp", b), axis("mp", h)
    bh_spec = lambda ndim: P(dp, mp, *([None] * (ndim - 2)))
    in_specs = tuple(bh_spec(x.ndim) for x in bh_arrays) + tuple(
        None if x is None else P(dp, *([None] * (x.ndim - 1))) for x in b_arrays
    )
    out_specs = tuple(bh_spec(n) for n in out_ndims)
    # inside a partial-manual region the context mesh must be used as is
    where = {} if manual else {"mesh": mesh}
    fn = jax.shard_map(
        local, in_specs=in_specs, out_specs=out_specs, axis_names=auto,
        check_vma=False, **where,
    )
    return fn(*bh_arrays, *b_arrays)


def _key_padding_bias(mask, b, sk):
    """If `mask` is a plain key-padding mask — additive, broadcast over the
    q rows and heads, i.e. shape [mb, 1, 1, sk] with mb in {1, b} — lower it
    to a [b, sk] f32 per-key bias the Pallas kernels add in-kernel.  Any
    other mask geometry returns None (those stay on the XLA fallback)."""
    if mask is None:
        return None
    if mask.ndim != 4 or mask.shape[1] != 1 or mask.shape[2] != 1:
        return None
    mb = mask.shape[0]
    if mb not in (1, b) or mask.shape[3] != sk:
        return None
    return jnp.broadcast_to(
        mask.reshape(mb, sk).astype(jnp.float32), (b, sk)
    )


def _pad_flash_inputs(q, k, v, segments, kbias):
    """Pad the sequence dim of [b,h,s,d] q/k/v up to the next 128 multiple
    so the Pallas kernels' block geometry holds on ragged serving shapes.
    Padded positions MUST be fenced or they poison real rows' softmax
    denominators (a zero-key column scores 0, not -inf) — so the pad path
    always carries segment ids: real positions keep their ids (or 0 when
    the caller had none), pad positions get -1 and are masked against
    everything real.  kbias pads with 0 (pad columns are already fenced by
    the segment ids).  Returns (q, k, v, segments, kbias, s_pad)."""
    b, h, s, d = q.shape
    s_pad = -(-s // 128) * 128
    if s_pad == s:
        return q, k, v, segments, kbias, s
    pad = s_pad - s
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if segments is None:
        segments = jnp.zeros((b, s), jnp.int32)
    segments = jnp.pad(
        jnp.asarray(segments, jnp.int32), ((0, 0), (0, pad)),
        constant_values=-1,
    )
    if kbias is not None:
        kbias = jnp.pad(kbias, ((0, 0), (0, pad)))
    return q, k, v, segments, kbias, s_pad


def _pallas_viable(q, k, mask, kbias):
    """Static eligibility for the dense Pallas kernels.  Non-128-multiple
    sequences are no longer refused (the wrapper pads and fences them) and
    plain key-padding masks lower to an in-kernel bias — the remaining
    reasons are structural."""
    d = q.shape[3]
    if mask is not None and kbias is None:
        return False, "attn_mask not key-padding"
    if q.shape != k.shape:
        return False, "q/k shapes differ"
    if d > 256:
        return False, "head_dim > 256"
    from ..distributed import mesh as _mesh

    if q.shape[1] % _mesh.axis_size("mp"):
        # under an 'mp' mesh the kernels are mapped per device over heads
        # (_per_device); heads that do not divide cannot be
        return False, "heads not divisible by mp"
    return True, None


def _segments_mask(segments, b, h):
    """[b, s] segment ids -> additive [b, 1, s, s] mask for the XLA paths."""
    eq = segments[:, None, :, None] == segments[:, None, None, :]
    return jnp.where(eq, 0.0, _NEG_INF).astype(jnp.float32)


def _seg_flat(segments, h):
    """[b, s] -> [b, s, 1] int32 for the Pallas kernels (the kernels' seg
    BlockSpecs divide the bh grid coordinate by n_heads, so no per-head
    broadcast is materialized)."""
    return segments[:, :, None].astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_attention_core(q, k, v, mask, segments, causal, scale):
    out, _, _ = _flash_fwd_impl(q, k, v, mask, segments, causal, scale)
    return out


def _flash_fwd_impl(q, k, v, mask, segments, causal, scale):
    """q,k,v: [b, h, s, d] → (out, lse [b,h,s], used_pallas)."""
    b, h, s, d = q.shape
    interpret = _FORCE_INTERPRET
    if _on_tpu() or interpret:
        kbias = _key_padding_bias(mask, b, k.shape[2])
        ok, reason = _pallas_viable(q, k, mask, kbias)
        if ok:
            qp, kp, vp, segp, kbp, s_pad = _pad_flash_inputs(
                q, k, v, segments, kbias
            )
            _log_pallas_call("flash_fwd")

            def local(qx, kx, vx, segx, kbx):
                bl, hl = qx.shape[:2]  # this device's batch rows and heads
                out, lse = _pallas_flash_forward(
                    qx.reshape(bl * hl, s_pad, d),
                    kx.reshape(bl * hl, s_pad, d),
                    vx.reshape(bl * hl, s_pad, d),
                    causal, scale,
                    segments=None if segx is None else _seg_flat(segx, hl),
                    n_heads=hl, interpret=interpret,
                    kbias=None if kbx is None else kbx[:, None, :],
                )
                return out.reshape(bl, hl, s_pad, d), lse.reshape(bl, hl, s_pad)

            out, lse = _per_device(local, (qp, kp, vp), (segp, kbp), (4, 3))
            return out[:, :, :s], lse[:, :, :s], True
        _log_pallas_fallback(reason, shape=q.shape)
    if segments is not None:
        seg_mask = _segments_mask(segments, b, h)
        mask = seg_mask if mask is None else mask + seg_mask
    out, lse = _blockwise_attention(q, k, v, mask, causal, scale)
    return out, lse, False


def _flash_fwd_rule(q, k, v, mask, segments, causal, scale):
    out, lse, used_pallas = _flash_fwd_impl(q, k, v, mask, segments, causal, scale)
    return out, (q, k, v, mask, segments, out, lse, used_pallas)


def _flash_bwd_rule(causal, scale, res, g):
    q, k, v, mask, segments, out, lse, used_pallas = res
    if used_pallas:
        b, h, s, d = q.shape
        # reconstruct the forward's padded geometry deterministically; pad
        # g/out/lse with zeros — a padded q row's p is either 0 (masked vs
        # real keys) or hits g=0/delta=0, so it contributes exactly nothing
        # to dk/dv, and its own dq row is sliced off
        kbias = _key_padding_bias(mask, b, k.shape[2])
        qp, kp, vp, segp, kbp, s_pad = _pad_flash_inputs(q, k, v, segments, kbias)
        gp, outp, lsep = g, out, lse
        if s_pad != s:
            pad = s_pad - s
            gp = jnp.pad(g, ((0, 0), (0, 0), (0, pad), (0, 0)))
            outp = jnp.pad(out, ((0, 0), (0, 0), (0, pad), (0, 0)))
            lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad)))
        _log_pallas_call("flash_bwd")

        def local(qx, kx, vx, gx, ox, lx, segx, kbx):
            bl, hl = qx.shape[:2]  # this device's batch rows and heads
            flat = lambda x: x.reshape(bl * hl, s_pad, d)
            dq, dk, dv = _pallas_flash_backward(
                flat(qx), flat(kx), flat(vx), flat(gx), flat(ox),
                lx.reshape(bl * hl, s_pad, 1), causal, scale,
                segments=None if segx is None else _seg_flat(segx, hl),
                n_heads=hl, interpret=_FORCE_INTERPRET,
                kbias=None if kbx is None else kbx[:, None, :],
            )
            return tuple(x.reshape(bl, hl, s_pad, d) for x in (dq, dk, dv))

        dq, dk, dv = _per_device(
            local, (qp, kp, vp, gp, outp, lsep), (segp, kbp), (4, 4, 4)
        )
        return (
            dq[:, :, :s], dk[:, :, :s], dv[:, :, :s], None, None,
        )
    if segments is not None:
        seg_mask = _segments_mask(segments, q.shape[0], q.shape[1])
        mask = seg_mask if mask is None else mask + seg_mask
    dq, dk, dv = _flash_backward(q, k, v, mask, out, lse, g, causal, scale)
    return dq, dk, dv, None, None


_flash_attention_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def sdpa_array(q, k, v, mask=None, causal=False, scale=None, segment_ids=None):
    """Array-level SDPA used by models and by the Tensor-level op below.

    q,k,v: [batch, seq, heads, dim] → out [batch, seq, heads, dim].
    segment_ids: optional [batch, seq] int — attention is confined to
    positions with equal ids (packed-sequence / varlen semantics).
    """
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # grouped-query attention: expand kv heads if fewer than q heads
    hq, hk = qt.shape[1], kt.shape[1]
    if hk != hq:
        rep = hq // hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    out = _flash_attention_core(qt, kt, vt, mask, segment_ids, causal, scale)
    return jnp.transpose(out, (0, 2, 1, 3))


def cu_seqlens_to_segment_ids(cu_seqlens, total_len):
    """[n+1] cumulative lengths -> [total_len] segment ids (padding tail,
    if any, lands in the last registered segment's id + 1 region and is
    masked against everything by construction)."""
    pos = jnp.arange(total_len, dtype=jnp.int32)
    return jnp.searchsorted(jnp.asarray(cu_seqlens, jnp.int32)[1:], pos, side="right")


def flash_attn_varlen_array(q, k, v, cu_seqlens, causal=True, scale=None):
    """Packed varlen attention (reference: phi flash_attn_varlen /
    flash_attn_unpadded, paddle/phi/kernels/gpu/flash_attn_kernel.cu).

    q,k,v: [total, heads, dim] — sequences packed along dim 0;
    cu_seqlens: [n+1] int with cu[0]==0, cu[-1]<=total.  TPU-native: the
    packed layout + segment-id masking keeps shapes static for XLA.
    """
    total = q.shape[0]
    seg = cu_seqlens_to_segment_ids(cu_seqlens, total)[None, :]  # [1, total]
    out = sdpa_array(
        q[None], k[None], v[None], None, causal, scale, segment_ids=seg
    )
    return out[0]


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True,
    segment_ids=None,
):
    """segment_ids: optional [b, s] int Tensor — packed-sequence / padding
    masking that KEEPS the Pallas kernel eligible (an additive attn_mask
    forces the XLA fallback; models with plain key-padding masks should
    pass segment ids instead — see models/bert.py)."""
    query, key, value = coerce(query), coerce(key), coerce(value)
    ins = [query, key, value]
    has_mask = attn_mask is not None
    if has_mask:
        mask = coerce(attn_mask)
        if mask.dtype == "bool":
            from . import cast as _  # noqa

            mask = apply(
                lambda m: jnp.where(m, 0.0, _NEG_INF).astype(jnp.float32), [mask]
            )
        ins.append(mask)
    has_segs = segment_ids is not None
    if has_segs:
        ins.append(coerce(segment_ids))

    def f(q, k, v, *rest):
        m = rest[0] if has_mask else None
        segs = rest[-1] if has_segs else None
        return sdpa_array(q, k, v, m, is_causal, segment_ids=segs)

    out = apply(f, ins, name="flash_attention")
    if dropout_p > 0.0 and training:
        from ..nn.functional import dropout as _dropout

        out = _dropout(out, dropout_p, training=training)
    return out


def flash_attn_varlen(query, key, value, cu_seqlens_q, cu_seqlens_k=None, causal=True, scale=None):
    """Tensor-level varlen entry (reference: paddle flash_attn_unpadded).
    Only self-attention layouts (shared cu_seqlens) are supported."""
    query, key, value = coerce(query), coerce(key), coerce(value)
    cu = coerce(cu_seqlens_q)
    if cu_seqlens_k is not None and cu_seqlens_k is not cu_seqlens_q:
        cu_k = coerce(cu_seqlens_k)
        traced = isinstance(cu._raw, jax.core.Tracer) or isinstance(
            cu_k._raw, jax.core.Tracer
        )
        if traced:
            # values can't be compared under tracing, and trusting a shape
            # match would silently mis-compute cross-attention layouts —
            # require the SAME object (or omit cu_seqlens_k) inside traced
            # code; only self-attention layouts are supported either way
            raise NotImplementedError(
                "flash_attn_varlen: cu_seqlens_k equality cannot be "
                "verified under @to_static tracing; pass cu_seqlens_k as "
                "the same tensor object as cu_seqlens_q (or omit it) — "
                "only self-attention layouts are supported"
            )
        else:
            same = cu_k._raw.shape == cu._raw.shape and bool(
                (cu_k._raw == cu._raw).all()
            )
            if not same:
                raise NotImplementedError(
                    "flash_attn_varlen: distinct cu_seqlens_k is not supported "
                    "(self-attention layouts only); pass equal cu_seqlens"
                )

    def f(q, k, v, cq):
        return flash_attn_varlen_array(q, k, v, cq, causal, scale)

    return apply(f, [query, key, value, cu], name="flash_attn_varlen")
