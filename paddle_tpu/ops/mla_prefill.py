"""A prefill chunk's multi-head latent attention (MLA) as one Pallas kernel
that keeps every score tile in VMEM.

`s` query rows of one sequence (q_nope [s, H, dn], q_pe [s, H, dr]) attend
the sequence's latent rows `ctx_lat` [L, W] (`[ckv | k_pe | 0]`, ckv the
first `c` columns): per head, K and V are expanded from the latent rows
(`[k_nope | v] = ckv W_ukv[:, head]`, `w_ukv` [c, H (dn + dv)]) and the rope
part of the key, `k_pe`, is every head's.  Scores are `(q_nope . k_nope +
q_pe . k_pe) * scale` under one of two masks:

- causal: a key is seen where its position is at most the query's, the
  query at `start + row`;
- selected: DeepSeek-V3.2's `chosen` [s, L] (the indexer's exact top-k),
  passed in packed, 32 lane-chunks of keys to an int32.

The XLA form (`models/deepseek_v32.py:_attend_expanded`, the path off the
TPU) writes each `[rows, heads, keys]` float32 score tile to HBM and reads it
back for the mask, the maximum, the exponential and the PV product.  Here the
grid is (head group, key block): the chunk's q rows of a head group, their
accumulator and softmax statistics stay in VMEM over all key blocks; a grid
step copies in one key block's latent rows, expands K and V for the group's
heads once, then for each `qb`-row tile of queries builds the mask once as an
additive bias (0 or -inf) and runs the online softmax for each head.  Key
blocks past the context (`n_blocks`, scalar-prefetched) are neither copied nor
computed; a (q tile, key block) pair whose mask is empty is skipped (causal:
from `start`; selected: a per-tile "any" made in XLA and scalar-prefetched).

Operands stay in their dtype (bf16 on the chip): K and V are rounded to it
after the expansion and the probabilities before the PV product, as the XLA
form rounds them; scores, the softmax statistics and the accumulator are
float32; every key the mask admits is attended, none dropped.

The dispatch lives with the model (`models/deepseek_v32.py:_attend_expanded`);
`refusal` says why a shape cannot lower for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# scoped VMEM the call may ask Mosaic for (a v5e core has 128 MiB)
_VMEM_CEILING = 100 * 1024 * 1024
# the most heads a grid step takes: the group's q rows, accumulators and
# statistics are resident, so VMEM grows with it
HEADS_PER_STEP = 8
LANES = 128
WORD_BITS = 32


def _chunk(kb):
    """Keys a mask lane-chunk holds: one vreg row of lanes, or the whole block."""
    return min(kb, LANES)


def _heads_per_step(H, fits):
    g = min(H, HEADS_PER_STEP)
    while H % g or not fits(g):
        g -= 1
        if g == 0:
            return 0
    return g


def _geometry(q_nope, q_pe, ctx_lat, w_ukv, kb, qb, selected):
    s, H, dn = q_nope.shape
    L, W = ctx_lat.shape
    c = w_ukv.shape[0]
    dv = w_ukv.shape[1] // H - dn
    dq = dn + W - c  # a head's query: [q_nope | q_pe | 0] against [k_nope | the latent rope columns]
    it = q_nope.dtype.itemsize

    def vmem(g):
        blocks = 2 * (g * s * dq * it + s * g * dv * it + c * g * (dn + dv) * it + kb * W * it
                      + (s * _chunk(kb) * 4 if selected else 0))
        scratch = g * kb * (dq + dv) * it + g * s * (2 * LANES + dv) * 4 + qb * kb * 4
        temps = 4 * qb * kb * 4 + kb * (dn + dv) * 4
        return blocks + scratch + temps

    g = _heads_per_step(H, lambda g: vmem(g) <= _VMEM_CEILING)
    return dict(s=s, H=H, dn=dn, dv=dv, c=c, L=L, W=W, dq=dq, g=g, vmem=vmem(max(g, 1)))


def refusal(q_nope, q_pe, ctx_lat, w_ukv, kb, qb, mask=None, interpret=False):
    """None where the kernel takes these shapes (for the TPU, or interpreted),
    else why not."""
    geo = _geometry(q_nope, q_pe, ctx_lat, w_ukv, kb, qb, mask is not None)
    s, L, cw = geo["s"], geo["L"], _chunk(kb)
    if s % qb or L % kb:
        return f"{s} rows in tiles of {qb} or {L} keys in blocks of {kb}"
    if q_pe.shape[-1] > geo["W"] - geo["c"]:
        return f"rope width {q_pe.shape[-1]} past the latent row's {geo['W'] - geo['c']} columns after ckv"
    if mask is not None and (kb % cw or WORD_BITS % (kb // cw)):
        return f"a key block of {kb} does not pack into {WORD_BITS}-bit words of {cw}-key chunks"
    if interpret:
        return None
    if not q_nope.dtype == ctx_lat.dtype == w_ukv.dtype or q_nope.dtype not in (jnp.bfloat16, jnp.float32):
        return f"dtypes {q_nope.dtype} / {ctx_lat.dtype} / {w_ukv.dtype}"
    if any(d % LANES for d in (geo["dn"], geo["dv"], geo["c"], geo["W"], kb)):
        return (f"dn {geo['dn']}, dv {geo['dv']}, kv_lora_rank {geo['c']}, latent width {geo['W']} "
                f"or key block {kb} not whole lanes")
    if qb % 16:
        return f"a q tile of {qb} rows is not whole sublane tiles"
    if not geo["g"]:
        return f"one head's {s} resident rows over the VMEM a call may take"
    return None


def pack_mask(chosen, kb):
    """chosen [s, L] bool -> int32 [s, ceil(L / cw / 32) * cw]: key `k` is bit
    `(k // cw) % 32` of lane `k % cw` in word column group `k // (32 cw)`
    (cw = `_chunk(kb)` keys).  A key block's chunks share one word: its bits
    come out with a shift and a mask a lane, no lane moves."""
    s, L = chosen.shape
    cw = _chunk(kb)
    chunks = L // cw
    groups = -(-chunks // WORD_BITS)
    m = jnp.pad(chosen.reshape(s, chunks, cw), ((0, 0), (0, groups * WORD_BITS - chunks), (0, 0)))
    bits = m.reshape(s, groups, WORD_BITS, cw).astype(jnp.uint32) << jnp.arange(
        WORD_BITS, dtype=jnp.uint32)[:, None]
    return lax.bitcast_convert_type(jnp.sum(bits, axis=2, dtype=jnp.uint32), jnp.int32).reshape(s, groups * cw)


def tile_any(chosen, kb, qb):
    """chosen [s, L] -> int32 [s / qb * L / kb]: 1 where q tile i and key
    block j share a chosen key, at i * (L / kb) + j."""
    s, L = chosen.shape
    return jnp.any(chosen.reshape(s // qb, qb, L // kb, kb), axis=(1, 3)).astype(jnp.int32).reshape(-1)


def _forward(q_nope, q_pe, ctx_lat, w_ukv, n_blocks, start, chosen, kb, qb, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    selected = chosen is not None
    geo = _geometry(q_nope, q_pe, ctx_lat, w_ukv, kb, qb, selected)
    s, H, dn, dv, c, L, W, dq, g = (geo[k] for k in ("s", "H", "dn", "dv", "c", "L", "W", "dq", "g"))
    NB, n_qt, cw = L // kb, s // qb, _chunk(kb)
    nc = kb // cw  # lane-chunks a key block
    per_word = WORD_BITS // nc  # key blocks a word column group holds
    dt = q_nope.dtype
    # [H, s, dn + W - c]: a head's rows, the rope part padded with zeros to the latent row's tail
    q = jnp.concatenate([q_nope, q_pe, jnp.zeros((s, H, W - c - q_pe.shape[-1]), dt)], -1).transpose(1, 0, 2)
    geom = jnp.stack([jnp.reshape(n_blocks, ()), jnp.reshape(start, ())]).astype(jnp.int32)

    def kernel(geom_ref, *refs):
        if selected:
            any_ref, q_ref, lat_ref, w_ref, words_ref, o_ref, k_scr, v_scr, m_scr, l_scr, acc_scr, bias_scr = refs
        else:
            q_ref, lat_ref, w_ref, o_ref, k_scr, v_scr, m_scr, l_scr, acc_scr, bias_scr = refs
        j = pl.program_id(1)
        nb = geom_ref[0]

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def _tile(i):
            r = pl.ds(i * qb, qb)
            if selected:  # this block's bits of the tile's rows: chunk t is bit base + t
                words = words_ref[r, :] >> (j % per_word) * nc  # the sign's copies land above bit nc
                seen = jnp.concatenate([(words >> t) & 1 for t in range(nc)], axis=1) != 0
            else:
                kpos = j * kb + lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
                seen = kpos <= geom_ref[1] + i * qb + lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
            bias_scr[...] = jnp.where(seen, 0.0, -jnp.inf).astype(jnp.float32)

            def head(h, carry):
                sc = lax.dot_general(q_ref[h, r, :], k_scr[h], (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                lg = sc * scale + bias_scr[...]
                m_prev = m_scr[h, r, :]
                m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
                m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)  # no key seen yet
                p = jnp.exp(lg - m_safe[:, :1])
                fade = jnp.exp(jnp.where(m_prev == -jnp.inf, -jnp.inf, m_prev - m_safe))
                l_scr[h, r, :] = l_scr[h, r, :] * fade + jnp.sum(p, axis=1, keepdims=True)
                acc_scr[h, r, :] = acc_scr[h, r, :] * fade[:, :1] + jnp.dot(
                    p.astype(dt), v_scr[h], preferred_element_type=jnp.float32)
                m_scr[h, r, :] = m_new
                return carry

            lax.fori_loop(0, g, head, 0)

        @pl.when(j < nb)
        def _block():
            rows = lat_ref[...]
            ckv, kpe = rows[:, :c], rows[:, c:]
            for h in range(g):  # K and V of the group's heads, once a key block
                kv = jnp.dot(ckv, w_ref[:, h * (dn + dv):(h + 1) * (dn + dv)],
                             preferred_element_type=jnp.float32).astype(dt)
                k_scr[h, :, :dn] = kv[:, :dn]
                k_scr[h, :, dn:] = kpe
                v_scr[h] = kv[:, dn:]
            for i in range(n_qt):
                if selected:
                    live = any_ref[i * NB + j] != 0
                else:
                    live = j * kb <= geom_ref[1] + (i + 1) * qb - 1
                pl.when(live)(functools.partial(_tile, i))

        @pl.when(j == jnp.maximum(nb, 1) - 1)
        def _finish():
            for h in range(g):
                o_ref[:, h * dv:(h + 1) * dv] = (
                    acc_scr[h] / jnp.maximum(l_scr[h][:, :1], 1e-30)).astype(o_ref.dtype)

    # blocks past the context map to the last one in it: the pipeline copies nothing new
    last = lambda j, geom_ref: jnp.minimum(j, jnp.maximum(geom_ref[0], 1) - 1)
    in_specs = [
        pl.BlockSpec((g, s, dq), lambda hg, j, geom_ref, *_: (hg, 0, 0)),
        pl.BlockSpec((kb, W), lambda hg, j, geom_ref, *_: (last(j, geom_ref), 0)),
        pl.BlockSpec((c, g * (dn + dv)), lambda hg, j, geom_ref, *_: (0, hg)),
    ]
    operands = [q, ctx_lat, w_ukv]
    scalars = [geom]
    if selected:
        scalars.append(tile_any(chosen, kb, qb))
        operands.append(pack_mask(chosen, kb))
        in_specs.append(pl.BlockSpec((s, cw), lambda hg, j, geom_ref, *_: (0, last(j, geom_ref) // per_word)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(H // g, NB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((s, g * dv), lambda hg, j, *_: (0, hg)),
        scratch_shapes=[pltpu.VMEM((g, kb, dq), dt), pltpu.VMEM((g, kb, dv), dt),
                        pltpu.VMEM((g, s, LANES), jnp.float32), pltpu.VMEM((g, s, LANES), jnp.float32),
                        pltpu.VMEM((g, s, dv), jnp.float32), pltpu.VMEM((qb, kb), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, H * dv), dt),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=min(geo["vmem"] + 8 * 1024 * 1024,
                                                                  _VMEM_CEILING)),
        interpret=interpret,
        name="mla_prefill",
    )(*scalars, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _opaque(q_nope, q_pe, ctx_lat, w_ukv, n_blocks, start, chosen, kb, qb, scale, interpret):
    """Differentiation-opaque like the page walk: a scalar-prefetch
    `pallas_call` has no JVP rule, and a prefill chunk is inference only."""
    return _forward(q_nope, q_pe, ctx_lat, w_ukv, n_blocks, start, chosen, kb, qb, scale, interpret)


def _bwd(kb, qb, scale, interpret, res, g):
    raise NotImplementedError("mla_prefill is inference-only (no backward)")


_opaque.defvjp(lambda q_nope, q_pe, ctx_lat, w_ukv, n_blocks, start, chosen, kb, qb, scale, interpret: (
    _forward(q_nope, q_pe, ctx_lat, w_ukv, n_blocks, start, chosen, kb, qb, scale, interpret), None), _bwd)
# jit keeps the trace: a model's MLA layers trace and lower the kernel once
_attend = jax.jit(_opaque, static_argnums=(7, 8, 9, 10))


def mla_prefill(q_nope, q_pe, ctx_lat, w_ukv, n_blocks, kb, qb, scale, start=0, chosen=None, interpret=False):
    """q_nope [s, H, dn], q_pe [s, H, dr], ctx_lat [L, W], w_ukv [c, H (dn +
    dv)], n_blocks (the key blocks of `kb` in context, data), q tiles of `qb`
    rows -> [s, H * dv] in q's dtype.  Causal from `start` (data) where
    `chosen` is None, else under `chosen` [s, L] bool."""
    from .. import profiler as _prof

    geo = _geometry(q_nope, q_pe, ctx_lat, w_ukv, kb, qb, chosen is not None)
    _prof.record_mla_prefill(rows=geo["s"], heads=geo["H"], kb=kb, heads_per_step=geo["g"],
                             grid_steps=geo["H"] // geo["g"] * (geo["L"] // kb),
                             mask="causal" if chosen is None else "selected", vmem_bytes=geo["vmem"])
    return _attend(q_nope, q_pe, ctx_lat, w_ukv, jnp.asarray(n_blocks, jnp.int32),
                   jnp.asarray(start, jnp.int32), chosen, kb, qb, float(scale), bool(interpret))
