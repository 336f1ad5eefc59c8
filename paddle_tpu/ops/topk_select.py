"""DeepSeek-V3.2's exact top-k key selection for a prefill chunk as one
Pallas kernel that reads the context's scores once into VMEM.

The indexer's score `[s, L]` (float32, `-inf` where a query may not see a
key and past the context) gives each query row its `k` largest keys, ties to
the lower position; a row with fewer than `k` finite keys takes every one.
The XLA form (`models/deepseek_v32.py:_select_mask`, the path off the TPU)
takes the exact k-th value by bisection over the floats' ordered integer
image, 32 counting passes over all `L` columns of the page table in HBM.

Here the grid is (row tile, key block).  A row tile's key blocks inside the
context (`n_blocks`, scalar-prefetched) are copied in and turned into their
ordered image in a VMEM scratch; blocks past the context map to the last one
in it, so the pipeline copies nothing for them.  At the tile's last grid
step the 32 passes run over the scratch and one more pass writes the mask (two
where a row's ties outnumber its room).

The same answer, bit for bit:

- the image: `-0.0` is `0.0`; a negative float's bits flipped below the sign,
  so a larger float is a larger signed int32 (the XLA form's unsigned code
  with its top bit flipped);
- the bisection: the k-th code is the largest `t` with at least `k` codes
  `>= t`, a bit at a time from the top;
- the columns not read: each is a `-inf` column, which is what the XLA form
  counts there.  A candidate at or below `-inf`'s image is met by all of
  them, one above it by none, so the count adds the unread columns exactly
  where `t <= image(-inf)`.  Where fewer than `k` keys are finite the k-th
  code is `-inf`'s: every finite key lies above it and none ties;
- ties: a key at the k-th code that is not `-inf` ties.  The last pass that
  moved the k-th code counted the keys at or above it, so the ties outnumber
  a row's room (`k` less the keys above) exactly where that count passes `k`.
  There the first `room` ties in position order are taken, by a prefix count
  a lane chunk at a time (a product with a triangle of ones: exact, the
  counts are small integers) plus the count of the chunks before.  Ties lie
  inside the context, so the prefix over the columns read is the XLA form's.

The kernel writes the mask as int8 (a bool result would be int32 in memory),
columns past the context 0; `topk_select` hands back `!= 0`, which XLA fuses
into the attention's packing of it.  The dispatch lives with the model
(`models/deepseek_v32.py:_select_topk`); `refusal` says why a shape cannot
lower for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# query rows a grid step holds: the int8 output's sublane tile, and 3 MiB of
# image a tile at 24,576 columns (64 and 128 rows were no faster on the v5e)
ROW_TILE = 32
LANES = 128
BITS = 32
# scoped VMEM the call may ask Mosaic for (a v5e core has 128 MiB)
_VMEM_CEILING = 100 * 1024 * 1024
_NEG_INF_KEY = -2139095041  # the image of -inf: 0xFF800000 ^ 0x7FFFFFFF as int32


def _chunk(kb):
    """Keys a lane chunk holds: one vreg row of lanes, or the whole block."""
    return min(kb, LANES)


def _geometry(score, kb):
    s, L = score.shape
    R = min(s, ROW_TILE)
    while s % R:
        R -= 1
    cw = _chunk(kb)
    vmem = (R * L * 4  # the tile's image
            + 2 * R * L  # the int8 mask block, double-buffered
            + 2 * R * kb * 4  # the score block, double-buffered
            + 8 * R * cw * 4 + cw * cw * 2)  # accumulators, chunk temporaries, the triangle
    return dict(s=s, L=L, R=R, cw=cw, vmem=vmem)


def refusal(score, kb, interpret=False):
    """None where the kernel takes these shapes (for the TPU, or interpreted),
    else why not."""
    geo = _geometry(score, kb)
    if geo["L"] % kb or kb % geo["cw"]:
        return f"{geo['L']} keys in blocks of {kb}"
    if interpret:
        return None
    if score.dtype != jnp.float32:
        return f"a {score.dtype} score"
    if kb % LANES:
        return f"a key block of {kb} is not whole lanes"
    if geo["R"] % ROW_TILE:
        return f"{geo['s']} rows are not whole tiles of {ROW_TILE}"
    if geo["vmem"] > _VMEM_CEILING:
        return f"a tile of {geo['R']} rows over {geo['L']} keys over the VMEM a call may take"
    return None


def _image(x):
    """float32 -> int32, order kept: -0.0 is 0.0, and a negative float's bits
    below the sign flipped (its magnitude reversed)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits == jnp.int32(-(2 ** 31)), 0, bits)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _forward(score, n_blocks, k, kb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    geo = _geometry(score, kb)
    s, L, R, cw = geo["s"], geo["L"], geo["R"], geo["cw"]
    NB, nc = L // kb, kb // cw
    k = min(k, L)

    def kernel(nb_ref, score_ref, out_ref, key_scr):
        j = pl.program_id(1)
        nb = nb_ref[0]

        @pl.when(j < nb)
        def _copy():
            key_scr[:, pl.ds(pl.multiple_of(j * kb, kb), kb)] = _image(score_ref[...])

        def chunks(body, init):
            """body(column offset, carry) over the read columns' lane chunks."""
            def block(jj, carry):
                for t in range(nc):
                    carry = body(pl.multiple_of(jj * kb + t * cw, cw), carry)
                return carry
            return lax.fori_loop(0, nb, block, init)

        @pl.when(j == NB - 1)
        def _select():
            unread = (L - nb * kb).astype(jnp.float32)

            def count(pred):  # [R, 1] f32: the read columns where pred(key chunk)
                acc = chunks(lambda c, acc: acc + jnp.where(pred(key_scr[:, pl.ds(c, cw)]), 1.0, 0.0),
                             jnp.zeros((R, cw), jnp.float32))
                return jnp.sum(acc, axis=1, keepdims=True)

            def bit(b, carry):  # the largest t with at least k codes >= t, a bit at a time from the top
                kth, at = carry
                cand = kth ^ lax.shift_left(jnp.int32(1), (BITS - 1 - b).astype(jnp.int32))
                wide = jnp.broadcast_to(cand, (R, cw))
                n = count(lambda key: key >= wide) + jnp.where(cand <= _NEG_INF_KEY, unread, 0.0)
                return jnp.where(n >= k, cand, kth), jnp.where(n >= k, n, at)

            # `at`: the codes >= kth, so above the k-th value and at it where it is not -inf's
            kth, at = lax.fori_loop(0, BITS, bit, (jnp.full((R, 1), -(2 ** 31), jnp.int32),
                                                   jnp.full((R, 1), L, jnp.float32)))
            wide = jnp.broadcast_to(kth, (R, cw))
            tie = lambda key: (key == wide) & (key > _NEG_INF_KEY)

            def write(prefix, room=None):
                tri = (lax.broadcasted_iota(jnp.int32, (cw, cw), 0)  # a chunk's ties times it: their prefix count
                       <= lax.broadcasted_iota(jnp.int32, (cw, cw), 1)).astype(jnp.bfloat16)

                def body(c, before):
                    key = key_scr[:, pl.ds(c, cw)]
                    t = tie(key)
                    if prefix:
                        tf = t.astype(jnp.bfloat16)
                        t = t & (before + jnp.dot(tf, tri, preferred_element_type=jnp.float32) <= room)
                        before = before + jnp.sum(tf.astype(jnp.float32), axis=1, keepdims=True)
                    out_ref[:, pl.ds(c, cw)] = ((key > wide) | t).astype(jnp.int8)
                    return before

                chunks(body, jnp.zeros((R, 1), jnp.float32))

            spill = jnp.max(jnp.where(kth > _NEG_INF_KEY, at, 0.0)) > k  # a row's ties outnumber its room

            @pl.when(spill)
            def _prefix():
                write(True, k - count(lambda key: key > wide))

            pl.when(jnp.logical_not(spill))(functools.partial(write, False))

            def clear(jj, carry):
                out_ref[:, pl.ds(pl.multiple_of(jj * kb, kb), kb)] = jnp.zeros((R, kb), jnp.int8)
                return carry

            lax.fori_loop(nb, NB, clear, 0)

    # blocks past the context map to the last one in it: the pipeline copies nothing new
    last = lambda j, nb_ref: jnp.minimum(j, jnp.maximum(nb_ref[0], 1) - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // R, NB),
        in_specs=[pl.BlockSpec((R, kb), lambda i, j, nb_ref: (i, last(j, nb_ref)))],
        out_specs=pl.BlockSpec((R, L), lambda i, j, nb_ref: (i, 0)),
        scratch_shapes=[pltpu.VMEM((R, L), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, L), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=min(geo["vmem"] + 8 * 1024 * 1024,
                                                                  _VMEM_CEILING)),
        interpret=interpret,
        name="topk_select",
    )(jnp.reshape(n_blocks, (1,)).astype(jnp.int32), score)


# jit keeps the trace: a model's layers trace and lower the kernel once
_select = jax.jit(_forward, static_argnums=(2, 3, 4))


def topk_select(score, k, n_blocks, kb, interpret=False):
    """score [s, L] float32 (-inf where a key may not be seen), the top `k`,
    n_blocks (the key blocks of `kb` in context, data; the columns past them
    are -inf) -> bool [s, L]: `_select_mask`'s mask, bit for bit."""
    from .. import profiler as _prof

    geo = _geometry(score, kb)
    _prof.record_topk_select(rows=geo["s"], cols=geo["L"], row_tile=geo["R"], kb=kb, vmem_bytes=geo["vmem"])
    # a mask carries no gradient to the score (nor does the XLA form's), and a
    # scalar-prefetch `pallas_call` has no JVP rule: differentiation stops here
    return _select(lax.stop_gradient(score), jnp.asarray(n_blocks, jnp.int32), int(k), int(kb), bool(interpret)) != 0
