"""A small step's routed-expert sum as one Pallas kernel that copies only the
experts the step HIT (ISSUE 36).

A decode step of T tokens (T <= 128) over `held` experts whose weights stay
in HBM (`w1`, `w3` [held, D, I], `w2` [held, I, D]): the kernel walks the
list of hit experts (`ids`, ascending; `n_hit` of them, both data, both
scalar-prefetched as the page walk's table is) in a `fori_loop`, copies an
expert's three matrices with its own `make_async_copy` while the expert
before it computes (two experts in flight), and adds

    (weight[:, e] * silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]

into a float32 `[T, D]` accumulator that stays in VMEM for the whole call.
The router's weight is folded into the rows of the middle product BEFORE the
down dot, so there is no `[held, T, D]` intermediate and no select-and-sum
epilogue; bf16 operands, float32 accumulation.  An expert nobody picked is
never copied, a step that hits nothing returns zeros, no pick is left out
and there is no capacity.

The dispatch lives with the model (`models/deepseek_v32.py:_routed_experts`);
`refusal` says why a shape cannot lower for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# experts whose copies are in flight or being computed: one computes while the
# next one's three matrices arrive
EXPERTS_IN_FLIGHT = 2
# scoped VMEM the call may ask Mosaic for (a v5e core has 128 MiB): the ring of
# experts, and x, the weights, the accumulator and the dots' temporaries beside it
_VMEM_CEILING = 100 * 1024 * 1024
_VMEM_BESIDE_RING = 12 * 1024 * 1024


def _expert_bytes(w1):
    return 3 * w1.shape[1] * w1.shape[2] * w1.dtype.itemsize


def _vmem_limit(w1):
    return EXPERTS_IN_FLIGHT * _expert_bytes(w1) + _VMEM_BESIDE_RING


def refusal(x, w1):
    """None where the kernel lowers for the TPU at these shapes, else why not."""
    T, D = x.shape
    I = w1.shape[2]
    if x.dtype != w1.dtype or x.dtype not in (jnp.bfloat16, jnp.float32):
        return f"dtypes {x.dtype} / {w1.dtype}"
    if D % 128 or I % 128:
        return f"hidden {D} or intermediate {I} not whole lanes"
    if T > 128:
        return f"{T} tokens"
    if _vmem_limit(w1) > _VMEM_CEILING:
        return f"{EXPERTS_IN_FLIGHT} experts of {_expert_bytes(w1)} B over the VMEM a call may take"
    return None


def hit_list(counts):
    """counts [held] (picks an expert got) -> (ids [held] int32, n_hit [1]
    int32): the hit experts ascending in `ids[:n_hit]`; what follows is never
    read (held - 1, in range)."""
    held = counts.shape[0]
    hit = counts > 0
    upto = jnp.cumsum(hit, dtype=jnp.int32)  # hits at or before an expert
    # the j-th hit expert is the number of experts with at most j hits up to them
    ids = jnp.sum(upto[None, :] <= jnp.arange(held, dtype=jnp.int32)[:, None], axis=1, dtype=jnp.int32)
    return jnp.minimum(ids, held - 1), upto[-1:]


def _forward(x, weight, ids, n_hit, w1, w3, w2, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import profiler as _prof

    T, D = x.shape
    held, _, I = w1.shape
    sub = 32 // x.dtype.itemsize  # rows of one sublane tile: 8 (f32), 16 (bf16)
    Tp = -(-T // sub) * sub
    if Tp != T:  # pad rows weigh 0 and are sliced off
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        weight = jnp.pad(weight, ((0, Tp - T), (0, 0)))
    _prof.record_grouped_experts(tokens=T, held=held, expert_bytes=_expert_bytes(w1),
                                 experts_in_flight=EXPERTS_IN_FLIGHT, grid_steps=1)
    R = EXPERTS_IN_FLIGHT

    def kernel(ids_ref, n_ref, x_ref, wt_ref, w1_hbm, w3_hbm, w2_hbm, o_ref, b1, b3, b2, sem):
        n = n_ref[0]

        def copies(j, slot):
            e = ids_ref[j]
            return [pltpu.make_async_copy(hbm.at[e], buf.at[slot], sem.at[m, slot])
                    for m, (hbm, buf) in enumerate(((w1_hbm, b1), (w3_hbm, b3), (w2_hbm, b2)))]

        def start(j, slot):
            for c in copies(j, slot):
                c.start()

        for ahead in range(R - 1):  # the ring's first experts
            pl.when(ahead < n)(functools.partial(start, ahead, ahead))
        o_ref[...] = jnp.zeros_like(o_ref)
        lane = lax.broadcasted_iota(jnp.int32, (Tp, held), 1)

        def body(j, carry):
            slot = j % R
            pl.when(j + R - 1 < n)(lambda: start(j + R - 1, (j + R - 1) % R))
            up1, up3, down = copies(j, slot)
            xb = x_ref[...]
            up1.wait()
            up3.wait()
            mid = jax.nn.silu(jnp.dot(xb, b1[slot], preferred_element_type=jnp.float32)) * jnp.dot(
                xb, b3[slot], preferred_element_type=jnp.float32)
            # column ids[j] of the weights, as a column: a token that did not pick the expert weighs 0
            col = jnp.sum(jnp.where(lane == ids_ref[j], wt_ref[...], 0.0), axis=1, keepdims=True)
            down.wait()
            o_ref[...] += jnp.dot((mid * col).astype(xb.dtype), b2[slot], preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, n, body, 0)

    whole = lambda shape: pl.BlockSpec(shape, lambda i, *scalars: (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole((Tp, D)), whole((Tp, held)), hbm, hbm, hbm],
        out_specs=whole((Tp, D)),
        scratch_shapes=[pltpu.VMEM((R, D, I), w1.dtype), pltpu.VMEM((R, D, I), w3.dtype),
                        pltpu.VMEM((R, I, D), w2.dtype), pltpu.SemaphoreType.DMA((3, R))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(w1)),
        interpret=interpret,
        name="grouped_experts",
    )(ids, n_hit, x, weight, w1, w3, w2)
    return out[:T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def grouped_experts(x, weight, ids, n_hit, w1, w3, w2, interpret=False):
    """x [T, D], weight [T, held] float32 (the router's weight of a token for
    an expert, 0 where it did not pick it), `ids`, `n_hit` from `hit_list`,
    w1 / w3 [held, D, I], w2 [held, I, D] -> the routed sum [T, D] float32.

    Differentiation-opaque like the page walk: the dispatch layer's eager
    path computes a vjp over every op and a scalar-prefetch `pallas_call` has
    no JVP rule; a decode step is inference only."""
    return _forward(x, weight, ids, n_hit, w1, w3, w2, interpret)


def _bwd(interpret, res, g):
    raise NotImplementedError("grouped_experts is inference-only (no backward): a larger step takes the loop")


grouped_experts.defvjp(lambda x, weight, ids, n_hit, w1, w3, w2, interpret: (
    _forward(x, weight, ids, n_hit, w1, w3, w2, interpret), None), _bwd)
