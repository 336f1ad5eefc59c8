"""Kimi Delta Attention's decode step over its float32 state as one Pallas
kernel that reads the state once and writes it back in place (ISSUE 38).

A decode step of S slots, H heads, a state `[dk, dv]` a head: per head, with
`a = exp(g)` the decay of each key channel,

    S' = diag(a) S;  pred = S'^T k;  u = beta (v - pred)
    o = S'^T q + u (k . q);  S <- S' + k u^T

XLA cannot fuse this into one pass (the update needs `pred`, a reduction over
the whole decayed state), so it reads the state once for `pred` and `o` and
again for the update.  The kernel holds one slot's state (every head) in
VMEM a grid step, does all four steps there and writes it back over
the same HBM buffer (`input_output_aliases`): one read and one write.

The decays, keys and queries come in as rows `[heads, dk]`; the state wants
them as columns down its `dk` sublanes, so one transpose a grid step turns
the slot's rows into columns.  Arithmetic is float32 throughout, elementwise
products and sums over `dk` as `models/ling3.py:_kda_recurrence` has them: no
dot, nothing rounded below float32.  A slot that is not live (`live`,
scalar-prefetched) gets its state back unchanged, bit for bit.

The dispatch lives with the model (`models/ling3.py:_kda_recurrence`);
`refusal` says why a shape cannot lower for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# scoped VMEM the call may ask Mosaic for (a v5e core has 128 MiB)
_VMEM_CEILING = 100 * 1024 * 1024
# beside a slot's state (in and out, double-buffered): the rows, their columns
# and one head's temporaries
_VMEM_BESIDE_BLOCKS = 8 * 1024 * 1024


def _slot_bytes(state):
    return state.shape[1] * state.shape[2] * state.shape[3] * 4


def _vmem_limit(state):
    return 4 * _slot_bytes(state) + _VMEM_BESIDE_BLOCKS


def refusal(state):
    """None where the kernel lowers for the TPU at this state's shape, else why not."""
    dk, dv = state.shape[2:]
    if state.dtype != jnp.float32:
        return f"state dtype {state.dtype}"
    if dk % 128 or dv % 128:
        return f"dk {dk} or dv {dv} not whole lanes"
    if _vmem_limit(state) > _VMEM_CEILING:
        return f"a slot's state of {_slot_bytes(state)} B over the VMEM a call may take"
    return None


def _forward(q, k, g, v, beta, live, state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk, dv = state.shape

    def kernel(live_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref, o_ref, out_ref):
        keep = live_ref[pl.program_id(0)] != 0
        # the slot's decays, keys and queries as columns down dk: [dk, 3 H]
        cols = jnp.concatenate([jnp.exp(g_ref[0]), k_ref[0], q_ref[0]], axis=0).T
        beta = beta_ref[0]  # [H, 1]
        for h in range(H):
            a_c, k_c, q_c = (cols[:, i * H + h:i * H + h + 1] for i in range(3))  # [dk, 1]
            st = s_ref[0, h]
            sp = a_c * st
            pred = jnp.sum(sp * k_c, axis=0, keepdims=True)  # [1, dv]
            u = beta[h:h + 1] * (v_ref[0, h:h + 1] - pred)
            qk = jnp.sum(q_c * k_c, axis=0, keepdims=True)  # [1, 1]
            o_ref[0, h:h + 1] = jnp.sum(sp * q_c, axis=0, keepdims=True) + u * qk
            out_ref[0, h] = jnp.where(keep, sp + k_c * u, st)

    # one slot a grid step: at `reason64`'s state (2 MiB a slot) a call took
    # 0.455-0.457 ms, with 16 heads a step 0.463, with 8 0.491 (PR 38, chip
    # runs): a grid step costs a few tenths of a microsecond whatever it moves
    rows = lambda d: pl.BlockSpec((1, H, d), lambda s, lv: (s, 0, 0))
    block = pl.BlockSpec((1, H, dk, dv), lambda s, lv: (s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(1), block],
        out_specs=[rows(dv), block],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},  # live, q, k, g, v, beta, state: the state comes back in place
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=_vmem_limit(state)),
        interpret=interpret,
        name="kda_state_step",
    )(live.astype(jnp.int32), q, k, g, v, beta[..., None], state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _opaque_step(q, k, g, v, beta, live, state, interpret):
    """Differentiation-opaque like the page walk: a scalar-prefetch
    `pallas_call` has no JVP rule, and a decode step is inference only."""
    return _forward(q, k, g, v, beta, live, state, interpret)


def _bwd(interpret, res, g):
    raise NotImplementedError("kda_state_step is inference-only (no backward): a prefill takes the scan")


_opaque_step.defvjp(lambda q, k, g, v, beta, live, state, interpret: (
    _forward(q, k, g, v, beta, live, state, interpret), None), _bwd)
# jit keeps the trace: the six KDA layers of a step trace and lower the kernel
# once (six traces cost `reason64`'s set-up seconds, PR 38)
_state_step = jax.jit(_opaque_step, static_argnums=(7,))


def kda_state_step(q, k, g, v, beta, live, state, interpret=False):
    """q, k, g [S, H, dk] float32 (g the log decay), v [S, H, dv] float32,
    beta [S, H] float32, live [S] bool, state [S, H, dk, dv] float32 -> (o [S,
    H, dv] float32, unscaled; the state after the step, in the input's buffer)."""
    from .. import profiler as _prof

    S, H, dk, dv = state.shape
    _prof.record_kda_decode(slots=S, heads=H, dk=dk, dv=dv, heads_per_step=H, grid_steps=S,
                            state_bytes_per_step=_slot_bytes(state))
    return _state_step(q, k, g, v, beta, live, state, interpret)
