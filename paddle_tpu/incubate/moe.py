"""Mixture-of-Experts with expert parallelism (reference:
python/paddle/incubate/distributed/models/moe/MoELayer — gshard/switch
gating, capacity, alltoall dispatch — SURVEY.md §2.2 "EP").

TPU-native:
- gating is fully vectorized (lax.top_k + one-hot/cumsum capacity
  assignment; the k rounds are a tiny static unroll, not a per-token loop)
- dense path: GShard one-hot dispatch/combine einsums (MXU-friendly,
  static shapes), expert dim sharded over 'ep' (or 'mp' when no ep axis)
- expert-parallel path (axis_size('ep') > 1): shard_map over the 'ep'
  axis with EXPLICIT lax.all_to_all token exchange — each device gates its
  local tokens, exchanges [E, C_local, D] slots so it holds its E/ep
  experts' tokens from every peer, runs its local experts, and all-to-alls
  back (the reference's alltoall dispatch on the MoE process group).
  Per-device capacity is per-GROUP capacity, exactly the reference's
  local-group semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..ops.dispatch import apply, coerce
from ..distributed import mesh as _mesh
from ..tensor import Tensor


def gate_dispatch_tensors(lg, k, capacity, valid=None):
    """From router logits [T, E] build (dispatch [T, E, C], combine
    [T, E, C], aux_loss, stats).  Pure jax; shared by the dense path and
    the per-shard EP path.  Vectorized: lax.top_k picks the k experts at
    once; the static k-round unroll only sequences capacity priority
    (round 0 tokens claim slots before round 1), matching GShard.

    valid: optional [T] bool — rows marked invalid (EP tail-batch padding)
    make no slot claims and never appear in aux/drop accounting.
    stats: (dropped_assignments f32 scalar, expert_used i32 [E]) — the
    overflow accounting the reference's MoE layer exposes."""
    tokens, e = lg.shape
    probs = jax.nn.softmax(lg.astype(jnp.float32), -1)  # [T, E]
    # aux load-balance loss (GShard eq.): E * sum(me * ce)
    if valid is not None:
        v32 = valid.astype(jnp.float32)
        n_valid = jnp.maximum(v32.sum(), 1.0)
        me = (probs * v32[:, None]).sum(0) / n_valid
        ce = (
            jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32)
            * v32[:, None]
        ).sum(0) / n_valid
    else:
        me = probs.mean(0)
        ce = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32).mean(0)
    aux = (me * ce).sum() * e

    topv, topi = lax.top_k(probs, k)  # [T, k] each
    sel = jax.nn.one_hot(topi, e, dtype=jnp.int32)  # [T, k, E]
    if valid is not None:
        # pad rows claim no capacity slots and count no drops (their
        # all-zero sel rows yield slot 0 -> fits True -> zero contribution)
        sel = sel * valid.astype(jnp.int32)[:, None, None]
    disp = jnp.zeros((tokens, e, capacity), jnp.float32)
    comb = jnp.zeros((tokens, e, capacity), jnp.float32)
    used = jnp.zeros((e,), jnp.int32)
    gates_accum = jnp.zeros((tokens,), jnp.float32)
    dropped = jnp.zeros((), jnp.float32)
    for r in range(k):
        s = sel[:, r]  # [T, E]
        pos = jnp.cumsum(s, 0) * s - s + used[None, :] * s
        slot = (pos * s).sum(-1)  # [T]
        fits = slot < capacity
        onehot_slot = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
        contrib = (
            s.astype(jnp.float32)[:, :, None]
            * onehot_slot[:, None, :]
            * fits.astype(jnp.float32)[:, None, None]
        )
        disp = disp + contrib
        comb = comb + contrib * topv[:, r][:, None, None]
        used = used + (s * fits[:, None].astype(jnp.int32)).sum(0)
        gates_accum = gates_accum + topv[:, r] * fits.astype(jnp.float32)
        dropped = dropped + (1.0 - fits.astype(jnp.float32)).sum()
    comb = comb / jnp.maximum(gates_accum, 1e-9)[:, None, None]
    return disp, comb, aux, (dropped, used)


def expert_choice_tensors(lg, capacity, valid=None):
    """Expert-choice routing (Zhou et al. 2022; the reference exposes it as
    a gate option): each EXPERT picks its top-`capacity` tokens, so load is
    balanced by construction (aux loss identically 0) and no token-side
    overflow exists — tokens chosen by no expert pass through with zero
    update (residual handles them).  Returns the same (disp, comb, aux,
    stats) contract as gate_dispatch_tensors."""
    tokens, e = lg.shape
    capacity = min(capacity, tokens)  # an expert cannot pick more tokens than exist
    if valid is not None:
        # pad rows are unpickable: -inf affinity, zero softmax weight
        lg = jnp.where(valid[:, None], lg.astype(jnp.float32), -jnp.inf)
    scores = jax.nn.softmax(lg.astype(jnp.float32), 0)  # over tokens, per expert
    g, i = lax.top_k(scores.T, capacity)  # [E, C] each: expert -> its tokens
    sel = jax.nn.one_hot(i, tokens, dtype=jnp.float32)  # [E, C, T]
    disp = jnp.transpose(sel, (2, 0, 1))  # [T, E, C]
    comb = disp * g[None]  # g: [E, C] broadcast over tokens
    covered = jnp.clip(disp.sum((1, 2)), 0.0, 1.0)  # token picked by >=1 expert
    if valid is not None:
        v32 = valid.astype(jnp.float32)
        dropped = (v32 * (1.0 - covered)).sum()  # uncovered REAL tokens only
    else:
        dropped = (1.0 - covered).sum()
    used = jnp.full((e,), capacity, jnp.int32)
    return disp, comb, jnp.zeros((), jnp.float32), (dropped, used)


def route_tokens(lg, k, capacity, expert_choice, valid=None):
    """Single routing entry shared by the dense gate and the EP shard body
    (keeps the two paths from diverging)."""
    if expert_choice:
        return expert_choice_tensors(lg, capacity, valid=valid)
    return gate_dispatch_tensors(lg, k, capacity, valid=valid)


class TopKGate(nn.Layer):
    """top-1 (switch) / top-2 (gshard) gate with capacity + aux loss."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=1.25, gate_type="gshard"):
        super().__init__()
        if gate_type not in ("gshard", "switch", "expert_choice"):
            raise ValueError(f"unknown gate_type {gate_type!r}")
        self.num_experts = num_experts
        self.gate_type = gate_type
        self.top_k = 1 if gate_type == "switch" else top_k
        self.capacity_factor = capacity_factor
        self.wg = nn.Linear(d_model, num_experts, bias_attr=False)

    def capacity(self, tokens):
        return max(int(self.capacity_factor * tokens * self.top_k / self.num_experts), 1)

    def forward(self, x):
        # returns (dispatch [tokens, E, C], combine [tokens, E, C],
        # aux_loss, dropped, expert_used)
        logits = self.wg(x)
        cap = self.capacity(int(x.shape[0]))
        k = self.top_k
        ec = self.gate_type == "expert_choice"

        def f(lg):
            disp, comb, aux, (dropped, used) = route_tokens(lg, k, cap, ec)
            return disp, comb, aux, dropped, used

        return apply(f, [coerce(logits)], multi=True, name="moe_gate")


class ExpertFFN(nn.Layer):
    """E experts' FFN weights as stacked tensors, expert dim sharded over
    'ep' when the mesh provides it (falling back to 'mp')."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu"):
        super().__init__()
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden], default_initializer=I.XavierNormal())
        self.b1 = self.create_parameter([num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model], default_initializer=I.XavierNormal())
        self.b2 = self.create_parameter([num_experts, 1, d_model], is_bias=True)
        self.activation = activation
        axis = _expert_axis()
        if axis is not None:
            for t in (self.w1, self.b1, self.w2, self.b2):
                _mesh.shard_tensor_(t, P(axis, None, None))

    def forward(self, x):
        """x: [E, C, d_model] → [E, C, d_model]; batched per-expert matmul."""
        ins = [coerce(x), self.w1, self.b1, self.w2, self.b2]
        act = jax.nn.gelu if self.activation == "gelu" else jax.nn.relu

        def f(a, w1, b1, w2, b2):
            return _expert_ffn_arrays(a, w1, b1, w2, b2, act)

        return apply(f, ins, name="expert_ffn")


def _expert_ffn_arrays(a, w1, b1, w2, b2, act):
    h = act(jnp.einsum("ecd,edh->ech", a, w1) + b1)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2


def _expert_axis():
    if _mesh.axis_size("ep") > 1:
        return "ep"
    if _mesh.axis_size("mp") > 1:
        return "mp"
    return None


class MoELayer(nn.Layer):
    """Reference API: MoELayer(gate, experts, ...); here gate config + fused
    expert stack.  Input [B, S, D] → output [B, S, D] + aux loss stored on
    `.aux_loss` after each forward."""

    def __init__(self, d_model, d_hidden, num_experts, top_k=2, capacity_factor=1.25, gate="gshard", activation="gelu"):
        super().__init__()
        self.num_experts = num_experts
        self.gate = TopKGate(d_model, num_experts, top_k, capacity_factor, gate)
        self.experts = ExpertFFN(num_experts, d_model, d_hidden, activation)
        self.aux_loss = None
        # routing telemetry, refreshed every forward (reference: the MoE
        # layer's overflow counters): dropped assignment count, fraction of
        # the T*k routing slots dropped, per-expert slot usage [E]
        self.drop_stats = None

    def _set_stats(self, dropped, used, tokens):
        k = self.gate.top_k if self.gate.gate_type != "expert_choice" else 1
        self.drop_stats = {
            "dropped_tokens": dropped,
            "dropped_fraction": dropped / float(max(tokens * k, 1)),
            "expert_used": used,
        }

    def forward(self, x):
        b, s, d = x.shape[0], x.shape[1], x.shape[2]
        flat = x.reshape([b * s, d])
        if _mesh.axis_size("ep") > 1:
            out, aux = self._ep_forward(flat)
            self.aux_loss = aux
            return out.reshape([b, s, d])
        disp, comb, aux, dropped, used = self.gate(flat)
        self.aux_loss = aux
        self._set_stats(dropped, used, int(flat.shape[0]))
        ins = [coerce(flat), coerce(disp)]

        def dispatch(a, dsp):
            return jnp.einsum("td,tec->ecd", a, dsp.astype(a.dtype))

        expert_in = apply(dispatch, ins, name="moe_dispatch")
        axis = _expert_axis()
        if axis is not None:
            spec = P(axis, None, None)
            expert_in = apply(lambda a: _mesh.constraint(a, spec), [expert_in], name="moe_ep_shard")
        expert_out = self.experts(expert_in)

        def combine(eo, cmb):
            return jnp.einsum("ecd,tec->td", eo, cmb.astype(eo.dtype))

        out = apply(combine, [coerce(expert_out), coerce(comb)], name="moe_combine")
        return out.reshape([b, s, d])

    def _ep_forward(self, flat):
        """shard_map over 'ep': local gating → all_to_all dispatch → local
        experts → all_to_all combine.  Tokens are ep-sharded on entry; the
        expert count must divide by ep.  A token count that does NOT divide
        by ep (the varlen tail-batch case) is zero-padded up and the pad
        rows sliced off after the exchange — they occupy gate slots on the
        last shard only, the same skew the reference's padded dispatch has."""
        mesh = _mesh.get_mesh()
        ep = mesh.shape["ep"]
        e = self.num_experts
        if e % ep != 0:
            raise ValueError(f"num_experts {e} must divide by ep degree {ep}")
        tokens = int(flat.shape[0])
        pad = (-tokens) % ep
        if pad:
            from .. import ops as _ops

            zeros = apply(
                lambda a: jnp.zeros((pad, a.shape[1]), a.dtype), [coerce(flat)],
                name="moe_pad",
            )
            flat = _ops.concat([flat, zeros], axis=0)
        tokens_p = tokens + pad
        cap_local = self.gate.capacity(tokens_p // ep)
        k = self.gate.top_k
        ec = self.gate.gate_type == "expert_choice"
        act = jax.nn.gelu if self.experts.activation == "gelu" else jax.nn.relu

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(
                P("ep", None),            # tokens
                P("ep"),                  # valid-row mask (pad accounting)
                P(None, None),            # gate weight (replicated)
                P("ep", None, None),      # expert stacks sharded on ep
                P("ep", None, None),
                P("ep", None, None),
                P("ep", None, None),
            ),
            out_specs=(P("ep", None), P(), P(), P(None)),
            check_vma=False,
        )
        def local(fl, vl, wg, w1, b1, w2, b2):
            lg = fl.astype(jnp.float32) @ wg.astype(jnp.float32)  # [T_l, E]
            disp, comb, aux, (dropped, used) = route_tokens(
                lg, k, cap_local, ec, valid=None if pad == 0 else vl
            )
            ein = jnp.einsum("td,tec->ecd", fl, disp.astype(fl.dtype))  # [E, C_l, D]
            # exchange: split experts across peers, gather their token slots
            ein = lax.all_to_all(ein, "ep", split_axis=0, concat_axis=1, tiled=True)
            # [E/ep, ep*C_l, D] — this device's experts, everyone's tokens
            h = _expert_ffn_arrays(ein, w1, b1, w2, b2, act)
            h = lax.all_to_all(h, "ep", split_axis=1, concat_axis=0, tiled=True)
            out = jnp.einsum("ecd,tec->td", h, comb.astype(h.dtype))  # [T_l, D]
            aux = lax.pmean(aux, "ep")
            dropped = lax.psum(dropped, "ep")
            used = lax.psum(used, "ep")
            return out, aux, dropped, used

        xp = self.experts

        def f(fl, wg, w1, b1, w2, b2):
            fl = _mesh.constraint(fl, P("ep", None))
            vl = jnp.arange(tokens_p) < tokens
            vl = _mesh.constraint(vl, P("ep"))
            out, aux, dropped, used = local(fl, vl, wg, w1, b1, w2, b2)
            if pad:
                out = out[:tokens]
            return out, aux, dropped, used

        out, aux, dropped, used = apply(
            f,
            [coerce(flat), self.gate.wg.weight, xp.w1, xp.b1, xp.w2, xp.b2],
            multi=True,
            name="moe_ep_a2a",
        )
        # stats over REAL tokens only (pads make no claims and count none)
        self._set_stats(dropped, used, tokens)
        return out, aux
