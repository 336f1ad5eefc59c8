"""The plain reference of Mellum2 (`configs/mellum2-*`): its forward pass in
straightforward float32 `jax.numpy`, matmuls at `highest` precision.  No cache,
no batching, no kernels, no page table, nothing imported from the program:
weights come from `weights_mellum2.py` by seed, one layer at a time, and one
sequence goes through at a time; the attention mask is built from positions.

For layer l of type `t = layer_types[l]`, `n = rmsnorm(x)`:

    h = x + Attn_t(n);  y = h + MoE(rmsnorm(h));  a final rmsnorm; an untied head
    Attn_t: q = n W_q (H x d), k = n W_k, v = n W_v (KV x d), no bias; rope of
      type t on q and k over the whole head, element i paired with i + d/2;
      scores q . k / sqrt(d); key j visible to query i iff j <= i and, for t =
      sliding_attention, i - j < sliding_window; softmax; o = concat W_o
    rope: sliding inv_freq_i = theta^(-2i/d); full: YaRN's blend of interpolated
      (/factor) and extrapolated frequencies by the linear ramp between the
      dimensions that beta_fast and beta_slow give at the original length, cos
      and sin times attention_factor
    MoE: p = softmax(n W_g) over all experts; the num_experts_per_tok largest;
      w = p_sel / sum(p_sel); sum_e w_e W_down,e (silu(W_gate,e n) * W_up,e n)

The readings the published config does not spell out (no q/k norm, the
pairing, the window counting the query's own position, softmax before top-k)
are written under `assumed` in the configuration's file.  Computed in blocks
(`QUERY_BLOCK` queries against every key, `TOKEN_BLOCK` rows of the token-wise
half, every expert over every token of a block) so that 32k tokens fit.

`linear=` swaps the matmul of every linear layer (`reference.fp8_linear` is the
control of `correct`); `window=False` switches the sliding window OFF (every
layer sees every key before it): the fault `benchmarks/tests` holds the
comparison to.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_mellum2 as W
from .reference import HIGHEST, f32_linear
from .reference_ling3 import rms_norm, rotate, swiglu

QUERY_BLOCK = 64    # rows of one block of queries (a [heads, 64, 32768] score block is 0.27 GB)
TOKEN_BLOCK = 2048  # rows of one block of the token-wise layers


def inv_freq(cfg, layer_type):
    """(inv_freq [d / 2], the factor on cos and sin) of one layer type."""
    rp, d = cfg["rope_parameters"][layer_type], cfg["head_dim"]
    base = float(rp["rope_theta"])
    freqs = 1.0 / (base ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if rp.get("rope_type", "default") == "default":
        return freqs, 1.0
    orig, factor = rp["original_max_position_embeddings"], rp["factor"]

    def turns_dim(n):  # the dimension that turns n times over the original length
        return d * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rp["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return freqs / factor * ramp + freqs * (1 - ramp), float(rp.get("attention_factor", 1.0))


def rope_tables(cfg, layer_type, seqlen):
    inv, factor = inv_freq(cfg, layer_type)
    f = np.outer(np.arange(seqlen, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f) * factor, jnp.float32), jnp.asarray(np.sin(f) * factor, jnp.float32)


def attention(cfg, linear, lw, x, cos, sin, n_valid, window):
    """x [n, hidden] (normed), one sequence: K and V of every position, then a
    block of queries at a time over the keys each may see (`window`: None, or
    the number of keys a query sees, itself among them)."""
    n = x.shape[0]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pre = "self_attn."
    k = rotate(linear(x, lw[pre + "k_proj.weight"]).reshape(n, KV, d), cos[:, None], sin[:, None])
    v = linear(x, lw[pre + "v_proj.weight"]).reshape(n, KV, d)
    qb = min(QUERY_BLOCK, n)
    if n % qb:
        raise ValueError(f"{n} rows do not divide into query blocks of {qb}")
    at = jnp.arange(n)

    def one_block(i, out):
        t0 = i * qb
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)
        q = rotate(linear(sl(x), lw[pre + "q_proj.weight"]).reshape(qb, KV, H // KV, d),
                   sl(cos)[:, None, None], sl(sin)[:, None, None])
        pos = t0 + jnp.arange(qb)
        seen = at[None, :] <= pos[:, None]
        if window is not None:
            seen &= pos[:, None] - at[None, :] < window
        logits = jnp.einsum("tgrd,sgd->grts", q, k, precision=HIGHEST) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), axis=-1)
        o = jnp.einsum("grts,sgd->tgrd", p, v, precision=HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(qb, H * d), t0, 0)

    o = jax.lax.fori_loop(0, (n_valid + qb - 1) // qb, one_block, jnp.zeros((n, H * d), jnp.float32))
    return linear(o, lw[pre + "o_proj.weight"])


def route(cfg, linear, lw, x):
    """-> [n, num_experts] float32: an expert's weight for each token, 0
    where it was not picked (the picks: every expert whose probability is at
    least the k-th largest)."""
    p = jax.nn.softmax(linear(x, lw["mlp.gate.weight"]), axis=-1)
    kth = jax.lax.top_k(p, cfg["num_experts_per_tok"])[0][:, -1:]
    w = jnp.where(p >= kth, p, 0.0)
    return w / jnp.sum(w, axis=1, keepdims=True) if cfg["norm_topk_prob"] else w


def moe(cfg, linear, lw, x):
    """The routed sum, as plain as it gets: every expert's SwiGLU over EVERY
    token of the block, weighted by the router, 0 where the token did not
    pick the expert.  (Each expert over its own picks alone, as
    `reference_ling3.moe` gathers them, never came back on the chip at these
    sizes: PERF.md, PR 35.)"""
    w = route(cfg, linear, lw, x)

    def one_expert(e, y):
        take = lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, False)
        gate, up, down = (take(lw[f"mlp.experts.{m}_proj"]) for m in ("gate", "up", "down"))
        return y + jax.lax.dynamic_index_in_dim(w, e, 1, True) * swiglu(linear, x, gate, up, down)

    return jax.lax.fori_loop(0, cfg["num_experts"], one_expert, jnp.zeros_like(x))


def feed_forward(cfg, linear, lw, x, n_valid):
    """The token-wise half of a layer, a block of rows at a time."""
    n = x.shape[0]
    tb = min(TOKEN_BLOCK, n)
    if n % tb:
        raise ValueError(f"{n} rows do not divide into token blocks of {tb}")

    def one_block(i, out):
        h = jax.lax.dynamic_slice_in_dim(x, i * tb, tb, 0)
        return jax.lax.dynamic_update_slice_in_dim(out, moe(cfg, linear, lw, h), i * tb, 0)

    return jax.lax.fori_loop(0, (n_valid + tb - 1) // tb, one_block, jnp.zeros_like(x))


def block(cfg, linear, window, lw, x, cos, sin, n_valid):
    """One decoder layer over one sequence.  lw: the layer's leaves by their
    short names; rows at or past `n_valid` are padding."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, linear, lw, rms_norm(x, lw["input_layernorm.weight"], eps), cos, sin,
                      n_valid, window)
    return x + feed_forward(cfg, linear, lw, rms_norm(x, lw["post_attention_layernorm.weight"], eps),
                            n_valid)


def head_logits(cfg, linear, ow, x):
    return linear(rms_norm(x, ow["model.norm.weight"], cfg["rms_norm_eps"]), ow["lm_head.weight"])


def layer_weights(seed, cfg, layer):
    pre = f"model.layers.{layer}."
    full = W.make(seed, cfg, W.layer_leaves(cfg, layer), jnp.float32)
    return {n[len(pre):]: a for n, a in full.items()}


def outer_weights(seed, cfg):
    return W.make(seed, cfg, W.outer_leaves(cfg), jnp.float32)


def hidden_states(cfg, seed, sequences, linear=f32_linear, pad_to=None, log=None, window=True):
    """The final hidden states (before the last norm) of each sequence,
    [pad_to, hidden] each, one layer's weights on the chip at a time."""
    longest = max(len(s) for s in sequences)
    pad_to = pad_to or -(-longest // TOKEN_BLOCK) * TOKEN_BLOCK
    ropes = {t: rope_tables(cfg, t, pad_to) for t in sorted(set(cfg["layer_types"]))}
    ow = outer_weights(seed, cfg)
    xs = []
    for s in sequences:
        ids = np.zeros((pad_to,), np.int32)
        ids[: len(s)] = s
        xs.append(ow["model.embed_tokens.weight"][jnp.asarray(ids)])
    reach = {W.SLIDING: cfg["sliding_window"] if window else None, W.FULL: None}
    steps = {t: jax.jit(functools.partial(block, cfg, linear, reach[t])) for t in ropes}
    for layer, kind in enumerate(cfg["layer_types"]):
        t = time.perf_counter()
        lw = layer_weights(seed, cfg, layer)
        xs = [steps[kind](lw, x, *ropes[kind], jnp.int32(len(s))) for x, s in zip(xs, sequences)]
        del lw
        if log is not None:
            jax.block_until_ready(xs)
            log(f"reference layer {layer} ({kind}): {len(sequences)} sequences in "
                f"{time.perf_counter() - t:.1f}s")
    return xs, ow


def served_logit_gaps(cfg, seed, sequences, answer_starts, linear=f32_linear, pad_to=None, log=None,
                      window=True):
    """As `reference.served_logit_gaps`: for each sequence (prompt followed by
    its served tokens) the reference's logits at every position from
    `answer_starts[i]` on that produced a served token: (best logit, logit of
    the served token, argmax, logits)."""
    xs, ow = hidden_states(cfg, seed, sequences, linear, pad_to, log, window)
    head = jax.jit(functools.partial(head_logits, cfg, linear))
    out = []
    for x, s, a0 in zip(xs, sequences, answer_starts):
        # the token at position t is produced from the hidden state at t - 1
        lg = head(ow, x[a0 - 1: len(s) - 1])
        served = jnp.asarray(np.asarray(s[a0:], np.int32))
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        out.append((np.asarray(best), np.asarray(got), np.asarray(jnp.argmax(lg, -1)), lg))
    return out
