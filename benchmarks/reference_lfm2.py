"""The plain reference of LFM2-8B-A1B (`configs/lfm2-*`): its forward pass in
straightforward float32 `jax.numpy`, matmuls at `highest` precision.  No
cache, no batching, no kernels, nothing imported from the program: weights
come from `weights_lfm2.py` by seed, one layer at a time, and one sequence
goes through at a time.

Layer l is a gated short convolution or grouped-query attention by
`layer_types[l]`; the first `num_dense_layers` have a dense SwiGLU, the rest
the expert layer.  Block: `h = x + op(rms(x))`, `out = h + ff(rms(h))`.

    conv:       [B | C | u] = x W_in;  v = B * u;  w = conv(v);  y = (C * w) W_out
    attention:  q = rope(rms_q(x W_q)), k = rope(rms_k(x W_k)), v = x W_v;
                softmax over every s <= t of q . k * d^-0.5 (each KV head shared
                by its group of query heads);  o W_o
    experts:    s = sigmoid(x W_g); the top-k of s + bias are chosen; weights
                s over their sum + 1e-6, times routed_scaling_factor;
                sum_e w_e W2_e (silu(W1_e x) * W3_e x)
    output:     rms(x) E^T, the head tied to the embedding

`conv` is the full causal depthwise convolution over the whole sequence
(`conv_L_cache` taps, zeros before the first token), never the tail
recurrence the program keeps; attention goes over every key, a block of
queries at a time; each expert over the tokens that picked it.  The
readings the published config does not spell out are the
program's (`paddle_tpu/models/lfm2_moe.py`, and the configuration's
`assumed`): rope rotate-half, the head tied.

`linear=` swaps the matmul of every linear layer (`reference.fp8_linear` is
the control of `correct`).
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_lfm2 as W
from .reference import HIGHEST, f32_linear
from .reference_kimi_linear import HEAD_BLOCK
from .reference_ling3 import PICK_BLOCK, QUERY_BLOCK, TOKEN_BLOCK, causal_conv, rms_norm, rotate, swiglu


def rope_tables(cfg, seqlen):
    d = cfg["head_dim"]
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    f = np.outer(np.arange(seqlen, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), jnp.asarray(np.sin(f), jnp.float32)


def conv(cfg, linear, lw, x, n_valid):
    """x [n, hidden] (normed), one sequence."""
    b, c, u = jnp.split(linear(x, lw["conv.in_proj.weight"]), 3, axis=-1)
    return linear(c * causal_conv(b * u, lw["conv.conv.weight"]), lw["conv.out_proj.weight"])


def attention(cfg, linear, lw, x, cos, sin, n_valid):
    """x [n, hidden] (normed), one sequence: K and V of every position, then a
    block of queries at a time over the keys at or before each."""
    n = x.shape[0]
    H, KV, d, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["norm_eps"]
    pre = "self_attn."
    k = rms_norm(linear(x, lw[pre + "k_proj.weight"]).reshape(n, KV, d), lw[pre + "k_layernorm.weight"], eps)
    k = rotate(k, cos[:, None], sin[:, None])
    v = linear(x, lw[pre + "v_proj.weight"]).reshape(n, KV, d)
    qb = min(QUERY_BLOCK, n)
    if n % qb:
        raise ValueError(f"{n} rows do not divide into query blocks of {qb}")
    at = jnp.arange(n)

    def one_block(i, out):
        t0 = i * qb
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)
        q = rms_norm(linear(sl(x), lw[pre + "q_proj.weight"]).reshape(qb, KV, H // KV, d),
                     lw[pre + "q_layernorm.weight"], eps)
        q = rotate(q, sl(cos)[:, None, None], sl(sin)[:, None, None])
        seen = at[None, :] <= (t0 + jnp.arange(qb))[:, None]
        logits = jnp.einsum("tgrd,sgd->grts", q, k, precision=HIGHEST) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), axis=-1)
        o = jnp.einsum("grts,sgd->tgrd", p, v, precision=HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(qb, H * d), t0, 0)

    o = jax.lax.fori_loop(0, (n_valid + qb - 1) // qb, one_block, jnp.zeros((n, H * d), jnp.float32))
    return linear(o, lw[pre + "out_proj.weight"])


def route(cfg, linear, lw, x):
    """-> [n, num_experts] float32: an expert's weight for each token, 0
    where it was not picked."""
    n, E = x.shape[0], cfg["num_experts"]
    s = jax.nn.sigmoid(linear(x, lw["feed_forward.gate.weight"]))
    picked = jax.lax.top_k(s + lw["feed_forward.expert_bias"], cfg["num_experts_per_tok"])[1]
    on = jnp.zeros((n, E), bool).at[jnp.arange(n)[:, None], picked].set(True)
    w = jnp.where(on, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-6)
    return w * cfg["routed_scaling_factor"]


def moe(cfg, linear, lw, x):
    """The routed sum: each expert's SwiGLU over the tokens that picked it,
    `PICK_BLOCK` of them at a time in position order, as many rounds as its
    picks need (no pick is left out), weighted by the router.  The tokens
    are found by a stable sort, and a round that holds no pick is skipped
    by a `cond`: no `nonzero` and no `while_loop` (PERF.md, PR 35)."""
    n, h = x.shape
    cap = min(n, PICK_BLOCK)
    w = route(cfg, linear, lw, x)
    picked = w > 0
    counts = jnp.sum(picked, axis=0, dtype=jnp.int32)
    rows = jnp.concatenate([x, jnp.zeros((1, h), x.dtype)])  # row n stands for "no token"
    at_n = jnp.arange(n, dtype=jnp.int32)

    def one_expert(e, y):
        take = lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, False)
        w1, w3, w2 = (take(lw[f"feed_forward.experts.{m}"]) for m in ("w1", "w3", "w2"))
        we = jnp.concatenate([jax.lax.dynamic_index_in_dim(w, e, 1, False), jnp.zeros((1,), w.dtype)])
        count = take(counts)
        first = jnp.argsort(jnp.where(jax.lax.dynamic_index_in_dim(picked, e, 1, False), 0, 1), stable=True)
        tokens = jnp.where(at_n < count, first, n)  # the picking tokens in position order, then "none"

        def one_round(r, y):
            at = jax.lax.dynamic_slice_in_dim(tokens, r * cap, cap)
            return y.at[at].add(we[at][:, None] * swiglu(linear, rows[at], w1, w3, w2), mode="drop")

        for r in range(-(-n // cap)):
            y = jax.lax.cond(r * cap < count, functools.partial(one_round, r), lambda y: y, y)
        return y

    return jax.lax.fori_loop(0, cfg["num_experts"], one_expert, jnp.zeros_like(x))


def feed_forward(cfg, linear, lw, x, n_valid):
    """The token-wise half of a layer, a block of rows at a time."""
    n = x.shape[0]
    tb = min(TOKEN_BLOCK, n)
    if n % tb:
        raise ValueError(f"{n} rows do not divide into token blocks of {tb}")
    if "feed_forward.gate.weight" in lw:
        f = functools.partial(moe, cfg, linear, lw)
    else:
        f = lambda h: swiglu(linear, h, lw["feed_forward.w1.weight"], lw["feed_forward.w3.weight"],
                             lw["feed_forward.w2.weight"])

    def one_block(i, out):
        h = jax.lax.dynamic_slice_in_dim(x, i * tb, tb, 0)
        return jax.lax.dynamic_update_slice_in_dim(out, f(h), i * tb, 0)

    return jax.lax.fori_loop(0, (n_valid + tb - 1) // tb, one_block, jnp.zeros_like(x))


def block(cfg, linear, lw, x, cos, sin, n_valid):
    """One decoder layer over one sequence.  lw: the layer's leaves by their
    short names; rows at or past `n_valid` are padding."""
    eps = cfg["norm_eps"]
    h = rms_norm(x, lw["operator_norm.weight"], eps)
    if "conv.in_proj.weight" in lw:
        x = x + conv(cfg, linear, lw, h, n_valid)
    else:
        x = x + attention(cfg, linear, lw, h, cos, sin, n_valid)
    return x + feed_forward(cfg, linear, lw, rms_norm(x, lw["ffn_norm.weight"], eps), n_valid)


def head_logits(cfg, linear, ow, x):
    return linear(rms_norm(x, ow["model.embedding_norm.weight"], cfg["norm_eps"]),
                  ow["model.embed_tokens.weight"].T)


def layer_weights(seed, cfg, layer):
    pre = f"model.layers.{layer}."
    full = W.make(seed, cfg, W.layer_leaves(cfg, layer), jnp.float32)
    return {n[len(pre):]: a for n, a in full.items()}


def hidden_states(cfg, seed, sequences, linear=f32_linear, pad_to=None, log=None):
    """The final hidden states (before the last norm) of each sequence,
    [pad_to, hidden] each, one layer's weights on the chip at a time."""
    cfg = W.model_cfg(cfg)
    longest = max(len(s) for s in sequences)
    pad_to = pad_to or -(-longest // TOKEN_BLOCK) * TOKEN_BLOCK
    cos, sin = rope_tables(cfg, pad_to)
    ow = W.make(seed, cfg, W.outer_leaves(cfg), jnp.float32)
    xs = []
    for s in sequences:
        ids = np.zeros((pad_to,), np.int32)
        ids[: len(s)] = s
        xs.append(ow["model.embed_tokens.weight"][jnp.asarray(ids)])
    step = jax.jit(functools.partial(block, cfg, linear))
    for layer in range(cfg["num_hidden_layers"]):
        t = time.perf_counter()
        lw = layer_weights(seed, cfg, layer)
        xs = [step(lw, x, cos, sin, jnp.int32(len(s))) for x, s in zip(xs, sequences)]
        del lw
        if log is not None:
            jax.block_until_ready(xs)
            log(f"reference layer {layer} ({W.layer_kind(cfg, layer)}, "
                f"{'experts' if W.is_moe(cfg, layer) else 'dense'}): "
                f"{len(sequences)} sequences in {time.perf_counter() - t:.1f}s")
    return xs, ow


def served_logit_gaps(cfg, seed, sequences, answer_starts, linear=f32_linear, pad_to=None, log=None,
                      pick=None):
    """As `reference_kimi_linear.served_logit_gaps`: for each sequence (prompt
    followed by its served tokens) at every position from `answer_starts[i]`
    on that produced a served token, (best logit, logit of the served token,
    argmax, logit of `pick[i]`'s token or None), a block of positions at a
    time (a 20k-token stream's logits are 5 GB)."""
    cfg = W.model_cfg(cfg)
    xs, ow = hidden_states(cfg, seed, sequences, linear, pad_to, log)

    @jax.jit
    def reduce(ow, x, served, picked):
        lg = head_logits(cfg, linear, ow, x)
        at = lambda ids: jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1), at(served), jnp.argmax(lg, -1), at(picked)

    out = []
    for i, (x, s, a0) in enumerate(zip(xs, sequences, answer_starts)):
        served = np.asarray(s[a0:], np.int32)
        picked = served if pick is None else np.asarray(pick[i], np.int32)
        parts = []
        for b in range(0, len(served), HEAD_BLOCK):
            e = min(b + HEAD_BLOCK, len(served))
            rows = jnp.pad(x[a0 - 1 + b: a0 - 1 + e], ((0, HEAD_BLOCK - (e - b)), (0, 0)))
            ids = lambda a: jnp.asarray(np.pad(a[b:e], (0, HEAD_BLOCK - (e - b))))
            # the token at position t is produced from the hidden state at t - 1
            parts.append([np.asarray(r)[: e - b] for r in reduce(ow, rows, ids(served), ids(picked))])
        best, got, first, at = (np.concatenate(c) for c in zip(*parts))
        out.append((best, got, first, None if pick is None else at))
    return out
