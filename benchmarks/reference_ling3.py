"""The plain reference of Ling-3.0-flash, text only (`configs/ling-3.0-flash-*`):
its forward pass in straightforward float32 `jax.numpy`, matmuls at `highest`
precision.  No cache, no batching, no kernels, no chunked scan, nothing
imported from the program: weights come from `weights_ling3.py` by seed, one
layer at a time, and one sequence goes through at a time.

Layer p (published index, 0-based) is multi-head latent attention (MLA) where
`(p + 1) % layer_group_size == 0` and Kimi Delta Attention (KDA) elsewhere; a
kept layer's kind follows its PUBLISHED index (`weights_ling3.layer_kind`).

KDA, per head, `x` the normed input, written as the recurrence itself, one
position after another (`lax.fori_loop` over the sequence):

    q = l2norm(silu(conv(x W_q))), k = l2norm(silu(conv(x W_k))), v = silu(conv(x W_v))
    g = kda_lower_bound * sigmoid(exp(A) * (x W_f + b_f)),  beta = sigmoid(x W_b)
    S' = diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q * d_k^-0.5
    y = (rmsnorm(o) * w_norm * sigmoid(x W_g)) W_o

`conv`: causal, depthwise, kernel `short_conv_kernel_size`, no bias; `l2norm(z)
= z * rsqrt(sum z^2 + 1e-6)` over a head; the output norm is over each head
with one learned weight, the gate one scalar a head.  MLA: `q = x W_q` (no
low-rank step), `[ckv | k_pe] = x W_dkv`, `ckv = rms(ckv)`, plain rope
(`rope_theta`, element i paired with i + d/2) on `q_pe` and `k_pe`, `[k_nope |
v] = ckv W_ukv` for every position, softmax over every `s <= t` of `(q_nope .
k_nope + q_pe . k_pe) * (d_nope + d_rope)^-0.5`, the same head-wise gate, `W_o`.
The readings the published config does not spell out are the program's
(`paddle_tpu/models/ling3.py`, marked (assumed) there and in the
configuration's file).

It is given the program's share and slice: the router scores all
`num_experts`, only the `experts_held` experts from `expert_offset` are
computed, the shared expert is added in full; ids and logits are over the
sliced vocabulary.  Each held expert's SwiGLU is applied to the tokens that
picked it (every held expert over EVERY token, weighted 0 where not picked,
is plainer still and was timed on the chip in PR 33: 15 s an expert layer
for 40k tokens, 90 of a run's 154 s of reference); `hidden_states` logs each
layer's seconds.  Not built, as in the program: vision tower, MTP, the SwiGLU clamp
(0 for every kept layer).

`linear=` swaps the matmul of every linear layer (`reference.fp8_linear` is
the control of `correct`).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_ling3 as W
from .reference import HIGHEST, f32_linear

QUERY_BLOCK = 64    # rows of one block of queries (a [heads, 64, 32768] score block is 0.27 GB)
TOKEN_BLOCK = 2048  # rows of one block of the token-wise layers
PICK_BLOCK = 128    # tokens of one expert's picks computed together (a block expects 2048 * 8 / 512 = 32 an expert)


def rope_tables(cfg, seqlen):
    d = cfg["qk_rope_head_dim"]
    inv = 1.0 / (float(cfg["rope_theta"]) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    f = np.outer(np.arange(seqlen, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), jnp.asarray(np.sin(f), jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def rotate(x, cos, sin):
    """x [..., d], cos/sin [..., d/2]: element i pairs with i + d/2."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_conv(x, taps):
    """x [n, channels], taps [K, channels]: y[t] = sum_i taps[i] x[t - (K - 1)
    + i], rows before the sequence zero."""
    K, n = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[i:i + n] * taps[i] for i in range(K))


def kda(cfg, linear, lw, x, n_valid):
    """x [n, hidden] (normed), one sequence: the recurrence over positions 0
    .. n_valid - 1 (rows past them are padding and stay zero)."""
    n = x.shape[0]
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    pre = "self_attn."
    taps = jnp.split(lw[pre + "conv.weight"], 3, axis=1)
    q, k, v = (jax.nn.silu(causal_conv(linear(x, lw[pre + f"{m}_proj.weight"]), t)).reshape(n, H, d)
               for m, t in zip("qkv", taps))
    q, k = l2_norm(q), l2_norm(k)
    f = (linear(x, lw[pre + "f_proj.weight"]) + lw[pre + "f_proj.bias"]).reshape(n, H, d)
    a = jnp.exp(cfg["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(lw[pre + "A_log"])[None, :, None] * f))
    beta = jax.nn.sigmoid(linear(x, lw[pre + "b_proj.weight"]))

    def position(t, carry):
        S, out = carry  # S [H, dk, dv]
        qt, kt, vt, at, bt = q[t], k[t], v[t], a[t], beta[t]
        Sp = at[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", Sp, kt, precision=HIGHEST))
        S = Sp + kt[:, :, None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST) * d ** -0.5
        return S, jax.lax.dynamic_update_index_in_dim(out, o, t, 0)

    _, o = jax.lax.fori_loop(0, n_valid, position,
                             (jnp.zeros((H, d, d), jnp.float32), jnp.zeros((n, H, d), jnp.float32)))
    y = rms_norm(o, lw[pre + "o_norm.weight"], cfg["rms_norm_eps"])
    y = y * jax.nn.sigmoid(linear(x, lw[pre + "g_proj.weight"]))[..., None]
    return linear(y.reshape(n, H * d), lw[pre + "o_proj.weight"])


def mla(cfg, linear, lw, x, cos, sin, n_valid):
    """x [n, hidden] (normed), one sequence: K and V of every position, then a
    block of queries at a time over the keys at or before each."""
    n = x.shape[0]
    H, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    pre = "self_attn."
    kv = linear(x, lw[pre + "kv_a_proj_with_mqa.weight"])
    ckv = rms_norm(kv[:, :c], lw[pre + "kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    k_pe = rotate(kv[:, c:], cos, sin)
    kvu = linear(ckv, lw[pre + "kv_b_proj.weight"]).reshape(n, H, dn + dv)
    k_nope, v = kvu[..., :dn], kvu[..., dn:]
    gate = jax.nn.sigmoid(linear(x, lw[pre + "g_proj.weight"]))
    scale = (dn + dr) ** -0.5
    qb = min(QUERY_BLOCK, n)
    if n % qb:
        raise ValueError(f"{n} rows do not divide into query blocks of {qb}")

    def one_block(i, out):
        t0 = i * qb
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)
        q = linear(sl(x), lw[pre + "q_proj.weight"]).reshape(qb, H, dn + dr)
        q_nope, q_pe = q[..., :dn], rotate(q[..., dn:], sl(cos)[:, None], sl(sin)[:, None])
        seen = jnp.arange(n)[None, :] <= (t0 + jnp.arange(qb))[:, None]
        logits = (jnp.einsum("thd,shd->hts", q_nope, k_nope, precision=HIGHEST)
                  + jnp.einsum("thr,sr->hts", q_pe, k_pe, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST) * sl(gate)[..., None]
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(qb, H * dv), t0, 0)

    o = jax.lax.fori_loop(0, (n_valid + qb - 1) // qb, one_block, jnp.zeros((n, H * dv), jnp.float32))
    return linear(o, lw[pre + "o_proj.weight"])


def swiglu(linear, x, gate, up, down):
    return linear(jax.nn.silu(linear(x, gate)) * linear(x, up), down)


def route(cfg, linear, lw, x):
    """-> [n, num_experts] float32: an expert's weight for each token, 0
    where it was not picked."""
    n, E, G = x.shape[0], cfg["num_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(linear(x, lw["mlp.gate.weight"]))
    choice = s + lw["mlp.gate.e_score_correction_bias"]
    group = jnp.sum(jax.lax.top_k(choice.reshape(n, G, E // G), 2)[0], axis=-1)
    best = jax.lax.top_k(group, cfg["topk_group"])[1]
    keep = jnp.zeros((n, G), bool).at[jnp.arange(n)[:, None], best].set(True)
    choice = jnp.where(jnp.repeat(keep, E // G, axis=1), choice, -jnp.inf)
    picked = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[1]
    on = jnp.zeros((n, E), bool).at[jnp.arange(n)[:, None], picked].set(True)
    w = jnp.where(on, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return w * cfg["routed_scaling_factor"]


def moe(cfg, linear, lw, x):
    """The held experts' part of the routed sum plus the shared expert: each
    held expert's SwiGLU over the tokens that picked it, `PICK_BLOCK` of them
    at a time in position order (as many rounds as its picks need: no pick is
    left out), weighted by the router."""
    first, held = int(cfg.get("expert_offset", 0)), cfg["experts_held"]
    n = x.shape[0]
    cap = min(n, PICK_BLOCK)
    w = route(cfg, linear, lw, x)[:, first:first + held]
    y = swiglu(linear, x, lw["mlp.shared_experts.gate_proj.weight"],
               lw["mlp.shared_experts.up_proj.weight"], lw["mlp.shared_experts.down_proj.weight"])
    rows = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])  # row n stands for "no token"

    def one_expert(e, y):
        take = lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, False)
        gate, up, down = (take(lw[f"mlp.experts.{m}_proj"]) for m in ("gate", "up", "down"))
        we = jnp.concatenate([jax.lax.dynamic_index_in_dim(w, e, 1, False), jnp.zeros((1,), w.dtype)])
        picked = we[:n] > 0
        rank = jnp.cumsum(picked) - 1  # a picking token's number among the expert's picks

        def one_round(carry):
            r, y = carry
            at = jnp.nonzero(picked & (rank >= r * cap) & (rank < (r + 1) * cap), size=cap, fill_value=n)[0]
            out = swiglu(linear, rows[at], gate, up, down)
            return r + 1, y.at[at].add(we[at][:, None] * out, mode="drop")

        return jax.lax.while_loop(lambda c: c[0] * cap < jnp.sum(picked), one_round, (0, y))[1]

    return jax.lax.fori_loop(0, held, one_expert, y)


def feed_forward(cfg, linear, lw, x, n_valid):
    """The token-wise half of a layer, a block of rows at a time."""
    n = x.shape[0]
    tb = min(TOKEN_BLOCK, n)
    if n % tb:
        raise ValueError(f"{n} rows do not divide into token blocks of {tb}")
    if "mlp.gate.weight" in lw:
        f = functools.partial(moe, cfg, linear, lw)
    else:
        f = lambda h: swiglu(linear, h, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
                             lw["mlp.down_proj.weight"])

    def one_block(i, out):
        h = jax.lax.dynamic_slice_in_dim(x, i * tb, tb, 0)
        return jax.lax.dynamic_update_slice_in_dim(out, f(h), i * tb, 0)

    return jax.lax.fori_loop(0, (n_valid + tb - 1) // tb, one_block, jnp.zeros_like(x))


def attention(cfg, linear, lw, x, cos, sin, n_valid):
    if "self_attn.kv_b_proj.weight" in lw:
        return mla(cfg, linear, lw, x, cos, sin, n_valid)
    return kda(cfg, linear, lw, x, n_valid)


def block(cfg, linear, lw, x, cos, sin, n_valid):
    """One decoder layer over one sequence.  lw: the layer's leaves by their
    short names; rows at or past `n_valid` are padding."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, linear, lw, rms_norm(x, lw["input_layernorm.weight"], eps), cos, sin, n_valid)
    return x + feed_forward(cfg, linear, lw,
                            rms_norm(x, lw["post_attention_layernorm.weight"], eps), n_valid)


def head_logits(cfg, linear, ow, x):
    return linear(rms_norm(x, ow["model.norm.weight"], cfg["rms_norm_eps"]), ow["lm_head.weight"])


def layer_weights(seed, cfg, layer):
    pre = f"model.layers.{layer}."
    full = W.make(seed, cfg, W.layer_leaves(cfg, layer), jnp.float32)
    return {n[len(pre):]: a for n, a in full.items()}


def outer_weights(seed, cfg):
    return W.make(seed, cfg, W.outer_leaves(cfg), jnp.float32)


def hidden_states(cfg, seed, sequences, linear=f32_linear, pad_to=None, log=None):
    """The final hidden states (before the last norm) of each sequence,
    [pad_to, hidden] each, one layer's weights on the chip at a time."""
    cfg = W.model_cfg(cfg)
    longest = max(len(s) for s in sequences)
    pad_to = pad_to or -(-longest // TOKEN_BLOCK) * TOKEN_BLOCK
    cos, sin = rope_tables(cfg, pad_to)
    ow = outer_weights(seed, cfg)
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


def served_logit_gaps(cfg, seed, sequences, answer_starts, linear=f32_linear, pad_to=None, log=None):
    """As `reference.served_logit_gaps`: for each sequence (prompt followed by
    its served tokens) the reference's logits at every position from
    `answer_starts[i]` on that produced a served token: (best logit, logit of
    the served token, argmax, logits)."""
    cfg = W.model_cfg(cfg)
    xs, ow = hidden_states(cfg, seed, sequences, linear, pad_to, log)
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
