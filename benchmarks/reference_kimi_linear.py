"""The plain reference of Kimi-Linear-48B-A3B (`configs/kimi-linear-*`): its
forward pass in straightforward float32 `jax.numpy`, matmuls at `highest`
precision.  No cache, no batching, no kernels, no chunked scan, nothing
imported from the program: weights come from `weights_kimi_linear.py` by
seed, one layer at a time, and one sequence goes through at a time.

Layer i (0-based) is multi-head latent attention (MLA) where `i + 1` is in
`linear_attn_config["full_attn_layers"]` and Kimi Delta Attention (KDA) where
it is in `kda_layers` (`weights_kimi_linear.layer_kind`).

KDA, per head, `x` the normed input, written as the recurrence itself, one
position after another (`lax.fori_loop` over the sequence):

    q = l2norm(silu(conv(x W_q))), k = l2norm(silu(conv(x W_k))), v = silu(conv(x W_v))
    g = -exp(A) * softplus(x W_fa W_fb + dt_bias),  beta = sigmoid(x W_b)
    S' = diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q * d^-0.5
    y = (rmsnorm(o) * w_norm * sigmoid(x W_ga W_gb)) W_o

`conv`: causal, depthwise, kernel `short_conv_kernel_size`, no bias; `l2norm(z)
= z * rsqrt(sum z^2 + 1e-6)` over a head; the output norm over each head with
one learned weight, the gate one a channel.  MLA (`mla_use_nope`): `q = x
W_q` (no low-rank step), `[ckv | k_pe] = x W_dkv`, `ckv = rms(ckv)`, `[k_nope
| v] = ckv W_ukv` for every position, softmax over every `s <= t` of `(q_nope
. k_nope + q_pe . k_pe) * (d_nope + d_rope)^-0.5` with NO rope on either side,
no gate, `W_o`.  The readings the published config does not spell out are
the program's (`paddle_tpu/models/kimi_linear.py`, and the configuration
file's `assumed`).

The feed-forward half is `reference_ling3.feed_forward` as it stands (a dense
SwiGLU, or the router over all `num_experts`, each HELD expert's SwiGLU over
the tokens that picked it, the shared expert in full), under the router's
names `weights_kimi_linear.model_cfg` gives.  Ids and logits are over the
sliced vocabulary.

`linear=` swaps the matmul of every linear layer (`reference.fp8_linear` is
the control of `correct`).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_kimi_linear as W
from .reference import HIGHEST, f32_linear
from .reference_ling3 import QUERY_BLOCK, TOKEN_BLOCK, feed_forward, head_logits, l2_norm, rms_norm


def kda(cfg, linear, lw, x, n_valid):
    """x [n, hidden] (normed), one sequence: the recurrence over positions 0
    .. n_valid - 1 (rows past them are padding and stay zero), a block of
    `TOKEN_BLOCK` positions' inputs at a time (at 64k positions the whole
    sequence's q, k, v and decays would be 5 GB), the state carried on."""
    n, h = x.shape
    H, d = cfg["num_attention_heads"], cfg["linear_attn_config"]["head_dim"]
    K = cfg["linear_attn_config"]["short_conv_kernel_size"]
    tb = min(TOKEN_BLOCK, n)
    if n % tb:
        raise ValueError(f"{n} rows do not divide into token blocks of {tb}")
    pre = "self_attn."
    w = lambda name: lw[pre + name]
    taps = jnp.split(w("conv.weight"), 3, axis=1)
    history = jnp.concatenate([jnp.zeros((K - 1, h), x.dtype), x])  # row j + K - 1 is x[j]

    def position(t, carry):
        S, out, q, k, v, a, beta = carry  # S [H, dk, dv]
        Sp = a[t][:, :, None] * S
        u = beta[t][:, None] * (v[t] - jnp.einsum("hkv,hk->hv", Sp, k[t], precision=HIGHEST))
        S = Sp + k[t][:, :, None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", S, q[t], precision=HIGHEST) * d ** -0.5
        return S, jax.lax.dynamic_update_index_in_dim(out, o, t, 0), q, k, v, a, beta

    def one_block(i, carry):
        S, y = carry
        t0 = i * tb
        xb = jax.lax.dynamic_slice_in_dim(x, t0, tb, 0)
        rows = jax.lax.dynamic_slice_in_dim(history, t0, tb + K - 1, 0)  # K - 1 before the block, then it
        # causal and depthwise: position t sees rows t - K + 1 .. t, the rows before the sequence zero
        q, k, v = (jax.nn.silu(sum(p[j:j + tb] * c[j] for j in range(K))).reshape(tb, H, d)
                   for p, c in ((linear(rows, w(f"{m}_proj.weight")), t) for m, t in zip("qkv", taps)))
        q, k = l2_norm(q), l2_norm(k)
        f = linear(linear(xb, w("f_a_proj.weight")), w("f_b_proj.weight")) + w("dt_bias")
        a = jnp.exp(-jnp.exp(w("A_log"))[None, :, None] * jax.nn.softplus(f.reshape(tb, H, d)))
        beta = jax.nn.sigmoid(linear(xb, w("b_proj.weight")))
        S, o, *_ = jax.lax.fori_loop(0, jnp.clip(n_valid - t0, 0, tb), position,
                                     (S, jnp.zeros((tb, H, d), jnp.float32), q, k, v, a, beta))
        gate = jax.nn.sigmoid(linear(linear(xb, w("g_a_proj.weight")), w("g_b_proj.weight")))
        yb = rms_norm(o, w("o_norm.weight"), cfg["rms_norm_eps"]) * gate.reshape(tb, H, d)
        return S, jax.lax.dynamic_update_slice_in_dim(y, linear(yb.reshape(tb, H * d), w("o_proj.weight")), t0, 0)

    _, y = jax.lax.fori_loop(0, (n_valid + tb - 1) // tb, one_block,
                             (jnp.zeros((H, d, d), jnp.float32), jnp.zeros_like(x)))
    return y


def mla(cfg, linear, lw, x, n_valid):
    """x [n, hidden] (normed), one sequence: K and V of every position, then a
    block of queries at a time over the keys at or before each; no rope."""
    n = x.shape[0]
    H, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    pre = "self_attn."
    kv = linear(x, lw[pre + "kv_a_proj_with_mqa.weight"])
    ckv = rms_norm(kv[:, :c], lw[pre + "kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    k_pe = kv[:, c:]
    kvu = linear(ckv, lw[pre + "kv_b_proj.weight"]).reshape(n, H, dn + dv)
    k_nope, v = kvu[..., :dn], kvu[..., dn:]
    scale = (dn + dr) ** -0.5
    qb = min(QUERY_BLOCK, n)
    if n % qb:
        raise ValueError(f"{n} rows do not divide into query blocks of {qb}")

    def one_block(i, out):
        t0 = i * qb
        q = linear(jax.lax.dynamic_slice_in_dim(x, t0, qb, 0), lw[pre + "q_proj.weight"]).reshape(qb, H, dn + dr)
        seen = jnp.arange(n)[None, :] <= (t0 + jnp.arange(qb))[:, None]
        logits = (jnp.einsum("thd,shd->hts", q[..., :dn], k_nope, precision=HIGHEST)
                  + jnp.einsum("thr,sr->hts", q[..., dn:], k_pe, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(out, o.reshape(qb, H * dv), t0, 0)

    o = jax.lax.fori_loop(0, (n_valid + qb - 1) // qb, one_block, jnp.zeros((n, H * dv), jnp.float32))
    return linear(o, lw[pre + "o_proj.weight"])


def block(cfg, linear, lw, x, n_valid):
    """One decoder layer over one sequence.  lw: the layer's leaves by their
    short names; rows at or past `n_valid` are padding."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, lw["input_layernorm.weight"], eps)
    x = x + (mla if "self_attn.kv_b_proj.weight" in lw else kda)(cfg, linear, lw, h, n_valid)
    return x + feed_forward(cfg, linear, lw, rms_norm(x, lw["post_attention_layernorm.weight"], eps), n_valid)


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
        xs = [step(lw, x, jnp.int32(len(s))) for x, s in zip(xs, sequences)]
        del lw
        if log is not None:
            jax.block_until_ready(xs)
            log(f"reference layer {layer} ({W.layer_kind(cfg, layer)}, "
                f"{'experts' if W.is_moe(cfg, layer) else 'dense'}): "
                f"{len(sequences)} sequences in {time.perf_counter() - t:.1f}s")
    return xs, ow


HEAD_BLOCK = 4096  # positions of one block of logits (4096 x 81,920 float32 is 1.3 GB)


def served_logit_gaps(cfg, seed, sequences, answer_starts, linear=f32_linear, pad_to=None, log=None,
                      pick=None):
    """For each sequence (prompt followed by its served tokens) the
    reference's logits at every position from `answer_starts[i]` on that
    produced a served token, reduced a block of positions at a time: (best
    logit, logit of the served token, argmax, logit of `pick[i]`'s token at
    each position or None).  A served stream of 10k tokens holds 3 GB of
    logits, so none are kept whole."""
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
