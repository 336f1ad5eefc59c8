"""The plain reference of DeepSeek-V3.2 (`configs/deepseek-v3.2-*`): its forward
pass in straightforward float32 `jax.numpy`, matmuls at `highest` precision.
No cache, no batching, no kernels, nothing imported from the program: weights
come from `weights_deepseek_v32.py` by seed, one layer at a time, and one
sequence goes through at a time, a block of queries at a time, so that a
24k-token sequence fits beside nothing else on the chip.

It is given the program's share and slice (the configuration's file): the
router scores all `n_routed_experts`, only picks that land on the
`experts_held` experts from `expert_offset` are computed, the shared expert is
added in full; ids and logits are over the sliced vocabulary.  It makes its
OWN top-`index_topk` selection.

Departures from the published model, the same as the program's and written in
the configuration's file: the MTP module is not built; weights, cache and
indexer are bfloat16-valued, not FP8 with block scales, so the indexer's
Hadamard rotation (which leaves a dot product unchanged) is left out; rope
pairs element i with i + d/2 in MLA and in the indexer; the router's bias is
the seed's.  In the mathematics: none.

`linear=` swaps the matmul of every linear layer (`reference.fp8_linear` is
the control of `correct`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_deepseek_v32 as W
from .reference import HIGHEST, f32_linear

QUERY_BLOCK = 64    # rows of one block of queries (a [heads, 64, 24576] score block is 0.8 GB)
TOKEN_BLOCK = 2048  # rows of one block of the token-wise layers


def yarn_inv_freq(cfg):
    d, base, rs = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg["rope_scaling"]
    freqs = 1.0 / (base ** (np.arange(0, d, 2, dtype=np.float64) / d))
    orig = rs["original_max_position_embeddings"]
    if cfg["max_position_embeddings"] <= orig:
        return freqs
    dim_of = lambda turns: d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs * (1 - ramp) + freqs / rs["factor"] * ramp


def rope_tables(cfg, seqlen):
    f = np.outer(np.arange(seqlen, dtype=np.float64), yarn_inv_freq(cfg))
    return jnp.asarray(np.cos(f), jnp.float32), jnp.asarray(np.sin(f), jnp.float32)


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if cfg["max_position_embeddings"] > rs["original_max_position_embeddings"]:
        scale *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0) ** 2
    return scale


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, axis=-1, keepdims=True) + eps) * w + b


def rotate(x, cos, sin):
    """x [..., d], cos/sin [..., d/2]: element i pairs with i + d/2."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rotate_head(x, r, cos, sin):
    return jnp.concatenate([rotate(x[..., :r], cos, sin), x[..., r:]], axis=-1)


def top_mask(score, k):
    """score [q, n], -inf where a key may not be seen -> bool [q, n]: the k
    largest of each row, equal scores to the lower position."""
    n = score.shape[1]
    order = jnp.argsort(-score, axis=1, stable=True)[:, : min(k, n)]
    mask = jnp.zeros(score.shape, bool).at[jnp.arange(score.shape[0])[:, None], order].set(True)
    return mask & (score > -jnp.inf)


def attention(cfg, linear, lw, x, cos, sin, n_valid):
    """x [n, hidden] (normed), one sequence.  Keys' rows for every position,
    then a block of queries at a time: the indexer's scores over the keys at
    or before each query, its top `index_topk`, softmax over exactly those.
    Blocks of padding past `n_valid` are not visited."""
    n = x.shape[0]
    H, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    Hi, Di, eps = cfg["index_n_heads"], cfg["index_head_dim"], cfg["rms_norm_eps"]
    cq = rms_norm(linear(x, lw["self_attn.q_a_proj.weight"]), lw["self_attn.q_a_layernorm.weight"], eps)
    kv = linear(x, lw["self_attn.kv_a_proj_with_mqa.weight"])
    ckv = rms_norm(kv[:, :c], lw["self_attn.kv_a_layernorm.weight"], eps)
    k_pe = rotate(kv[:, c:], cos, sin)
    kvu = linear(ckv, lw["self_attn.kv_b_proj.weight"]).reshape(n, H, dn + dv)
    k_nope, v = kvu[..., :dn], kvu[..., dn:]
    ki = rotate_head(layer_norm(linear(x, lw["self_attn.indexer.wk.weight"]),
                                lw["self_attn.indexer.k_norm.weight"],
                                lw["self_attn.indexer.k_norm.bias"], eps), dr, cos, sin)
    wi = linear(x, lw["self_attn.indexer.weights_proj.weight"]) * (Hi ** -0.5 * Di ** -0.5)
    scale = softmax_scale(cfg)
    qb = min(QUERY_BLOCK, n)
    if n % qb:
        raise ValueError(f"{n} rows do not divide into query blocks of {qb}")

    def one_block(i, out):
        t0 = i * qb
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)
        cqb, cb, sb = sl(cq), sl(cos), sl(sin)
        q = linear(cqb, lw["self_attn.q_b_proj.weight"]).reshape(qb, H, dn + dr)
        q_nope, q_pe = q[..., :dn], rotate(q[..., dn:], cb[:, None], sb[:, None])
        qi = rotate_head(linear(cqb, lw["self_attn.indexer.wq_b.weight"]).reshape(qb, Hi, Di),
                         dr, cb[:, None], sb[:, None])
        seen = jnp.arange(n)[None, :] <= (t0 + jnp.arange(qb))[:, None]
        index = jnp.einsum("th,ths->ts", sl(wi), jax.nn.relu(
            jnp.einsum("thd,sd->ths", qi, ki, precision=HIGHEST)), precision=HIGHEST)
        chosen = top_mask(jnp.where(seen, index, -jnp.inf), cfg["index_topk"])
        logits = (jnp.einsum("thd,shd->hts", q_nope, k_nope, precision=HIGHEST)
                  + jnp.einsum("thr,sr->hts", q_pe, k_pe, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(chosen[None], logits, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST).reshape(qb, H * dv)
        return jax.lax.dynamic_update_slice_in_dim(out, o, t0, 0)

    blocks = (n_valid + qb - 1) // qb
    o = jax.lax.fori_loop(0, blocks, one_block, jnp.zeros((n, H * dv), jnp.float32))
    return linear(o, lw["self_attn.o_proj.weight"])


def indexer_selection(cfg, linear, lw, x, cos, sin, rows):
    """The reference's own selection for the queries at `rows` (int32 [r]) of
    one sequence x [n, hidden] (normed): int32 [r, k] in position order, then
    -1 where fewer than k keys are in context."""
    n = x.shape[0]
    Hi, Di, dr, eps = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"], cfg["rms_norm_eps"]
    at = jnp.asarray(rows, jnp.int32)
    cq = rms_norm(linear(x[at], lw["self_attn.q_a_proj.weight"]), lw["self_attn.q_a_layernorm.weight"], eps)
    qi = rotate_head(linear(cq, lw["self_attn.indexer.wq_b.weight"]).reshape(at.shape[0], Hi, Di),
                     dr, cos[at][:, None], sin[at][:, None])
    ki = rotate_head(layer_norm(linear(x, lw["self_attn.indexer.wk.weight"]),
                                lw["self_attn.indexer.k_norm.weight"],
                                lw["self_attn.indexer.k_norm.bias"], eps), dr, cos, sin)
    wi = linear(x[at], lw["self_attn.indexer.weights_proj.weight"]) * (Hi ** -0.5 * Di ** -0.5)
    index = jnp.einsum("th,ths->ts", wi, jax.nn.relu(
        jnp.einsum("thd,sd->ths", qi, ki, precision=HIGHEST)), precision=HIGHEST)
    chosen = top_mask(jnp.where(jnp.arange(n)[None, :] <= at[:, None], index, -jnp.inf), cfg["index_topk"])
    k = min(cfg["index_topk"], n)
    pos = jnp.where(chosen, jnp.arange(n)[None, :], n)
    sel = jnp.sort(pos, axis=1)[:, :k]
    return jnp.where(sel < n, sel, -1).astype(jnp.int32)


def first_layer_selection(cfg, seed, ids, rows, linear=f32_linear):
    """`indexer_selection` of the first layer, whose input is the embedding:
    the one layer where program and reference select from the same state.
    `ids` int32 [n] (padded on the right to one length, so that one compiled
    program serves every run), `rows` int32 [r]."""
    cfg = W.model_cfg(cfg)
    ow = outer_weights(seed, cfg)
    lw = layer_weights(seed, cfg, 0)
    cos, sin = rope_tables(cfg, len(ids))

    @jax.jit
    def f(embed, lw, ids, rows):
        x = rms_norm(embed[ids], lw["input_layernorm.weight"], cfg["rms_norm_eps"])
        return indexer_selection(cfg, linear, lw, x, cos, sin, rows)

    return f(ow["model.embed_tokens.weight"], lw, jnp.asarray(ids, jnp.int32), jnp.asarray(rows, jnp.int32))


def swiglu(linear, x, gate, up, down):
    return linear(jax.nn.silu(linear(x, gate)) * linear(x, up), down)


def route(cfg, linear, lw, x):
    """-> [n, n_routed_experts] float32: an expert's weight for each token,
    0 where it was not picked."""
    n, E, G = x.shape[0], cfg["n_routed_experts"], cfg["n_group"]
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
    """The held experts' part of the routed sum plus the shared expert: every
    held expert over every token, weighted by the router (0 where the token
    did not pick it)."""
    first, held = int(cfg.get("expert_offset", 0)), cfg["experts_held"]
    w = route(cfg, linear, lw, x)[:, first:first + held]
    y = swiglu(linear, x, lw["mlp.shared_experts.gate_proj.weight"],
               lw["mlp.shared_experts.up_proj.weight"], lw["mlp.shared_experts.down_proj.weight"])
    for e in range(held):
        y = y + w[:, e:e + 1] * swiglu(linear, x, lw["mlp.experts.gate_proj"][e],
                                       lw["mlp.experts.up_proj"][e], lw["mlp.experts.down_proj"][e])
    return y


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


def block(cfg, linear, lw, x, cos, sin, n_valid):
    """One decoder layer over one sequence.  lw: the layer's leaves by their
    short names; rows at or past `n_valid` are padding (their blocks are
    skipped and hold zeros afterwards)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, linear, lw, rms_norm(x, lw["input_layernorm.weight"], eps),
                      cos, sin, n_valid)
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


def hidden_states(cfg, seed, sequences, linear=f32_linear, pad_to=None):
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
        lw = layer_weights(seed, cfg, layer)
        xs = [step(lw, x, cos, sin, jnp.int32(len(s))) for x, s in zip(xs, sequences)]
        del lw
    return xs, ow


def served_logit_gaps(cfg, seed, sequences, answer_starts, linear=f32_linear, pad_to=None):
    """As `reference.served_logit_gaps`: for each sequence (prompt followed by
    its served tokens) the reference's logits at every position that produced
    a served token: (best logit, logit of the served token, argmax, logits)."""
    cfg = W.model_cfg(cfg)
    xs, ow = hidden_states(cfg, seed, sequences, linear, pad_to)
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
