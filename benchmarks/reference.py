"""The plain reference: this decoder's forward pass, loss, gradients and
AdamW step in straightforward float32 `jax.numpy`, matmuls at `highest`
precision.  No kernels, no cache, no batching tricks, nothing imported from
the program and nothing taken from it: weights come from `weights.py` by
seed, one block at a time, so that it fits beside nothing else on the chip.

Departures from the published model: none in the mathematics.  Attention is
computed one (row, KV-head group) at a time so that a 4096 x 4096 score
matrix is 67 MB a head and not 4 GB a batch.

`linear=` swaps the matmul of every linear layer; `fp8_linear` is the
control of `correct`: the same reference with weights and activations
rounded to the float8 e4m3 grid (per-tensor scale), accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def f32_linear(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


FP8_MAX = 240.0  # the largest finite value of 4 exponent and 3 mantissa bits


@jax.custom_vjp
def _fp8_round(x):
    """x on the float8 e4m3 grid, per-tensor scale.  `reduce_precision`
    and not a convert to float8 and back: the TPU's compiler may keep the
    excess precision of such a pair, and did (PERF.md, PR 24)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s


_fp8_round.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (g,))


def fp8_linear(x, w):
    """W8A8: both operands through e4m3; gradients pass straight through the
    rounding and see the rounded operands."""
    return jnp.matmul(_fp8_round(x), _fp8_round(w), precision=HIGHEST)


def rope_tables(cfg, seqlen):
    d = cfg["head_dim"]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d))
    f = np.outer(np.arange(seqlen, dtype=np.float64), inv)
    emb = np.concatenate([f, f], axis=-1)
    return jnp.asarray(np.cos(emb), jnp.float32), jnp.asarray(np.sin(emb), jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def _attend_group(q, k, v):
    """q [g, s, d] (the query heads of one KV head), k, v [s, d]; causal."""
    s, d = k.shape
    scores = jnp.einsum("gqd,kd->gqk", q, k, precision=HIGHEST) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->gqd", p, v, precision=HIGHEST)


def attention(cfg, q, k, v):
    """q [b, s, heads, d], k, v [b, s, kv_heads, d] -> [b, s, heads * d]."""
    b, s, nh, d = q.shape
    kvh = cfg["num_key_value_heads"]
    g = nh // kvh
    qg = q.reshape(b, s, kvh, g, d).transpose(0, 2, 3, 1, 4).reshape(b * kvh, g, s, d)
    kg = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vg = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    out = jax.lax.map(lambda a: jax.checkpoint(_attend_group)(*a), (qg, kg, vg))
    return out.reshape(b, kvh, g, s, d).transpose(0, 3, 1, 2, 4).reshape(b, s, nh * d)


def block(cfg, linear, lw, x, cos, sin):
    """One decoder layer.  lw: this layer's leaves by their short names."""
    b, s, _ = x.shape
    nh, kvh, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, lw["input_layernorm.weight"], eps)
    q = linear(h, lw["self_attn.q_proj.weight"]).reshape(b, s, nh, d)
    k = linear(h, lw["self_attn.k_proj.weight"]).reshape(b, s, kvh, d)
    v = linear(h, lw["self_attn.v_proj.weight"]).reshape(b, s, kvh, d)
    c, si = cos[None, :, None, :], sin[None, :, None, :]
    a = attention(cfg, _rotate(q, c, si), _rotate(k, c, si), v)
    x = x + linear(a, lw["self_attn.o_proj.weight"])
    h = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    up = jax.nn.silu(linear(h, lw["mlp.gate_proj.weight"])) * linear(h, lw["mlp.up_proj.weight"])
    return x + linear(up, lw["mlp.down_proj.weight"])


def head_logits(cfg, linear, ow, x):
    return linear(rms_norm(x, ow["llama.norm.weight"], cfg["rms_norm_eps"]), ow["lm_head.weight"])


def head_loss(cfg, linear, ow, x, labels):
    """Mean next-token cross entropy over every label."""
    logits = head_logits(cfg, linear, ow, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def layer_weights(seed, cfg, layer):
    """One layer's leaves in float32, by short name."""
    pre = f"llama.layers.{layer}."
    full = W.make(seed, cfg, W.layer_leaves(cfg, layer), jnp.float32)
    return {n[len(pre):]: a for n, a in full.items()}


def outer_weights(seed, cfg):
    return W.make(seed, cfg, W.outer_leaves(cfg), jnp.float32)


# -- serving: teacher-forced logits ------------------------------------------

def served_logit_gaps(cfg, seed, sequences, answer_starts, linear=f32_linear,
                      pad_to=None):
    """For each sequence (prompt followed by its served tokens), the
    reference's logits at every position that produced a served token.

    Returns one array per sequence, [n_answer, vocab]-reduced to three
    vectors: (best logit, logit of the served token, argmax token).
    Sequences are padded on the right to one length (causal attention: the
    padding cannot reach back), and run through the layers together, one
    layer's weights on the chip at a time."""
    n = len(sequences)
    longest = max(len(s) for s in sequences)
    pad_to = pad_to or -(-longest // 256) * 256
    ids = np.zeros((n, pad_to), np.int32)
    for i, s in enumerate(sequences):
        ids[i, : len(s)] = s
    cos, sin = rope_tables(cfg, pad_to)
    ow = outer_weights(seed, cfg)
    x = ow["llama.embed_tokens.weight"][jnp.asarray(ids)]
    step = jax.jit(functools.partial(block, cfg, linear))
    for layer in range(cfg["num_hidden_layers"]):
        x = step(layer_weights(seed, cfg, layer), x, cos, sin)
    out = []
    head = jax.jit(functools.partial(head_logits, cfg, linear))
    for i, (s, a0) in enumerate(zip(sequences, answer_starts)):
        # the token at position t is produced from the hidden state at t - 1
        rows = x[i, a0 - 1 : len(s) - 1]
        lg = head(ow, rows)
        served = jnp.asarray(np.asarray(s[a0:], np.int32))
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        out.append((np.asarray(best), np.asarray(got), np.asarray(jnp.argmax(lg, -1)), lg))
    return out


# -- training: three AdamW steps, block by block -------------------------------

def adamw(hp, t, w, g, m, v):
    b1, b2 = hp["beta1"], hp["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["epsilon"])
    return w - hp["learning_rate"] * (upd + hp["weight_decay"] * w), m, v


def _norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(a)))) for k, a in tree.items()}


def train_steps(cfg, seed, batches, linear=f32_linear, fault=None):
    """Follow the first steps of training from the seed's weights.

    batches: [(ids [b, s], labels [b, s])] numpy, one per step.
    Returns {"losses": [...], "grad_norms": {leaf: norm of the first
    gradient}, "change_norms": {leaf: norm of w_after - w_before}}.

    Forward keeps each block's input; backward walks the blocks in reverse,
    re-running each block under `jax.vjp` and applying AdamW to its leaves at
    once, so only one block's gradients exist at a time.

    `fault` plants one of the faults `correct` must catch, for the tests and
    the upper readings: "half_batch" (the second half of the rows left out,
    the mean taken over the rest)."""
    hp = cfg["optimizer"]
    nl = cfg["num_hidden_layers"]
    ow = outer_weights(seed, cfg)
    lws = [layer_weights(seed, cfg, l) for l in range(nl)]
    zeros = lambda t: {k: jnp.zeros_like(a) for k, a in t.items()}
    om, ov = zeros(ow), zeros(ow)
    lm, lv = [zeros(t) for t in lws], [zeros(t) for t in lws]
    fwd = jax.jit(functools.partial(block, cfg, linear))

    @jax.jit
    def bwd(lw, x, cos, sin, gy):
        _, pull = jax.vjp(lambda w_, x_: block(cfg, linear, w_, x_, cos, sin), lw, x)
        return pull(gy)

    @jax.jit
    def top(ow_, x, labels):
        # embedding rows take their gradient from the scatter of gx below
        f = lambda w_, x_: head_loss(cfg, linear, w_, x_, labels)
        loss, (gw, gx) = jax.value_and_grad(f, argnums=(0, 1))(ow_, x)
        return loss, gw, gx

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 3, 4))
    def update(t, w, g, m, v):
        out = {k: adamw(hp, t, w[k], g[k], m[k], v[k]) for k in w}
        return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    losses, grad_norms = [], {}
    for t, (ids, labels) in enumerate(batches, start=1):
        if fault == "half_batch":
            ids, labels = ids[: len(ids) // 2], labels[: len(labels) // 2]
        ids_d, labels_d = jnp.asarray(ids), jnp.asarray(labels)
        cos, sin = rope_tables(cfg, ids.shape[1])
        xs = [ow["llama.embed_tokens.weight"][ids_d]]
        for l in range(nl):
            xs.append(fwd(lws[l], xs[-1], cos, sin))
        loss, gow, gx = top(ow, xs.pop(), labels_d)
        losses.append(float(loss))
        for l in reversed(range(nl)):
            glw, gx = bwd(lws[l], xs.pop(), cos, sin, gx)
            if t == 1:
                grad_norms.update({f"llama.layers.{l}.{k}": n for k, n in _norms(glw).items()})
            lws[l], lm[l], lv[l] = update(t, lws[l], glw, lm[l], lv[l])
            del glw
        gow["llama.embed_tokens.weight"] = (
            jnp.zeros_like(ow["llama.embed_tokens.weight"]).at[ids_d].add(gx)
        )
        if t == 1:
            grad_norms.update(_norms(gow))
        ow, om, ov = update(t, ow, gow, om, ov)
        del gow, gx
    # the update donates its buffers, so the weights as they began are made
    # again from the seed, one block at a time
    diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})
    change = {k: float(n) for k, n in diff(ow, outer_weights(seed, cfg)).items()}
    for l in range(nl):
        for k, n in diff(lws[l], layer_weights(seed, cfg, l)).items():
            change[f"llama.layers.{l}.{k}"] = float(n)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
