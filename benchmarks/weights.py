"""Seeded weights of the Llama/Mistral decoder, made on the device.

One function of (seed, leaf name) gives each leaf, so the program's model
(all leaves in one jitted call, in the served dtype) and the plain reference
(one block at a time, float32) get the same numbers without either taking
anything from the other.  Matrices are normal(0, std) rounded to bfloat16,
the type they are served in; norm weights are ones.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

LAYER_MATRICES = (
    ("self_attn.q_proj.weight", "hidden_size", "q_out"),
    ("self_attn.k_proj.weight", "hidden_size", "kv_out"),
    ("self_attn.v_proj.weight", "hidden_size", "kv_out"),
    ("self_attn.o_proj.weight", "q_out", "hidden_size"),
    ("mlp.gate_proj.weight", "hidden_size", "intermediate_size"),
    ("mlp.up_proj.weight", "hidden_size", "intermediate_size"),
    ("mlp.down_proj.weight", "intermediate_size", "hidden_size"),
)
LAYER_NORMS = ("input_layernorm.weight", "post_attention_layernorm.weight")


def dims(cfg):
    """The sizes the leaf shapes are written in."""
    d = dict(cfg)
    d["q_out"] = cfg["num_attention_heads"] * cfg["head_dim"]
    d["kv_out"] = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d


def layer_leaves(cfg, layer):
    """[(name, shape, is_matrix)] of one decoder layer, program's names and
    layout (Linear weights are [in, out])."""
    d = dims(cfg)
    pre = f"llama.layers.{layer}."
    out = [(pre + n, (d[a], d[b]), True) for n, a, b in LAYER_MATRICES]
    out += [(pre + n, (d["hidden_size"],), False) for n in LAYER_NORMS]
    return out


def outer_leaves(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return [
        ("llama.embed_tokens.weight", (v, h), True),
        ("llama.norm.weight", (h,), False),
        ("lm_head.weight", (h, v), True),
    ]


def all_leaves(cfg):
    out = outer_leaves(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, layer)
    return out


def seed_key(seed):
    """A key from any whole number up to and past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def leaf(key, name, shape, is_matrix, std, dtype):
    if not is_matrix:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32) * std
    # `reduce_precision` and not a convert to bfloat16 and back: on the TPU
    # the compiler may keep the excess precision of such a pair, and the
    # reference's float32 weights then differ from the program's by a
    # rounding each (PERF.md, PR 24)
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7).astype(dtype)


def make(seed, cfg, leaves, matrix_dtype):
    """{name: array} for `leaves`, in one jitted call; norms in float32."""
    std = float(cfg["init"]["matrix_std"])
    leaves = tuple(leaves)

    @jax.jit
    def f(key):
        return {
            n: leaf(key, n, s, m, std, matrix_dtype if m else jnp.float32)
            for n, s, m in leaves
        }

    return f(seed_key(seed))
