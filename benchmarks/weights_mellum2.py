"""Seeded weights of the Mellum2 decoder (`configs/mellum2-*`), made on the
device, one function of (seed, leaf name) as in `weights.py`: the program's
model (a layer at a time, in the served dtype) and the plain reference (a layer
at a time, float32) get the same numbers without either taking anything from
the other.  Matrices (the router's and each expert's among them) are normal(0,
`init.matrix_std`) rounded to bfloat16; norm weights ones.  An expert's
matrices are keyed by its index, one draw an expert.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

from .weights import seed_key

MATRIX, ONES, EXPERTS = "matrix", "ones", "experts"
SLIDING, FULL = "sliding_attention", "full_attention"


def layer_leaves(cfg, layer):
    """[(name, shape, kind)] of one decoder layer, the program's names and
    layout (a Linear's weight is [in, out]; the experts are stacked)."""
    h, H, KV, d = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    E, im = cfg["num_experts"], cfg["moe_intermediate_size"]
    pre = f"model.layers.{layer}."
    return [
        (pre + "input_layernorm.weight", (h,), ONES),
        (pre + "post_attention_layernorm.weight", (h,), ONES),
        (pre + "self_attn.q_proj.weight", (h, H * d), MATRIX),
        (pre + "self_attn.k_proj.weight", (h, KV * d), MATRIX),
        (pre + "self_attn.v_proj.weight", (h, KV * d), MATRIX),
        (pre + "self_attn.o_proj.weight", (H * d, h), MATRIX),
        (pre + "mlp.gate.weight", (h, E), MATRIX),
        (pre + "mlp.experts.gate_proj", (E, h, im), EXPERTS),
        (pre + "mlp.experts.up_proj", (E, h, im), EXPERTS),
        (pre + "mlp.experts.down_proj", (E, im, h), EXPERTS),
    ]


def outer_leaves(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("model.embed_tokens.weight", (v, h), MATRIX), ("model.norm.weight", (h,), ONES),
            ("lm_head.weight", (h, v), MATRIX)]


def all_leaves(cfg):
    out = outer_leaves(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, layer)
    return out


def _code(name):
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _one(key, code, std, shape, kind, dtype):
    """One leaf from the seed's key and its name's code (a traced number: the
    layers' leaves differ in it alone, so they share one compiled program)."""
    if kind == ONES:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, code)
    if kind == EXPERTS:
        x = jax.vmap(lambda e: jax.random.normal(jax.random.fold_in(k, e), shape[1:], jnp.float32))(
            jnp.arange(shape[0]))
    else:
        x = jax.random.normal(k, shape, jnp.float32)
    # `reduce_precision`, not a convert pair: `weights.leaf` says why
    return jax.lax.reduce_precision(x * std, exponent_bits=8, mantissa_bits=7).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(std, shapes_kinds, dtype):
    @jax.jit
    def f(key, codes):
        return [_one(key, codes[i], std, s, k, dtype) for i, (s, k) in enumerate(shapes_kinds)]

    return f


def make(seed, cfg, leaves, matrix_dtype):
    """{name: array} for `leaves`, in one jitted call; norms in float32.  The
    same numbers as `weights.leaf` draws for the name."""
    leaves = tuple(leaves)
    f = _maker(float(cfg["init"]["matrix_std"]), tuple((tuple(s), k) for _, s, k in leaves),
               jnp.dtype(matrix_dtype))
    made = f(seed_key(seed), jnp.asarray([_code(n) for n, _, _ in leaves], jnp.int32))
    return {n: a for (n, _, _), a in zip(leaves, made)}
