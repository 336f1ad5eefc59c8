"""nn.functional (reference: python/paddle/nn/functional/) — XLA lowerings.

Convs/pools use lax.conv_general_dilated / lax.reduce_window (MXU-friendly,
NCHW accepted and handled natively by XLA layout assignment); norms are
written so XLA fuses them; attention routes to the Pallas flash kernel.
"""

from __future__ import annotations

import math
import numbers

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ...framework import core as _core
from ...framework.random import default_generator
from ...tensor import Tensor
from ...ops.dispatch import apply, coerce, amp_cast_inputs
from ...ops import matmul as _matmul
from ...ops.manipulation import label_smooth  # noqa: F401  (F.label_smooth)

# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def _unary(fn, name):
    def op(x, *args, **kwargs):
        x = coerce(x)
        return apply(fn, [x], name=name)

    op.__name__ = name
    return op


relu = _unary(jax.nn.relu, "relu")
relu6 = _unary(jax.nn.relu6, "relu6")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
tanh = _unary(jnp.tanh, "tanh")
silu = _unary(jax.nn.silu, "silu")
swish = silu
mish = _unary(lambda a: a * jnp.tanh(jax.nn.softplus(a)), "mish")
tanhshrink = _unary(lambda a: a - jnp.tanh(a), "tanhshrink")
softsign = _unary(jax.nn.soft_sign, "softsign")
hardswish = _unary(jax.nn.hard_swish, "hardswish")
hardsigmoid = _unary(lambda a: jnp.clip(a / 6.0 + 0.5, 0.0, 1.0), "hardsigmoid")


def relu_(x):
    from ...ops.dispatch import inplace_rebind

    return inplace_rebind(x, relu(x))


def gelu(x, approximate=False, name=None):
    x = coerce(x)
    return apply(lambda a: jax.nn.gelu(a, approximate=approximate), [x], name="gelu")


def leaky_relu(x, negative_slope=0.01, name=None):
    x = coerce(x)
    return apply(lambda a: jax.nn.leaky_relu(a, negative_slope), [x], name="leaky_relu")


def elu(x, alpha=1.0, name=None):
    x = coerce(x)
    return apply(lambda a: jax.nn.elu(a, alpha), [x], name="elu")


def celu(x, alpha=1.0, name=None):
    x = coerce(x)
    return apply(lambda a: jax.nn.celu(a, alpha), [x], name="celu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    x = coerce(x)
    return apply(
        lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), [x], name="selu"
    )


def prelu(x, weight, data_format="NCHW", name=None):
    x, weight = coerce(x), coerce(weight)

    def f(a, w):
        if w.size > 1:
            shape = [1] * a.ndim
            ch_axis = 1 if data_format.startswith("NC") else a.ndim - 1
            shape[ch_axis] = w.size
            w = w.reshape(shape)
        return jnp.where(a > 0, a, w * a)

    return apply(f, [x, weight], name="prelu")


def rrelu(x, lower=0.125, upper=0.333, training=False, name=None):
    x = coerce(x)
    if training:
        key = default_generator.next_key()
        return apply(
            lambda a: jnp.where(
                a >= 0, a, a * jax.random.uniform(key, a.shape, a.dtype, lower, upper)
            ),
            [x],
            name="rrelu",
        )
    mid = (lower + upper) / 2
    return apply(lambda a: jnp.where(a >= 0, a, a * mid), [x], name="rrelu")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    x = coerce(x)
    return apply(lambda a: jnp.clip(a, min, max), [x], name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    x = coerce(x)
    return apply(
        lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0).astype(a.dtype), [x]
    )


def softshrink(x, threshold=0.5, name=None):
    x = coerce(x)
    return apply(
        lambda a: jnp.where(
            a > threshold, a - threshold, jnp.where(a < -threshold, a + threshold, 0.0)
        ).astype(a.dtype),
        [x],
    )


def softplus(x, beta=1.0, threshold=20.0, name=None):
    x = coerce(x)
    return apply(
        lambda a: jnp.where(a * beta > threshold, a, jax.nn.softplus(a * beta) / beta),
        [x],
        name="softplus",
    )


def maxout(x, groups, axis=1, name=None):
    x = coerce(x)

    def f(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        newshape = a.shape[:ax] + (c // groups, groups) + a.shape[ax + 1 :]
        return jnp.max(a.reshape(newshape), axis=ax + 1)

    return apply(f, [x], name="maxout")


def softmax(x, axis=-1, dtype=None, name=None):
    x = coerce(x)
    (x,) = amp_cast_inputs([x], "black")
    return apply(lambda a: jax.nn.softmax(a, axis=axis), [x], name="softmax")


def log_softmax(x, axis=-1, dtype=None, name=None):
    x = coerce(x)
    (x,) = amp_cast_inputs([x], "black")
    return apply(lambda a: jax.nn.log_softmax(a, axis=axis), [x], name="log_softmax")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    x = coerce(x)
    key = default_generator.next_key()

    def f(a):
        g = jax.random.gumbel(key, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y).at[...].set(
                jnp.where(
                    jnp.arange(y.shape[axis]).reshape(
                        [-1 if i == (axis % y.ndim) else 1 for i in range(y.ndim)]
                    )
                    == idx,
                    1.0,
                    0.0,
                ).astype(y.dtype)
            )
            return y_hard - lax.stop_gradient(y) + y
        return y

    return apply(f, [x], name="gumbel_softmax")


def glu(x, axis=-1, name=None):
    x = coerce(x)
    return apply(lambda a: jax.nn.glu(a, axis=axis), [x], name="glu")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    x = coerce(x)
    return apply(
        lambda a: a
        / jnp.maximum(
            jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p), epsilon
        ),
        [x],
        name="normalize",
    )


# ---------------------------------------------------------------------------
# linear / embedding
# ---------------------------------------------------------------------------


def linear(x, weight, bias=None, name=None):
    """paddle semantics: weight shape [in_features, out_features]."""
    x, weight = coerce(x), coerce(weight)
    ins = [x, weight]
    if bias is not None:
        ins.append(coerce(bias))
    ins = amp_cast_inputs(ins, "white")

    def f(a, w, *b):
        out = jnp.matmul(a, w)
        if b:
            out = out + b[0]
        return out

    return apply(f, ins, name="linear")


def embedding(x, weight, padding_idx=None, sparse=False, name=None, max_norm=None, norm_type=2.0, scale_grad_by_freq=False):
    x, weight = coerce(x), coerce(weight)

    def f(i, w):
        idx = i.astype(jnp.int32)
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros((), w.dtype), out)
        return out

    return apply(f, [x, weight], name="embedding")


def one_hot(x, num_classes, name=None):
    from ...ops.manipulation import one_hot as _oh

    return _oh(x, num_classes)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    label = coerce(label)
    n = label.shape[-1]
    if prior_dist is not None:
        prior_dist = coerce(prior_dist)
        return apply(
            lambda l, p: (1 - epsilon) * l + epsilon * p, [label, prior_dist]
        )
    return apply(lambda l: (1 - epsilon) * l + epsilon / n, [label], name="label_smooth")


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------


def _tuplize(v, n):
    if isinstance(v, (list, tuple)):
        if len(v) == n:
            return tuple(int(x) for x in v)
        if len(v) == 1:
            return tuple(int(v[0]) for _ in range(n))
        return tuple(int(x) for x in v)
    return tuple(int(v) for _ in range(n))


def _conv_padding(padding, nsp, strides, kernel, dilation):
    """Returns lax padding spec: 'SAME'/'VALID' or list of (lo, hi)."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, (list, tuple)) and len(padding) and isinstance(padding[0], (list, tuple)):
        # [[0,0],[0,0],[h0,h1],[w0,w1]] paddle style or per-dim pairs
        pairs = [tuple(p) for p in padding]
        if len(pairs) == nsp:
            return pairs
        return pairs[-nsp:]
    p = _tuplize(padding, nsp)
    if len(p) == 2 * nsp:
        return [(p[2 * i], p[2 * i + 1]) for i in range(nsp)]
    return [(pi, pi) for pi in p]


def conv2d(
    x,
    weight,
    bias=None,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    data_format="NCHW",
    name=None,
):
    x, weight = coerce(x), coerce(weight)
    ins = [x, weight]
    if bias is not None:
        ins.append(coerce(bias))
    ins = amp_cast_inputs(ins, "white")
    strides = _tuplize(stride, 2)
    dil = _tuplize(dilation, 2)
    pad = _conv_padding(padding, 2, strides, None, dil)
    dn = ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")

    s2d = _space_to_depth_plan(x.shape, weight.shape, strides, pad, dil, groups, data_format)

    def f(a, w, *b):
        if s2d is not None:
            out = _space_to_depth_conv(a, w, s2d, data_format)
        else:
            if data_format == "NHWC":
                w = jnp.transpose(w, (2, 3, 1, 0))
            out = lax.conv_general_dilated(
                a,
                w,
                window_strides=strides,
                padding=pad,
                rhs_dilation=dil,
                dimension_numbers=dn,
                feature_group_count=groups,
            )
        if b:
            bias_arr = b[0]
            shape = [1, -1, 1, 1] if data_format == "NCHW" else [1, 1, 1, -1]
            out = out + bias_arr.reshape(shape)
        return out

    return apply(f, ins, name="conv2d")


def _space_to_depth_plan(xshape, wshape, strides, pad, dil, groups, data_format):
    """Decide whether a low-channel strided conv (a ResNet-style stem) should
    be rewritten as space-to-depth + dense conv.

    A 7x7/s2 conv on C=3 uses 3/128 of the MXU's lanes; regrouping sxs input
    pixels into channels turns it into an equivalent (k/s)x(k/s)/s1 conv on
    s*s*C channels, which tiles the MXU far better.  Returns a plan dict or
    None.  (TPU-native move; the reference's cuDNN picks specialized stem
    kernels instead — paddle/phi/kernels/gpu conv via cudnnFind.)
    """
    if groups != 1 or dil != (1, 1) or isinstance(pad, str):
        return None
    sh, sw = strides
    if sh != sw or sh < 2:
        return None
    cin = wshape[1]
    kh, kw = wshape[2], wshape[3]
    if cin * sh * sw > 32 or max(kh, kw) <= sh:
        return None
    hdim, wdim = (2, 3) if data_format == "NCHW" else (1, 2)
    H, W = xshape[hdim], xshape[wdim]
    k2h = -(-kh // sh) * sh  # kernel padded up to a stride multiple
    k2w = -(-kw // sw) * sw
    plan = {"s": sh, "k2": (k2h, k2w), "cin": cin, "cout": wshape[0], "k": (kh, kw)}
    for dim_len, (pl, pr), k, k2, key in (
        (H, pad[0], kh, k2h, "ph"),
        (W, pad[1], kw, k2w, "pw"),
    ):
        n_win = (dim_len + pl + pr - k) // sh + 1
        found = None
        for extra in range(0, 2 * sh):
            L = dim_len + pl + pr + extra
            if L % sh == 0 and (L - k2) // sh + 1 == n_win:
                found = (pl, pr + extra)
                break
        if found is None:
            return None
        plan[key] = found
    return plan


def _space_to_depth_conv(a, w, plan, data_format):
    """Equivalent conv after space-to-depth regrouping (see plan above)."""
    s = plan["s"]
    kh, kw = plan["k"]
    k2h, k2w = plan["k2"]
    cin, cout = plan["cin"], plan["cout"]
    (plh, prh), (plw, prw) = plan["ph"], plan["pw"]
    if data_format == "NCHW":
        a = jnp.transpose(a, (0, 2, 3, 1))  # stem only: one-off relayout
    n, _, _, _ = a.shape
    a = jnp.pad(a, ((0, 0), (plh, prh), (plw, prw), (0, 0)))
    H2, W2 = a.shape[1] // s, a.shape[2] // s
    # [N, H2, s, W2, s, C] -> [N, H2, W2, s*s*C]  (dh, dw, c) channel order
    a = a.reshape(n, H2, s, W2, s, cin).transpose(0, 1, 3, 2, 4, 5).reshape(n, H2, W2, s * s * cin)
    # weight OIHW -> padded HWIO -> regrouped [k2h/s, k2w/s, s*s*C, O]
    w = jnp.transpose(w, (2, 3, 1, 0))  # HWIO
    w = jnp.pad(w, ((0, k2h - kh), (0, k2w - kw), (0, 0), (0, 0)))
    w = w.reshape(k2h // s, s, k2w // s, s, cin, cout)
    w = w.transpose(0, 2, 1, 3, 4, 5).reshape(k2h // s, k2w // s, s * s * cin, cout)
    out = lax.conv_general_dilated(
        a, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if data_format == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL", name=None):
    x, weight = coerce(x), coerce(weight)
    ins = [x, weight]
    if bias is not None:
        ins.append(coerce(bias))
    ins = amp_cast_inputs(ins, "white")
    strides = _tuplize(stride, 1)
    dil = _tuplize(dilation, 1)
    pad = _conv_padding(padding, 1, strides, None, dil)
    dn = ("NCH", "OIH", "NCH") if data_format == "NCL" else ("NHC", "HIO", "NHC")

    def f(a, w, *b):
        out = lax.conv_general_dilated(
            a, w, window_strides=strides, padding=pad, rhs_dilation=dil,
            dimension_numbers=dn, feature_group_count=groups,
        )
        if b:
            shape = [1, -1, 1] if data_format == "NCL" else [1, 1, -1]
            out = out + b[0].reshape(shape)
        return out

    return apply(f, ins, name="conv1d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW", name=None):
    x, weight = coerce(x), coerce(weight)
    ins = [x, weight]
    if bias is not None:
        ins.append(coerce(bias))
    ins = amp_cast_inputs(ins, "white")
    strides = _tuplize(stride, 3)
    dil = _tuplize(dilation, 3)
    pad = _conv_padding(padding, 3, strides, None, dil)
    dn = ("NCDHW", "OIDHW", "NCDHW")

    def f(a, w, *b):
        out = lax.conv_general_dilated(
            a, w, window_strides=strides, padding=pad, rhs_dilation=dil,
            dimension_numbers=dn, feature_group_count=groups,
        )
        if b:
            out = out + b[0].reshape([1, -1, 1, 1, 1])
        return out

    return apply(f, ins, name="conv3d")


def conv2d_transpose(
    x, weight, bias=None, stride=1, padding=0, output_padding=0,
    groups=1, dilation=1, data_format="NCHW", output_size=None, name=None,
):
    if output_size is not None:
        # resolve the stride>1 output-length ambiguity the way the
        # reference does: derive the implied output_padding
        strides2 = _tuplize(stride, 2)
        dil2 = _tuplize(dilation, 2)
        pad2 = _conv_padding(padding, 2, strides2, None, dil2)
        if isinstance(pad2, str):
            raise NotImplementedError(
                "conv2d_transpose output_size with string padding is unsupported"
            )
        osz = _tuplize(output_size, 2)
        kh, kw = int(weight.shape[2]), int(weight.shape[3])
        opad = []
        for i, (k, insz) in enumerate(zip((kh, kw), (int(x.shape[2]), int(x.shape[3])))):
            base = (insz - 1) * strides2[i] - pad2[i][0] - pad2[i][1] + dil2[i] * (k - 1) + 1
            extra = int(osz[i]) - base
            if not 0 <= extra < strides2[i]:
                raise ValueError(
                    f"requested output_size[{i}]={osz[i]} unreachable "
                    f"(valid range [{base}, {base + strides2[i]}))"
                )
            opad.append(extra)
        output_padding = tuple(opad)
    x, weight = coerce(x), coerce(weight)
    ins = [x, weight]
    if bias is not None:
        ins.append(coerce(bias))
    ins = amp_cast_inputs(ins, "white")
    strides = _tuplize(stride, 2)
    dil = _tuplize(dilation, 2)
    pad = _conv_padding(padding, 2, strides, None, dil)
    opad = _tuplize(output_padding, 2)

    def f(a, w, *b):
        # weight layout: [in_c, out_c/groups, kh, kw] (paddle transpose-conv)
        kh, kw = w.shape[2], w.shape[3]
        if isinstance(pad, str):
            padding_pairs = pad
        else:
            padding_pairs = [
                (dil[i] * (k - 1) - pad[i][0], dil[i] * (k - 1) - pad[i][1] + opad[i])
                for i, k in enumerate((kh, kw))
            ]
        if groups > 1:
            # split input channels into groups for grouped transpose conv
            # (each group's kernel is flipped/transposed in the loop)
            ic = a.shape[1]
            outs = []
            icg = ic // groups
            for g in range(groups):
                outs.append(
                    lax.conv_general_dilated(
                        a[:, g * icg : (g + 1) * icg],
                        jnp.transpose(jnp.flip(w[g * icg : (g + 1) * icg], (2, 3)), (1, 0, 2, 3)),
                        window_strides=(1, 1),
                        padding=padding_pairs,
                        lhs_dilation=strides,
                        rhs_dilation=dil,
                        dimension_numbers=("NCHW", "OIHW", "NCHW"),
                    )
                )
            out = jnp.concatenate(outs, axis=1)
        else:
            # IOHW → rotate 180° → [out_c, in_c, kh, kw]
            w2 = jnp.transpose(jnp.flip(w, (2, 3)), (1, 0, 2, 3))
            out = lax.conv_general_dilated(
                a, w2, window_strides=(1, 1), padding=padding_pairs,
                lhs_dilation=strides, rhs_dilation=dil,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            )
        if b:
            out = out + b[0].reshape([1, -1, 1, 1])
        return out

    return apply(f, ins, name="conv2d_transpose")


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


def _pool2d_spec(kernel_size, stride, padding, nhwc):
    """Shared window/stride/padding construction for the 2D pools.

    Returns (k, s, pad_spec, dims, strides).  A 4-pair paddle-style padding
    list is given in the data layout's order, so the spatial pairs are at
    [2:4] for NCHW but [1:3] for NHWC."""
    k = _tuplize(kernel_size, 2)
    s = _tuplize(stride if stride is not None else kernel_size, 2)
    if (
        nhwc
        and isinstance(padding, (list, tuple))
        and len(padding) == 4
        and isinstance(padding[0], (list, tuple))
    ):
        padding = [padding[0], padding[3], padding[1], padding[2]]  # -> NCHW order
    pad = _conv_padding(padding, 2, s, k, (1, 1))
    if isinstance(pad, str):
        pad_spec = pad
    elif nhwc:
        pad_spec = [(0, 0)] + list(pad) + [(0, 0)]
    else:
        pad_spec = [(0, 0), (0, 0)] + list(pad)
    dims = (1,) + k + (1,) if nhwc else (1, 1) + k
    strides = (1,) + s + (1,) if nhwc else (1, 1) + s
    return k, s, pad_spec, dims, strides


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False, data_format="NCHW", name=None):
    x = coerce(x)
    k, s, pad_spec, dims, strides = _pool2d_spec(kernel_size, stride, padding, data_format == "NHWC")

    def f(a):
        init = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
        return lax.reduce_window(a, init, lax.max, dims, strides, pad_spec)

    out = apply(f, [x], name="max_pool2d")
    if return_mask:
        idx = apply(lambda a: jnp.zeros_like(a, jnp.int32), [out.detach()])
        return out, idx
    return out


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    x = coerce(x)
    k, s, pad_spec, dims, strides = _pool2d_spec(kernel_size, stride, padding, data_format == "NHWC")

    def f(a):
        summed = lax.reduce_window(a, 0.0, lax.add, dims, strides, pad_spec)
        if divisor_override:
            return summed / divisor_override
        if exclusive and not isinstance(pad_spec, str):
            ones = jnp.ones_like(a)
            counts = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pad_spec)
            return summed / counts
        return summed / (k[0] * k[1])

    return apply(f, [x], name="avg_pool2d")


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False, ceil_mode=False, name=None):
    x = coerce(x)
    k = _tuplize(kernel_size, 1)
    s = _tuplize(stride if stride is not None else kernel_size, 1)
    pad = _conv_padding(padding, 1, s, k, (1,))
    pad_spec = pad if isinstance(pad, str) else [(0, 0), (0, 0)] + list(pad)

    def f(a):
        return lax.reduce_window(a, -jnp.inf, lax.max, (1, 1) + k, (1, 1) + s, pad_spec)

    return apply(f, [x], name="max_pool1d")


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True, ceil_mode=False, name=None):
    x = coerce(x)
    k = _tuplize(kernel_size, 1)
    s = _tuplize(stride if stride is not None else kernel_size, 1)
    pad = _conv_padding(padding, 1, s, k, (1,))
    pad_spec = pad if isinstance(pad, str) else [(0, 0), (0, 0)] + list(pad)

    def f(a):
        summed = lax.reduce_window(a, 0.0, lax.add, (1, 1) + k, (1, 1) + s, pad_spec)
        return summed / k[0]

    return apply(f, [x], name="avg_pool1d")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    x = coerce(x)
    out_hw = _tuplize(output_size, 2)
    # one implementation parameterized over the spatial axes
    h_ax, w_ax = (2, 3) if data_format == "NCHW" else (1, 2)

    def f(a):
        h, w = a.shape[h_ax], a.shape[w_ax]
        oh, ow = out_hw
        if h % oh == 0 and w % ow == 0:
            ns = list(a.shape)
            ns[h_ax : h_ax + 1] = [oh, h // oh]
            ns[w_ax + 1 : w_ax + 2] = [ow, w // ow]
            return a.reshape(ns).mean((h_ax + 1, w_ax + 2))

        def _sl(axis, lo, hi):
            idx = [slice(None)] * a.ndim
            idx[axis] = slice(lo, hi)
            return tuple(idx)

        # general: mean over variable windows
        rows = [a[_sl(h_ax, (i * h) // oh, max((i * h) // oh + 1, ((i + 1) * h + oh - 1) // oh))].mean(h_ax, keepdims=True) for i in range(oh)]
        a2 = jnp.concatenate(rows, h_ax)
        cols = [a2[_sl(w_ax, (j * w) // ow, max((j * w) // ow + 1, ((j + 1) * w + ow - 1) // ow))].mean(w_ax, keepdims=True) for j in range(ow)]
        return jnp.concatenate(cols, w_ax)

    return apply(f, [x], name="adaptive_avg_pool2d")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    x = coerce(x)
    out_hw = _tuplize(output_size, 2)

    def f(a):
        n, c, h, w = a.shape
        oh, ow = out_hw
        if h % oh == 0 and w % ow == 0:
            return a.reshape(n, c, oh, h // oh, ow, w // ow).max((3, 5))
        rows = [a[:, :, (i * h) // oh : ((i + 1) * h + oh - 1) // oh, :].max(2, keepdims=True) for i in range(oh)]
        a2 = jnp.concatenate(rows, 2)
        cols = [a2[:, :, :, (j * w) // ow : ((j + 1) * w + ow - 1) // ow].max(3, keepdims=True) for j in range(ow)]
        return jnp.concatenate(cols, 3)

    return apply(f, [x], name="adaptive_max_pool2d")


def adaptive_avg_pool1d(x, output_size, name=None):
    x = coerce(x)
    o = int(output_size) if not isinstance(output_size, (list, tuple)) else int(output_size[0])

    def f(a):
        n, c, l = a.shape
        if l % o == 0:
            return a.reshape(n, c, o, l // o).mean(3)
        parts = [a[:, :, (i * l) // o : ((i + 1) * l + o - 1) // o].mean(2, keepdims=True) for i in range(o)]
        return jnp.concatenate(parts, 2)

    return apply(f, [x], name="adaptive_avg_pool1d")


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    x = coerce(x)
    (x,) = amp_cast_inputs([x], "black")
    if isinstance(normalized_shape, numbers.Integral):
        normalized_shape = (int(normalized_shape),)
    naxes = tuple(range(-len(tuple(normalized_shape)), 0))
    ins = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        ins.append(amp_cast_inputs([coerce(weight)], "black")[0])
    if has_b:
        ins.append(amp_cast_inputs([coerce(bias)], "black")[0])

    def f(a, *wb):
        # stats in fp32, output in the activation dtype; weight/bias are cast
        # to the activation dtype so fp32 norm params never promote the
        # residual stream (the round-1 AMP-O2 OOM: bf16 * f32 -> f32 matmuls)
        dtype = a.dtype
        a32 = a.astype(jnp.float32)
        mean = jnp.mean(a32, axis=naxes, keepdims=True)
        var = jnp.var(a32, axis=naxes, keepdims=True)
        out = ((a32 - mean) * lax.rsqrt(var + epsilon)).astype(dtype)
        i = 0
        if has_w:
            out = out * wb[i].astype(dtype)
            i += 1
        if has_b:
            out = out + wb[i].astype(dtype)
        return out

    return apply(f, ins, name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """TPU-native extension (reference counterpart: fused_rms_norm in
    paddle/phi/kernels/fusion — standard in the Llama family)."""
    x = coerce(x)
    ins = [x]
    if weight is not None:
        ins.append(coerce(weight))

    def f(a, *w):
        dtype = a.dtype
        a32 = a.astype(jnp.float32)
        out = a32 * lax.rsqrt(jnp.mean(a32 * a32, axis=-1, keepdims=True) + epsilon)
        out = out.astype(dtype)
        if w:
            # cast fp32 norm weight down — bf16 * f32 would promote the whole
            # residual stream to f32 (round-1 AMP-O2 OOM)
            out = out * w[0].astype(dtype)
        return out

    return apply(f, ins, name="rms_norm")


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-5,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    x = coerce(x)
    # The activation stays in its AMP dtype (bf16 under O2): stats and the
    # per-channel scale/shift are computed in fp32 *inside* the kernel so XLA
    # fuses the casts into the elementwise op — HBM traffic stays bf16.
    # (Black-casting x here doubled activation bytes across the whole ResNet.)
    ch_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis] if x.ndim > 1 else 1

    use_batch_stats = training and not use_global_stats

    if use_batch_stats:
        stats_ins = [x]
        has_shift = running_mean is not None
        if has_shift:
            stats_ins.append(coerce(running_mean))

        def _stats(a, *k_in):
            # one fused pass: shifted sum and sum-of-squares reduce together
            # (XLA multi-output fusion).  Shifting by the running mean (an
            # independent [C] input, so the broadcast-subtract fuses into the
            # reduce) keeps the single-pass E[(x-k)^2] - E[x-k]^2 form from
            # cancelling catastrophically when |mean| >> std once stats have
            # warmed up; shift-invariance makes the x-gradient exact either
            # way.  (A data-derived shift would be exact from step 0 but
            # forces XLA to materialize the shifted activations — measured
            # ~10% off ResNet50 step time.)
            #
            # Channels-last inputs reduce over a [rows, C] VIEW: XLA's
            # row-major column reduction is ~10x faster than the
            # multi-axis-keep-minor form on TPU (measured 80 -> 7 ms
            # standalone on [256,56,56,256]).
            if ch_axis == a.ndim - 1:
                a32 = a.reshape(-1, a.shape[-1]).astype(jnp.float32)
                red = (0,)
                kshape = (1, a.shape[-1])
            else:
                a32 = a.astype(jnp.float32)
                red = reduce_axes
                kshape = shape
            k = (
                jax.lax.stop_gradient(k_in[0].astype(jnp.float32)).reshape(kshape)
                if k_in
                else jnp.zeros(kshape, jnp.float32)
            )
            d = a32 - k
            m = jnp.mean(d, axis=red)
            ms = jnp.mean(d * d, axis=red)
            return m + k.reshape(m.shape), jnp.maximum(ms - m * m, 0.0)

        mean, var = apply(_stats, stats_ins, name="bn_stats", multi=True)
        # update running stats in-place (buffers)
        if running_mean is not None:
            from ... import ops as _ops

            with _core.no_grad_ctx():
                running_mean._data = (
                    momentum * running_mean._data + (1 - momentum) * mean._data
                )
                n = int(np.prod([x.shape[i] for i in reduce_axes]))
                unbiased = var._data * (n / max(n - 1, 1))
                running_var._data = (
                    momentum * running_var._data + (1 - momentum) * unbiased
                )
    else:
        mean = coerce(running_mean)
        var = coerce(running_var)

    ins = [x, mean, var]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        ins.append(amp_cast_inputs([coerce(weight)], "black")[0])
    if has_b:
        ins.append(amp_cast_inputs([coerce(bias)], "black")[0])

    def f(a, m, v, *wb):
        dtype = a.dtype
        m32 = m.astype(jnp.float32)
        inv = lax.rsqrt(v.astype(jnp.float32) + epsilon)
        i = 0
        if has_w:
            inv = inv * wb[i].astype(jnp.float32)
            i += 1
        shift = -m32 * inv
        if has_b:
            shift = shift + wb[i].astype(jnp.float32)
        # one FMA per element; per-channel scale/shift precomputed on [C]
        out = a.astype(jnp.float32) * inv.reshape(shape) + shift.reshape(shape)
        return out.astype(dtype)

    return apply(f, ins, name="batch_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None, data_format="NCHW", name=None):
    x = coerce(x)
    ins = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        ins.append(coerce(weight))
    if has_b:
        ins.append(coerce(bias))

    def f(a, *wb):
        dtype = a.dtype
        n, c = a.shape[0], a.shape[1]
        spatial = a.shape[2:]
        g = num_groups
        a2 = a.reshape((n, g, c // g) + spatial).astype(jnp.float32)
        axes = tuple(range(2, a2.ndim))
        mean = jnp.mean(a2, axis=axes, keepdims=True)
        var = jnp.var(a2, axis=axes, keepdims=True)
        out = ((a2 - mean) * lax.rsqrt(var + epsilon)).reshape(a.shape).astype(dtype)
        shape = [1, c] + [1] * len(spatial)
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape).astype(dtype)
            i += 1
        if has_b:
            out = out + wb[i].reshape(shape).astype(dtype)
        return out

    return apply(f, ins, name="group_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None, use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    x = coerce(x)
    ins = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        ins.append(coerce(weight))
    if has_b:
        ins.append(coerce(bias))

    def f(a, *wb):
        dtype = a.dtype
        a32 = a.astype(jnp.float32)
        axes = tuple(range(2, a.ndim))
        mean = jnp.mean(a32, axis=axes, keepdims=True)
        var = jnp.var(a32, axis=axes, keepdims=True)
        out = ((a32 - mean) * lax.rsqrt(var + eps)).astype(dtype)
        shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape).astype(dtype)
            i += 1
        if has_b:
            out = out + wb[i].reshape(shape).astype(dtype)
        return out

    return apply(f, ins, name="instance_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    x = coerce(x)

    def f(a):
        sq = jnp.square(a)
        half = size // 2
        pads = [(0, 0)] * a.ndim
        pads[1] = (half, size - half - 1)
        sq_p = jnp.pad(sq, pads)
        acc = jnp.zeros_like(a)
        for i in range(size):
            acc = acc + lax.slice_in_dim(sq_p, i, i + a.shape[1], axis=1)
        return a / (k + alpha * acc) ** beta

    return apply(f, [x], name="local_response_norm")


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    x = coerce(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply(lambda a: a * (1 - p), [x], name="dropout_infer")
        return x
    if p == 1.0:
        return apply(lambda a: jnp.zeros_like(a), [x], name="dropout")
    key = default_generator.next_key()

    def f(a):
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), jnp.zeros((), a.dtype)).astype(a.dtype)
        return jnp.where(keep, a, jnp.zeros((), a.dtype)).astype(a.dtype)

    return apply(f, [x], name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    return dropout(x, p, axis=[0, 1] if data_format == "NCHW" else [0, 3], training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    return dropout(x, p, axis=[0, 1], training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = coerce(x)
    if not training or p == 0.0:
        return x
    key = default_generator.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p**2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return (a_coef * jnp.where(keep, a, alpha_p) + b_coef).astype(a.dtype)

    return apply(f, [x], name="alpha_dropout")


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def _reduce(v, reduction):
    from ... import ops as _ops

    if reduction == "mean":
        return _ops.mean(v)
    if reduction == "sum":
        return _ops.sum(v)
    return v


def cross_entropy(
    input,
    label,
    weight=None,
    ignore_index=-100,
    reduction="mean",
    soft_label=False,
    axis=-1,
    use_softmax=True,
    label_smoothing=0.0,
    name=None,
):
    input, label = coerce(input), coerce(label)
    (input,) = amp_cast_inputs([input], "black")
    ins = [input, label]
    has_w = weight is not None
    if has_w:
        ins.append(coerce(weight))

    def f(logits, lab, *w):
        out_dtype = logits.dtype if jnp.issubdtype(logits.dtype, jnp.floating) else jnp.float32
        # fp32 math expressed so XLA fuses the upcast into the reductions —
        # never materialize a full fp32 [*, vocab] log-softmax (at vocab=32k
        # that's a 2GB HBM temp per buffer, the round-1 bench OOM tail)
        nclass = logits.shape[axis]
        logits32 = logits.astype(jnp.float32)
        if use_softmax:
            lse = jax.scipy.special.logsumexp(logits32, axis=axis, keepdims=True)
        else:
            lse = jnp.zeros_like(jnp.sum(logits32, axis=axis, keepdims=True))
            logits32 = jnp.log(jnp.maximum(logits32, 1e-30))
        if soft_label:
            tgt = lab.astype(jnp.float32)
            if label_smoothing > 0:
                tgt = (1 - label_smoothing) * tgt + label_smoothing / nclass
            # sum(tgt * (logits - lse)) fuses; tgt rows sum to 1
            loss = -(tgt * (logits32 - lse)).sum(axis=axis)
            valid = jnp.ones(loss.shape, jnp.float32)
        else:
            idx = lab.astype(jnp.int32)
            if idx.ndim == logits32.ndim and idx.shape[axis] == 1:
                idx = jnp.squeeze(idx, axis)
            valid = (idx != ignore_index).astype(jnp.float32)
            safe_idx = jnp.where(idx == ignore_index, 0, idx)
            picked = (
                jnp.take_along_axis(logits32, jnp.expand_dims(safe_idx, axis), axis=axis)
                - lse
            ).squeeze(axis)
            if label_smoothing > 0:
                smooth = -(jnp.mean(logits32, axis=axis, keepdims=True) - lse).squeeze(axis)
                loss = (1 - label_smoothing) * (-picked) + label_smoothing * smooth
            else:
                loss = -picked
            if use_softmax:
                # softmax CE is >= 0 exactly; XLA's fused bf16 rounding can
                # leave -ulp noise on fully-confident samples — clamp it
                loss = jnp.maximum(loss, 0.0)
            loss = loss * valid
            if w:
                cw = jnp.take(w[0], safe_idx, axis=0).astype(jnp.float32) * valid
                loss = loss * jnp.take(w[0], safe_idx, axis=0).astype(jnp.float32)
                if reduction == "mean":
                    return (loss.sum() / jnp.maximum(cw.sum(), 1e-12)).astype(out_dtype)
        if reduction == "mean":
            return (loss.sum() / jnp.maximum(valid.sum(), 1.0)).astype(out_dtype)
        if reduction == "sum":
            return loss.sum().astype(out_dtype)
        return loss.astype(out_dtype)

    return apply(f, ins, name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100, numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label, ignore_index=ignore_index, reduction="none", axis=axis)
    from ...ops.manipulation import unsqueeze

    loss = unsqueeze(loss, axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    input, label = coerce(input), coerce(label)
    ins = [input, label]
    has_w = weight is not None
    if has_w:
        ins.append(coerce(weight))

    def f(logp, lab, *w):
        idx = lab.astype(jnp.int32)
        valid = (idx != ignore_index).astype(logp.dtype)
        safe = jnp.where(idx == ignore_index, 0, idx)
        picked = jnp.take_along_axis(logp, safe[..., None] if logp.ndim == idx.ndim + 1 else safe, axis=1 if logp.ndim == 2 else 1)
        if logp.ndim == 2:
            picked = jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
        loss = -picked * valid
        if w:
            cw = jnp.take(w[0], safe, axis=0)
            loss = loss * cw
            if reduction == "mean":
                return loss.sum() / jnp.maximum((cw * valid).sum(), 1e-12)
        if reduction == "mean":
            return loss.sum() / jnp.maximum(valid.sum(), 1.0)
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, ins, name="nll_loss")


def mse_loss(input, label, reduction="mean", name=None):
    input, label = coerce(input), coerce(label)

    def f(a, b):
        d = jnp.square(a - b.astype(a.dtype))
        if reduction == "mean":
            return d.mean()
        if reduction == "sum":
            return d.sum()
        return d

    return apply(f, [input, label], name="mse_loss")


def l1_loss(input, label, reduction="mean", name=None):
    input, label = coerce(input), coerce(label)

    def f(a, b):
        d = jnp.abs(a - b.astype(a.dtype))
        if reduction == "mean":
            return d.mean()
        if reduction == "sum":
            return d.sum()
        return d

    return apply(f, [input, label], name="l1_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    input, label = coerce(input), coerce(label)

    def f(a, b):
        d = a - b.astype(a.dtype)
        ad = jnp.abs(d)
        loss = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, [input, label], name="smooth_l1_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    input, label = coerce(input), coerce(label)
    ins = [input, label] + ([coerce(weight)] if weight is not None else [])

    def f(p, y, *w):
        y = y.astype(p.dtype)
        eps = 1e-12
        loss = -(y * jnp.log(jnp.maximum(p, eps)) + (1 - y) * jnp.log(jnp.maximum(1 - p, eps)))
        if w:
            loss = loss * w[0]
        return _red(loss)

    def _red(loss):
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, ins, name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean", pos_weight=None, name=None):
    logit, label = coerce(logit), coerce(label)
    ins = [logit, label]
    has_w = weight is not None
    has_pw = pos_weight is not None
    if has_w:
        ins.append(coerce(weight))
    if has_pw:
        ins.append(coerce(pos_weight))

    def f(z, y, *rest):
        y = y.astype(z.dtype)
        i = 0
        w = None
        pw = None
        if has_w:
            w = rest[i]
            i += 1
        if has_pw:
            pw = rest[i]
        # stable: max(z,0) - z*y + log(1+exp(-|z|)), with pos_weight variant
        if pw is not None:
            log_weight = (pw - 1) * y + 1
            loss = (1 - y) * z + log_weight * (jnp.logaddexp(0.0, -jnp.abs(z)) + jnp.maximum(-z, 0.0))
        else:
            loss = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        if w is not None:
            loss = loss * w
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, ins, name="bce_with_logits")


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    input, label = coerce(input), coerce(label)

    def f(lp, t):
        if log_target:
            loss = jnp.exp(t) * (t - lp)
        else:
            t = t.astype(lp.dtype)
            loss = t * (jnp.log(jnp.maximum(t, 1e-12)) - lp)
        if reduction == "mean":
            return loss.mean()
        if reduction == "batchmean":
            return loss.sum() / lp.shape[0]
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, [input, label], name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    input, other, label = coerce(input), coerce(other), coerce(label)

    def f(a, b, y):
        loss = jnp.maximum(0.0, -y.astype(a.dtype) * (a - b) + margin)
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, [input, other, label], name="margin_ranking_loss")


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    x1, x2 = coerce(x1), coerce(x2)

    def f(a, b):
        num = (a * b).sum(axis)
        den = jnp.sqrt(jnp.square(a).sum(axis)) * jnp.sqrt(jnp.square(b).sum(axis))
        return num / jnp.maximum(den, eps)

    return apply(f, [x1, x2], name="cosine_similarity")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum", name=None):
    logit, label = coerce(logit), coerce(label)
    ins = [logit, label] + ([coerce(normalizer)] if normalizer is not None else [])

    def f(z, y, *n):
        y = y.astype(z.dtype)
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if n:
            loss = loss / n[0]
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, ins, name="sigmoid_focal_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    input, label = coerce(input), coerce(label)

    def f(a, y):
        y = y.astype(a.dtype)
        loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, [input, label], name="hinge_embedding_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, epsilon=1e-6, swap=False, reduction="mean", name=None):
    input, positive, negative = coerce(input), coerce(positive), coerce(negative)

    def f(a, pos, neg):
        def dist(u, v):
            return jnp.sum(jnp.abs(u - v) ** p, axis=-1) ** (1.0 / p)

        d_ap = dist(a, pos)
        d_an = dist(a, neg)
        if swap:
            d_pn = dist(pos, neg)
            d_an = jnp.minimum(d_an, d_pn)
        loss = jnp.maximum(d_ap - d_an + margin, 0.0)
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return apply(f, [input, positive, negative], name="triplet_margin_loss")


# ---------------------------------------------------------------------------
# attention (routes to pallas flash attention)
# ---------------------------------------------------------------------------


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True,
    name=None, *, segment_ids=None
):
    """Inputs [batch, seq, heads, head_dim] (paddle convention).
    segment_ids: optional [batch, seq] int packed-sequence/padding masking
    that keeps the Pallas kernel eligible (see ops/flash_attention.py)."""
    from ...ops.flash_attention import scaled_dot_product_attention as _sdpa

    return _sdpa(query, key, value, attn_mask, dropout_p, is_causal, training, segment_ids)


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal)
    if return_softmax:
        return out, None
    return out, None


def flash_decode(query, key, value, pos, scale=None):
    """Cached static-KV attention: q [b, sq, h, d] against full cache
    buffers k/v [b, L, kv_h, d]; `pos` (scalar int32 Tensor) is the write
    position — validity is computed in-kernel from it, so the decode path
    stays Pallas-eligible (no additive mask)."""
    from ...ops.flash_attention import flash_decode as _fd

    return _fd(query, key, value, pos, scale)


def paged_flash_decode(query, arena_k, arena_v, tables, pos, max_len, scale=None,
                       kernel="auto", k_scale=None, v_scale=None):
    """Cached attention over a block-paged KV pool: q [b, sq, h, d] against
    per-layer arenas [num_pages, kv_h, page_size, d], addressed through
    `tables` ([b, max_pages_per_seq] int32, traced data).  The page
    indirection happens inside the compiled step; validity comes from `pos`
    exactly as in flash_decode, so paged and dense decode are bit-identical.
    `kernel` selects the dispatch: "auto" (fused Pallas arena-reading kernel
    when eligible, else gather-then-dense), "fused", or "gather".  When the
    arenas are int8-quantized, pass their per-row scale arenas as
    `k_scale`/`v_scale` ([num_pages, kv_h, 1, page_size] float32) — both
    dispatches then dequantize through the same page tables."""
    from ...ops.flash_attention import paged_flash_decode as _pfd

    return _pfd(query, arena_k, arena_v, tables, pos, max_len, scale,
                kernel=kernel, k_scale=k_scale, v_scale=v_scale)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    x = coerce(x)
    k = _tuplize(kernel_sizes, 2)
    s = _tuplize(strides, 2)
    d = _tuplize(dilations, 2)
    p = _tuplize(paddings, 2)

    def f(a):
        n, c, h, w = a.shape
        a_p = jnp.pad(a, [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
        oh = (h + 2 * p[0] - d[0] * (k[0] - 1) - 1) // s[0] + 1
        ow = (w + 2 * p[1] - d[1] * (k[1] - 1) - 1) // s[1] + 1
        cols = []
        for i in range(k[0]):
            for j in range(k[1]):
                patch = a_p[:, :, i * d[0] : i * d[0] + oh * s[0] : s[0], j * d[1] : j * d[1] + ow * s[1] : s[1]]
                cols.append(patch.reshape(n, c, -1))
        return jnp.stack(cols, 2).reshape(n, c * k[0] * k[1], -1)

    return apply(f, [x], name="unfold")


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False, align_mode=0, data_format="NCHW", name=None):
    x = coerce(x)

    def f(a):
        n, c, h, w = a.shape
        if size is not None:
            oh, ow = _tuplize(size, 2)
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor, scale_factor)
            oh, ow = int(h * sf[0]), int(w * sf[1])
        method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
        a2 = jnp.moveaxis(a, 1, -1)
        out = jax.image.resize(a2, (n, oh, ow, c), method=method)
        return jnp.moveaxis(out, -1, 1)

    return apply(f, [x], name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    x = coerce(x)
    r = upscale_factor

    def f(a):
        n, c, h, w = a.shape
        a2 = a.reshape(n, c // (r * r), r, r, h, w)
        a2 = jnp.transpose(a2, (0, 1, 4, 2, 5, 3))
        return a2.reshape(n, c // (r * r), h * r, w * r)

    return apply(f, [x], name="pixel_shuffle")


def pad(x, pad_width, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ...ops.manipulation import pad as _pad

    return _pad(x, pad_width, mode, value, data_format)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    x = coerce(x)

    def f(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        a2 = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([a2[:, 1:, :fold], jnp.zeros_like(a2[:, :1, :fold])], 1)
        right = jnp.concatenate([jnp.zeros_like(a2[:, :1, fold : 2 * fold]), a2[:, :-1, fold : 2 * fold]], 1)
        rest = a2[:, :, 2 * fold :]
        return jnp.concatenate([left, right, rest], 2).reshape(nt, c, h, w)

    return apply(f, [x], name="temporal_shift")


def linear_fp8(x, weight, bias=None, name=None):
    """Linear through the fp8 (e4m3) quantization grid with per-tensor
    scaling — see paddle_tpu.incubate.fp8 (reference: incubate fp8)."""
    from ...incubate.fp8 import linear_fp8 as _impl

    return _impl(x, weight, bias)


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    """x if x > threshold else value (reference: F.thresholded_relu)."""
    x = coerce(x)
    return apply(
        lambda a: jnp.where(a > threshold, a, jnp.asarray(value, a.dtype)),
        [x],
        name="thresholded_relu",
    )


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    """[..., L] mask with mask[..., j] = j < lengths[...] (reference:
    paddle.nn.functional.sequence_mask).  maxlen must be static (XLA
    shapes); defaults to int(max(lengths)) computed eagerly."""
    lengths = coerce(lengths)
    if maxlen is None:
        if isinstance(lengths._data, jax.core.Tracer):
            raise ValueError(
                "sequence_mask needs an explicit maxlen inside traced code "
                "(output shape must be static for XLA)"
            )
        maxlen = int(jnp.max(lengths._raw))
    jd = _core.to_jax_dtype(dtype)

    def f(l):
        pos = jnp.arange(maxlen)
        return (pos[None, :] < l.reshape(-1, 1)).reshape(l.shape + (maxlen,)).astype(jd)

    return apply(f, [lengths], name="sequence_mask")


def conv1d_transpose(
    x, weight, bias=None, stride=1, padding=0, output_padding=0,
    groups=1, dilation=1, data_format="NCL", output_size=None, name=None,
):
    """1-D transpose conv via the 2-D kernel on a unit spatial dim
    (reference: F.conv1d_transpose)."""
    from ... import ops as _ops

    if output_size is not None:
        raise NotImplementedError(
            "conv1d_transpose output_size is not supported; use "
            "output_padding to resolve the stride ambiguity"
        )
    if data_format != "NCL":
        raise NotImplementedError("conv1d_transpose supports NCL layout only")
    x = coerce(x)
    weight = coerce(weight)
    def lift(v, kind):
        """1-D arg -> 2-D with a unit leading spatial dim (stride/dilation
        lead with 1, paddings with 0)."""
        lead = {"stride": 1, "dil": 1, "pad": 0, "opad": 0}[kind]
        if isinstance(v, str):
            # lax.conv_general_dilated rejects string padding for transposed
            # convs; surface that up-front instead of deep in lax
            raise NotImplementedError(
                "conv1d_transpose does not support string padding; pass "
                "explicit int/[lo, hi] padding"
            )
        if isinstance(v, (list, tuple)):
            if len(v) == 1:
                return (lead, int(v[0]))
            if kind == "pad":
                if all(isinstance(e, (list, tuple)) and len(e) == 2 for e in v):
                    # reference pair forms: [[lo,hi]] or [[0,0],[0,0],[lo,hi]]
                    lo, hi = v[-1]
                    return [[0, 0], [int(lo), int(hi)]]
                if len(v) == 2:
                    # asymmetric [lo, hi] on L -> [[0, 0], [lo, hi]]
                    return [[0, 0], [int(v[0]), int(v[1])]]
            raise ValueError(f"conv1d_transpose {kind}={v!r} not understood")
        return (lead, int(v))

    x4 = _ops.unsqueeze(x, 2)  # [N, C, 1, L]
    w4 = _ops.unsqueeze(weight, 2)  # [in, out/g, 1, K]
    out = conv2d_transpose(
        x4, w4, bias=bias,
        stride=lift(stride, "stride"),
        padding=lift(padding, "pad"),
        output_padding=lift(output_padding, "opad"),
        groups=groups,
        dilation=lift(dilation, "dil"),
    )
    return _ops.squeeze(out, 2)


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """[N, 2, 3] affine matrices -> [N, H, W, 2] sampling grid in [-1, 1]
    coords (reference: F.affine_grid)."""
    theta = coerce(theta)
    n, c, h, w = [int(s) for s in out_shape]

    def f(th):
        if align_corners:
            ys = jnp.linspace(-1.0, 1.0, h)
            xs = jnp.linspace(-1.0, 1.0, w)
        else:
            ys = (jnp.arange(h) * 2 + 1) / h - 1.0
            xs = (jnp.arange(w) * 2 + 1) / w - 1.0
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [H, W, 3]
        return jnp.einsum("hwk,njk->nhwj", base.astype(th.dtype), th)

    return apply(f, [theta], name="affine_grid")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True, name=None):
    """Bilinear/nearest sampling of x [N,C,H,W] at grid [N,Ho,Wo,2] (x,y in
    [-1,1]) — reference: F.grid_sample."""
    if mode not in ("bilinear", "nearest"):
        raise ValueError(f"grid_sample mode must be bilinear/nearest, got {mode}")
    if padding_mode not in ("zeros", "border"):
        raise NotImplementedError("grid_sample padding_mode: zeros/border only")
    x, grid = coerce(x), coerce(grid)

    def f(a, g):
        n, c, h, w = a.shape
        gx, gy = g[..., 0], g[..., 1]
        if align_corners:
            fx = (gx + 1) * (w - 1) / 2
            fy = (gy + 1) * (h - 1) / 2
        else:
            fx = ((gx + 1) * w - 1) / 2
            fy = ((gy + 1) * h - 1) / 2

        def fetch(ix, iy):
            # gather with border clamp; zeros handled by validity mask
            cx = jnp.clip(ix, 0, w - 1)
            cy = jnp.clip(iy, 0, h - 1)
            vals = a[jnp.arange(n)[:, None, None], :, cy, cx]  # [N,Ho,Wo,C]
            if padding_mode == "zeros":
                ok = (ix >= 0) & (ix <= w - 1) & (iy >= 0) & (iy <= h - 1)
                vals = vals * ok[..., None].astype(vals.dtype)
            return vals

        if mode == "nearest":
            # half-away-from-zero like the reference kernel's ::round (jnp
            # rounds half to even, and floor(t+0.5) is half-UP, which picks
            # pixel 0 instead of -1 at negative half positions)
            rnd = lambda t: jnp.where(
                t >= 0, jnp.floor(t + 0.5), jnp.ceil(t - 0.5)
            ).astype(jnp.int32)
            out = fetch(rnd(fx), rnd(fy))
        else:
            x0 = jnp.floor(fx).astype(jnp.int32)
            y0 = jnp.floor(fy).astype(jnp.int32)
            wx = (fx - x0)[..., None]
            wy = (fy - y0)[..., None]
            out = (
                fetch(x0, y0) * (1 - wx) * (1 - wy)
                + fetch(x0 + 1, y0) * wx * (1 - wy)
                + fetch(x0, y0 + 1) * (1 - wx) * wy
                + fetch(x0 + 1, y0 + 1) * wx * wy
            )
        return jnp.transpose(out, (0, 3, 1, 2))  # [N,C,Ho,Wo]

    return apply(f, [x, grid], name="grid_sample")


# ---------------------------------------------------------------------------
# round-4 API-breadth pass (§2.3 long tail): losses, 3D pools, fold, CTC
# ---------------------------------------------------------------------------


log_sigmoid = _unary(jax.nn.log_sigmoid, "log_sigmoid")


def square_error_cost(input, label):
    input, label = coerce(input), coerce(label)
    return apply(lambda a, b: (a - b) ** 2, [input, label], name="square_error_cost")


def log_loss(input, label, epsilon=1e-4, name=None):
    input, label = coerce(input), coerce(label)
    return apply(
        lambda p, y: -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon),
        [input, label],
        name="log_loss",
    )


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    input, label = coerce(input), coerce(label)

    def f(a, b):
        d = a - b
        ad = jnp.abs(d)
        out = jnp.where(ad <= delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
        if reduction == "mean":
            return out.mean()
        if reduction == "sum":
            return out.sum()
        return out

    return apply(f, [input, label], name="huber_loss")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    x, y = coerce(x), coerce(y)
    return apply(
        lambda a, b: jnp.sum(jnp.abs(a - b + epsilon) ** p, axis=-1, keepdims=keepdim)
        ** (1.0 / p),
        [x, y],
        name="pairwise_distance",
    )


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    input1, input2, label = coerce(input1), coerce(input2), coerce(label)

    def f(a, b, y):
        cos = jnp.sum(a * b, -1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12
        )
        out = jnp.where(y > 0, 1 - cos, jnp.maximum(0.0, cos - margin))
        if reduction == "mean":
            return out.mean()
        if reduction == "sum":
            return out.sum()
        return out

    return apply(f, [input1, input2, label], name="cosine_embedding_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):
    """input [N, ..., C] probabilities, label [N, ..., 1] int (reference
    semantics: one-hot overlap over all but the batch dim)."""
    input, label = coerce(input), coerce(label)

    def f(p, y):
        c = p.shape[-1]
        oh = jax.nn.one_hot(y[..., 0].astype(jnp.int32), c, dtype=p.dtype)
        reduce_dims = tuple(range(1, p.ndim))
        inter = jnp.sum(p * oh, axis=reduce_dims)
        union = jnp.sum(p, axis=reduce_dims) + jnp.sum(oh, axis=reduce_dims)
        return (1 - (2 * inter + epsilon) / (union + epsilon)).mean()

    return apply(f, [input, label], name="dice_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002, name=None):
    anchor, positive, labels = coerce(anchor), coerce(positive), coerce(labels)

    def f(a, p, y):
        reg = l2_reg * (jnp.sum(a * a, -1).mean() + jnp.sum(p * p, -1).mean()) / 4
        sim = a @ p.T  # [B, B]
        same = (y[:, None] == y[None, :]).astype(jnp.float32)
        tgt = same / jnp.maximum(same.sum(-1, keepdims=True), 1)
        ce = -(tgt * jax.nn.log_softmax(sim, -1)).sum(-1).mean()
        return ce + reg

    return apply(f, [anchor, positive, labels], name="npair_loss")


def bilinear(x1, x2, weight, bias=None, name=None):
    """out[.., o] = x1 W_o x2 (+b); weight [out, in1, in2]."""
    x1, x2, weight = coerce(x1), coerce(x2), coerce(weight)
    ins = [x1, x2, weight]
    if bias is not None:
        ins.append(coerce(bias))

    def f(a, b, w, *bb):
        out = jnp.einsum("...i,oij,...j->...o", a, w, b)
        if bb:
            out = out + bb[0]
        return out

    return apply(f, ins, name="bilinear")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    x = coerce(x)
    r = downscale_factor

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            a = a.reshape(n, c, h // r, r, w // r, r)
            return a.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * r * r, h // r, w // r)
        n, h, w, c = a.shape
        a = a.reshape(n, h // r, r, w // r, r, c)
        return a.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // r, w // r, c * r * r)

    return apply(f, [x], name="pixel_unshuffle")


def zeropad2d(x, padding, data_format="NCHW", name=None):
    x = coerce(x)
    pl, pr, pt, pb = (padding, padding, padding, padding) if isinstance(padding, int) else padding

    def f(a):
        if data_format == "NCHW":
            return jnp.pad(a, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
        return jnp.pad(a, ((0, 0), (pt, pb), (pl, pr), (0, 0)))

    return apply(f, [x], name="zeropad2d")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """col2im (reference: F.fold): x [N, C*kh*kw, L] -> [N, C, H, W] with
    overlapping windows SUMMED — expressed as a scatter-add XLA handles."""
    x = coerce(x)
    oh, ow = _tuplize(output_sizes, 2)
    kh, kw = _tuplize(kernel_sizes, 2)
    sh, sw = _tuplize(strides, 2)
    ph, pw = _tuplize(paddings, 2)
    dh, dw = _tuplize(dilations, 2)
    n_h = (oh + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    n_w = (ow + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1

    def f(a):
        n, ckk, L = a.shape
        c = ckk // (kh * kw)
        cols = a.reshape(n, c, kh, kw, n_h, n_w)
        # absolute row/col for every (kernel pos, window) pair, padded coords
        ih = (jnp.arange(kh) * dh)[:, None] + (jnp.arange(n_h) * sh)[None, :]  # [kh, n_h]
        iw = (jnp.arange(kw) * dw)[:, None] + (jnp.arange(n_w) * sw)[None, :]
        out = jnp.zeros((n, c, oh + 2 * ph, ow + 2 * pw), a.dtype)
        flat_idx = (
            ih[:, None, :, None] * (ow + 2 * pw) + iw[None, :, None, :]
        ).reshape(-1)  # [kh*kw*n_h*n_w]
        vals = cols.reshape(n, c, -1)
        out = out.reshape(n, c, -1).at[:, :, flat_idx].add(vals)
        out = out.reshape(n, c, oh + 2 * ph, ow + 2 * pw)
        return out[:, :, ph : ph + oh, pw : pw + ow]

    return apply(f, [x], name="fold")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0, reduction="mean", norm_by_times=False):
    """Connectionist Temporal Classification (reference: F.ctc_loss over
    warpctc).  TPU-native: the standard alpha recursion in log space as a
    lax.scan over time — static shapes, batched over B.

    log_probs: [T, B, C] (paddle layout), labels: [B, S] int32 padded,
    input_lengths/label_lengths: [B]."""
    log_probs, labels = coerce(log_probs), coerce(labels)
    input_lengths, label_lengths = coerce(input_lengths), coerce(label_lengths)

    def f(lp, lab, in_len, lab_len):
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), -1)
        T, B, C = lp.shape
        S = lab.shape[1]
        # extended label sequence with interleaved blanks: length 2S+1
        ext = jnp.full((B, 2 * S + 1), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
        Lext = 2 * lab_len.astype(jnp.int32) + 1  # [B]
        NEG = -1e30

        # emission log-prob of each extended symbol at each time
        def emit(t_lp):  # [B, C] -> [B, 2S+1]
            return jnp.take_along_axis(t_lp, ext, axis=1)

        # allowed skip: ext[s] != ext[s-2] (and s >= 2)
        skip_ok = jnp.concatenate(
            [jnp.zeros((B, 2), bool), ext[:, 2:] != ext[:, :-2]], axis=1
        )

        alpha0 = jnp.full((B, 2 * S + 1), NEG)
        alpha0 = alpha0.at[:, 0].set(emit(lp[0])[:, 0])
        alpha0 = alpha0.at[:, 1].set(jnp.where(lab_len > 0, emit(lp[0])[:, 1], NEG))

        def step(alpha, t):
            prev1 = jnp.concatenate([jnp.full((B, 1), NEG), alpha[:, :-1]], 1)
            prev2 = jnp.concatenate([jnp.full((B, 2), NEG), alpha[:, :-2]], 1)
            prev2 = jnp.where(skip_ok, prev2, NEG)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2)
            new = merged + emit(lp[t])
            # freeze past each sequence's input length
            new = jnp.where((t < in_len)[:, None], new, alpha)
            return new, None

        alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
        idx_last = jnp.maximum(Lext - 1, 0)
        idx_prev = jnp.maximum(Lext - 2, 0)
        a_last = jnp.take_along_axis(alpha, idx_last[:, None], 1)[:, 0]
        a_prev = jnp.where(
            Lext >= 2, jnp.take_along_axis(alpha, idx_prev[:, None], 1)[:, 0], NEG
        )
        nll = -jnp.logaddexp(a_last, a_prev)
        if norm_by_times:
            nll = nll / jnp.maximum(in_len.astype(jnp.float32), 1.0)
        if reduction == "mean":
            return (nll / jnp.maximum(lab_len.astype(jnp.float32), 1.0)).mean()
        if reduction == "sum":
            return nll.sum()
        return nll

    return apply(f, [log_probs, labels, input_lengths, label_lengths], name="ctc_loss")


def _pool3d_spec(kernel_size, stride, padding, ndhwc):
    k = _tuplize(kernel_size, 3)
    s = _tuplize(stride if stride is not None else kernel_size, 3)
    pad = _conv_padding(padding, 3, s, k, (1, 1, 1))
    if isinstance(pad, str):
        pad_spec = pad
    elif ndhwc:
        pad_spec = [(0, 0)] + list(pad) + [(0, 0)]
    else:
        pad_spec = [(0, 0), (0, 0)] + list(pad)
    dims = (1,) + k + (1,) if ndhwc else (1, 1) + k
    strides = (1,) + s + (1,) if ndhwc else (1, 1) + s
    return k, pad_spec, dims, strides


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False, data_format="NCDHW", name=None):
    x = coerce(x)
    k, pad_spec, dims, strides = _pool3d_spec(kernel_size, stride, padding, data_format == "NDHWC")

    def f(a):
        init = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
        return lax.reduce_window(a, init, lax.max, dims, strides, pad_spec)

    out = apply(f, [x], name="max_pool3d")
    if return_mask:
        idx = apply(lambda a: jnp.zeros_like(a, jnp.int32), [out.detach()])
        return out, idx
    return out


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, divisor_override=None, data_format="NCDHW", name=None):
    x = coerce(x)
    k, pad_spec, dims, strides = _pool3d_spec(kernel_size, stride, padding, data_format == "NDHWC")

    def f(a):
        summed = lax.reduce_window(a, 0.0, lax.add, dims, strides, pad_spec)
        if divisor_override:
            return summed / divisor_override
        if exclusive and not isinstance(pad_spec, str):
            counts = lax.reduce_window(jnp.ones_like(a), 0.0, lax.add, dims, strides, pad_spec)
            return summed / counts
        return summed / (k[0] * k[1] * k[2])

    return apply(f, [x], name="avg_pool3d")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    x = coerce(x)
    od, oh, ow = _tuplize(output_size, 3)

    def f(a):
        n, c, d, h, w = a.shape
        if d % od == 0 and h % oh == 0 and w % ow == 0:
            return a.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow).mean((3, 5, 7))
        raise NotImplementedError("adaptive_avg_pool3d needs divisible sizes")

    return apply(f, [x], name="adaptive_avg_pool3d")


def conv3d_transpose(
    x, weight, bias=None, stride=1, padding=0, output_padding=0,
    groups=1, dilation=1, data_format="NCDHW", output_size=None, name=None,
):
    """3-D transposed conv via input (lhs) dilation — the 2-D path's
    formulation lifted to DHW.  weight: [in, out, kd, kh, kw]."""
    if groups != 1:
        raise NotImplementedError("conv3d_transpose: groups > 1 not supported")
    if output_size is not None:
        raise NotImplementedError(
            "conv3d_transpose: output_size not supported; use output_padding"
        )
    x, weight = coerce(x), coerce(weight)
    ins = [x, weight]
    if bias is not None:
        ins.append(coerce(bias))
    ins = amp_cast_inputs(ins, "white")
    strides = _tuplize(stride, 3)
    dil = _tuplize(dilation, 3)
    pad = _conv_padding(padding, 3, strides, None, dil)
    op = _tuplize(output_padding, 3)

    def f(a, w, *b):
        ks = w.shape[2:]
        if isinstance(pad, str):
            raise NotImplementedError("conv3d_transpose: string padding unsupported")
        pairs = [
            (dil[i] * (ks[i] - 1) - pad[i][0], dil[i] * (ks[i] - 1) - pad[i][1] + op[i])
            for i in range(3)
        ]
        w2 = jnp.transpose(jnp.flip(w, (2, 3, 4)), (1, 0, 2, 3, 4))
        out = lax.conv_general_dilated(
            a, w2, window_strides=(1, 1, 1), padding=pairs,
            lhs_dilation=strides, rhs_dilation=dil,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        )
        if b:
            out = out + b[0].reshape([1, -1, 1, 1, 1])
        return out

    return apply(f, ins, name="conv3d_transpose")


# ---------------------------------------------------------------------------
# round-5 long tail (reference python/paddle/nn/functional/)
# ---------------------------------------------------------------------------


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    """NCL adaptive max pool (reference: F.adaptive_max_pool1d)."""
    if return_mask:
        raise NotImplementedError("adaptive_max_pool1d: return_mask unsupported")
    x = coerce(x)
    o = int(output_size) if not isinstance(output_size, (list, tuple)) else int(output_size[0])

    def f(a):
        n, c, l = a.shape
        if l % o == 0:
            return a.reshape(n, c, o, l // o).max(-1)
        segs = [a[:, :, (i * l) // o : ((i + 1) * l + o - 1) // o].max(2, keepdims=True) for i in range(o)]
        return jnp.concatenate(segs, 2)

    return apply(f, [x], name="adaptive_max_pool1d")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    """Shuffle channels across groups (reference: F.channel_shuffle)."""
    x = coerce(x)

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            return a.reshape(n, groups, c // groups, h, w).swapaxes(1, 2).reshape(a.shape)
        n, h, w, c = a.shape
        return a.reshape(n, h, w, groups, c // groups).swapaxes(3, 4).reshape(a.shape)

    return apply(f, [x], name="channel_shuffle")


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0, data_format="NCL", output_size=None, name=None):
    """Inverse of max_pool1d via the pooling indices (reference:
    F.max_unpool1d)."""
    x, indices = coerce(x), coerce(indices)
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    st = k if stride is None else (stride if isinstance(stride, int) else stride[0])
    out_l = (
        int(output_size[-1]) if output_size is not None
        else (x.shape[-1] - 1) * st + k - 2 * padding
    )

    def f(a, idx):
        n, c, l = a.shape
        flat = jnp.zeros((n, c, out_l), a.dtype)
        return flat.at[
            jnp.arange(n)[:, None, None], jnp.arange(c)[None, :, None], idx
        ].set(a)

    return apply(f, [x, indices], name="max_unpool1d")


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0, data_format="NCHW", output_size=None, name=None):
    """Inverse of max_pool2d via flattened HW indices (reference:
    F.max_unpool2d)."""
    x, indices = coerce(x), coerce(indices)
    kh, kw = _tuplize(kernel_size, 2)
    sh, sw = (kh, kw) if stride is None else _tuplize(stride, 2)
    ph, pw = _tuplize(padding, 2)
    if output_size is not None:
        oh, ow = int(output_size[-2]), int(output_size[-1])
    else:
        oh = (x.shape[-2] - 1) * sh + kh - 2 * ph
        ow = (x.shape[-1] - 1) * sw + kw - 2 * pw

    def f(a, idx):
        n, c, h, w = a.shape
        flat = jnp.zeros((n, c, oh * ow), a.dtype)
        flat = flat.at[
            jnp.arange(n)[:, None, None],
            jnp.arange(c)[None, :, None],
            idx.reshape(n, c, h * w),
        ].set(a.reshape(n, c, h * w))
        return flat.reshape(n, c, oh, ow)

    return apply(f, [x, indices], name="max_unpool2d")


def soft_margin_loss(input, label, reduction="mean", name=None):
    """log(1 + exp(-label * input)) (reference: F.soft_margin_loss)."""
    input, label = coerce(input), coerce(label)
    v = apply(
        lambda a, y: jnp.log1p(jnp.exp(-y.astype(a.dtype) * a)),
        [input, label], name="soft_margin_loss",
    )
    return _reduce(v, reduction)


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean", name=None):
    """Per-class BCE-with-logits averaged over classes (reference:
    F.multi_label_soft_margin_loss)."""
    input, label = coerce(input), coerce(label)
    ins = [input, label] + ([coerce(weight)] if weight is not None else [])

    def f(a, y, *w):
        y = y.astype(a.dtype)
        per = y * jax.nn.log_sigmoid(a) + (1 - y) * jax.nn.log_sigmoid(-a)
        if w:
            per = per * w[0]
        return -per.mean(-1)

    return _reduce(apply(f, ins, name="multi_label_soft_margin_loss"), reduction)


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8, reduction="mean", name=None):
    """Poisson NLL (reference: F.poisson_nll_loss)."""
    input, label = coerce(input), coerce(label)

    def f(a, y):
        y = y.astype(a.dtype)
        if log_input:
            v = jnp.exp(a) - y * a
        else:
            v = a - y * jnp.log(a + epsilon)
        if full:
            stirling = y * jnp.log(y + epsilon) - y + 0.5 * jnp.log(2 * jnp.pi * (y + epsilon))
            v = v + jnp.where(y > 1, stirling, 0.0)
        return v

    return _reduce(apply(f, [input, label], name="poisson_nll_loss"), reduction)


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6, reduction="mean", name=None):
    """Gaussian NLL with predicted variance (reference: F.gaussian_nll_loss)."""
    input, label, variance = coerce(input), coerce(label), coerce(variance)

    def f(mu, y, var):
        var = jnp.clip(var, epsilon, None)
        v = 0.5 * (jnp.log(var) + (y - mu) ** 2 / var)
        if full:
            v = v + 0.5 * jnp.log(jnp.asarray(2 * jnp.pi, v.dtype))
        return v

    return _reduce(apply(f, [input, label, variance], name="gaussian_nll_loss"), reduction)


def triplet_margin_with_distance_loss(input, positive, negative, distance_function=None, margin=1.0, swap=False, reduction="mean", name=None):
    """Triplet loss with a custom distance callable (reference:
    F.triplet_margin_with_distance_loss)."""
    from ... import ops as _ops

    if distance_function is None:
        distance_function = lambda a, b: pairwise_distance(a, b)  # noqa: E731
    d_pos = distance_function(coerce(input), coerce(positive))
    d_neg = distance_function(coerce(input), coerce(negative))
    if swap:
        d_pn = distance_function(coerce(positive), coerce(negative))
        d_neg = _ops.minimum(d_neg, d_pn)
    v = _ops.clip(d_pos - d_neg + margin, min=0.0)
    return _reduce(v, reduction)
