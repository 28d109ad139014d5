"""``nn.functional`` — functional neural-net ops.

Reference: ``python/paddle/nn/functional/`` (17.9k lines).  Everything lowers
to jnp/lax; XLA fuses the elementwise chains and lowers convs/matmuls to the
MXU.  The fused attention entry points route to the Pallas kernel library
(``paddle_tpu.kernels``), the TPU counterpart of the reference's
``phi/kernels/fusion/gpu``.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as rnd
from ..framework.dispatch import apply_op
from ..framework.tensor import Tensor
from ..ops.common import unary_op, binary_op, int_list, axis_or_none

__all__ = [
    # activations
    "relu", "relu6", "gelu", "sigmoid", "silu", "softmax", "log_softmax", "tanh",
    "hardswish", "hardsigmoid", "leaky_relu", "elu", "selu", "celu", "mish",
    "softplus", "softsign", "swish", "glu", "hardtanh", "tanhshrink", "softshrink",
    "hardshrink", "prelu", "log_sigmoid", "gumbel_softmax", "thresholded_relu",
    # linear & conv & pool
    "linear", "conv1d", "conv2d", "conv3d", "conv1d_transpose", "conv2d_transpose",
    "conv3d_transpose", "avg_pool1d", "avg_pool2d", "avg_pool3d", "max_pool1d",
    "max_pool2d", "max_pool3d", "adaptive_avg_pool1d", "adaptive_avg_pool2d",
    "adaptive_avg_pool3d", "adaptive_max_pool1d", "adaptive_max_pool2d",
    # norm
    "batch_norm", "layer_norm", "group_norm", "instance_norm", "rms_norm",
    "local_response_norm", "normalize",
    # regularization
    "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    # embedding
    "embedding", "one_hot",
    # loss
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "smooth_l1_loss",
    "nll_loss", "kl_div", "margin_ranking_loss", "sigmoid_focal_loss",
    "cosine_embedding_loss", "triplet_margin_loss", "hinge_embedding_loss",
    "poisson_nll_loss", "gaussian_nll_loss", "multi_label_soft_margin_loss",
    "soft_margin_loss", "square_error_cost", "ctc_loss",
    # misc
    "interpolate", "upsample", "pixel_shuffle", "pixel_unshuffle", "cosine_similarity",
    "pad", "pairwise_distance", "label_smooth", "sequence_mask", "unfold",
    "scaled_dot_product_attention", "flash_attention", "flash_attn_unpadded", "channel_shuffle",
    "temporal_shift", "npair_loss", "rrelu", "zeropad2d",
]


def _t(v, ref=None):
    if isinstance(v, Tensor):
        return v
    return Tensor(v)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def relu(x, name=None):
    return unary_op("relu", jax.nn.relu, x)


def relu_(x, name=None):
    out = relu(x)
    from ..framework.tensor import inplace_rebind_

    return inplace_rebind_(x, out)


def relu6(x, name=None):
    return unary_op("relu6", jax.nn.relu6, x)


def gelu(x, approximate=False, name=None):
    return unary_op("gelu", lambda a: jax.nn.gelu(a, approximate=approximate), x)


def sigmoid(x, name=None):
    return unary_op("sigmoid", jax.nn.sigmoid, x)


def silu(x, name=None):
    return unary_op("silu", jax.nn.silu, x)


def softmax(x, axis=-1, dtype=None, name=None):
    def f(a):
        if dtype is not None:
            a = a.astype(dtype)
        return jax.nn.softmax(a, axis=axis)

    return unary_op("softmax", f, x)


def log_softmax(x, axis=-1, dtype=None, name=None):
    def f(a):
        if dtype is not None:
            a = a.astype(dtype)
        return jax.nn.log_softmax(a, axis=axis)

    return unary_op("log_softmax", f, x)


def tanh(x, name=None):
    return unary_op("tanh", jnp.tanh, x)


def hardswish(x, name=None):
    return unary_op("hardswish", lambda a: a * jnp.clip(a + 3.0, 0.0, 6.0) / 6.0, x)


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return unary_op("hardsigmoid", lambda a: jnp.clip(slope * a + offset, 0.0, 1.0), x)


def leaky_relu(x, negative_slope=0.01, name=None):
    return unary_op("leaky_relu", lambda a: jax.nn.leaky_relu(a, negative_slope), x)


def elu(x, alpha=1.0, name=None):
    return unary_op("elu", lambda a: jax.nn.elu(a, alpha), x)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return unary_op("selu", lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), x)


def celu(x, alpha=1.0, name=None):
    return unary_op("celu", lambda a: jax.nn.celu(a, alpha), x)


def mish(x, name=None):
    return unary_op("mish", lambda a: a * jnp.tanh(jax.nn.softplus(a)), x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return unary_op(
        "softplus",
        lambda a: jnp.where(beta * a > threshold, a, jax.nn.softplus(beta * a) / beta),
        x,
    )


def softsign(x, name=None):
    return unary_op("softsign", jax.nn.soft_sign, x)


def swish(x, name=None):
    return unary_op("swish", jax.nn.silu, x)


def glu(x, axis=-1, name=None):
    def f(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)

    return unary_op("glu", f, x)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return unary_op("hardtanh", lambda a: jnp.clip(a, min, max), x)


def tanhshrink(x, name=None):
    return unary_op("tanhshrink", lambda a: a - jnp.tanh(a), x)


def softshrink(x, threshold=0.5, name=None):
    return unary_op(
        "softshrink",
        lambda a: jnp.where(a > threshold, a - threshold, jnp.where(a < -threshold, a + threshold, 0.0)),
        x,
    )


def hardshrink(x, threshold=0.5, name=None):
    return unary_op("hardshrink", lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), x)


def prelu(x, weight, data_format="NCHW", name=None):
    def f(a, w):
        if w.size == 1:
            return jnp.where(a > 0, a, w.reshape(()) * a)
        if data_format == "NCHW":
            shape = [1, -1] + [1] * (a.ndim - 2)
        else:
            shape = [1] * (a.ndim - 1) + [-1]
        return jnp.where(a > 0, a, w.reshape(shape) * a)

    return apply_op("prelu", f, (_t(x), _t(weight)), {})



def _stochastic_key():
    """PRNG key for a stochastic op, as a TENSOR INPUT: an RNG source node
    under a static Program (Executor.run feeds fresh subkeys per run), the
    eager generator key otherwise."""
    from ..static.graph import current_builder, rng_key_input

    if current_builder() is not None:
        return rng_key_input()
    return Tensor(rnd.next_key())

def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, name=None):
    if not training:
        return unary_op("rrelu", lambda a: jnp.where(a >= 0, a, a * ((lower + upper) / 2.0)), x)

    def f(a, key):
        slopes = jax.random.uniform(key, a.shape, dtype=jnp.float32, minval=lower, maxval=upper).astype(a.dtype)
        return jnp.where(a >= 0, a, a * slopes)

    return apply_op("rrelu", f, (_t(x), _stochastic_key()), {})


def log_sigmoid(x, name=None):
    return unary_op("log_sigmoid", jax.nn.log_sigmoid, x)


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    return unary_op("thresholded_relu", lambda a: jnp.where(a > threshold, a, value), x)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    def f(a, key):
        g = -jnp.log(-jnp.log(jax.random.uniform(key, a.shape, dtype=jnp.float32, minval=1e-20, maxval=1.0)))
        y = jax.nn.softmax((a + g.astype(a.dtype)) / temperature, axis=axis)
        if hard:
            # straight-through: hard one-hot forward, soft gradient backward
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y)
            y_hard = jnp.put_along_axis(y_hard, idx, jnp.asarray(1.0, y.dtype), axis=axis, inplace=False)
            y = y_hard + y - jax.lax.stop_gradient(y)
        return y

    return apply_op("gumbel_softmax", f, (_t(x), _stochastic_key()), {})


# ---------------------------------------------------------------------------
# linear / conv / pool
# ---------------------------------------------------------------------------

def linear(x, weight, bias=None, name=None):
    """y = x @ W (+ b); paddle stores weight as [in_features, out_features]."""
    if bias is not None:
        return apply_op("linear", lambda a, w, b: jnp.matmul(a, w) + b, (_t(x), _t(weight), _t(bias)), {})
    return apply_op("linear", jnp.matmul, (_t(x), _t(weight)), {})


def _conv_padding(padding, ndim, kernel, dilation):
    if isinstance(padding, str):
        return padding.upper()
    p = int_list(padding)
    if len(p) == 1:
        p = p * ndim
    if len(p) == ndim:
        return [(pi, pi) for pi in p]
    if len(p) == 2 * ndim:
        return [(p[2 * i], p[2 * i + 1]) for i in range(ndim)]
    raise ValueError(f"bad padding {padding}")


def _conv(x, weight, bias, stride, padding, dilation, groups, nd, data_format, transpose=False, output_padding=0):
    st = int_list(stride)
    st = st * nd if len(st) == 1 else st
    dl = int_list(dilation)
    dl = dl * nd if len(dl) == 1 else dl
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if nd == 1:
        dn_l = "NCH" if not channel_last else "NHC"
        dims = ("NCH", "OIH", "NCH") if not channel_last else ("NHC", "OIH", "NHC")
    elif nd == 2:
        dims = ("NCHW", "OIHW", "NCHW") if not channel_last else ("NHWC", "OIHW", "NHWC")
    else:
        dims = ("NCDHW", "OIDHW", "NCDHW") if not channel_last else ("NDHWC", "OIDHW", "NDHWC")
    pad = _conv_padding(padding, nd, None, dl)

    if not transpose:
        def f(a, w, *b):
            out = jax.lax.conv_general_dilated(
                a, w, window_strides=st, padding=pad, rhs_dilation=dl,
                dimension_numbers=dims, feature_group_count=groups,
                preferred_element_type=jnp.float32 if a.dtype == jnp.float32 else None,
            )
            if b:
                bias_shape = [1] * out.ndim
                c_axis = out.ndim - 1 if channel_last else 1
                bias_shape[c_axis] = -1
                out = out + b[0].reshape(bias_shape)
            return out.astype(a.dtype)
    else:
        op = int_list(output_padding)
        op = op * nd if len(op) == 1 else op

        def f(a, w, *b):
            # paddle conv_transpose weight layout: [in, out//groups, *k]
            k_spatial = w.shape[2:]
            if isinstance(pad, str):
                pad_t = pad
            else:
                pad_t = [
                    (dl[i] * (k_spatial[i] - 1) - pad[i][0], dl[i] * (k_spatial[i] - 1) - pad[i][1] + op[i])
                    for i in range(nd)
                ]
            w_t = jnp.swapaxes(w, 0, 1)  # -> [out//g, in, *k]
            w_t = jnp.flip(w_t, axis=tuple(range(2, w_t.ndim)))
            if groups > 1:
                # grouped transpose conv: block-diagonal trick
                i_per_g = w.shape[0] // groups
                o_per_g = w.shape[1]
                w_g = w.reshape((groups, i_per_g) + w.shape[1:])
                outs = []
                a_split = jnp.split(a, groups, axis=-1 if channel_last else 1)
                for g in range(groups):
                    wg = jnp.swapaxes(w_g[g], 0, 1)
                    wg = jnp.flip(wg, axis=tuple(range(2, wg.ndim)))
                    outs.append(jax.lax.conv_general_dilated(
                        a_split[g], wg, window_strides=[1] * nd, padding=pad_t,
                        lhs_dilation=st, rhs_dilation=dl, dimension_numbers=dims))
                out = jnp.concatenate(outs, axis=-1 if channel_last else 1)
            else:
                out = jax.lax.conv_general_dilated(
                    a, w_t, window_strides=[1] * nd, padding=pad_t,
                    lhs_dilation=st, rhs_dilation=dl, dimension_numbers=dims)
            if b:
                bias_shape = [1] * out.ndim
                c_axis = out.ndim - 1 if channel_last else 1
                bias_shape[c_axis] = -1
                out = out + b[0].reshape(bias_shape)
            return out.astype(a.dtype)

    args = (_t(x), _t(weight)) + ((_t(bias),) if bias is not None else ())
    return apply_op("conv", f, args, {})


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL", name=None):
    df = "NCH" if data_format == "NCL" else "NHC"
    return _conv(x, weight, bias, stride, padding, dilation, groups, 1, df)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 2, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 3, data_format)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1, dilation=1, output_size=None, data_format="NCL", name=None):
    df = "NCH" if data_format == "NCL" else "NHC"
    return _conv(x, weight, bias, stride, padding, dilation, groups, 1, df, transpose=True, output_padding=output_padding)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1, dilation=1, output_size=None, data_format="NCHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 2, data_format, transpose=True, output_padding=output_padding)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1, dilation=1, output_size=None, data_format="NCDHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 3, data_format, transpose=True, output_padding=output_padding)


def _pool(x, kernel, stride, padding, nd, reducer, init, data_format, ceil_mode=False, exclusive=True, count_include_pad=False):
    ks = int_list(kernel)
    ks = ks * nd if len(ks) == 1 else ks
    st = int_list(stride) if stride is not None else ks
    st = st * nd if len(st) == 1 else st
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    pd = _conv_padding(padding, nd, ks, [1] * nd)

    def f(a):
        if channel_last:
            window = (1,) + tuple(ks) + (1,)
            strides = (1,) + tuple(st) + (1,)
            pads = [(0, 0)] + (pd if not isinstance(pd, str) else pd) + [(0, 0)] if not isinstance(pd, str) else pd
        else:
            window = (1, 1) + tuple(ks)
            strides = (1, 1) + tuple(st)
            pads = [(0, 0), (0, 0)] + pd if not isinstance(pd, str) else pd
        if isinstance(pd, str):
            pads = pd
        out = jax.lax.reduce_window(a, init(a.dtype), reducer, window, strides, pads)
        return out

    return f


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    ks = int_list(kernel_size)
    ks = ks * 2 if len(ks) == 1 else ks
    st = int_list(stride) if stride is not None else ks
    st = st * 2 if len(st) == 1 else st
    pd = _conv_padding(padding, 2, ks, [1, 1])
    channel_last = data_format == "NHWC"

    def f(a):
        if channel_last:
            window, strides = (1,) + tuple(ks) + (1,), (1,) + tuple(st) + (1,)
            pads = pd if isinstance(pd, str) else [(0, 0)] + pd + [(0, 0)]
        else:
            window, strides = (1, 1) + tuple(ks), (1, 1) + tuple(st)
            pads = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
        s = jax.lax.reduce_window(a, 0.0, jax.lax.add, window, strides, pads)
        if divisor_override:
            return s / divisor_override
        if exclusive and not isinstance(pads, str):
            ones = jnp.ones_like(a)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
            return s / cnt
        return s / float(np.prod(ks))

    return unary_op("avg_pool2d", f, x)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True, ceil_mode=False, name=None):
    x4 = x.unsqueeze(-1) if isinstance(x, Tensor) else Tensor(x)
    ks = int_list(kernel_size) + [1]
    st = (int_list(stride) + [1]) if stride is not None else ks
    pd = int_list(padding) + [0] if not isinstance(padding, str) else padding
    out = avg_pool2d(x4, ks, st, pd, ceil_mode=ceil_mode, exclusive=exclusive)
    return out.squeeze(-1)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, divisor_override=None, data_format="NCDHW", name=None):
    ks = int_list(kernel_size)
    ks = ks * 3 if len(ks) == 1 else ks
    st = int_list(stride) if stride is not None else ks
    st = st * 3 if len(st) == 1 else st
    pd = _conv_padding(padding, 3, ks, [1, 1, 1])

    def f(a):
        window, strides = (1, 1) + tuple(ks), (1, 1) + tuple(st)
        pads = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
        s = jax.lax.reduce_window(a, 0.0, jax.lax.add, window, strides, pads)
        if divisor_override:
            return s / divisor_override
        if exclusive and not isinstance(pads, str):
            cnt = jax.lax.reduce_window(jnp.ones_like(a), 0.0, jax.lax.add, window, strides, pads)
            return s / cnt
        return s / float(np.prod(ks))

    return unary_op("avg_pool3d", f, x)


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False, ceil_mode=False, data_format="NCHW", name=None):
    ks = int_list(kernel_size)
    ks = ks * 2 if len(ks) == 1 else ks
    st = int_list(stride) if stride is not None else ks
    st = st * 2 if len(st) == 1 else st
    pd = _conv_padding(padding, 2, ks, [1, 1])
    channel_last = data_format == "NHWC"

    def f(a):
        if channel_last:
            window, strides = (1,) + tuple(ks) + (1,), (1,) + tuple(st) + (1,)
            pads = pd if isinstance(pd, str) else [(0, 0)] + pd + [(0, 0)]
        else:
            window, strides = (1, 1) + tuple(ks), (1, 1) + tuple(st)
            pads = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
        # init value must be a PYTHON scalar: an array init defeats JAX's
        # monoid detection, losing reduce_window_max's autodiff rule
        neg = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else int(jnp.iinfo(a.dtype).min)
        return jax.lax.reduce_window(a, neg, jax.lax.max, window, strides, pads)

    out = unary_op("max_pool2d", f, x)
    if return_mask:
        # indices within each window (flattened HxW index), computed separately
        def fi(a):
            n, c, h, w = a.shape
            idx = jnp.arange(h * w, dtype=jnp.float32).reshape(1, 1, h, w)
            idx = jnp.broadcast_to(idx, a.shape)
            window, strides = (1, 1) + tuple(ks), (1, 1) + tuple(st)
            pads = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
            neg = jnp.asarray(-jnp.inf, jnp.float32)

            def sel(acc, cur):
                av, ai = acc
                cv, ci = cur
                take = cv > av
                return jnp.where(take, cv, av), jnp.where(take, ci, ai)

            vals, idxs = jax.lax.reduce_window(
                (a.astype(jnp.float32), idx), (neg, jnp.asarray(0.0)), sel, window, strides, pads
            )
            return idxs.astype(jnp.int32)

        mask = unary_op("max_pool2d_mask", fi, x)
        return out, mask
    return out


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False, ceil_mode=False, name=None):
    x4 = x.unsqueeze(-1)
    ks = int_list(kernel_size) + [1]
    st = (int_list(stride) + [1]) if stride is not None else ks
    pd = int_list(padding) + [0] if not isinstance(padding, str) else padding
    out = max_pool2d(x4, ks, st, pd, ceil_mode=ceil_mode)
    return out.squeeze(-1)


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False, ceil_mode=False, data_format="NCDHW", name=None):
    ks = int_list(kernel_size)
    ks = ks * 3 if len(ks) == 1 else ks
    st = int_list(stride) if stride is not None else ks
    st = st * 3 if len(st) == 1 else st
    pd = _conv_padding(padding, 3, ks, [1, 1, 1])

    def f(a):
        window, strides = (1, 1) + tuple(ks), (1, 1) + tuple(st)
        pads = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
        return jax.lax.reduce_window(a, -jnp.inf, jax.lax.max, window, strides, pads)

    return unary_op("max_pool3d", f, x)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    os = int_list(output_size)
    os = os * 2 if len(os) == 1 else os

    def f(a):
        h, w = a.shape[-2], a.shape[-1]
        oh, ow = os
        if h % oh == 0 and w % ow == 0:
            kh, kw = h // oh, w // ow
            r = a.reshape(a.shape[:-2] + (oh, kh, ow, kw))
            return r.mean(axis=(-3, -1))
        # general: interpolate-style mean over variable windows (host loop, static)
        out_rows = []
        for i in range(oh):
            r0, r1 = (i * h) // oh, -(-((i + 1) * h) // oh)
            cols = []
            for j in range(ow):
                c0, c1 = (j * w) // ow, -(-((j + 1) * w) // ow)
                cols.append(a[..., r0:r1, c0:c1].mean(axis=(-2, -1)))
            out_rows.append(jnp.stack(cols, axis=-1))
        return jnp.stack(out_rows, axis=-2)

    return unary_op("adaptive_avg_pool2d", f, x)


def adaptive_avg_pool1d(x, output_size, name=None):
    out = adaptive_avg_pool2d(x.unsqueeze(-1), [int(output_size) if not isinstance(output_size, (list, tuple)) else output_size[0], 1])
    return out.squeeze(-1)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    os = int_list(output_size)
    os = os * 3 if len(os) == 1 else os

    def f(a):
        d, h, w = a.shape[-3:]
        od, oh, ow = os
        if d % od == 0 and h % oh == 0 and w % ow == 0:
            kd, kh, kw = d // od, h // oh, w // ow
            r = a.reshape(a.shape[:-3] + (od, kd, oh, kh, ow, kw))
            return r.mean(axis=(-5, -3, -1))
        raise NotImplementedError("adaptive_avg_pool3d with non-divisible sizes")

    return unary_op("adaptive_avg_pool3d", f, x)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    os = int_list(output_size)
    os = os * 2 if len(os) == 1 else os

    def f(a):
        h, w = a.shape[-2], a.shape[-1]
        oh, ow = os
        if h % oh == 0 and w % ow == 0:
            kh, kw = h // oh, w // ow
            r = a.reshape(a.shape[:-2] + (oh, kh, ow, kw))
            return r.max(axis=(-3, -1))
        out_rows = []
        for i in range(oh):
            r0, r1 = (i * h) // oh, -(-((i + 1) * h) // oh)
            cols = []
            for j in range(ow):
                c0, c1 = (j * w) // ow, -(-((j + 1) * w) // ow)
                cols.append(a[..., r0:r1, c0:c1].max(axis=(-2, -1)))
            out_rows.append(jnp.stack(cols, axis=-1))
        return jnp.stack(out_rows, axis=-2)

    return unary_op("adaptive_max_pool2d", f, x)


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    out = adaptive_max_pool2d(x.unsqueeze(-1), [int(output_size), 1])
    return out.squeeze(-1)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(normalized_shape)

    def f(a, *wb):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        mu = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32)
        return out.astype(a.dtype)

    args = (_t(x),) + tuple(_t(v) for v in (weight, bias) if v is not None)
    return apply_op("layer_norm", f, args, {})


def rms_norm(x, weight=None, epsilon=1e-6, name=None, shard=None):
    """Root-mean-square norm — routed to the Pallas kernel on TPU.
    ``shard``: see ``kernels.rms_norm.rms_norm``."""
    from ..kernels import rms_norm as _krms

    args = (_t(x),) + ((_t(weight),) if weight is not None else ())
    return apply_op("rms_norm", lambda *xs: _krms.rms_norm(
        *xs, epsilon=epsilon, shard=shard), args, {})


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False, momentum=0.9, epsilon=1e-05, data_format="NCHW", use_global_stats=None, name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    use_batch_stats = training and not use_global_stats

    # running stats are op INPUTS (not closed over): graph capture (fragment
    # or static Program) then sees stat updates between calls instead of a
    # mean/var baked at build time
    def f(a, rm, rv, *wb):
        c_axis = a.ndim - 1 if channel_last else 1
        axes = tuple(i for i in range(a.ndim) if i != c_axis)
        if use_batch_stats:
            mu = jnp.mean(a.astype(jnp.float32), axis=axes)
            var = jnp.var(a.astype(jnp.float32), axis=axes)
        else:
            mu, var = rm.astype(jnp.float32), rv.astype(jnp.float32)
        shape = [1] * a.ndim
        shape[c_axis] = -1
        out = (a.astype(jnp.float32) - mu.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32).reshape(shape)
        return out.astype(a.dtype)

    args = (_t(x), _t(running_mean), _t(running_var)) + tuple(
        _t(v) for v in (weight, bias) if v is not None)
    out = apply_op("batch_norm", f, args, {})

    # update running stats eagerly (matches reference semantics); routed
    # through apply_op so graph capture (fragment/static) records it as a
    # buffer mutation instead of forcing a break
    if use_batch_stats and isinstance(running_mean, Tensor):
        xt = _t(x)
        if not isinstance(xt._data, jax.core.Tracer):
            def upd(a, rm_, rv_):
                c_axis = a.ndim - 1 if channel_last else 1
                axes = tuple(i for i in range(a.ndim) if i != c_axis)
                mu = jnp.mean(a.astype(jnp.float32), axis=axes)
                var = jnp.var(a.astype(jnp.float32), axis=axes)
                new_rm = (momentum * rm_.astype(jnp.float32)
                          + (1 - momentum) * mu).astype(rm_.dtype)
                new_rv = (momentum * rv_.astype(jnp.float32)
                          + (1 - momentum) * var).astype(rv_.dtype)
                return new_rm, new_rv

            from ..framework.autograd import no_grad

            with no_grad():
                new_rm, new_rv = apply_op(
                    "batch_norm_stats", upd, (xt, running_mean, running_var),
                    {}, num_outputs=2)
            running_mean._data = new_rm._data
            running_var._data = new_rv._data
    return out


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-05, data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    def f(a, *wb):
        if channel_last:
            a_m = jnp.moveaxis(a, -1, 1)
        else:
            a_m = a
        n, c = a_m.shape[0], a_m.shape[1]
        g = num_groups
        r = a_m.reshape((n, g, c // g) + a_m.shape[2:])
        axes = tuple(range(2, r.ndim))
        mu = jnp.mean(r.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(r.astype(jnp.float32), axis=axes, keepdims=True)
        out = ((r.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + epsilon)).reshape(a_m.shape)
        shape = [1, -1] + [1] * (a_m.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32).reshape(shape)
        out = out.astype(a.dtype)
        return jnp.moveaxis(out, 1, -1) if channel_last else out

    args = (_t(x),) + tuple(_t(v) for v in (weight, bias) if v is not None)
    return apply_op("group_norm", f, args, {})


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None, use_input_stats=True, momentum=0.9, eps=1e-05, data_format="NCHW", name=None):
    def f(a, *wb):
        axes = tuple(range(2, a.ndim))
        mu = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
        shape = [1, -1] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32).reshape(shape)
        return out.astype(a.dtype)

    args = (_t(x),) + tuple(_t(v) for v in (weight, bias) if v is not None)
    return apply_op("instance_norm", f, args, {})


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def f(a):
        sq = jnp.square(a)
        c = a.shape[1]
        half = size // 2
        padded = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (a.ndim - 2))
        acc = sum(padded[:, i:i + c] for i in range(size))
        return a / jnp.power(k + alpha * acc / size, beta)

    return unary_op("local_response_norm", f, x)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(a):
        n = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)

    return unary_op("normalize", f, x)


# ---------------------------------------------------------------------------
# dropout / embedding
# ---------------------------------------------------------------------------

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)

    def f(a, key):
        shape = list(a.shape)
        if axis is not None:
            axes = axis if isinstance(axis, (list, tuple)) else [axis]
            shape = [s if i in [ax % a.ndim for ax in axes] else 1 for i, s in enumerate(a.shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), jnp.zeros((), a.dtype)).astype(a.dtype)
        return jnp.where(keep, a, jnp.zeros((), a.dtype)).astype(a.dtype)

    return apply_op("dropout", f, (_t(x), _stochastic_key()), {})


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(a, key):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * (1 - q)) ** -0.5
        b_coef = -a_coef * alpha_p * (1 - q)
        return (a_coef * jnp.where(keep, a, jnp.asarray(alpha_p, a.dtype)) + b_coef).astype(a.dtype)

    return apply_op("alpha_dropout", f, (_t(x), _stochastic_key()), {})


def embedding(x, weight, padding_idx=None, sparse=False, name=None, max_norm=None, norm_type=2.0, scale_grad_by_freq=False):
    # indices passed as an op input (int primals take float0 cotangents the
    # autograd zero-fills) so graph capture can record the lookup
    def g(w, idx):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros((), out.dtype), out)
        return out

    return apply_op("embedding", g, (_t(weight), _t(x)), {})


def one_hot(x, num_classes, name=None):
    return unary_op("one_hot", lambda a: jax.nn.one_hot(a, num_classes, dtype=jnp.float32), x)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean", soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0, name=None):
    """Reference: ``python/paddle/nn/functional/loss.py`` cross_entropy —
    fused softmax+CE with hard/soft labels, ignore_index, class weights,
    label smoothing.  Lowered as log_softmax + gather; XLA fuses the chain.
    """
    wt = weight._data if isinstance(weight, Tensor) else weight
    it = _t(input)
    lt = _t(label)

    def _logp(logits):
        l32 = logits.astype(jnp.float32)
        if use_softmax:
            return jax.nn.log_softmax(l32, axis=axis)
        return jnp.log(jnp.clip(l32, 1e-15, 1.0))

    if soft_label:
        def f_soft(logits, lab):
            lp = _logp(logits)
            n_classes = logits.shape[axis]
            soft = lab.astype(jnp.float32)
            if label_smoothing > 0.0:
                soft = soft * (1 - label_smoothing) + label_smoothing / n_classes
            loss = -jnp.sum(soft * lp, axis=axis)
            return _reduce(loss, reduction)

        return apply_op("cross_entropy", f_soft, (it, lt), {})

    def f_hard(logits, lab):
        lp = _logp(logits)
        n_classes = logits.shape[axis]
        idx = lab.astype(jnp.int32)
        if idx.ndim == lp.ndim:
            idx = jnp.squeeze(idx, axis=axis)
        oh = jax.nn.one_hot(idx, n_classes, axis=axis if axis >= 0 else lp.ndim + axis, dtype=jnp.float32)
        if label_smoothing > 0.0:
            oh = oh * (1 - label_smoothing) + label_smoothing / n_classes
        loss = -jnp.sum(oh * lp, axis=axis)
        valid = idx != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if wt is not None:
            w_per = jnp.take(jnp.asarray(wt, jnp.float32), jnp.clip(idx, 0, n_classes - 1))
            loss = loss * jnp.where(valid, w_per, 0.0)
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, w_per, 0.0)), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        return _reduce(loss, reduction)

    # label passed as an op input (not closed over): int primals take float0
    # cotangents which autograd zero-fills, and graph capture (fragment /
    # static Program) can record the op instead of breaking on the closure
    return apply_op("cross_entropy", f_hard, (it, lt), {})


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100, numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label, ignore_index=ignore_index, reduction="none", axis=axis)
    loss = loss.unsqueeze(axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def f(p, y, *w):
        p32, y32 = p.astype(jnp.float32), y.astype(jnp.float32)
        loss = -(y32 * jnp.log(jnp.clip(p32, 1e-12, 1.0)) + (1 - y32) * jnp.log(jnp.clip(1 - p32, 1e-12, 1.0)))
        if w:
            loss = loss * w[0].astype(jnp.float32)
        return _reduce(loss, reduction)

    args = (_t(input), _t(label)) + ((_t(weight),) if weight is not None else ())
    return apply_op("bce", f, args, {})


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean", pos_weight=None, name=None):
    def f(z, y, *rest):
        z32, y32 = z.astype(jnp.float32), y.astype(jnp.float32)
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = rest[i].astype(jnp.float32)
            i += 1
        if pos_weight is not None:
            pw = rest[i].astype(jnp.float32)
        max_val = jnp.clip(-z32, 0, None)
        if pw is not None:
            log_w = (pw - 1) * y32 + 1
            loss = (1 - y32) * z32 + log_w * (jnp.log1p(jnp.exp(-jnp.abs(z32))) + max_val)
        else:
            loss = (1 - y32) * z32 + jnp.log1p(jnp.exp(-jnp.abs(z32))) + max_val - jnp.clip(z32, None, 0) * 0
            loss = jnp.clip(z32, 0, None) - z32 * y32 + jnp.log1p(jnp.exp(-jnp.abs(z32)))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)

    args = (_t(logit), _t(label)) + tuple(_t(v) for v in (weight, pos_weight) if v is not None)
    return apply_op("bce_logits", f, args, {})


def mse_loss(input, label, reduction="mean", name=None):
    return apply_op("mse_loss", lambda a, b: _reduce(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)), reduction), (_t(input), _t(label)), {})


def l1_loss(input, label, reduction="mean", name=None):
    return apply_op("l1_loss", lambda a, b: _reduce(jnp.abs(a - b), reduction), (_t(input), _t(label)), {})


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        d = a - b
        ad = jnp.abs(d)
        loss = jnp.where(ad < delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
        return _reduce(loss, reduction)

    return apply_op("smooth_l1_loss", f, (_t(input), _t(label)), {})


huber_loss = smooth_l1_loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    wt = weight._data if isinstance(weight, Tensor) else weight
    lt = _t(label)

    def f(lp, lab):
        n_classes = lp.shape[1]
        ii = lab.astype(jnp.int32)
        gathered = jnp.take_along_axis(lp, ii[:, None] if lp.ndim == 2 else ii[:, None, ...], axis=1)
        loss = -jnp.squeeze(gathered, axis=1)
        valid = ii != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if wt is not None:
            w_per = jnp.take(jnp.asarray(wt, lp.dtype), jnp.clip(ii, 0, n_classes - 1))
            loss = loss * w_per
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, w_per, 0.0)), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(lp.dtype)), 1.0)
        return _reduce(loss, reduction)

    return apply_op("nll_loss", f, (_t(input), lt), {})


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def f(lp, t):
        t32 = t.astype(jnp.float32)
        lp32 = lp.astype(jnp.float32)
        if log_target:
            loss = jnp.exp(t32) * (t32 - lp32)
        else:
            loss = t32 * (jnp.log(jnp.clip(t32, 1e-12, None)) - lp32)
        if reduction == "batchmean":
            return jnp.sum(loss) / lp.shape[0]
        return _reduce(loss, reduction)

    return apply_op("kl_div", f, (_t(input), _t(label)), {})


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    def f(a, b, y):
        loss = jnp.clip(-y * (a - b) + margin, 0, None)
        return _reduce(loss, reduction)

    return apply_op("margin_ranking_loss", f, (_t(input), _t(other), _t(label)), {})


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum", name=None):
    def f(z, y, *n):
        p = jax.nn.sigmoid(z.astype(jnp.float32))
        y32 = y.astype(jnp.float32)
        ce = jnp.clip(z, 0, None) - z * y32 + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y32 + (1 - p) * (1 - y32)
        a_t = alpha * y32 + (1 - alpha) * (1 - y32)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce(loss, reduction)

    args = (_t(logit), _t(label)) + ((_t(normalizer),) if normalizer is not None else ())
    return apply_op("sigmoid_focal_loss", f, args, {})


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    def f(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / (jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.clip(cos - margin, 0, None))
        return _reduce(loss, reduction)

    return apply_op("cosine_embedding_loss", f, (_t(input1), _t(input2), _t(label)), {})


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, epsilon=1e-06, swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        d_ap = jnp.sum(jnp.abs(a - pos) ** p, axis=-1) ** (1.0 / p)
        d_an = jnp.sum(jnp.abs(a - neg) ** p, axis=-1) ** (1.0 / p)
        if swap:
            d_pn = jnp.sum(jnp.abs(pos - neg) ** p, axis=-1) ** (1.0 / p)
            d_an = jnp.minimum(d_an, d_pn)
        loss = jnp.clip(d_ap - d_an + margin, 0, None)
        return _reduce(loss, reduction)

    return apply_op("triplet_margin_loss", f, (_t(input), _t(positive), _t(negative)), {})


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    def f(a, y):
        loss = jnp.where(y == 1, a, jnp.clip(margin - a, 0, None))
        return _reduce(loss, reduction)

    return apply_op("hinge_embedding_loss", f, (_t(input), _t(label)), {})


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8, reduction="mean", name=None):
    def f(z, y):
        if log_input:
            loss = jnp.exp(z) - y * z
        else:
            loss = z - y * jnp.log(z + epsilon)
        if full:
            stirling = y * jnp.log(y + 1e-12) - y + 0.5 * jnp.log(2 * math.pi * jnp.clip(y, 1e-12, None))
            loss = loss + jnp.where(y > 1, stirling, 0.0)
        return _reduce(loss, reduction)

    return apply_op("poisson_nll_loss", f, (_t(input), _t(label)), {})


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6, reduction="mean", name=None):
    def f(mu, y, var):
        v = jnp.clip(var, epsilon, None)
        loss = 0.5 * (jnp.log(v) + jnp.square(y - mu) / v)
        if full:
            loss = loss + 0.5 * math.log(2 * math.pi)
        return _reduce(loss, reduction)

    return apply_op("gaussian_nll_loss", f, (_t(input), _t(label), _t(variance)), {})


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean", name=None):
    def f(z, y, *w):
        loss = -(y * jax.nn.log_sigmoid(z) + (1 - y) * jax.nn.log_sigmoid(-z))
        loss = loss.mean(axis=-1)
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)

    args = (_t(input), _t(label)) + ((_t(weight),) if weight is not None else ())
    return apply_op("multi_label_soft_margin_loss", f, args, {})


def soft_margin_loss(input, label, reduction="mean", name=None):
    def f(z, y):
        loss = jnp.log1p(jnp.exp(-y * z))
        return _reduce(loss, reduction)

    return apply_op("soft_margin_loss", f, (_t(input), _t(label)), {})


def square_error_cost(input, label):
    return apply_op("square_error_cost", lambda a, b: jnp.square(a - b), (_t(input), _t(label)), {})


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def f(a, p, y):
        sim = a @ p.T
        n = a.shape[0]
        yv = y.reshape(-1, 1)
        same = (yv == yv.T).astype(jnp.float32)
        same = same / jnp.sum(same, axis=1, keepdims=True)
        lp = jax.nn.log_softmax(sim, axis=1)
        xent = -jnp.mean(jnp.sum(same * lp, axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(a), axis=1)) + jnp.mean(jnp.sum(jnp.square(p), axis=1))) * 0.25
        return xent + reg

    return apply_op("npair_loss", f, (_t(anchor), _t(positive), _t(labels)), {})


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0, reduction="mean", norm_by_times=False):
    """CTC via the standard forward algorithm in log space (lax.scan over time).

    Reference uses warpctc (``third_party/warpctc``); here the dynamic program
    is expressed directly and XLA compiles it.
    log_probs: [T, B, C] (paddle layout) — raw logits are accepted and
    log-softmaxed internally, matching paddle's ``warpctc`` op.
    """
    lt = _t(labels)
    ilt = _t(input_lengths)
    llt = _t(label_lengths)
    lab = lt._data.astype(jnp.int32)
    in_len = ilt._data.astype(jnp.int32)
    lab_len = llt._data.astype(jnp.int32)

    def f(lp):
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        T, B, C = lp.shape
        S_max = lab.shape[1]
        L = 2 * S_max + 1
        NEG = jnp.asarray(-1e30, jnp.float32)

        # extended label sequence: blank a1 blank a2 ... blank
        ext = jnp.full((B, L), blank, dtype=jnp.int32)
        ext = ext.at[:, 1::2].set(lab)
        s_idx = jnp.arange(L)

        alpha0 = jnp.full((B, L), NEG)
        alpha0 = alpha0.at[:, 0].set(lp[0, :, blank])
        first_lab = ext[:, 1]
        alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(lp[0], first_lab[:, None], axis=1)[:, 0])

        same_as_two_back = jnp.concatenate(
            [jnp.ones((B, 2), dtype=bool), ext[:, 2:] == ext[:, :-2]], axis=1
        )
        is_blank_pos = (s_idx % 2 == 0)[None, :]

        def step(carry, t):
            alpha = carry
            a_prev1 = jnp.concatenate([jnp.full((B, 1), NEG), alpha[:, :-1]], axis=1)
            a_prev2 = jnp.concatenate([jnp.full((B, 2), NEG), alpha[:, :-2]], axis=1)
            allow_skip = (~is_blank_pos) & (~same_as_two_back)
            a_prev2 = jnp.where(allow_skip, a_prev2, NEG)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, a_prev1), a_prev2)
            emit = jnp.take_along_axis(lp[t], ext, axis=1)
            new_alpha = merged + emit
            # freeze past input_lengths
            active = (t < in_len)[:, None]
            new_alpha = jnp.where(active, new_alpha, alpha)
            return new_alpha, None

        alphaT, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
        end1 = 2 * lab_len
        end2 = 2 * lab_len - 1
        ll1 = jnp.take_along_axis(alphaT, end1[:, None], axis=1)[:, 0]
        ll2 = jnp.take_along_axis(alphaT, jnp.clip(end2, 0, None)[:, None], axis=1)[:, 0]
        log_like = jnp.logaddexp(ll1, jnp.where(lab_len > 0, ll2, NEG))
        loss = -log_like
        if norm_by_times:
            loss = loss / jnp.maximum(in_len.astype(jnp.float32), 1.0)
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(lab_len.astype(jnp.float32), 1.0))
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    return apply_op("ctc_loss", f, (_t(log_probs),), {})


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False, align_mode=0, data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NWC", "NDHWC")

    def f(a):
        spatial_ndim = a.ndim - 2
        if channel_last:
            cur = a.shape[1:-1]
        else:
            cur = a.shape[2:]
        if size is not None:
            out_size = tuple(int_list(size))
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * spatial_ndim
            out_size = tuple(int(c * s) for c, s in zip(cur, sf))
        jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear", "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
        if channel_last:
            new_shape = (a.shape[0],) + out_size + (a.shape[-1],)
        else:
            new_shape = a.shape[:2] + out_size
        if jmode == "nearest":
            return jax.image.resize(a, new_shape, method="nearest").astype(a.dtype)
        if align_corners:
            # jax.image.resize has no align_corners; emulate via linear map on indices
            idxs = []
            if channel_last:
                moved = jnp.moveaxis(a, -1, 1)
            else:
                moved = a
            out = moved
            for d in range(spatial_ndim):
                n_in = cur[d]
                n_out = out_size[d]
                if n_out == 1:
                    pos = jnp.zeros((1,))
                else:
                    pos = jnp.linspace(0, n_in - 1, n_out)
                i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n_in - 1)
                i1 = jnp.clip(i0 + 1, 0, n_in - 1)
                w = (pos - i0).astype(a.dtype)
                ax = 2 + d
                g0 = jnp.take(out, i0, axis=ax)
                g1 = jnp.take(out, i1, axis=ax)
                bshape = [1] * out.ndim
                bshape[ax] = -1
                out = g0 + w.reshape(bshape) * (g1 - g0)
            return (jnp.moveaxis(out, 1, -1) if channel_last else out).astype(a.dtype)
        return jax.image.resize(a, new_shape, method=jmode).astype(a.dtype)

    return unary_op("interpolate", f, x)


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def f(a):
        n, c, h, w = a.shape
        oc = c // (r * r)
        out = a.reshape(n, oc, r, r, h, w)
        out = out.transpose(0, 1, 4, 2, 5, 3)
        return out.reshape(n, oc, h * r, w * r)

    return unary_op("pixel_shuffle", f, x)


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def f(a):
        n, c, h, w = a.shape
        out = a.reshape(n, c, h // r, r, w // r, r)
        out = out.transpose(0, 1, 3, 5, 2, 4)
        return out.reshape(n, c * r * r, h // r, w // r)

    return unary_op("pixel_unshuffle", f, x)


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def f(a):
        n, c, h, w = a.shape
        return a.reshape(n, groups, c // groups, h, w).transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)

    return unary_op("channel_shuffle", f, x)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    def f(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        r = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([r[:, 1:, :fold], jnp.zeros_like(r[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold:2 * fold]), r[:, :-1, fold:2 * fold]], axis=1)
        rest = r[:, :, 2 * fold:]
        return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)

    return unary_op("temporal_shift", f, x)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def f(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)

    return apply_op("cosine_similarity", f, (_t(x1), _t(x2)), {})


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    def f(a, b):
        d = a - b + epsilon
        return jnp.sum(jnp.abs(d) ** p, axis=-1, keepdims=keepdim) ** (1.0 / p)

    return apply_op("pairwise_distance", f, (_t(x), _t(y)), {})


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", pad_from_left_axis=True, name=None):
    from ..ops.manipulation import pad as _pad

    return _pad(x, pad, mode=mode, value=value, data_format=data_format)


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return pad(x, padding, mode="constant", value=0.0, data_format=data_format)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(y, *pd):
        k = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / k

    args = (_t(label),) + ((_t(prior_dist),) if prior_dist is not None else ())
    return apply_op("label_smooth", f, args, {})


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    lt = _t(lengths)
    ml = maxlen or int(jnp.max(lt._data))

    def f(l):
        return (jnp.arange(ml)[None, :] < l.reshape(-1, 1)).reshape(tuple(l.shape) + (ml,))

    out = apply_op("sequence_mask", f, (lt,), {})
    return out.astype("int32" if dtype in ("int64", "int32") else dtype)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col patch extraction (reference ``nn/functional/common.py`` unfold;
    NOT the strided-view ``paddle.unfold(x, axis, size, step)``)."""
    ks = int_list(kernel_sizes)
    ks = ks * 2 if len(ks) == 1 else ks
    st = int_list(strides)
    st = st * 2 if len(st) == 1 else st
    pd = int_list(paddings)
    pd = pd * 2 if len(pd) == 1 else pd
    dl = int_list(dilations)
    dl = dl * 2 if len(dl) == 1 else dl

    def f(a):
        n, c, h, w = a.shape
        # paddle's 4-int paddings are [top, left, bottom, right]; JAX wants
        # per-spatial-dim (low, high): H=(top, bottom), W=(left, right)
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=ks, window_strides=st,
            padding=[(pd[0], pd[0]), (pd[1], pd[1])] if len(pd) == 2 else [(pd[0], pd[2]), (pd[1], pd[3])],
            rhs_dilation=dl, dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return patches.reshape(n, c * ks[0] * ks[1], -1)

    return unary_op("unfold", f, x)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None):
    """Fused attention entry point (reference: ``nn/functional/flash_attention.py:976``).

    Inputs are [batch, seq, heads, head_dim] (paddle convention); routes to the
    Pallas flash-attention kernel on TPU, XLA reference path elsewhere.
    """
    from ..kernels import flash_attention as fa

    args = [_t(query), _t(key), _t(value)]
    if attn_mask is not None:
        args.append(_t(attn_mask))

        def f(q, k, v, m):
            return fa.flash_attention(q, k, v, causal=is_causal, mask=m)
    else:
        def f(q, k, v):
            return fa.flash_attention(q, k, v, causal=is_causal)

    return apply_op("scaled_dot_product_attention", f, tuple(args), {})


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, fixed_seed_offset=None, rng_name="", training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, fixed_seed_offset=None,
                        rng_name="", training=True, name=None):
    """Varlen (packed) attention (reference
    ``nn/functional/flash_attention.py:652`` flash_attn_unpadded, the
    ``flash_attn_varlen_fwd`` kernel's API).

    query/key/value: ``[total_seq, H, D]`` — multiple sequences packed along
    axis 0; ``cu_seqlens_*``: ``[B+1]`` cumulative boundaries.  Each sequence
    attends only within itself (optionally causally).  XLA fallback path: one
    masked attention over the packed length with a segment mask — a Pallas
    varlen kernel would additionally SKIP cross-segment blocks.
    """
    cu_q = jnp.asarray(cu_seqlens_q._data if isinstance(cu_seqlens_q, Tensor)
                       else cu_seqlens_q, jnp.int32)
    cu_k = jnp.asarray(cu_seqlens_k._data if isinstance(cu_seqlens_k, Tensor)
                       else cu_seqlens_k, jnp.int32)

    def f(q, k, v):
        from ..kernels.flash_attention import _attention_reference

        Tq, Tk = q.shape[0], k.shape[0]
        seg_q = jnp.searchsorted(cu_q[1:], jnp.arange(Tq), side="right")
        seg_k = jnp.searchsorted(cu_k[1:], jnp.arange(Tk), side="right")
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            # BOTTOM-RIGHT alignment (flash-attn varlen convention, matching
            # _attention_reference's tril k=Sk-Sq): when a segment's k side is
            # longer than its q side (decode), the queries sit at the END
            rel_q = jnp.arange(Tq) - cu_q[seg_q]
            rel_k = jnp.arange(Tk) - cu_k[seg_k]
            len_q = (cu_q[seg_q + 1] - cu_q[seg_q])
            len_k_of_q = (cu_k[seg_q + 1] - cu_k[seg_q])
            row_shift = rel_q + (len_k_of_q - len_q)
            mask = mask & (row_shift[:, None] >= rel_k[None, :])
        out = _attention_reference(q[None], k[None], v[None], False,
                                   mask[None, None], scale)
        return out[0]

    out = apply_op("flash_attn_unpadded", f, (_t(query), _t(key), _t(value)), {})
    return out, None


# ---------------------------------------------------------------------------
# long-tail functionals (geometry/pooling/losses/packed attention/inplace)
# ---------------------------------------------------------------------------
from ._functional_extras import *  # noqa: E402,F401,F403
from . import _functional_extras as _fx  # noqa: E402

__all__ = __all__ + _fx.__all__
