"""Functional NN ops (reference surface: python/paddle/nn/functional/).

All ops are jnp/lax compositions routed through core.tensor.apply so both
the eager tape and jit tracing work. Convs/matmuls hit the MXU via
lax.conv_general_dilated / jnp.matmul; XLA fuses the elementwise epilogues.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtypes
from ..core.flags import matmul_precision
from ..core.random import in_trace_rng, make_rng
from ..core.tensor import (Tensor, annotate_test_variant, apply,
                           record_mutation)
from . import layout as _layout

__all__ = [
    # activations
    "relu", "relu6", "leaky_relu", "prelu", "elu", "selu", "celu", "gelu",
    "sigmoid", "hardsigmoid", "hardswish", "hardtanh", "hardshrink",
    "softshrink", "tanhshrink", "swish", "silu", "mish", "softplus",
    "softsign", "tanh", "log_sigmoid", "maxout", "glu", "rrelu",
    # softmax family
    "softmax", "log_softmax", "gumbel_softmax",
    # linear / conv
    "linear", "conv1d", "conv2d", "conv3d", "conv1d_transpose",
    "conv2d_transpose", "conv3d_transpose",
    # pooling
    "avg_pool1d", "avg_pool2d", "avg_pool3d", "max_pool1d", "max_pool2d",
    "max_pool3d", "adaptive_avg_pool1d", "adaptive_avg_pool2d",
    "adaptive_avg_pool3d", "adaptive_max_pool1d", "adaptive_max_pool2d",
    # norm
    "batch_norm", "fused_conv_bn", "layer_norm", "instance_norm",
    "group_norm", "local_response_norm", "normalize",
    # dropout
    "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    # embedding / one-hot
    "embedding", "one_hot",
    # losses
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "kl_div", "smooth_l1_loss", "margin_ranking_loss", "ctc_loss",
    "hinge_embedding_loss", "cosine_embedding_loss", "sigmoid_focal_loss",
    "square_error_cost", "log_loss", "npair_loss", "triplet_margin_loss",
    # shape ops
    "pad", "interpolate", "upsample", "pixel_shuffle", "pixel_unshuffle",
    "unfold", "fold", "affine_grid", "grid_sample",
    # misc
    "cosine_similarity", "label_smooth", "sequence_mask", "temporal_shift",
    "class_center_sample", "scaled_dot_product_attention", "sparse_attention",
    "adaptive_max_pool3d", "max_pool2d_with_index", "max_unpool2d",
    "pairwise_distance", "hsigmoid_loss",
]


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(x)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def _unary(fn, name):
    def op(x, name=None):
        return apply(fn, _t(x), name=name or op.__name__)
    op.__name__ = name
    return op


relu = _unary(jax.nn.relu, "relu")
relu6 = _unary(jax.nn.relu6, "relu6")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
tanh = _unary(jnp.tanh, "tanh")
softsign = _unary(jax.nn.soft_sign, "softsign")
silu = _unary(jax.nn.silu, "silu")
log_sigmoid = _unary(jax.nn.log_sigmoid, "log_sigmoid")
mish = _unary(lambda x: x * jnp.tanh(jax.nn.softplus(x)), "mish")
tanhshrink = _unary(lambda x: x - jnp.tanh(x), "tanhshrink")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(lambda a: jax.nn.leaky_relu(a, negative_slope), _t(x), name="leaky_relu")


def prelu(x, weight, data_format="NCHW", name=None):
    def _prelu(a, w):
        if w.size == 1:
            return jnp.where(a > 0, a, w.reshape(()) * a)
        shape = [1] * a.ndim
        ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
        shape[ch_axis] = w.size
        return jnp.where(a > 0, a, w.reshape(shape) * a)
    return apply(_prelu, _t(x), _t(weight), name="prelu")


def elu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.elu(a, alpha), _t(x), name="elu")


def celu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.celu(a, alpha), _t(x), name="celu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply(lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)),
                 _t(x), name="selu")


def gelu(x, approximate=False, name=None):
    return apply(lambda a: jax.nn.gelu(a, approximate=approximate), _t(x), name="gelu")


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply(lambda a: jnp.clip(slope * a + offset, 0.0, 1.0), _t(x), name="hardsigmoid")


def hardswish(x, name=None):
    return apply(lambda a: a * jnp.clip(a + 3.0, 0.0, 6.0) / 6.0, _t(x), name="hardswish")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(lambda a: jnp.clip(a, min, max), _t(x), name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    return apply(lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), _t(x), name="hardshrink")


def softshrink(x, threshold=0.5, name=None):
    return apply(lambda a: jnp.where(a > threshold, a - threshold,
                                     jnp.where(a < -threshold, a + threshold, 0.0)),
                 _t(x), name="softshrink")


def swish(x, name=None):
    return silu(x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(lambda a: jnp.where(beta * a > threshold, a,
                                     jnp.log1p(jnp.exp(beta * a)) / beta),
                 _t(x), name="softplus")


def maxout(x, groups, axis=1, name=None):
    def _maxout(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        new_shape = a.shape[:ax] + (groups, c // groups) + a.shape[ax + 1:]
        return jnp.max(a.reshape(new_shape), axis=ax + 1)
    return apply(_maxout, _t(x), name="maxout")


def glu(x, axis=-1, name=None):
    def _glu(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)
    return apply(_glu, _t(x), name="glu")


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=False, name=None):
    if training:
        key = make_rng()
        def _rr(a):
            slope = jax.random.uniform(key, a.shape, a.dtype, lower, upper)
            return jnp.where(a >= 0, a, slope * a)
        return apply(_rr, _t(x), name="rrelu")
    mid = (lower + upper) / 2.0
    return leaky_relu(x, mid)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

def softmax(x, axis=-1, dtype=None, name=None):
    d = dtypes.convert_dtype(dtype)
    def _sm(a):
        if d is not None:
            a = a.astype(d)
        return jax.nn.softmax(a, axis=axis)
    return apply(_sm, _t(x), name="softmax",
                 _cache_token=("softmax", axis, str(d)))


def log_softmax(x, axis=-1, dtype=None, name=None):
    d = dtypes.convert_dtype(dtype)
    def _lsm(a):
        if d is not None:
            a = a.astype(d)
        return jax.nn.log_softmax(a, axis=axis)
    return apply(_lsm, _t(x), name="log_softmax",
                 _cache_token=("log_softmax", axis, str(d)))


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    key = make_rng()
    def _gs(a):
        g = jax.random.gumbel(key, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            y_hard = jax.nn.one_hot(jnp.argmax(y, axis=axis), a.shape[axis],
                                    axis=axis, dtype=a.dtype)
            y = y_hard - jax.lax.stop_gradient(y) + y
        return y
    return apply(_gs, _t(x), name="gumbel_softmax")


# ---------------------------------------------------------------------------
# Linear / conv — the MXU path
# ---------------------------------------------------------------------------

def _amp_int8_active(weight_t) -> bool:
    """FLAGS_amp_int8_matmul routing gate for :func:`linear`: only under
    an ACTIVE amp.auto_cast region, with the Pallas int8 kernel enabled
    and a 2-D weight the kernel can tile. Resolved at dispatch time
    (before any trace) and folded into the op-cache token, so a cached
    f32 linear can never serve an int8 call or vice versa."""
    from ..core.flags import get_flag
    if not get_flag("amp_int8_matmul"):
        return False
    from ..amp.auto_cast import amp_state
    st = amp_state()
    if st is None or not st.enabled:
        return False
    from ..ops import pallas as pallas_ops
    if not pallas_ops.kernel_enabled("int8_matmul"):
        return False
    if weight_t.ndim != 2:
        return False
    from ..ops.pallas.quant_matmul import matmul_shapes_supported
    if not matmul_shapes_supported(int(weight_t.shape[0]),
                                   int(weight_t.shape[1])):
        pallas_ops.note_fallback("int8_matmul", "shape")
        return False
    return True


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b. Weight layout [in, out] (reference: nn/functional/common.py linear).

    Under ``FLAGS_amp_int8_matmul`` (+ an active autocast region) the
    matmul runs through the Pallas int8 kernel with dynamically
    quantized operands and a straight-through dense backward
    (ops.pallas.quant_matmul.int8_amp_linear) — an experimental
    throughput knob, off by default."""
    prec = matmul_precision()
    w_t = _t(weight)
    if _amp_int8_active(w_t):
        from ..ops.pallas.quant_matmul import int8_amp_linear
        if bias is None:
            return apply(lambda a, w: int8_amp_linear(a, w),
                         _t(x), w_t, name="linear",
                         _cache_token=("linear_int8",))
        return apply(lambda a, w, b: int8_amp_linear(a, w, b),
                     _t(x), w_t, _t(bias), name="linear",
                     _cache_token=("linear_int8",))
    if bias is None:
        return apply(lambda a, w: jnp.matmul(a, w, precision=prec),
                     _t(x), w_t, name="linear",
                     _cache_token=("linear", str(prec)))
    return apply(lambda a, w, b: jnp.matmul(a, w, precision=prec) + b,
                 _t(x), w_t, _t(bias), name="linear",
                 _cache_token=("linear", str(prec)))


def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(i) for i in v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _conv_accum_f32(a, w, stride, pad, lhs_dilation, rhs_dilation, dn,
                    groups):
    """Low-precision conv with EXPLICIT f32 accumulation: bf16/f16 operands
    stream through the MXU, the accumulator is pinned to f32 via
    ``preferred_element_type``, and the result is rounded back to the
    activation dtype — the production AMP conv contract, stated in the HLO
    instead of left to backend defaults.

    The custom VJP exists because ``preferred_element_type`` breaks jax's
    conv transpose rules under autodiff (the rhs rule feeds the f32
    cotangent into a conv against bf16 primals and lax rejects the mixed
    dtypes). The backward therefore differentiates the PLAIN low-precision
    conv — its cotangents are already in the activation dtype, and the two
    backward convs get the same implicit f32 accumulation from XLA:TPU.
    """
    out = jax.lax.conv_general_dilated(
        a, w, window_strides=stride, padding=pad,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.float32)
    return out.astype(a.dtype)


def _conv_accum_f32_fwd(a, w, stride, pad, lhs_dilation, rhs_dilation, dn,
                        groups):
    out = _conv_accum_f32(a, w, stride, pad, lhs_dilation, rhs_dilation,
                          dn, groups)
    return out, (a, w)


def _conv_accum_f32_bwd(stride, pad, lhs_dilation, rhs_dilation, dn, groups,
                        res, g):
    a, w = res

    def plain(a_, w_):
        return jax.lax.conv_general_dilated(
            a_, w_, window_strides=stride, padding=pad,
            lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
            dimension_numbers=dn, feature_group_count=groups)

    _, vjp = jax.vjp(plain, a, w)
    return vjp(g.astype(a.dtype))


_conv_accum_f32.defvjp(_conv_accum_f32_fwd, _conv_accum_f32_bwd)


def _run_conv(a, w, stride, pad, lhs_dilation, rhs_dilation, dn, groups):
    """Dispatch one conv: explicit-f32-accumulation path for the bf16/f16
    activation stream (AMP), plain conv for full precision."""
    if a.dtype in (jnp.bfloat16, jnp.float16):
        return _conv_accum_f32(a, w.astype(a.dtype), stride, pad,
                               lhs_dilation, rhs_dilation, dn, groups)
    return jax.lax.conv_general_dilated(
        a, w, window_strides=stride, padding=pad,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=dn, feature_group_count=groups)


def _conv_specs(n, channel_last):
    if channel_last:
        return {1: ("NWC", "OIW", "NWC"), 2: ("NHWC", "OIHW", "NHWC"),
                3: ("NDHWC", "OIDHW", "NDHWC")}[n]
    return {1: ("NCW", "OIW", "NCW"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}[n]


def _conv_nd(x, weight, bias, stride, padding, dilation, groups, data_format, n):
    """Shared conv implementation over lax.conv_general_dilated."""
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    x = _t(x)
    # channels-last planner (nn.layout): inside an active scope a 2-D NCHW
    # conv runs NHWC-native — the first conv in the chain pays the ONE
    # entry transpose, everything downstream consumes the tag
    internal_cl = (n == 2 and not channel_last and _layout.is_active())
    if internal_cl:
        if x._layout != "NHWC":
            x = _layout.to_channels_last(x)
        channel_last = True
    spec = _conv_specs(n, channel_last)

    if isinstance(padding, str):
        pad = padding.upper()  # 'SAME' | 'VALID'
    else:
        p = _norm_tuple(padding, n) if not (isinstance(padding, (list, tuple)) and
                                            isinstance(padding[0], (list, tuple))) else padding
        if isinstance(p[0], (list, tuple)):
            pad = tuple(tuple(pp) for pp in p)
        else:
            pad = tuple((pi, pi) for pi in p)

    def _conv(a, w, *maybe_bias):
        out = _run_conv(a, w, stride, pad, None, dilation, spec, groups)
        if maybe_bias:
            b = maybe_bias[0]
            shape = [1] * out.ndim
            shape[-1 if channel_last else 1] = b.size
            out = out + b.reshape(shape).astype(out.dtype)
        return out

    args = (x, _t(weight)) + ((_t(bias),) if bias is not None else ())
    out = apply(_conv, *args, name=f"conv{n}d",
                _cache_token=("conv", n, stride, pad, dilation, groups,
                              spec))
    if internal_cl:
        _layout.tag(out)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    fmt = "NLC" if data_format == "NLC" else "NCL"
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    "NLC" if fmt == "NLC" else "NCW", 1)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, data_format, 2)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, data_format, 3)


def _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                       dilation, groups, data_format, n, output_size=None):
    """Transposed conv as a fractionally-strided conv: dilate the input by
    `stride` (lhs_dilation), flip the kernel, swap its in/out channels, and
    run a regular conv with padding (k_eff-1-p). Matches the reference's
    output-size formula (H-1)*s - 2p + d*(k-1) + 1 + output_padding."""
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pads_in = _norm_tuple(padding, n) if not isinstance(padding, str) else None
    opad = _norm_tuple(output_padding, n) if output_padding else (0,) * n
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    spec = _conv_specs(n, channel_last)

    def _convt(a, w, *maybe_bias):
        # w layout: [in_c, out_c/groups, *k] (reference conv_transpose layout)
        in_c = w.shape[0]
        outg = w.shape[1]
        k_spatial = w.shape[2:]
        g = groups
        w_ = w.reshape((g, in_c // g, outg) + k_spatial)
        w_ = jnp.swapaxes(w_, 1, 2)  # [g, out/g, in/g, *k]
        w_ = w_.reshape((g * outg, in_c // g) + k_spatial)
        w_ = jnp.flip(w_, axis=tuple(range(2, 2 + n)))

        if pads_in is None:  # 'SAME'/'VALID' string: treat as zero padding
            p_eff = (0,) * n
        else:
            p_eff = pads_in
        conv_pads = []
        for i in range(n):
            k_eff = (k_spatial[i] - 1) * dilation[i] + 1
            lo = k_eff - 1 - p_eff[i]
            hi = k_eff - 1 - p_eff[i] + opad[i]
            conv_pads.append((lo, hi))

        # same explicit-f32-accumulation contract as the forward conv —
        # and, via _conv_accum_f32's custom VJP, a backward that actually
        # differentiates under the bf16 activation stream (the raw
        # preferred_element_type form broke the conv transpose rule)
        out = _run_conv(a, w_, (1,) * n, tuple(conv_pads), stride, dilation,
                        spec, g)
        if maybe_bias:
            b = maybe_bias[0]
            shape = [1] * out.ndim
            shape[-1 if channel_last else 1] = b.size
            out = out + b.reshape(shape).astype(out.dtype)
        return out

    args = (_t(x), _t(weight)) + ((_t(bias),) if bias is not None else ())
    return apply(_convt, *args, name=f"conv{n}d_transpose",
                 _cache_token=("convt", n, stride, dilation, pads_in, opad,
                               groups, spec))


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None, data_format="NCL", name=None):
    return _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                              dilation, groups, "NLC" if data_format == "NLC" else "NCW", 1)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None, data_format="NCHW", name=None):
    return _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                              dilation, groups, data_format, 2, output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None, data_format="NCDHW", name=None):
    return _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                              dilation, groups, data_format, 3, output_size)


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

def _pool_nd(x, kernel_size, stride, padding, n, reducer, init, data_format,
             ceil_mode=False, count_include_pad=True, divisor_override=None):
    ks = _norm_tuple(kernel_size, n)
    st = _norm_tuple(stride if stride is not None else kernel_size, n)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    x = _t(x)
    # consume the channels-last planner tag: the pool runs NHWC-native
    # with no transpose on either side
    internal_cl = (n == 2 and not channel_last and _layout.is_active()
                   and x._layout == "NHWC")
    if internal_cl:
        channel_last = True
    if isinstance(padding, str):
        pad_mode = padding.upper()
        pads = None
    else:
        pad_mode = None
        pads = _norm_tuple(padding, n)

    def _pool(a):
        if channel_last:
            window = (1,) + ks + (1,)
            strides = (1,) + st + (1,)
            spatial = range(1, 1 + n)
        else:
            window = (1, 1) + ks
            strides = (1, 1) + st
            spatial = range(2, 2 + n)
        if pads is None:
            padding_cfg = pad_mode
        else:
            padding_cfg = [(0, 0)] * a.ndim
            for i, d in enumerate(spatial):
                padding_cfg[d] = (pads[i], pads[i])
        if reducer == "max":
            neg = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
            return jax.lax.reduce_window(a, neg, jax.lax.max, window, strides, padding_cfg)
        # avg
        summed = jax.lax.reduce_window(a.astype(jnp.float32), 0.0, jax.lax.add,
                                       window, strides, padding_cfg)
        if divisor_override:
            return (summed / divisor_override).astype(a.dtype)
        if count_include_pad or (pads is None or not any(pads)):
            denom = float(np.prod(ks))
            return (summed / denom).astype(a.dtype)
        ones = jnp.ones_like(a, jnp.float32)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, padding_cfg)
        return (summed / counts).astype(a.dtype)

    out = apply(_pool, x, name=f"{reducer}_pool{n}d",
                _cache_token=("pool", n, ks, st, pad_mode, pads, reducer,
                              channel_last, count_include_pad,
                              divisor_override))
    if internal_cl:
        _layout.tag(out)
    return out


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, name=None):
    return _pool_nd(x, kernel_size, stride, padding, 1, "max", None, "NCW", ceil_mode)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    if return_mask:
        if data_format != "NCHW" or ceil_mode:
            raise ValueError("return_mask supports NCHW, ceil_mode=False")
        return max_pool2d_with_index(x, kernel_size, stride, padding)
    return _pool_nd(x, kernel_size, stride, padding, 2, "max", None, data_format, ceil_mode)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 3, "max", None, data_format, ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    return _pool_nd(x, kernel_size, stride, padding, 1, "avg", None, "NCW",
                    ceil_mode, count_include_pad=not exclusive)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 2, "avg", None, data_format,
                    ceil_mode, count_include_pad=not exclusive,
                    divisor_override=divisor_override)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 3, "avg", None, data_format,
                    ceil_mode, count_include_pad=not exclusive,
                    divisor_override=divisor_override)


def _adaptive_pool(x, output_size, n, mode, data_format):
    out_sizes = _norm_tuple(output_size, n)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    x = _t(x)
    internal_cl = (n == 2 and not channel_last and _layout.is_active()
                   and x._layout == "NHWC")
    if internal_cl:
        channel_last = True

    def _ap(a):
        spatial0 = 1 if channel_last else 2
        out = a
        for i, osz in enumerate(out_sizes):
            ax = spatial0 + i
            isz = out.shape[ax]
            if osz is None or osz == isz:
                continue
            if isz % osz == 0:
                k = isz // osz
                new_shape = out.shape[:ax] + (osz, k) + out.shape[ax + 1:]
                r = out.reshape(new_shape)
                out = jnp.max(r, axis=ax + 1) if mode == "max" else jnp.mean(r, axis=ax + 1)
            else:
                # general adaptive: gather per output bin
                starts = (np.arange(osz) * isz) // osz
                ends = ((np.arange(osz) + 1) * isz + osz - 1) // osz
                slices = []
                for s, e in zip(starts, ends):
                    seg = jax.lax.slice_in_dim(out, int(s), int(e), axis=ax)
                    red = jnp.max(seg, axis=ax, keepdims=True) if mode == "max" \
                        else jnp.mean(seg, axis=ax, keepdims=True)
                    slices.append(red)
                out = jnp.concatenate(slices, axis=ax)
        return out

    out = apply(_ap, x, name=f"adaptive_{mode}_pool{n}d",
                _cache_token=("apool", n, out_sizes, mode, channel_last))
    if internal_cl:
        _layout.tag(out)
    return out


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_pool(x, output_size, 1, "avg", "NCW")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool(x, output_size, 2, "avg", data_format)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_pool(x, output_size, 3, "avg", data_format)


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool1d: return_mask not supported; use "
            "max_pool2d_with_index for pooled indices")
    return _adaptive_pool(x, output_size, 1, "max", "NCW")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool2d: return_mask not supported; use "
            "max_pool2d_with_index for pooled indices")
    return _adaptive_pool(x, output_size, 2, "max", "NCHW")




def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool3d: return_mask not supported; use "
            "max_pool2d_with_index for pooled indices")
    return _adaptive_pool(x, output_size, 3, "max", "NCDHW")


def max_pool2d_with_index(x, kernel_size, stride=None, padding=0, name=None):
    """Max pool returning (out, flat per-channel indices) — the mask the
    reference's max_pool2d(return_mask=True) produces (max_pool_with_index
    op) and MaxUnPool2D consumes."""
    ks = _norm_tuple(kernel_size, 2)
    st = _norm_tuple(stride if stride is not None else kernel_size, 2)
    pads = _norm_tuple(padding, 2)

    def _pool(a):
        N, C, H, W = a.shape
        # pad with dtype-min ourselves: patches' implicit padding is ZERO
        # (would beat negative maxima / corrupt indices), and -inf is out
        # too — patch extraction is a conv, and -inf * 0 = NaN
        if pads[0] or pads[1]:
            neg = jnp.finfo(a.dtype).min if \
                jnp.issubdtype(a.dtype, jnp.floating) else \
                jnp.iinfo(a.dtype).min
            a = jnp.pad(a, ((0, 0), (0, 0), (pads[0], pads[0]),
                            (pads[1], pads[1])), constant_values=neg)
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=ks, window_strides=st, padding="VALID")
        oH, oW = patches.shape[2], patches.shape[3]
        # [N, C*kh*kw, oH, oW] -> [N, C, kh*kw, oH, oW]
        patches = patches.reshape(N, C, ks[0] * ks[1], oH, oW)
        local = jnp.argmax(patches, axis=2)          # [N, C, oH, oW]
        out = jnp.max(patches, axis=2)
        oh = jnp.arange(oH)[:, None]
        ow = jnp.arange(oW)[None, :]
        row = oh * st[0] - pads[0] + local // ks[1]
        col = ow * st[1] - pads[1] + local % ks[1]
        idx = (row * W + col).astype(jnp.int32)
        return out, idx

    return apply(_pool, _t(x), name="max_pool2d_with_index")


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 output_size=None, data_format="NCHW", name=None):
    """Inverse of max_pool2d: scatter values at their pooled-from positions
    (reference: nn/functional/pooling.py max_unpool2d / unpool_op)."""
    if data_format != "NCHW":
        raise ValueError("max_unpool2d supports NCHW only")
    ks = _norm_tuple(kernel_size, 2)
    st = _norm_tuple(stride if stride is not None else kernel_size, 2)
    pads = _norm_tuple(padding, 2)

    def _unpool(a, idx):
        N, C, oH, oW = a.shape
        if output_size is not None:
            H, W = output_size[-2], output_size[-1]
        else:
            H = (oH - 1) * st[0] - 2 * pads[0] + ks[0]
            W = (oW - 1) * st[1] - 2 * pads[1] + ks[1]
        flat_vals = a.reshape(N, C, oH * oW)
        flat_idx = idx.reshape(N, C, oH * oW).astype(jnp.int32)
        zeros = jnp.zeros((N, C, H * W), a.dtype)
        out = jax.vmap(jax.vmap(
            lambda z, i, v: z.at[i].set(v)))(zeros, flat_idx, flat_vals)
        return out.reshape(N, C, H, W)

    return apply(_unpool, _t(x), _t(indices), name="max_unpool2d")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    """||x - y + eps||_p along the last dim (reference:
    nn/layer/distance.py PairwiseDistance)."""

    def _pd(a, b):
        d = a - b + epsilon
        if p == float("inf"):
            r = jnp.max(jnp.abs(d), axis=-1, keepdims=keepdim)
        elif p == 0:
            r = jnp.sum((d != 0).astype(a.dtype), axis=-1, keepdims=keepdim)
        else:
            r = jnp.sum(jnp.abs(d) ** p, axis=-1, keepdims=keepdim) \
                ** (1.0 / p)
        return r

    return apply(_pd, _t(x), _t(y), name="pairwise_distance")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference: nn/functional/loss.py:312,
    matrix_bit_code_functor's SimpleCode default tree).

    Default complete-binary-tree coding over num_classes leaves: for class
    c let v = c + num_classes; at step k the internal node is
    (v >> (k+1)) - 1 and the sigmoid target bit is (v >> k) & 1; steps run
    while v >> (k+1) >= 1. weight: [num_classes-1, D], bias:
    [num_classes-1]. Custom trees pass path_table/path_code
    [N, L] (padded with -1).
    """
    import math as _math
    L = max(1, int(_math.ceil(_math.log2(max(2, num_classes)))) + 1)

    def _hs(x, lab, w, *rest):
        b = rest[0] if rest else None
        lab = lab.astype(jnp.int32).reshape(-1)
        if path_table is not None:
            pt_raw = path_table._data if isinstance(path_table, Tensor) \
                else path_table
            pc_raw = path_code._data if isinstance(path_code, Tensor) \
                else path_code
            pt = jnp.asarray(pt_raw, jnp.int32)
            pc = jnp.asarray(pc_raw, jnp.float32)
            valid = (pt >= 0).astype(jnp.float32)
            idx = jnp.maximum(pt, 0)
            bits = pc
        else:
            v = lab + num_classes
            ks = jnp.arange(L)
            anc = v[:, None] >> (ks[None, :] + 1)          # [N, L]
            valid = (anc >= 1).astype(jnp.float32)
            idx = jnp.maximum(anc - 1, 0)
            bits = ((v[:, None] >> ks[None, :]) & 1).astype(jnp.float32)
        wk = w[idx]                                        # [N, L, D]
        pre = jnp.einsum("nd,nld->nl", x.astype(jnp.float32),
                         wk.astype(jnp.float32))
        if b is not None:
            pre = pre + b[idx]
        # bce-with-logits against the code bit; bit=1 -> sigmoid target 1
        per = jax.nn.softplus(pre) - bits * pre
        loss = jnp.sum(per * valid, axis=-1, keepdims=True)
        return loss

    args = [_t(input), _t(label), _t(weight)] + ([_t(bias)] if bias is not None else [])
    return apply(_hs, *args, name="hsigmoid_loss")


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def _bn_fold_scale_shift(mean, var, gamma, beta, epsilon):
    """Fold BN statistics (+optional affine) into one (scale, shift) pair,
    computed in f32 — shared by batch_norm and fused_conv_bn so the folded
    math can never diverge between the fused and unfused paths."""
    inv = jax.lax.rsqrt(var + epsilon)
    if gamma is not None:
        scale = gamma.astype(jnp.float32) * inv
        shift = beta.astype(jnp.float32) - mean * scale
    else:
        scale = inv
        shift = -mean * inv
    return scale, shift


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    """BatchNorm with running-stat update (reference: nn/functional/norm.py batch_norm).

    Running stats are updated in-place on the buffer tensors in training mode
    (eager). Inside jit traces training stats flow through pure state (the
    jitted trainer hoists buffers into the state pytree).
    """
    x = _t(x)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    # channels-last planner tag: normalize over the NHWC channel axis
    # without leaving the internal layout
    internal_cl = (not channel_last and _layout.is_active()
                   and x._layout == "NHWC")
    if internal_cl:
        channel_last = True
    ch_axis = x.ndim - 1 if channel_last else 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_batch_stats = training and not use_global_stats

    # dtype-preserving normalization: statistics and the folded
    # scale/shift compute in f32, the per-element application runs in the
    # INPUT dtype (one multiply + one add, fusable into the producing
    # conv's epilogue). Under the AMP O1 bf16 activation stream this
    # keeps conv->bn->relu chains entirely bf16 — the old blacklisted
    # form round-tripped every conv output through f32, which the
    # ResNet-50 trace showed as ~40 ms/step of pure convert/copy traffic.
    def _bn_apply(a, mean, var, wb):
        scale, shift = _bn_fold_scale_shift(
            mean, var, wb[0] if wb else None, wb[1] if wb else None,
            epsilon)
        shape = [1] * a.ndim
        shape[ch_axis] = a.shape[ch_axis]
        return a * scale.reshape(shape).astype(a.dtype) \
            + shift.reshape(shape).astype(a.dtype)

    def _bn_eval(a, rm, rv, *wb):
        return _bn_apply(a, rm.astype(jnp.float32),
                         rv.astype(jnp.float32), wb)

    if use_batch_stats:
        def _bn_train(a, rm, rv, *wb):
            a32 = a.astype(jnp.float32)
            mean = jnp.mean(a32, axis=reduce_axes)
            var = jnp.var(a32, axis=reduce_axes)
            out = _bn_apply(a, mean, var, wb)
            new_rm = momentum * rm + (1 - momentum) * mean.astype(rm.dtype)
            new_rv = momentum * rv + (1 - momentum) * var.astype(rv.dtype)
            return out, new_rm, new_rv

        args = [x, _t(running_mean), _t(running_var)]
        if weight is not None:
            args += [_t(weight), _t(bias)]
        out, new_rm, new_rv = apply(
            _bn_train, *args, name="batch_norm",
            _cache_token=("bn_train", ch_axis, reduce_axes, momentum,
                          epsilon))
        # in-place update of running stats (buffers); recorded as replayable
        # write events when a static Program is being built, with the eval
        # normalization as the clone(for_test=True) twin
        annotate_test_variant(_bn_eval)
        record_mutation(running_mean, new_rm)
        record_mutation(running_var, new_rv)
        if internal_cl:
            _layout.tag(out)
        return out

    args = [x, _t(running_mean), _t(running_var)]
    if weight is not None:
        args += [_t(weight), _t(bias)]
    out = apply(_bn_eval, *args, name="batch_norm",
                _cache_token=("bn_eval", ch_axis, epsilon))
    if internal_cl:
        _layout.tag(out)
    return out


def fused_conv_bn(x, weight, bias, running_mean, running_var, bn_weight,
                  bn_bias, stride=1, padding=0, dilation=1, groups=1,
                  data_format="NCHW", training=False, momentum=0.9,
                  epsilon=1e-5, activation=None, use_global_stats=None,
                  name=None):
    """Conv2D → BatchNorm → activation as ONE op: the vision fast path's
    epilogue fusion.

    Training: the conv runs on the bf16 activation stream (AMP policy
    resolved here, since the generic dispatch cast must not touch the f32
    EMA buffers), batch statistics accumulate in f32, and the folded
    scale/shift + activation land in the conv's XLA epilogue — one kernel
    region and ONE eager tape node instead of three. Running-stat EMA
    buffers stay f32 under every AMP level (the op is on the AMP
    keep-dtype list, mirroring batch_norm).

    Inference deployments fold the BN entirely into the conv weights
    instead — see paddle_tpu.inference.passes.fold_conv_bn.

    ``activation``: None | "relu" | "relu6".
    """
    if activation not in (None, "relu", "relu6"):
        raise ValueError(f"fused_conv_bn supports relu/relu6, got "
                         f"{activation!r}")
    n = 2
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    channel_last = data_format == "NHWC"
    x = _t(x)
    internal_cl = (not channel_last and _layout.is_active())
    if internal_cl:
        if x._layout != "NHWC":
            x = _layout.to_channels_last(x)
        channel_last = True
    spec = _conv_specs(n, channel_last)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _norm_tuple(padding, n)
        pad = tuple((pi, pi) for pi in p)
    ch_axis = x.ndim - 1 if channel_last else 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)

    # the conv's AMP cast target, resolved HERE: the op itself is
    # keep-dtype (a blanket input cast would round the f32 EMA buffers
    # through bf16), so the bf16 stream is applied to the conv operands
    # only, inside the op
    from ..core import tensor as _core_tensor
    amp_dt = (_core_tensor._amp_target_hook("conv2d")
              if _core_tensor._amp_target_hook is not None else None)
    act_fn = {None: None, "relu": jax.nn.relu, "relu6": jax.nn.relu6}[activation]
    has_cb = bias is not None
    has_affine = bn_weight is not None

    def _conv_part(a, w, cb):
        if amp_dt is not None:
            td = jnp.dtype(amp_dt)
            a = a.astype(td) if a.dtype != td else a
            w = w.astype(td) if w.dtype != td else w
        out = _run_conv(a, w, stride, pad, None, dilation, spec, groups)
        if cb is not None:
            shape = [1] * out.ndim
            shape[ch_axis] = cb.size
            out = out + cb.reshape(shape).astype(out.dtype)
        return out

    def _bn_part(out, mean, var, gamma, beta):
        scale, shift = _bn_fold_scale_shift(mean, var, gamma, beta, epsilon)
        shape = [1] * out.ndim
        shape[ch_axis] = out.shape[ch_axis]
        y = out * scale.reshape(shape).astype(out.dtype) \
            + shift.reshape(shape).astype(out.dtype)
        return act_fn(y) if act_fn is not None else y

    def _split_rest(rest):
        i = 0
        cb = gamma = beta = None
        if has_cb:
            cb = rest[i]; i += 1
        if has_affine:
            gamma, beta = rest[i], rest[i + 1]
        return cb, gamma, beta

    def _fcb_eval(a, w, rm, rv, *rest):
        cb, gamma, beta = _split_rest(rest)
        out = _conv_part(a, w, cb)
        return _bn_part(out, rm.astype(jnp.float32),
                        rv.astype(jnp.float32), gamma, beta)

    args = [x, _t(weight), _t(running_mean), _t(running_var)]
    if has_cb:
        args.append(_t(bias))
    if has_affine:
        args += [_t(bn_weight), _t(bn_bias)]
    token_tail = (stride, pad, dilation, groups, spec, ch_axis, momentum,
                  epsilon, activation, amp_dt, has_cb, has_affine)

    if training and not use_global_stats:
        def _fcb_train(a, w, rm, rv, *rest):
            cb, gamma, beta = _split_rest(rest)
            out = _conv_part(a, w, cb)
            out32 = out.astype(jnp.float32)
            mean = jnp.mean(out32, axis=reduce_axes)
            var = jnp.var(out32, axis=reduce_axes)
            y = _bn_part(out, mean, var, gamma, beta)
            new_rm = momentum * rm + (1 - momentum) * mean.astype(rm.dtype)
            new_rv = momentum * rv + (1 - momentum) * var.astype(rv.dtype)
            return y, new_rm, new_rv

        out, new_rm, new_rv = apply(
            _fcb_train, *args, name="fused_conv_bn",
            _cache_token=("fcb_train",) + token_tail)
        annotate_test_variant(_fcb_eval)
        record_mutation(running_mean, new_rm)
        record_mutation(running_var, new_rv)
    else:
        out = apply(_fcb_eval, *args, name="fused_conv_bn",
                    _cache_token=("fcb_eval",) + token_tail)
    if internal_cl:
        _layout.tag(out)
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n = len(tuple(normalized_shape))

    def _ln(a, *wb):
        # full f32 internal compute, output in the input dtype: under a
        # bf16 activation stream (AMP O1) the HBM traffic stays half-width
        # while the statistics and the normalization keep f32 accuracy
        # (this is why layer_norm is NOT on the AMP cast lists — the op
        # manages its own precision)
        axes = tuple(range(a.ndim - n, a.ndim))
        af = a.astype(jnp.float32)
        mean = jnp.mean(af, axis=axes, keepdims=True)
        var = jnp.var(af, axis=axes, keepdims=True)
        out = (af - mean) * jax.lax.rsqrt(var + epsilon)
        if wb:
            w = wb[0]
            out = out * w.astype(jnp.float32)
            if len(wb) > 1:
                out = out + wb[1].astype(jnp.float32)
        return out.astype(a.dtype)

    args = [_t(x)]
    if weight is not None:
        args.append(_t(weight))
        if bias is not None:
            args.append(_t(bias))
    return apply(_ln, *args, name="layer_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    def _in(a, *wb):
        axes = tuple(range(2, a.ndim))
        mean = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a - mean.astype(a.dtype)) * jax.lax.rsqrt(var + eps).astype(a.dtype)
        if wb:
            w, b = wb
            shape = [1] * a.ndim
            shape[1] = a.shape[1]
            out = out * w.reshape(shape) + b.reshape(shape)
        return out
    args = [_t(x)]
    if weight is not None:
        args += [_t(weight), _t(bias)]
    return apply(_in, *args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    def _gn(a, *wb):
        N, C = a.shape[0], a.shape[1]
        g = num_groups
        rest = a.shape[2:]
        r = a.reshape((N, g, C // g) + rest).astype(jnp.float32)
        axes = tuple(range(2, r.ndim))
        mean = jnp.mean(r, axis=axes, keepdims=True)
        var = jnp.var(r, axis=axes, keepdims=True)
        out = ((r - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a.shape).astype(a.dtype)
        if wb:
            w, b = wb
            shape = [1, C] + [1] * (a.ndim - 2)
            out = out * w.reshape(shape) + b.reshape(shape)
        return out
    args = [_t(x)]
    if weight is not None:
        args += [_t(weight), _t(bias)]
    return apply(_gn, *args, name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def _lrn(a):
        sq = jnp.square(a)
        half = size // 2
        pads = [(0, 0)] * a.ndim
        pads[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        window = [1] * a.ndim
        window[1] = size
        strides = [1] * a.ndim
        s = jax.lax.reduce_window(padded, 0.0, jax.lax.add, tuple(window),
                                  tuple(strides), [(0, 0)] * a.ndim)
        return a / jnp.power(k + alpha * s / size, beta)
    return apply(_lrn, _t(x), name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def _nm(a):
        nrm = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(nrm, epsilon)
    return apply(_nm, _t(x), name="normalize")


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None, rng_name=None):
    if not training or p == 0.0:
        return _t(x)
    if p >= 1.0:
        x = _t(x)
        out = apply(lambda a: jnp.zeros_like(a), x, name="dropout")
        if mode == "upscale_in_train":
            annotate_test_variant(lambda a: a)
        else:           # downscale_in_infer at eval: x*(1-p) == 0 for p>=1
            annotate_test_variant(lambda a: jnp.zeros_like(a))
        return out
    key = make_rng(rng_name)

    def _do(a):
        from ..distributed.spmd import auto_axes
        if axis is None and mode == "upscale_in_train" \
                and a.size >= 65536 and jax.default_backend() == "tpu" \
                and not auto_axes():
            # single-pass Pallas kernel: in-kernel counter-based mask,
            # regenerated in the backward — one HBM read + one write
            # instead of XLA's bits/mask/product round-trips. Not under
            # a mesh: GSPMD cannot partition a Mosaic kernel, and it
            # partitions the XLA composition along any activation layout
            from ..ops.pallas.dropout import fused_dropout
            return fused_dropout(a, p, key)
        if axis is None:
            shape = a.shape
        else:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            shape = tuple(a.shape[i] if i in axes else 1 for i in range(a.ndim))
        # integer threshold on raw 16-bit random words instead of
        # bernoulli's uniform-float path: half the RNG bytes and no
        # int->float convert chain, at a keep-probability granularity of
        # 2^-16 (irrelevant next to bf16 activation noise)
        bits = jax.random.bits(key, shape, dtype=jnp.uint16)
        keep = bits >= jnp.uint16(min(round(p * 65536.0), 65535))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    out = apply(_do, _t(x), name="dropout")
    # clone(for_test=True) twin: identity (upscale_in_train) / (1-p) scale
    if mode == "upscale_in_train":
        annotate_test_variant(lambda a: a)
    else:
        annotate_test_variant(lambda a: (a * (1.0 - p)).astype(a.dtype))
    return out


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ch_axes = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p, axis=list(ch_axes), training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ch_axes = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p, axis=list(ch_axes), training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return _t(x)
    key = make_rng()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def _ad(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return (a_coef * jnp.where(keep, a, alpha_p) + b_coef).astype(a.dtype)

    out = apply(_ad, _t(x), name="alpha_dropout")
    annotate_test_variant(lambda a: a)   # eval: identity
    return out


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    def _emb(ids, w):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return apply(_emb, _t(x), _t(weight), name="embedding")


def one_hot(x, num_classes, name=None):
    return apply(lambda a: jax.nn.one_hot(a.astype(jnp.int32), num_classes),
                 _t(x), name="one_hot")


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
                  name=None):
    """Softmax cross entropy (reference: nn/functional/loss.py cross_entropy).

    Above ``FLAGS_chunked_ce_threshold`` vocab entries (last-axis softmax,
    no label smoothing) the loss streams over vocab chunks with an online
    f32 logsumexp instead of materializing full-vocab f32 log-probs — see
    nn/chunked_ce.py. Same semantics (ignore_index / soft_label / weights /
    reduction), custom-VJP backward."""
    w = _t(weight) if weight is not None else None
    inp_t = _t(input)
    n_classes = inp_t.shape[axis]
    from . import chunked_ce as _cce
    if (use_softmax and not label_smoothing
            and axis in (-1, inp_t.ndim - 1)
            and _cce.enabled_for(n_classes)):
        chunk = _cce.chunk_size_for(n_classes)

        def _ce_chunked(logits, lab, *maybe_w):
            if soft_label:
                loss = _cce.soft_nll(logits, lab.astype(jnp.float32),
                                     chunk=chunk)
                valid = jnp.ones_like(loss, jnp.float32)
            else:
                ids = lab.astype(jnp.int32)
                if ids.ndim == logits.ndim:
                    ids = jnp.squeeze(ids, -1)
                valid = (ids != ignore_index).astype(jnp.float32)
                safe_ids = jnp.where(ids == ignore_index, 0, ids)
                loss = _cce.hard_nll(logits, safe_ids, chunk=chunk) * valid
                if maybe_w:
                    sample_w = jnp.take(maybe_w[0], safe_ids, axis=0) * valid
                    loss = loss * sample_w
                    valid = sample_w
            if reduction == "mean":
                denom = jnp.maximum(jnp.sum(valid), 1e-12)
                return jnp.sum(loss) / denom
            return _reduce(loss, reduction)

        args = [inp_t, _t(label)] + ([w] if w is not None else [])
        # hard_nll resolves its Pallas-vs-XLA dispatch at trace time, so
        # the outcome must ride the cache token: a kill-switch flip
        # (FLAGS_pallas_ce / FLAGS_pallas_interpret) would otherwise keep
        # serving the stale cached trace for already-seen signatures
        from ..ops import pallas as pallas_ops
        ce_kernel = (not soft_label
                     and pallas_ops.kernel_enabled("chunked_ce",
                                                   note=False))
        return apply(_ce_chunked, *args, name="cross_entropy",
                     _cache_token=("ce_chunked", reduction, ignore_index,
                                   bool(soft_label), chunk, ce_kernel))

    def _ce(logits, lab, *maybe_w):
        if use_softmax:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits.astype(jnp.float32), 1e-30))
        if soft_label:
            target = lab.astype(jnp.float32)
            if label_smoothing:
                n = logits.shape[axis]
                target = target * (1 - label_smoothing) + label_smoothing / n
            loss = -jnp.sum(target * logp, axis=axis)
            valid = jnp.ones_like(loss, jnp.float32)
        else:
            ids = lab.astype(jnp.int32)
            if ids.ndim == logp.ndim:
                ids = jnp.squeeze(ids, axis)
            valid = (ids != ignore_index).astype(jnp.float32)
            safe_ids = jnp.where(ids == ignore_index, 0, ids)
            if label_smoothing:
                n = logits.shape[axis]
                nll = -jnp.take_along_axis(logp, safe_ids[..., None], axis=axis)[..., 0]
                smooth = -jnp.mean(logp, axis=axis)
                loss = (1 - label_smoothing) * nll + label_smoothing * smooth
            else:
                loss = -jnp.take_along_axis(logp, safe_ids[..., None], axis=axis)[..., 0]
            loss = loss * valid
            if maybe_w:
                sample_w = jnp.take(maybe_w[0], safe_ids, axis=0) * valid
                loss = loss * sample_w
                # weighted mean divides by the gathered weight sum
                # (reference: nn/functional/loss.py ret = out_sum / weight_sum)
                valid = sample_w
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid), 1e-12)
            return jnp.sum(loss) / denom
        return _reduce(loss, reduction)

    args = [_t(input), _t(label)] + ([w] if w is not None else [])
    return apply(_ce, *args, name="cross_entropy",
                 _cache_token=("ce", reduction, axis, ignore_index,
                               bool(soft_label), bool(use_softmax),
                               float(label_smoothing)))


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False,
                               axis=-1, name=None):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    # paddle keeps the label-dim
    loss = loss.unsqueeze(axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def _bce(p, y, *mw):
        p32 = jnp.clip(p.astype(jnp.float32), 1e-7, 1 - 1e-7)
        loss = -(y * jnp.log(p32) + (1 - y) * jnp.log1p(-p32))
        if mw:
            loss = loss * mw[0]
        return _reduce(loss, reduction)
    args = [_t(input), _t(label)] + ([_t(weight)] if weight is not None else [])
    return apply(_bce, *args, name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    def _bcel(z, y, *extra):
        z32 = z.astype(jnp.float32)
        y32 = y.astype(jnp.float32)
        i = 0
        w = pw = None
        if weight is not None:
            w = extra[i]; i += 1
        if pos_weight is not None:
            pw = extra[i]; i += 1
        log_sig = jax.nn.log_sigmoid(z32)
        log_one_minus = jax.nn.log_sigmoid(-z32)
        if pw is not None:
            loss = -(pw * y32 * log_sig + (1 - y32) * log_one_minus)
        else:
            loss = -(y32 * log_sig + (1 - y32) * log_one_minus)
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)
    args = [_t(logit), _t(label)]
    if weight is not None:
        args.append(_t(weight))
    if pos_weight is not None:
        args.append(_t(pos_weight))
    return apply(_bcel, *args, name="bce_with_logits")


def mse_loss(input, label, reduction="mean", name=None):
    return apply(lambda a, b: _reduce(jnp.square(a - b), reduction),
                 _t(input), _t(label), name="mse_loss")


def l1_loss(input, label, reduction="mean", name=None):
    return apply(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                 _t(input), _t(label), name="l1_loss")


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    def _nll(logp, y, *mw):
        ids = y.astype(jnp.int32)
        valid = (ids != ignore_index).astype(jnp.float32)
        safe = jnp.where(ids == ignore_index, 0, ids)
        loss = -jnp.take_along_axis(logp, safe[..., None], axis=1)[..., 0] if logp.ndim == 2 \
            else -jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
        if mw:
            wv = jnp.take(mw[0], safe, axis=0)
            loss = loss * wv
            valid = valid * wv
        loss = loss * (ids != ignore_index)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1e-12)
        return _reduce(loss, reduction)
    args = [_t(input), _t(label)] + ([_t(weight)] if weight is not None else [])
    return apply(_nll, *args, name="nll_loss")


def kl_div(input, label, reduction="mean", name=None):
    def _kl(logp, y):
        loss = y * (jnp.log(jnp.maximum(y, 1e-30)) - logp)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)
    return apply(_kl, _t(input), _t(label), name="kl_div")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def _sl1(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce(loss, reduction)
    return apply(_sl1, _t(input), _t(label), name="smooth_l1_loss")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    def _mrl(a, b, y):
        loss = jnp.maximum(0.0, -y * (a - b) + margin)
        return _reduce(loss, reduction)
    return apply(_mrl, _t(input), _t(other), _t(label), name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    def _hel(a, y):
        loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(loss, reduction)
    return apply(_hel, _t(input), _t(label), name="hinge_embedding_loss")


def cosine_embedding_loss(input1, input2, label, margin=0, reduction="mean", name=None):
    def _cel(a, b, y):
        cos = jnp.sum(a * b, -1) / (jnp.linalg.norm(a, axis=-1) *
                                    jnp.linalg.norm(b, axis=-1) + 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)
    return apply(_cel, _t(input1), _t(input2), _t(label), name="cosine_embedding_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def _sfl(z, y, *mn):
        p = jax.nn.sigmoid(z)
        ce = -(y * jax.nn.log_sigmoid(z) + (1 - y) * jax.nn.log_sigmoid(-z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if mn:
            loss = loss / mn[0]
        return _reduce(loss, reduction)
    args = [_t(logit), _t(label)] + ([_t(normalizer)] if normalizer is not None else [])
    return apply(_sfl, *args, name="sigmoid_focal_loss")


def square_error_cost(input, label):
    return apply(lambda a, b: jnp.square(a - b), _t(input), _t(label),
                 name="square_error_cost")


def log_loss(input, label, epsilon=1e-4, name=None):
    def _ll(p, y):
        return -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon)
    return apply(_ll, _t(input), _t(label), name="log_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def _np(a, p, y):
        sim = jnp.matmul(a, p.T, precision=matmul_precision())
        y2 = (y[:, None] == y[None, :]).astype(jnp.float32)
        y2 = y2 / jnp.sum(y2, axis=1, keepdims=True)
        logp = jax.nn.log_softmax(sim, axis=1)
        xent = -jnp.mean(jnp.sum(y2 * logp, axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, 1)) + jnp.mean(jnp.sum(p * p, 1))) * 0.25
        return xent + reg
    return apply(_np, _t(anchor), _t(positive), _t(labels), name="npair_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def _tml(a, pos, neg):
        d_ap = jnp.sum(jnp.abs(a - pos) ** p, -1) ** (1 / p)
        d_an = jnp.sum(jnp.abs(a - neg) ** p, -1) ** (1 / p)
        if swap:
            d_pn = jnp.sum(jnp.abs(pos - neg) ** p, -1) ** (1 / p)
            d_an = jnp.minimum(d_an, d_pn)
        loss = jnp.maximum(d_ap - d_an + margin, 0.0)
        return _reduce(loss, reduction)
    return apply(_tml, _t(input), _t(positive), _t(negative), name="triplet_margin_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss via optax (reference: operators warpctc)."""
    import optax
    def _ctc(lp, lab, il, ll):
        # optax expects [B, T, V] logits and paddings
        logits = jnp.transpose(lp, (1, 0, 2)) if lp.ndim == 3 else lp
        B, T, V = logits.shape
        t_idx = jnp.arange(T)[None, :]
        logit_pad = (t_idx >= il[:, None]).astype(jnp.float32)
        L = lab.shape[1]
        l_idx = jnp.arange(L)[None, :]
        label_pad = (l_idx >= ll[:, None]).astype(jnp.float32)
        loss = optax.ctc_loss(logits, logit_pad, lab.astype(jnp.int32), label_pad,
                              blank_id=blank)
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(ll.astype(jnp.float32), 1.0))
        return _reduce(loss, reduction)
    return apply(_ctc, _t(log_probs), _t(labels), _t(input_lengths),
                 _t(label_lengths), name="ctc_loss")


# ---------------------------------------------------------------------------
# Shape ops
# ---------------------------------------------------------------------------

def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    x = _t(x)
    if isinstance(pad, Tensor):
        pad = pad.tolist()
    pad = [int(p) for p in pad]

    def _pad(a):
        if len(pad) == 2 * a.ndim:
            pairs = [(pad[2 * i], pad[2 * i + 1]) for i in range(a.ndim)]
        else:
            # paddle convention: pad applies to last len(pad)//2 spatial dims,
            # ordered (left, right, top, bottom, front, back) starting at the
            # innermost spatial axis; NC* dims get zero.
            n = len(pad) // 2
            pairs = [(0, 0)] * a.ndim
            channel_last = data_format in ("NHWC", "NLC", "NDHWC")
            spatial_axes = list(range(1, a.ndim - 1)) if channel_last \
                else list(range(2, a.ndim))
            for i in range(n):
                ax = spatial_axes[len(spatial_axes) - 1 - i]
                pairs[ax] = (pad[2 * i], pad[2 * i + 1])
        if mode == "constant":
            return jnp.pad(a, pairs, constant_values=value)
        jmode = {"reflect": "reflect", "replicate": "edge", "circular": "wrap"}[mode]
        return jnp.pad(a, pairs, mode=jmode)

    return apply(_pad, x, name="pad")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW", name=None):
    x = _t(x)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    nd = x.ndim - 2
    in_spatial = x.shape[1:-1] if channel_last else x.shape[2:]
    if size is not None:
        if isinstance(size, Tensor):
            size = [int(s) for s in np.asarray(size.data)]
        out_spatial = tuple(int(s) for s in (size if isinstance(size, (list, tuple)) else [size]))
    else:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * nd
        out_spatial = tuple(int(i * s) for i, s in zip(in_spatial, scale_factor))

    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def _interp(a):
        if channel_last:
            shape = (a.shape[0],) + out_spatial + (a.shape[-1],)
        else:
            shape = a.shape[:2] + out_spatial
        if method == "nearest" or not align_corners:
            return jax.image.resize(a, shape, method=method).astype(a.dtype)
        # align_corners linear: explicit gather-based interp
        out = a
        spatial0 = 1 if channel_last else 2
        for i, osz in enumerate(out_spatial):
            ax = spatial0 + i
            isz = out.shape[ax]
            if osz == isz:
                continue
            if osz == 1:
                idx = jnp.zeros((1,), jnp.float32)
            else:
                idx = jnp.arange(osz, dtype=jnp.float32) * (isz - 1) / (osz - 1)
            lo = jnp.floor(idx).astype(jnp.int32)
            hi = jnp.minimum(lo + 1, isz - 1)
            w = (idx - lo).astype(a.dtype)
            lo_vals = jnp.take(out, lo, axis=ax)
            hi_vals = jnp.take(out, hi, axis=ax)
            bshape = [1] * out.ndim
            bshape[ax] = osz
            w = w.reshape(bshape)
            out = lo_vals * (1 - w) + hi_vals * w
        return out

    return apply(_interp, x, name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor
    def _ps(a):
        N, C, H, W = a.shape
        out = a.reshape(N, C // (r * r), r, r, H, W)
        out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
        return out.reshape(N, C // (r * r), H * r, W * r)
    return apply(_ps, _t(x), name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor
    def _pu(a):
        N, C, H, W = a.shape
        out = a.reshape(N, C, H // r, r, W // r, r)
        out = jnp.transpose(out, (0, 1, 3, 5, 2, 4))
        return out.reshape(N, C * r * r, H // r, W // r)
    return apply(_pu, _t(x), name="pixel_unshuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    ks = _norm_tuple(kernel_sizes, 2)
    st = _norm_tuple(strides, 2)
    pd = _norm_tuple(paddings, 2)
    dl = _norm_tuple(dilations, 2)

    def _unfold(a):
        N, C, H, W = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=ks, window_strides=st,
            padding=[(pd[0], pd[0]), (pd[1], pd[1])], rhs_dilation=dl,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # patches: [N, C*kh*kw, oh, ow]
        return patches.reshape(N, patches.shape[1], -1)

    return apply(_unfold, _t(x), name="unfold")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    os_ = _norm_tuple(output_sizes, 2)
    ks = _norm_tuple(kernel_sizes, 2)
    st = _norm_tuple(strides, 2)
    pd = _norm_tuple(paddings, 2)

    def _fold(a):
        N, CKK, L = a.shape
        C = CKK // (ks[0] * ks[1])
        oh = (os_[0] + 2 * pd[0] - ks[0]) // st[0] + 1
        ow = (os_[1] + 2 * pd[1] - ks[1]) // st[1] + 1
        cols = a.reshape(N, C, ks[0], ks[1], oh, ow)
        out = jnp.zeros((N, C, os_[0] + 2 * pd[0], os_[1] + 2 * pd[1]), a.dtype)
        for i in range(ks[0]):
            for j in range(ks[1]):
                out = out.at[:, :, i:i + oh * st[0]:st[0], j:j + ow * st[1]:st[1]].add(
                    cols[:, :, i, j])
        return out[:, :, pd[0]:os_[0] + pd[0], pd[1]:os_[1] + pd[1]]

    return apply(_fold, _t(x), name="fold")


def affine_grid(theta, out_shape, align_corners=True, name=None):
    if isinstance(out_shape, Tensor):
        out_shape = [int(s) for s in np.asarray(out_shape.data)]

    def _ag(th):
        N, _, H, W = out_shape[0], out_shape[1], out_shape[2], out_shape[3]
        if align_corners:
            xs = jnp.linspace(-1, 1, W)
            ys = jnp.linspace(-1, 1, H)
        else:
            xs = (jnp.arange(W) * 2 + 1) / W - 1
            ys = (jnp.arange(H) * 2 + 1) / H - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1).reshape(-1, 3)  # [HW, 3]
        out = jnp.einsum("nij,kj->nki", th, base)  # [N, HW, 2]
        return out.reshape(N, H, W, 2)

    return apply(_ag, _t(theta), name="affine_grid")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    def _gs(a, g):
        N, C, H, W = a.shape
        gx = g[..., 0]
        gy = g[..., 1]
        if align_corners:
            fx = (gx + 1) * (W - 1) / 2
            fy = (gy + 1) * (H - 1) / 2
        else:
            fx = ((gx + 1) * W - 1) / 2
            fy = ((gy + 1) * H - 1) / 2

        def sample(ix, iy):
            ixc = jnp.clip(ix, 0, W - 1)
            iyc = jnp.clip(iy, 0, H - 1)
            v = a[jnp.arange(N)[:, None, None], :, iyc, ixc]  # [N,h,w,C]
            if padding_mode == "zeros":
                valid = ((ix >= 0) & (ix < W) & (iy >= 0) & (iy < H))[..., None]
                v = jnp.where(valid, v, 0.0)
            return v

        if mode == "nearest":
            out = sample(jnp.round(fx).astype(jnp.int32), jnp.round(fy).astype(jnp.int32))
        else:
            x0 = jnp.floor(fx).astype(jnp.int32)
            y0 = jnp.floor(fy).astype(jnp.int32)
            x1, y1 = x0 + 1, y0 + 1
            wa = ((x1 - fx) * (y1 - fy))[..., None]
            wb = ((x1 - fx) * (fy - y0))[..., None]
            wc = ((fx - x0) * (y1 - fy))[..., None]
            wd = ((fx - x0) * (fy - y0))[..., None]
            out = (sample(x0, y0) * wa + sample(x0, y1) * wb +
                   sample(x1, y0) * wc + sample(x1, y1) * wd)
        return jnp.transpose(out, (0, 3, 1, 2)).astype(a.dtype)

    return apply(_gs, _t(x), _t(grid), name="grid_sample")


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def _cs(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)
    return apply(_cs, _t(x1), _t(x2), name="cosine_similarity")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def _ls(y, *pd):
        n = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / n
    args = [_t(label)] + ([_t(prior_dist)] if prior_dist is not None else [])
    return apply(_ls, *args, name="label_smooth")


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    x = _t(x)
    ml = maxlen if maxlen is not None else int(np.asarray(x.data).max())
    d = dtypes.convert_dtype(dtype)
    return apply(lambda a: (jnp.arange(ml)[None, :] < a[..., None]).astype(d),
                 x, name="sequence_mask")


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    def _ts(a):
        NT, C, H, W = a.shape
        N = NT // seg_num
        r = a.reshape(N, seg_num, C, H, W)
        fold_c = int(C * shift_ratio)
        left = jnp.concatenate([r[:, 1:, :fold_c], jnp.zeros_like(r[:, :1, :fold_c])], 1)
        right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold_c:2 * fold_c]),
                                 r[:, :-1, fold_c:2 * fold_c]], 1)
        rest = r[:, :, 2 * fold_c:]
        out = jnp.concatenate([left, right, rest], axis=2)
        return out.reshape(NT, C, H, W)
    return apply(_ts, _t(x), name="temporal_shift")


def class_center_sample(label, num_classes, num_samples, group=None):
    """Partial-FC class-center sampling (reference:
    nn/functional/common.py:1586, class_center_sample_op.cu): keep every
    positive class in `label`, top up with uniformly sampled negatives to
    `num_samples`, and remap labels into the sampled index space (labels
    whose class was not sampled keep... all positives are always sampled,
    so every label remaps). Returns (remapped_label, sampled_class_index).

    Host-side op (eager data-prep, like the reference's usage before the
    sharded margin-softmax matmul); RNG comes from the global generator.
    """
    import numpy as np

    from ..core.random import default_generator

    arr = np.asarray(label._data if isinstance(label, Tensor) else label)
    if arr.ndim != 1:
        raise ValueError("class_center_sample expects 1-D labels")
    if num_samples > num_classes:
        raise ValueError(f"num_samples {num_samples} > num_classes "
                         f"{num_classes}")
    positives = np.unique(arr)
    if len(positives) >= num_samples:
        sampled = positives
    else:
        seed_key = default_generator().next_key()
        import jax
        seed = int(jax.random.randint(seed_key, (), 0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        mask = np.ones(num_classes, bool)
        mask[positives] = False
        negatives = np.nonzero(mask)[0]
        extra = rng.choice(negatives, num_samples - len(positives),
                           replace=False)
        sampled = np.sort(np.concatenate([positives, extra]))
    remap = np.full(num_classes, -1, np.int64)
    remap[sampled] = np.arange(len(sampled))
    out_label = remap[arr]
    return (Tensor(jnp.asarray(out_label, jnp.int32)),
            Tensor(jnp.asarray(sampled, jnp.int32)))


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """Fused attention entry (reference: operators/fused/fused_attention_op.cu).

    Dispatches to the Pallas flash-attention kernel on TPU for supported
    shapes; falls back to the XLA composition otherwise. Layout: [B, S, H, D].
    """
    from ..ops.attention import scaled_dot_product_attention as _sdpa
    args = [_t(query), _t(key), _t(value)]
    mask = _t(attn_mask) if attn_mask is not None else None
    return _sdpa(args[0], args[1], args[2], mask, dropout_p, is_causal, training)


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention (reference: nn/functional/sparse_attention.py).

    Implemented as dense attention with a mask built from the CSR pattern —
    on TPU the MXU prefers dense tiles; true block-sparsity comes from the
    Pallas flash kernel's block skipping.
    """
    def _sa(q, k, v, offs, cols):
        B, H, S, D = q.shape
        scale = 1.0 / math.sqrt(D)
        scores = jnp.einsum("bhsd,bhtd->bhst", q, k,
                            precision=matmul_precision()) * scale
        # build dense mask from CSR (host-side shapes, device gather)
        row_ids = jnp.repeat(jnp.arange(S), jnp.diff(offs[0, 0]), total_repeat_length=cols.shape[-1])
        mask = jnp.zeros((S, S), bool).at[row_ids, cols[0, 0]].set(True)
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v,
                          precision=matmul_precision())
    return apply(_sa, _t(query), _t(key), _t(value), _t(sparse_csr_offset),
                 _t(sparse_csr_columns), name="sparse_attention")


def bilinear(x1, x2, weight, bias=None, name=None):
    """out[b, o] = x1[b, i] W[o, i, j] x2[b, j] + bias (reference:
    nn/functional/common.py bilinear over bilinear_tensor_product_op)."""

    def _bl(a, b, w, *bb):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        return out + bb[0] if bb else out

    args = [_t(x1), _t(x2), _t(weight)] + ([_t(bias)]
                                           if bias is not None else [])
    return apply(_bl, *args, name="bilinear")


def thresholded_relu(x, threshold=1.0, name=None):
    """x if x > threshold else 0 (reference: activation.py
    thresholded_relu)."""
    return apply(lambda a: jnp.where(a > threshold, a, 0.0).astype(a.dtype),
                 _t(x), name="thresholded_relu")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean", name=None):
    """ArcFace/CosFace-style margin softmax CE (reference:
    operators/margin_cross_entropy_op.cu): the target logit cos(theta) is
    replaced by cos(m1*theta + m2) - m3, everything scaled by s."""

    def _mce(lg, lab):
        lg32 = lg.astype(jnp.float32)
        ids = lab.astype(jnp.int32).reshape(-1)
        tgt = jnp.take_along_axis(lg32, ids[:, None], axis=-1)[:, 0]
        theta = jnp.arccos(jnp.clip(tgt, -1.0, 1.0))
        tgt_m = jnp.cos(margin1 * theta + margin2) - margin3
        onehot = jax.nn.one_hot(ids, lg32.shape[-1], dtype=lg32.dtype)
        adj = lg32 * (1 - onehot) + tgt_m[:, None] * onehot
        adj = adj * scale
        lse = jax.nn.logsumexp(adj, axis=-1)
        per = lse - jnp.take_along_axis(adj, ids[:, None], axis=-1)[:, 0]
        sm = jax.nn.softmax(adj, axis=-1)
        if reduction == "mean":
            loss = jnp.mean(per)
        elif reduction == "sum":
            loss = jnp.sum(per)
        else:
            loss = per
        return (loss, sm)

    loss_sm = apply(_mce, _t(logits), _t(label), name="margin_cross_entropy")
    if return_softmax:
        return loss_sm
    return loss_sm[0]


def _make_inplace(fn_name):
    def inplace(x, *args, **kwargs):
        from ..tensor.tail import _rebind
        out = globals()[fn_name](x, *args, **kwargs)
        return _rebind(_t(x), out)
    inplace.__name__ = fn_name + "_"
    inplace.__doc__ = (f"Inplace variant of :func:`{fn_name}` "
                       "(rebinds the tensor's buffer).")
    return inplace


relu_ = _make_inplace("relu")
elu_ = _make_inplace("elu")
softmax_ = _make_inplace("softmax")


from ..tensor.tail import diag_embed  # noqa: E402,F401
