"""Pallas int8 quantized matmul (TPU).

The TPU-native replacement for the reference's slim int8 inference
kernels (reference: the MKLDNN/TensorRT int8 gemms behind
post_training_quantization.py) — a per-output-channel symmetric
int8 x int8 -> int32 matmul with a dequantize epilogue, running on the
MXU's native int8 path instead of dequantizing weights back to float
before the gemm (the pre-kernel ``slim.QuantizedLinear`` behavior this
replaces: weight HBM traffic stays at 1/4 the f32 bytes AND the MXU
runs at int8 rate).

Scheme (one convention across serving + flag-gated AMP training):

- weights: per-output-channel symmetric int8, ``w_q [K, N]`` with
  ``w_scale [N]`` f32 (``quantize_per_channel``, the observer
  ``slim._channel_scales`` / ``nn.quant`` records);
- activations: per-tensor symmetric int8 — a static calibrated scale
  (``act_scale``) or a dynamic absmax resolved in XLA right before the
  kernel (one cheap fused reduction; the quantize itself is an
  elementwise pass XLA fuses into the surrounding graph);
- kernel: grid ``(M/bm, N/bn, K/bk)``, k innermost, int32 VMEM
  accumulator, epilogue ``acc * (act_scale * w_scale[n])`` at the last
  k step in f32, cast to the activation dtype.

``int8_amp_linear`` wraps the kernel in a custom VJP whose backward is
the straight-through dense pair (``dx = g @ w^T``, ``dw = x^T @ g`` on
the UNquantized operands) so the flag-gated AMP path trains through
quantization noise without int8 gradients.

Block sizes: ``PTPU_INT8_BLOCK_M/N/K`` (defaults 128/128/512); N and K
must be multiples of 128 (lane tiles) — other geometries fall back.
Tests run the kernel on CPU via the Pallas interpreter
(FLAGS_pallas_interpret; the ``pallas`` pytest marker).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["int8_matmul", "int8_linear", "int8_amp_linear",
           "quantize_per_channel", "quantize_per_tensor",
           "matmul_shapes_supported"]

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512


def _env_block(var: str, default: int) -> int:
    raw = os.environ.get(var)
    if not raw:
        return default
    try:
        b = int(raw)
    except ValueError:
        raise ValueError(
            f"{var}={raw!r}: the int8-matmul block override must be an "
            f"integer") from None
    if b <= 0 or b % 128:
        raise ValueError(
            f"{var}={b}: the int8-matmul block override must be a "
            f"positive multiple of 128 (the TPU lane tile)")
    return b


def _divisor_block(dim: int, requested: int) -> int:
    """Largest multiple of 128 dividing ``dim``, capped at ``requested``."""
    start = (min(requested, dim) // 128) * 128
    for b in range(start, 127, -128):
        if dim % b == 0:
            return b
    return 128


def matmul_shapes_supported(K: int, N: int) -> bool:
    """The kernel's geometry gate: lane-tiled contraction and output
    channels. M is free (the row grid is ceil-divided and padded)."""
    return K % 128 == 0 and N % 128 == 0


# ---------------------------------------------------------------------------
# quantizers (XLA; fused into the surrounding graph)
# ---------------------------------------------------------------------------


def quantize_per_channel(w, axis: int = 1, bits: int = 8):
    """Symmetric per-channel quantization of a [K, N] weight along the
    output axis: returns (w_q int8, scale f32 [N])."""
    qmax = 2.0 ** (bits - 1) - 1
    red = tuple(i for i in range(w.ndim) if i != axis)
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=red)
    scale = jnp.maximum(absmax / qmax, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32)
                           / jnp.expand_dims(scale, red)),
                 -qmax, qmax).astype(jnp.int8)
    return q, scale


def quantize_per_tensor(x, act_scale=None, bits: int = 8):
    """Symmetric per-tensor quantization of activations: returns
    (x_q int8, scale f32 scalar). ``act_scale=None`` = dynamic absmax."""
    qmax = 2.0 ** (bits - 1) - 1
    x32 = x.astype(jnp.float32)
    if act_scale is None:
        act_scale = jnp.maximum(jnp.max(jnp.abs(x32)) / qmax, 1e-8)
    else:
        act_scale = jnp.asarray(act_scale, jnp.float32)
    q = jnp.clip(jnp.round(x32 / act_scale), -qmax, qmax).astype(jnp.int8)
    return q, act_scale


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _mm_kernel(xq_ref, wq_ref, ws_ref, as_ref, o_ref, acc_scr, *, out_dtype):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # int8 x int8 -> int32 on the MXU's native int8 path
    acc_scr[:] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _finish():
        # dequantize epilogue: one f32 multiply per output element
        scale = as_ref[0, 0] * ws_ref[0, :]              # [bn]
        o_ref[...] = (acc_scr[:].astype(jnp.float32)
                      * scale[None, :]).astype(out_dtype)


def int8_matmul(x_q, w_q, w_scale, act_scale, out_dtype=jnp.float32):
    """``x_q [M, K]`` int8 @ ``w_q [K, N]`` int8 with the dequantize
    epilogue ``acc * act_scale * w_scale[n]``. K and N must be 128-
    aligned (see :func:`matmul_shapes_supported`); M is padded by the
    grid. Returns ``[M, N]`` in ``out_dtype``."""
    M, K = x_q.shape
    N = w_q.shape[1]
    if not matmul_shapes_supported(K, N):
        raise ValueError(
            f"int8_matmul needs K % 128 == 0 and N % 128 == 0, got "
            f"K={K}, N={N} (the dispatch layer routes these shapes to "
            f"the XLA fallback)")
    bm = min(_env_block("PTPU_INT8_BLOCK_M", DEFAULT_BLOCK_M), max(8, M))
    bn = _divisor_block(N, _env_block("PTPU_INT8_BLOCK_N", DEFAULT_BLOCK_N))
    bk = _divisor_block(K, _env_block("PTPU_INT8_BLOCK_K", DEFAULT_BLOCK_K))
    act = jnp.reshape(jnp.asarray(act_scale, jnp.float32), (1, 1))
    ws = w_scale.astype(jnp.float32)[None, :]            # [1, N]
    return pl.pallas_call(
        functools.partial(_mm_kernel, out_dtype=out_dtype),
        grid=(pl.cdiv(M, bm), N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="int8_matmul",
    )(x_q, w_q, ws, act)


# ---------------------------------------------------------------------------
# linear entries
# ---------------------------------------------------------------------------


def _lead2d(x):
    lead = x.shape[:-1]
    return x.reshape((-1, x.shape[-1])), lead


def int8_linear(x, w_q, w_scale, bias=None, act_scale=None):
    """Quantized linear over pre-quantized weights (the serving path:
    ``slim.QuantizedLinear``): activations are quantized per tensor
    (statically via ``act_scale`` or dynamically via absmax), the gemm
    runs int8 end to end, bias adds in the activation dtype. ``x``
    ``[..., K]`` float; returns ``[..., N]`` in x's dtype."""
    x2, lead = _lead2d(x)
    x_q, a_s = quantize_per_tensor(x2, act_scale)
    y = int8_matmul(x_q, w_q, w_scale, a_s, out_dtype=x.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y.reshape(lead + (w_q.shape[1],))


@jax.custom_vjp
def _amp_mm(x2, w):
    w_q, w_s = quantize_per_channel(w)
    x_q, a_s = quantize_per_tensor(x2)
    return int8_matmul(x_q, w_q, w_s, a_s, out_dtype=x2.dtype)


def _amp_mm_fwd(x2, w):
    return _amp_mm(x2, w), (x2, w)


def _amp_mm_bwd(res, g):
    x2, w = res
    # straight-through: gradients flow to the UNquantized operands via
    # the dense pair (the master weights stay full precision; the int8
    # rounding is treated as identity, standard QAT practice)
    dx = jnp.matmul(g, w.T.astype(g.dtype)).astype(x2.dtype)
    dw = jnp.matmul(x2.T.astype(g.dtype), g).astype(w.dtype)
    return dx, dw


_amp_mm.defvjp(_amp_mm_fwd, _amp_mm_bwd)


def int8_amp_linear(x, w, bias=None):
    """Flag-gated AMP training matmul (``FLAGS_amp_int8_matmul``): both
    operands dynamically quantized per forward, straight-through dense
    backward. ``w [K, N]`` float parameter."""
    x2, lead = _lead2d(x)
    y = _amp_mm(x2, w)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y.reshape(lead + (w.shape[1],))
