"""Attention ops: XLA composition + Pallas flash-attention dispatch.

Replaces the reference's fused attention stack
(reference: paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h)
with a TPU design: a flash-attention Pallas kernel for the hot path and an
XLA softmax composition fallback (XLA already fuses scale+mask+softmax into
the surrounding matmuls well).
Layout convention: [batch, seq, heads, head_dim] (paddle MultiHeadAttention
uses [B, S, H*D] outside, [B, H, S, D] inside scores).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.flags import matmul_precision
from ..core.random import make_rng
from ..core.tensor import Tensor, apply


def _sdpa_xla(q, k, v, mask, dropout_p, is_causal, dropout_key):
    """Reference composition: works on [B, S, H, D]; ``k``/``v`` may
    hold ``Hkv`` heads, ``Hkv | H`` (query head ``h`` reads K/V head
    ``h // (H / Hkv)``), and are not repeated."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    prec = matmul_precision()
    scale = 1.0 / math.sqrt(D)
    if Hkv != H:
        scores = jnp.einsum(
            "bqngd,bknd->bngqk", q.reshape(B, Sq, Hkv, H // Hkv, D), k,
            precision=prec).reshape(B, H, Sq, Sk) * scale
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * scale
    if is_causal:
        causal = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        scores = jnp.where(causal[None, None], scores, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    if Hkv != H:
        return jnp.einsum(
            "bngqk,bknd->bqngd", probs.reshape(B, Hkv, H // Hkv, Sq, Sk), v,
            precision=prec).reshape(B, Sq, H, D)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=prec)


def _mesh_splits(B: int, H: int):
    """(batch shards, head shards) of the layout the flash kernel runs
    under on the active mesh — batch rows over the data axes, heads over
    ``mp``, what GPTAttention pins around its attention — or None when
    the shapes do not divide it. (1, 1) off-mesh."""
    from ..distributed.spmd import auto_axes, data_axes
    from ..distributed import env as dist_env
    axes = auto_axes()
    if not axes:
        return 1, 1
    mesh = dist_env.get_mesh()
    nb = math.prod(int(mesh.shape[a]) for a in data_axes(mesh) if a in axes)
    nh = int(mesh.shape["mp"]) if "mp" in axes else 1
    return (nb, nh) if B % nb == 0 and H % nh == 0 else None


def _flash_supported(q, k, v, mask, dropout_p, dropout_key=None) -> bool:
    if dropout_p > 0.0 and dropout_key is None:
        # no key: the XLA path silently skips dropout — keep that behavior
        # shape-independent rather than raising only on flash-eligible
        # shapes
        return False
    if mask is not None:
        # only additive key-padding masks [B, 1, 1, Sk] fit the kernel
        if (mask.dtype == jnp.bool_ or mask.ndim != 4
                or mask.shape[1] != 1 or mask.shape[2] != 1):
            return False
    B, S, H, D = q.shape
    Sk = k.shape[1]
    return (
        jax.default_backend() == "tpu"
        and S % 128 == 0 and Sk % 128 == 0
        and D in (64, 128, 256)
        and S >= 256
        and _mesh_splits(B, H) is not None
    )


def _flash(q, k, v, mask, dropout_p, is_causal, dropout_key):
    """The flash kernel — per device shard when a mesh is active, since
    GSPMD cannot partition a Mosaic kernel: attention is independent
    over batch rows and heads, so each device runs the kernel on its own
    (see :func:`_mesh_splits`); the sequence and head dims stay whole."""
    from ..distributed.spmd import BATCH, auto_axes, shard_kernel
    from .pallas.flash_attention import flash_attention
    axes = tuple(sorted(auto_axes()))

    def kernel(q, k, v, mask, key):
        if key is not None and axes:
            # one mask stream per shard: the kernel hashes LOCAL
            # coordinates, so shards would otherwise redraw one mask
            key = jax.random.fold_in(key, jax.lax.axis_index(axes))
        return flash_attention(q, k, v, bias=mask, causal=is_causal,
                               dropout_rate=dropout_p, dropout_key=key)

    qkv = P(BATCH, None, "mp", None)
    return shard_kernel(
        kernel,
        in_specs=(qkv, qkv, qkv,
                  None if mask is None else P(BATCH, None, None, None),
                  None if dropout_key is None else P()),
        out_specs=qkv)(q, k, v, mask, dropout_key)


def sdpa_array(q, k, v, mask=None, dropout_p=0.0, is_causal=False,
               dropout_key=None, use_flash=True):
    """Raw-array scaled dot-product attention with flash dispatch."""
    if use_flash and _flash_supported(q, k, v, mask, dropout_p,
                                      dropout_key):
        return _flash(q, k, v, mask, dropout_p, is_causal, dropout_key)
    return _sdpa_xla(q, k, v, mask, dropout_p, is_causal, dropout_key)


def scaled_dot_product_attention(query: Tensor, key: Tensor, value: Tensor,
                                 attn_mask: Optional[Tensor] = None,
                                 dropout_p: float = 0.0, is_causal: bool = False,
                                 training: bool = True) -> Tensor:
    dk = make_rng() if (dropout_p > 0.0 and training) else None
    p = dropout_p if training else 0.0

    def _fn(q, k, v, *maybe_mask):
        m = maybe_mask[0] if maybe_mask else None
        return sdpa_array(q, k, v, m, p, is_causal, dk)

    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    return apply(_fn, *args, name="scaled_dot_product_attention")
