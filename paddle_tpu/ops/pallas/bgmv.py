"""Pallas batched gather-matmul (bgmv) for multi-tenant LoRA serving.

The Punica/S-LoRA primitive: a batch where every row may use a DIFFERENT
low-rank adapter. Adapter weights live in stacked pools
``A [n_adapters, r, E]`` / ``B [n_adapters, r, O]``; a per-slot int32
``ids`` row picks which adapter serves each batch element, and the fused
shrink + expand

    delta[b] = (x[b] @ A[ids[b]].T) @ B[ids[b]]        # [S,E]->[S,r]->[S,O]

is added to the base model's fused-QKV projection inside the serving
dispatches (models/gpt.py). Row 0 of the pools is the reserved ZERO
adapter — base-model requests ride the same compiled program and their
delta is exactly 0.0, so mixing adapted and plain requests in one batch
costs no extra dispatch.

Kernel shape: grid ``(B,)`` with the adapter ids scalar-prefetched; the
BlockSpec index maps route block ``ids[i]`` of each pool straight into
VMEM, so the gathered ``[B, r, E]``/``[B, r, O]`` adapter copies the XLA
fallback materializes never exist in HBM — the gather IS the access
path, exactly like the paged flash-decode kernel's block-table indexing.
Both matmuls accumulate in f32 (``preferred_element_type``).

Dispatch follows the ONE convention of this layer (see
ops/pallas/__init__): kill switch ``FLAGS_pallas_bgmv`` whose off
position is the bit-compatible XLA gather+einsum oracle
(:func:`bgmv_xla`), TPU-only unless ``FLAGS_pallas_interpret``, counted
fallbacks, a registry row, a parity test and a bench line.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["bgmv", "bgmv_xla"]


def bgmv_xla(x, a, b, ids):
    """XLA oracle: gather each row's adapter then shrink + expand.

    ``x``: ``[B, S, E]``; ``a``: ``[A, r, E]``; ``b``: ``[A, r, O]``;
    ``ids``: ``[B]`` int32 adapter rows. Returns ``[B, S, O]`` in x's
    dtype — the flags-off fallback the kernel must match bit-for-bit on
    identical inputs (both paths accumulate in f32).
    """
    aw = a[ids]                                          # [B, r, E]
    bw = b[ids]                                          # [B, r, O]
    h = jnp.einsum("bse,bre->bsr", x.astype(jnp.float32),
                   aw.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("bsr,bro->bso", h, bw.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def _bgmv_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)                     # [S, E]
    a = a_ref[0].astype(jnp.float32)                     # [r, E]
    b = b_ref[0].astype(jnp.float32)                     # [r, O]
    # shrink: h[s, r] = x[s] . a[r]  (contract over E)
    h = jax.lax.dot_general(
        x, a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [S, r]
    # expand: o[s, o] = h[s] . b[:, o]  (contract over r)
    o = jax.lax.dot_general(
        h, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [S, O]
    o_ref[0] = o.astype(o_ref.dtype)


def bgmv(x, a, b, ids):
    """Batched gather-matmul: per-row adapter shrink + expand.

    Same contract as :func:`bgmv_xla`; the adapter pools are read in
    place via scalar-prefetch indexing (one ``[r, E]`` + ``[r, O]``
    DMA per batch row, no HBM gather).
    """
    B, S, E = x.shape
    r = a.shape[1]
    O = b.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                           # ids
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S, E), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((1, r, E), lambda i, ids: (ids[i], 0, 0)),
            pl.BlockSpec((1, r, O), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, O), lambda i, ids: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_bgmv_kernel),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, O), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="bgmv",
    )(ids.astype(jnp.int32), x, a, b)
