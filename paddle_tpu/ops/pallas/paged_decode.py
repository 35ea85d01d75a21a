"""Pallas paged flash-decode attention (TPU serving hot path).

Decode-step attention over the block-structured KV pool of
:mod:`paddle_tpu.serving.kv_cache` — the kernel form of PagedAttention
(vLLM, SOSP '23) and the TPU-native replacement for the reference's
fused decode attention (reference: fused_multi_transformer_op.cu's
masked attention over the growing cache).

The XLA fallback (``models/gpt.py _paged_attention``) materializes the
slot-contiguous context first: ``gather_pages`` writes a dense
``[B, MB*bs, H, D]`` copy of every slot's pages to HBM, the masked SDPA
reads it back, and most of that traffic is wasted — a slot at position
``p`` only owns ``ceil(p/bs)`` of its ``MB`` table entries, the rest
point at the scratch page. Here the block table IS the access path:
a scalar-prefetch grid ``(slots, MB)`` maps logical block ``j`` of slot
``b`` straight to physical page ``table[b, j]`` in the BlockSpec index
map, so each page is DMA'd from the pool into VMEM exactly once and the
gathered context never exists in HBM. Blocks past the slot's position
are compute-skipped (their table entries alias the scratch page, so
their DMA is a reread of one hot page, not pool traffic).

Online softmax over the block sweep (running (m, l) row stats per head,
f32 accumulation), additive key masking by per-slot position — the same
math as the fallback's ``cols <= pos`` mask, so decode stays TOKEN-EXACT
against the dense path (pinned in tests/test_pallas_kernels.py).

All heads of a page ride one program. The per-head query is a single
``[1, D]`` row, so scores and the weighted value sum are VPU
multiply-reduces, not ``dot_general``s: the MXU has nothing to gain from
a one-row operand, and Mosaic refuses a dot batched over heads while
the head dimension is not leading (a page is ``[bs, H, D]``). The page
size ``bs`` set by
``ServingConfig.block_size`` is the KV block size — there is no separate
kernel block knob.

Tests run this kernel on CPU via the Pallas interpreter
(FLAGS_pallas_interpret; the ``pallas`` pytest marker).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["paged_decode_attention", "paged_decode_attention_quant"]

NEG_INF = -1e30


def _decode_kernel(tbl_ref, pos_ref, q_ref, *refs, scale, bs, H, D,
                   quant=False):
    if quant:
        # int8 pools ride with their per-(row, head) f32 scale blocks
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    p = pos_ref[b]

    # blocks wholly past the written positions contribute nothing: skip
    # the compute (their table entries alias the scratch page, so the
    # page DMA above cost one hot-page reread, not pool bandwidth)
    @pl.when(j * bs <= p)
    def _step():
        q = q_ref[0].astype(jnp.float32)                 # [H, D]
        k = k_ref[0].astype(jnp.float32)                 # [bs, H, D]
        v = v_ref[0].astype(jnp.float32)
        if quant:
            # identical math to kv_cache.dequant_pages, so the kernel
            # stays token-exact against the XLA gather fallback
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        # s[h, c] = q[h] . k[c, h] on the VPU: the query is ONE row per
        # head, so the MXU has nothing to gain, and Mosaic refuses a
        # dot_general batched over a non-leading dim (k is [bs, H, D])
        s = jnp.sum(q[None] * k, axis=-1).T * scale      # [H, bs]
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        # slot b sees written positions 0..p (current token included) —
        # identical to the fallback's additive key mask
        s = jnp.where(cols <= p, s, NEG_INF)
        m_prev = m_scr[:, :1]                            # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        pr = jnp.exp(s - shift)                          # masked -> 0
        alpha = jnp.exp(m_prev - shift)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(pr, axis=1, keepdims=True),
            l_scr.shape)
        # acc[h] += pr[h] @ v[:, h] — same VPU form
        pv = jnp.sum(pr.T[:, :, None] * v, axis=0)       # [H, D]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)             # inactive slot
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_table, pos, *,
                           scale: float):
    """One decode step of attention over paged KV state.

    ``q``: ``[B, H, D]`` (the decode token's query, S dim squeezed);
    ``k_pages``/``v_pages``: ``[P, bs, H, D]`` pools;
    ``block_table``: ``[B, MB]`` int32 physical-page ids;
    ``pos``: ``[B]`` int32 per-slot positions (the current token's
    logical index — attended inclusively, like the XLA fallback).
    Returns ``[B, H, D]`` in q's dtype.
    """
    B, H, D = q.shape
    bs = k_pages.shape[1]
    MB = block_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                           # table, pos
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, tbl, p: (b, 0, 0)),
            pl.BlockSpec((1, bs, H, D),
                         lambda b, j, tbl, p: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, H, D),
                         lambda b, j, tbl, p: (tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, tbl, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 8), jnp.float32),
            pltpu.VMEM((H, 8), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), bs=bs,
                          H=H, D=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="paged_decode",
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32),
      q, k_pages, v_pages)


def paged_decode_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                 block_table, pos, *, scale: float):
    """Decode attention over an int8-quantized paged pool
    (``FLAGS_serve_kv_quant=int8``).

    Same contract as :func:`paged_decode_attention`, plus the parallel
    f32 scale pools ``k_scales``/``v_scales`` ``[P, bs, H]``. The scale
    blocks ride the SAME block-table index maps as their pages, so the
    dequantize (``int8 * scale``) happens in VMEM right before the
    existing online-softmax sweep — the dequantized context never exists
    in HBM. Must match ``kv_cache.gather_pages_quant`` + masked SDPA
    token-exactly (same dequant math, f32 accumulation).
    """
    B, H, D = q.shape
    bs = k_pages.shape[1]
    MB = block_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                           # table, pos
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, tbl, p: (b, 0, 0)),
            pl.BlockSpec((1, bs, H, D),
                         lambda b, j, tbl, p: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, H),
                         lambda b, j, tbl, p: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, bs, H, D),
                         lambda b, j, tbl, p: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, H),
                         lambda b, j, tbl, p: (tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, tbl, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 8), jnp.float32),
            pltpu.VMEM((H, 8), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), bs=bs,
                          H=H, D=D, quant=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="paged_decode_int8",
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32),
      q, k_pages, k_scales, v_pages, v_scales)
