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
a scalar-prefetch grid ``(slots, G, MB)`` maps logical block ``j`` of
slot ``b`` straight to physical page ``table[b, j]`` in the BlockSpec
index map, so each page is DMA'd from the pool into VMEM exactly once
and the gathered context never exists in HBM. Blocks past the slot's
position are compute-skipped (their table entries alias the scratch
page, so their DMA is a reread of one hot page, not pool traffic).

The pool is the engine's own array, ``[N, G, bs, (H/G)*D]``
(:mod:`paddle_tpu.serving.kv_cache`): a page block is
``(1, 1, bs, (H/G)*D)`` with the heads of a token row fused into the
minor dim, so the block the kernel DMAs is whole ``(8, 128)`` tiles
(345M: 16 rows x 1024 lanes) and the layout the kernel wants is the
layout the array already has — XLA relays nothing around the call. (A
``[bs, H, 64]`` page pads its 64 lanes to 128: twice the DMA, and a
pool-sized relayout copy on each side of the kernel.) ``N`` counts the
pages of ALL layers; the caller adds the layer's first page to the table.

Online softmax over the block sweep (running (m, l) row stats per head,
f32 accumulation), additive key masking by per-slot position — the same
math as the fallback's ``cols <= pos`` mask, so decode stays TOKEN-EXACT
against the dense path (pinned in tests/test_pallas_kernels.py).

All heads of a page ride one program, on the MXU: the query is laid out
block-diagonally once a slot (row ``h`` holds ``q[h]`` in head ``h``'s
``D`` lanes and zeros elsewhere), so ``q_bd @ k^T`` is the per-head score
``[H/G, bs]`` with no reshape of the fused dim, and ``p @ v``
``[H/G, (H/G)*D]`` holds head ``h``'s weighted value sum in head ``h``'s
lanes of row ``h`` (the other lanes are dropped when the slot finishes).
bf16 pools under a bf16 query multiply in bf16 passes with f32
accumulation and lose nothing by it: a product of two bf16 values is
exact in f32, and the f32 probabilities go through ``p @ v`` as three
bf16 parts (``_probs_dot``), so scores, probabilities and the weighted
sum have the precision of f32 arithmetic on values read from bf16.
Everything else multiplies in f32 at the highest precision. The page
size ``bs`` set by ``ServingConfig.block_size`` is the KV block size —
there is no separate kernel block knob.

``G > 1`` is the layout of a pool sharded over an ``mp`` mesh. Under a
mesh ``kernel_enabled`` routes decode to the XLA fallback, so until a
per-shard call exists only the tests run the kernel with two groups.

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


def _head_lanes(hg, D):
    """``[H/G, (H/G)*D]`` bool: lane ``f`` of the fused dim belongs to
    head ``f // D`` (compares, no vector division)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (hg, hg * D), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (hg, hg * D), 0)
    return (lane >= head * D) & (lane < (head + 1) * D)


def _probs_dot(pr, v, prec):
    """``pr @ v`` with the f32 probabilities at their full precision.
    Against bf16 values the probabilities split into three bf16 parts
    (8 + 8 + 8 = the 24 bits of an f32 mantissa): every product is exact
    in f32, so three one-pass products, the small parts summed first,
    give what an f32 multiply on the VPU gives, in half the passes of
    f32 operands at the highest precision."""
    if v.dtype != jnp.bfloat16:
        return jnp.dot(pr, v, precision=prec,
                       preferred_element_type=jnp.float32)

    def dot(part):
        return jnp.dot(part, v, preferred_element_type=jnp.float32)

    hi = pr.astype(jnp.bfloat16)
    rest = pr - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return (dot(lo) + dot(mid)) + dot(hi)


def _decode_kernel(tbl_ref, pos_ref, q_ref, *refs, scale, bs, hg, D,
                   quant=False):
    if quant:
        # int8 pools ride with their per-(row, head) f32 scale blocks
        (k_ref, ks_ref, v_ref, vs_ref, o_ref,
         qbd_scr, m_scr, l_scr, acc_scr) = refs
    else:
        k_ref, v_ref, o_ref, qbd_scr, m_scr, l_scr, acc_scr = refs
    b, j = pl.program_id(0), pl.program_id(2)
    nj = pl.num_programs(2)
    # one bf16 MXU pass is exact for bf16 x bf16; f32 operands need all
    # of their mantissa
    prec = (jax.lax.Precision.DEFAULT if qbd_scr.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        # the query, block-diagonal: row h = q[h] in head h's lanes
        qbd_scr[:] = jnp.where(
            _head_lanes(hg, D), q_ref[0, 0].astype(jnp.float32),
            0.0).astype(qbd_scr.dtype)

    p = pos_ref[b]

    # blocks wholly past the written positions contribute nothing: skip
    # the compute (their table entries alias the scratch page, so the
    # page DMA above cost one hot-page reread, not pool bandwidth)
    @pl.when(j * bs <= p)
    def _step():
        k = k_ref[0, 0].astype(qbd_scr.dtype)            # [bs, F]
        v = v_ref[0, 0].astype(qbd_scr.dtype)
        if quant:
            # identical math to kv_cache.dequant_pages (each int8 value
            # times its head's scale, in f32), so the kernel stays
            # token-exact against the XLA gather fallback; the scales
            # [bs, H/G] spread over their heads' lanes exactly: one
            # nonzero term a lane
            lanes = _head_lanes(hg, D).astype(jnp.float32)
            k = k * jnp.dot(ks_ref[0, 0], lanes, precision=prec)
            v = v * jnp.dot(vs_ref[0, 0], lanes, precision=prec)
        # s[h, c] = q[h] . k[c, head h's lanes]
        s = jax.lax.dot_general(
            qbd_scr[:], k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale  # [H/G, bs]
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (hg, bs), 1)
        # slot b sees written positions 0..p (current token included) —
        # identical to the fallback's additive key mask
        s = jnp.where(cols <= p, s, NEG_INF)
        m_prev = m_scr[:, :1]                            # [H/G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        pr = jnp.exp(s - shift)                          # masked -> 0
        alpha = jnp.exp(m_prev - shift)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(pr, axis=1, keepdims=True),
            l_scr.shape)
        # acc[h] += pr[h] @ v: head h's sum lands in head h's lanes
        pv = _probs_dot(pr, v, prec)                     # [H/G, F]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)             # inactive slot
        o_ref[0, 0] = jnp.sum(
            jnp.where(_head_lanes(hg, D), acc_scr[:] / safe_l, 0.0),
            axis=0, keepdims=True).astype(o_ref.dtype)


def _paged_decode(q, pools, block_table, pos, *, scale, quant, name):
    """The one pallas_call behind both entry points. ``pools`` is
    ``(k, v)`` or ``(k, k_scales, v, v_scales)``."""
    B, H, D = q.shape
    _, G, bs, F = pools[0].shape
    hg = H // G
    MB = block_table.shape[1]
    # bf16 end to end only when query and pool both are
    cdt = (jnp.bfloat16 if q.dtype == pools[0].dtype == jnp.bfloat16
           else jnp.float32)

    def page(width):
        return pl.BlockSpec((1, 1, bs, width),
                            lambda b, g, j, tbl, p: (tbl[b, j], g, 0, 0))

    def row():
        return pl.BlockSpec((1, 1, 1, F), lambda b, g, j, tbl, p: (b, g, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                           # table, pos
        grid=(B, G, MB),
        in_specs=[row()] + [page(a.shape[-1]) for a in pools],
        out_specs=row(),
        scratch_shapes=[
            pltpu.VMEM((hg, F), cdt),
            pltpu.VMEM((hg, 8), jnp.float32),
            pltpu.VMEM((hg, 8), jnp.float32),
            pltpu.VMEM((hg, F), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), bs=bs,
                          hg=hg, D=D, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, 1, F), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, G, 1, F), *pools)
    return out.reshape(B, H, D)


def paged_decode_attention(q, k_pages, v_pages, block_table, pos, *,
                           scale: float):
    """One decode step of attention over paged KV state.

    ``q``: ``[B, H, D]`` (the decode token's query, S dim squeezed);
    ``k_pages``/``v_pages``: ``[N, G, bs, (H/G)*D]`` pools;
    ``block_table``: ``[B, MB]`` int32 PHYSICAL page ids (the layer's
    first page already added);
    ``pos``: ``[B]`` int32 per-slot positions (the current token's
    logical index — attended inclusively, like the XLA fallback).
    Returns ``[B, H, D]`` in q's dtype.
    """
    return _paged_decode(q, (k_pages, v_pages), block_table, pos,
                         scale=scale, quant=False, name="paged_decode")


def paged_decode_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                 block_table, pos, *, scale: float):
    """Decode attention over an int8-quantized paged pool
    (``FLAGS_serve_kv_quant=int8``).

    Same contract as :func:`paged_decode_attention`, plus the parallel
    f32 scale pools ``k_scales``/``v_scales`` ``[N, G, bs, H/G]``. The
    scale blocks ride the SAME block-table index maps as their pages, so
    the dequantize (``int8 * scale``) happens in VMEM right before the
    existing online-softmax sweep — the dequantized context never exists
    in HBM. Must match ``kv_cache.gather_pages_quant`` + masked SDPA
    token-exactly (same dequant math, f32 accumulation).
    """
    return _paged_decode(q, (k_pages, k_scales, v_pages, v_scales),
                         block_table, pos, scale=scale, quant=True,
                         name="paged_decode_int8")
