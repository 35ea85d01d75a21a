"""Pallas flash attention (TPU).

Tiled online-softmax attention with a custom VJP; the TPU-native
replacement for the reference's fused CUDA attention stack
(reference: paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h,
fused_gate_attention_op.cu).

Design (FlashAttention-2 schedule, expressed the Mosaic way). Two kernel
generations share the public entry:

v2 (default, bias-free): consumes q/k/v as [B, S, H*D] — a free bitcast of
the framework layout, so NO transpose ever materializes around the kernel.
A head is a static lane-column slice (two D=64 heads share one 128-lane
block); each program processes `block_b` batch rows x the packed heads,
amortizing per-program pipeline overhead. The backward is ONE fused kernel
(grid q-sweep innermost): the score/dp tiles are computed once, dk/dv
accumulate in block scratch written as each k block completes, and dq
accumulates in a full-Sq f32 scratch flushed once through a
constant-indexed full-sequence output window. delta = rowsum(dO*O) is
computed in-kernel from blocks already in VMEM; lse rides a narrow
[B, H, S, 8] tile.

v1 (fallback: additive [B,1,1,Sk] bias, odd head counts): grid
(B, H, nq, nk) over [B, H, S, D] views with the classic dq/dkv kernel
split.

Common to both: online-softmax forward with running (m, l) scratch,
O(S*D) HBM traffic; causal tiles above the diagonal are compute-skipped
via `pl.when`. The v2 kernels know two kinds of running tile
(:func:`_causal_plan`): one wholly below the diagonal runs no mask code,
and one that straddles it takes its query rows in groups of 128, each
multiplied only against the key columns it can see and masked only in
the 128 x 128 piece on the diagonal (a 512 x 512 tile computes 10 of its
16 pieces; a forward whose grid step holds one head keeps the tile
whole, and v1 computes and masks every running tile whole). In-kernel
rematerialized dropout via a stateless
murmur3-finalizer hash over absolute coordinates (the backward REGENERATES
the mask, nothing is stored).

Precision (one rule, :func:`_dot`; ``paged_decode.py`` follows the same
one): the number of MXU passes of a product follows its operands' types,
and every product accumulates in f32. Two bf16 operands (``q k^T``,
``dO v^T`` of an AMP step) multiply in ONE bf16 pass, which is exact: a
product of two bf16 values has 16 significant bits. An f32 tile computed
in the kernel (the probabilities with the dropout factor folded in,
``ds``) against a bf16 operand goes through as THREE bf16 parts (8 + 8 +
8 bits, the small parts summed first), each one pass: no tile is rounded
to bf16, and the result is what f32 operands at ``Precision.HIGHEST``
give, up to the order of f32 sums, in three passes where those take six.
Two f32 operands follow ``FLAGS_tpu_matmul_precision``: ``HIGHEST``
unless the flag says ``default`` (then one bf16 pass, the backend's own
choice for f32). Nothing else chooses: no flag of this module, and the
Pallas interpreter (the CPU tests) takes the same path as the chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (FLASH_RESIDUAL_NAMES, interpret as _interpret,
               note_flash_causal_work)

# 512x512 tiles win on v5e: fewer grid steps amortize the VMEM loads and the
# p-tile (512*512*4B = 1 MiB) still fits comfortably; measured ~28% faster
# than 128x128 at S=2048 and ahead of XLA's fused sdpa.
DEFAULT_BLOCK = 512
NEG_INF = -1e30


def _mxu_dtype(in_dtype) -> jnp.dtype:
    """The type the MXU multiplies an operand of ``in_dtype`` in.

    bfloat16 stays bfloat16, whatever the flag says: there is nothing to
    round. float32 follows ``FLAGS_tpu_matmul_precision``, the policy for
    f32 operands: float32 (all of the mantissa) unless the flag is
    ``default``, which lets the backend round f32 operands to one bf16
    pass. Any other type (float16) is computed as float32."""
    from ...core.flags import matmul_precision
    if in_dtype == jnp.bfloat16:
        return jnp.bfloat16
    return jnp.float32 if matmul_precision() is not None else jnp.bfloat16


def _causal_mask(s, row0, col0, off):
    """Bottom-right-aligned causal mask of a score piece whose first
    element is (query row ``row0``, key column ``col0``): query row i sees
    keys j <= i + off where off = Sk - Sq (matches _sdpa_xla's
    tril(k=Sk-Sq) semantics for chunked prefill against a longer KV
    cache)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows + off >= cols, s, NEG_INF)


def _mask_from(s, lo, row0, col0, off):
    """``s`` with the causal mask applied to its columns from ``lo`` on
    (a multiple of 128); the columns before are all seen."""
    if lo == 0:
        return _causal_mask(s, row0, col0, off)
    return jnp.concatenate(
        [s[:, :lo], _causal_mask(s[:, lo:], row0, col0 + lo, off)], axis=1)


def _dropout_keep(seed_ref, b, h, row0, col0, shape, rate):
    """Deterministic keep mask scaled by 1/(1-rate), of a piece whose
    first element is (query row ``row0``, key column ``col0``).

    A STATELESS counter-based hash (murmur3 finalizer) over the absolute
    (batch, head, query-row, key-col) coordinates + the step seed: the
    backward kernels RE-GENERATE the identical mask instead of storing S^2
    bits — the dropout analogue of flash's no-residual rematerialization
    (reference's fused attention stores its uint8 mask, fmha_ref.h). A
    pure function of indices is bit-reproducible across the fwd/dq/dkv
    kernels by construction, which Mosaic's stateful hardware PRNG is
    not, and an element's keep bit does not depend on how a kernel cuts
    its tiles.
    """
    rows = (row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)) \
        .astype(jnp.uint32)
    cols = (col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)) \
        .astype(jnp.uint32)
    bh = (b.astype(jnp.uint32) * jnp.uint32(0xAC564B05)
          + h.astype(jnp.uint32) * jnp.uint32(19349663))
    from .rng import fmix32, keep_threshold
    x = fmix32(rows * jnp.uint32(0x9E3779B1)
               ^ cols * jnp.uint32(0x85EBCA6B)
               ^ bh
               ^ seed_ref[0].astype(jnp.uint32)
               ^ (seed_ref[1].astype(jnp.uint32) << 1))
    keep = x >= keep_threshold(rate)
    return keep.astype(jnp.float32) / (1.0 - rate)


def _mxu_parts(x, against):
    """``x`` as the parts the MXU multiplies against an operand of dtype
    ``against``, the smallest first; their sum is ``x``.

    One part, in ``x``'s own compute type, when both sides share it. A
    float32 ``x`` against a bfloat16 operand is three bf16 parts (hi, mid,
    lo: 8 + 8 + 8 = the 24 bits of an f32 mantissa, the arithmetic of
    ``paged_decode._probs_dot``): each part times a bf16 value is exact
    in f32, so three one-pass products, the small parts summed first,
    give what f32 operands at the highest precision give, in half the
    passes. A tile two products share (``ds``) is split once."""
    cd = _mxu_dtype(x.dtype)
    if cd == jnp.bfloat16 or _mxu_dtype(against) == cd:
        return (x.astype(cd),)
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return lo, mid, hi


def _dot(a, b, dims):
    """MXU matmul with f32 accumulation; the passes follow the operands'
    types (module docstring). ``a`` and ``b`` are arrays, or what
    :func:`_mxu_parts` made of one against the other.

    f32 parts mean both operands are f32 and the policy asked for all of
    it, and that has to be said to Mosaic too: at its default precision
    an f32 dot feeds the MXU bf16-rounded operands (measured on a v5e, PR
    22: 2.2e-3 off the f32 reference — the error of a bf16 pass — where
    XLA's ``highest`` composition is at 1e-6). bf16 parts need no
    ``precision=``: one pass is all there is."""
    if not isinstance(b, tuple):
        b = _mxu_parts(b, a[0].dtype if isinstance(a, tuple) else a.dtype)
    if not isinstance(a, tuple):
        a = _mxu_parts(a, b[0].dtype)
    prec = (jax.lax.Precision.HIGHEST if a[0].dtype == jnp.float32
            else None)
    return functools.reduce(jnp.add, [
        jax.lax.dot_general(x, y, (dims, ((), ())), precision=prec,
                            preferred_element_type=jnp.float32)
        for x in a for y in b])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                off, rate):
    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = ((qi * block_q + block_q - 1 + off >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _step():
        s = _dot(q_ref[0, 0], k_ref[0, 0], ((1,), (1,))) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)   # [1, bk] broadcast
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, off)

        m_prev = m_scr[:, :1]                            # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # fully-masked tile: m_new stays NEG_INF; shift by 0 to avoid inf-inf
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift)                           # [bq, bk]
        if causal:
            p = jnp.where(s == NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - shift)                  # [bq, 1] (<= 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = p
        if rate > 0.0:
            # dropout on the normalized probs commutes to masking the pv
            # accumulation only; the softmax denominator stays undropped
            pv = p * _dropout_keep(seed_ref, b, h, qi * block_q,
                                   ki * block_k, p.shape, rate)
        acc_scr[:] = acc_scr[:] * alpha + _dot(pv, v_ref[0, 0], ((1,), (0,)))
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)             # all-masked row -> 0
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        if lse_ref is not None:
            m = m_scr[:, :1]
            lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


def _mk_kernel(kern, has_bias, n_in=3, lse_out=True, has_seed=False, **kw):
    """Adapt ref lists: a leading seed_ref when dropout is on, bias_ref=None
    inserted after the n_in inputs when there is no bias input, and
    lse_ref=None after the o output when the lse output is dropped."""
    def wrapped(*refs):
        if has_seed:
            seed_ref, refs = refs[0], refs[1:]
        else:
            seed_ref = None
        n = n_in + (1 if has_bias else 0)
        ins, rest = list(refs[:n]), list(refs[n:])
        if not has_bias:
            ins = ins[:n_in] + [None] + ins[n_in:]
        if not lse_out:
            rest = rest[:1] + [None] + rest[1:]
        return kern(seed_ref, *ins, *rest, **kw)

    return wrapped


def _fwd_v1(q, k, v, bias, scale, causal, block_q, block_k,
            save_residuals=True, seed=None, rate=0.0, kv_group=1):
    """q: [B, H, S, D]; k,v: [B, H/kv_group, S, D] (query head ``h`` reads
    K/V head ``h // kv_group``). Returns (o, lse[B, H, S, 8]) — the lse rows
    stay in the narrow tile exactly as the kernel wrote them so the backward
    can consume them without an XLA re-broadcast; lse is None when
    save_residuals=False (inference: no lse write, saves S*128 f32 HBM
    traffic per (b, h), mirroring the upstream kernel's save_residuals)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // block_q, Sk // block_k

    qs = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    ks = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0)) \
        if kv_group == 1 else pl.BlockSpec(
            (1, 1, block_k, D), lambda b, h, i, j: (b, h // kv_group, j, 0))
    in_specs = []
    args = []
    if rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    in_specs += [qs, ks, ks]
    args += [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                     lambda b, h, i, j: (b, 0, 0, j)))
        args.append(bias)
    kern = _mk_kernel(_fwd_kernel, bias is not None, lse_out=save_residuals,
                      has_seed=rate > 0.0, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, off=Sk - Sq,
                      rate=rate)

    out_specs = [pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, i, j: (b, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype)]
    if save_residuals:
        # row stats ride a narrow 8-lane tile: [B, H, S, 8] is 16x less
        # HBM than a full 128-lane broadcast and Mosaic accepts last-dim 8
        out_specs.append(pl.BlockSpec((1, 1, block_q, 8),
                                      lambda b, h, i, j: (b, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, Sq, 8), jnp.float32))

    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 8), jnp.float32),
            pltpu.VMEM((block_q, 8), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_fwd_v1",
    )(*args)
    if save_residuals:
        o, lse = out
        return o, lse
    return out[0], None


# ---------------------------------------------------------------------------
# v2 kernels: native [B, S, H*D] layout, batched programs, fused backward
#
# The v1 kernels grid over every (batch, head) pair — for BERT-base shapes
# that is 576 programs of ~2 µs work each, and the [B,S,H,D]->[B,H,S,D]
# relayout XLA must materialize around them costs more HBM than the
# attention itself. v2 instead:
#   - consumes q/k/v as [B, S, E] (a free bitcast of the framework layout):
#     a head is a static lane-column slice, two D=64 heads share one
#     128-lane block, so no transpose ever materializes;
#   - processes `block_b` batch rows x `hp` heads per program, amortizing
#     the per-program pipeline overhead;
#   - fuses the whole backward into ONE kernel producing dq/dk/dv in a
#     single pass: the score and dp tiles are computed once (the v1 dq/dkv
#     split computes them twice) with dk/dv accumulated across q-blocks in
#     a full-S VMEM scratch.
# Bias is not supported here (the padded-batch case routes to v1).
# ---------------------------------------------------------------------------


def _heads_per_block(D: int, H: int):
    """Lane width of one kernel column block and the heads packed in it."""
    if D % 128 == 0:
        return 1, D
    if D == 64 and H % 2 == 0:
        return 2, 128
    return None, None


def _group_rows(block: int) -> int:
    """Rows of one row group of a tile that straddles the causal diagonal
    (:func:`_causal_plan`); it divides ``block``, and a block of its own
    size is one group. Follows from the block alone: nobody sets it."""
    return min(block, 128)


def _causal_plan(Sq, Sk, block_q, block_k, groups=True):
    """How the v2 kernels take the tiles of a causal call: ``(plan,
    computed)``.

    A tile is placed by its ``reach``, the tile column under the diagonal
    element of its first query row (``qi * block_q + off - ki * block_k``;
    row ``i`` of the tile sees its columns ``<= i + reach``). At
    ``reach <= -block_q`` nothing is seen and the tile is skipped; at
    ``reach >= block_k - 1`` everything is and the tile runs with no mask
    code. Between the two the tile STRADDLES the diagonal, and its query
    rows go in row groups of ``_group_rows(block_q)`` rows: a group
    ``(r0, rows, width, lo)`` multiplies rows ``[r0, r0 + rows)`` against
    the tile's first ``width`` key columns only, the last it can see, and
    masks the piece ``[lo, width)`` on the diagonal (``lo`` None: the
    group is wholly below it); a group that sees nothing is left out.
    ``plan`` maps each ``reach`` a straddling tile of this call has to
    its groups. It is None, and a straddling tile is ONE group under a
    mask of all of it, where a group's edge would not fall on a multiple
    of the group's rows: ``off`` or a block is not one, or the block is
    one group; and where the caller says ``groups=False``.
    ``computed`` is the score elements a head computes."""
    off = Sk - Sq
    sub = _group_rows(block_q)
    aligned = (groups and sub < block_q and off % sub == 0
               and block_q % sub == 0 and block_k % sub == 0)
    plan = {}
    computed = 0
    for qi in range(Sq // block_q):
        for ki in range(Sk // block_k):
            reach = qi * block_q + off - ki * block_k
            if reach <= -block_q:
                continue
            if reach >= block_k - 1 or not aligned:
                computed += block_q * block_k
                continue
            if reach not in plan:
                plan[reach] = tuple(
                    (r0, sub, min(reach + r0 + sub, block_k),
                     reach + r0 if reach + r0 < block_k else None)
                    for r0 in range(0, block_q, sub) if reach + r0 + sub > 0)
            computed += sum(rows * width for _, rows, width, _ in plan[reach])
    return (plan if aligned else None), computed


def _plan_of(kernel, causal, B, H, Sq, Sk, block_q, block_k, groups=True):
    """The :func:`_causal_plan` of one call of ``kernel`` (None when it
    is not causal), which this adds to the always-on record of what the
    causal diagonal costs (``ops.pallas.FLASH_CAUSAL_WORK``): the score
    elements the kernel will compute against those the mask keeps."""
    if not causal:
        return None
    plan, computed = _causal_plan(Sq, Sk, block_q, block_k, groups)
    kept = sum(min(max(i + Sk - Sq + 1, 0), Sk) for i in range(Sq))
    note_flash_causal_work(kernel, B * H * computed, B * H * kept)
    return plan


def _causal_tiles(causal, plan, reach, block_q, block_k, bb, hp, rows):
    """Run ``rows(bi, hh, r0, n, width, lo)`` for every row group of the
    kind of tile this grid step holds (:func:`_causal_plan`), for each of
    the program's ``bb`` batch rows and ``hp`` heads."""
    def tile(*groups):
        for bi in range(bb):
            for hh in range(hp):
                for group in groups:
                    rows(bi, hh, *group)

    if not causal:
        tile((0, block_q, block_k, None))
        return
    pl.when(reach >= block_k - 1)(
        functools.partial(tile, (0, block_q, block_k, None)))
    if plan is None:
        pl.when((reach > -block_q) & (reach < block_k - 1))(
            functools.partial(tile, (0, block_q, block_k, 0)))
        return
    for at, groups in plan.items():
        pl.when(reach == at)(functools.partial(tile, *groups))


def _fwd2_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                 m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                 off, rate, bb, hp, D, plan):
    bg, hg = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _rows(bi, hh, r0, n, width, lo):
        """ONE online-softmax update of the tile's query rows ``[r0, r0 +
        n)`` against its first ``width`` key columns."""
        lanes = slice(hh * D, (hh + 1) * D)
        rows = slice(r0, r0 + n)
        q = q_ref[bi, rows, lanes]
        k = k_ref[bi, :width, lanes]
        v = v_ref[bi, :width, lanes]
        s = _dot(q, k, ((1,), (1,))) * scale
        if lo is not None:
            s = _mask_from(s, lo, qi * block_q + r0, ki * block_k, off)
        m_prev = m_scr[bi, hh, rows][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift)
        if lo is not None and plan is None:
            # a row may see nothing of the tile. (A row of a plan's group
            # sees its own diagonal element: m_new is finite, and exp
            # gives the 0.)
            p = jnp.where(s == NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - shift)
        l_new = alpha * l_scr[bi, hh, rows][:, :1] \
            + jnp.sum(p, axis=1, keepdims=True)
        pv = p
        if rate > 0.0:
            pv = p * _dropout_keep(seed_ref, bg * bb + bi, hg * hp + hh,
                                   qi * block_q + r0, ki * block_k,
                                   p.shape, rate)
        acc_scr[bi, hh, rows] = acc_scr[bi, hh, rows] * alpha \
            + _dot(pv, v, ((1,), (0,)))
        m_scr[bi, hh, rows] = jnp.broadcast_to(m_new, (n, m_scr.shape[-1]))
        l_scr[bi, hh, rows] = jnp.broadcast_to(l_new, (n, l_scr.shape[-1]))

    _causal_tiles(causal, plan, qi * block_q + off - ki * block_k,
                  block_q, block_k, bb, hp, _rows)

    @pl.when(ki == nk - 1)
    def _finish():
        for bi in range(bb):
            outs = []
            for hh in range(hp):
                l = l_scr[bi, hh][:, :1]
                safe_l = jnp.where(l == 0.0, 1.0, l)
                outs.append(acc_scr[bi, hh] / safe_l)
                if lse_ref is not None:
                    m = m_scr[bi, hh][:, :1]
                    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
                    lse_ref[bi, hh] = jnp.broadcast_to(
                        lse, lse_ref[bi, hh].shape)
            o_ref[bi] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)


def _fwd2(q, k, v, scale, causal, block_q, block_k, hp, width,
          save_residuals=True, seed=None, rate=0.0, block_b=4, kv_group=1):
    """q: [B, S, E]; k,v: [B, S, E / kv_group] (one head a column block:
    query block ``h`` reads K/V block ``h // kv_group``). Returns
    (o [B,S,E], lse [B,H,Sq,8] or None)."""
    B, Sq, E = q.shape
    Sk = k.shape[1]
    D = width // hp
    H = E // D
    nq, nk = Sq // block_q, Sk // block_k
    while B % block_b:
        block_b //= 2
    bb = max(block_b, 1)
    # A grid step that holds ONE head of one batch row (a head of 128
    # lanes at batch 1: Command A+'s first chunks) has nothing to run
    # beside a group's chain of product, row maximum, exponential and
    # product, and four chains in a row cost the forward more than the
    # masked area saves: 3.68 ms a call for 3.50 at (1, 2048, 128 heads on
    # 8, 128), where two or more heads a step gain 8% (on the chip, PR 36)
    plan = _plan_of("flash_fwd", causal, B, H, Sq, Sk, block_q, block_k,
                    groups=bb * hp > 1)

    qs = pl.BlockSpec((bb, block_q, width), lambda b, h, i, j: (b, i, h))
    ks = pl.BlockSpec((bb, block_k, width), lambda b, h, i, j: (b, j, h)) \
        if kv_group == 1 else pl.BlockSpec(
            (bb, block_k, width), lambda b, h, i, j: (b, j, h // kv_group))
    in_specs = []
    args = []
    if rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    in_specs += [qs, ks, ks]
    args += [q, k, v]

    def kern(*refs):
        if rate > 0.0:
            seed_ref, refs = refs[0], refs[1:]
        else:
            seed_ref = None
        if save_residuals:
            q_r, k_r, v_r, o_r, lse_r, m_s, l_s, a_s = refs
        else:
            q_r, k_r, v_r, o_r, m_s, l_s, a_s = refs
            lse_r = None
        return _fwd2_kernel(seed_ref, q_r, k_r, v_r, o_r, lse_r, m_s, l_s,
                            a_s, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, off=Sk - Sq,
                            rate=rate, bb=bb, hp=hp, D=D, plan=plan)

    out_specs = [pl.BlockSpec((bb, block_q, width),
                              lambda b, h, i, j: (b, i, h))]
    out_shape = [jax.ShapeDtypeStruct((B, Sq, E), q.dtype)]
    if save_residuals:
        out_specs.append(pl.BlockSpec((bb, hp, block_q, 8),
                                      lambda b, h, i, j: (b, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, Sq, 8), jnp.float32))

    out = pl.pallas_call(
        kern,
        grid=(B // bb, H // hp, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bb, hp, block_q, 8), jnp.float32),
            pltpu.VMEM((bb, hp, block_q, 8), jnp.float32),
            pltpu.VMEM((bb, hp, block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_fwd",
    )(*args)
    if save_residuals:
        return out[0], out[1]
    return out[0], None


def _bwd2_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                 dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                 scale, causal, block_q, block_k, off, rate, bb, hp, D,
                 plan):
    """Fused backward: grid (B/bb, H/hp, nk, nq) with the q sweep innermost.

    dk/dv accumulate across the inner q sweep in block-sized scratch and
    are written at qi == nq-1 (their output block index is the OUTER ki,
    stable across the sweep, so the window flushes exactly once). dq
    accumulates across the whole (ki, qi) sweep in a full-Sq scratch; its
    output window spans the full sequence with a constant index per
    (b, h) program set and is written once at the final step."""
    bg, hg = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _rows(bi, hh, r0, n, width, lo):
        """The tile's query rows ``[r0, r0 + n)`` against its first
        ``width`` key columns: one accumulation into their rows of dq and
        into the first ``width`` rows of dk and dv."""
        lanes = slice(hh * D, (hh + 1) * D)
        rows = slice(r0, r0 + n)
        q = q_ref[bi, rows, lanes]
        do = do_ref[bi, rows, lanes]
        o = o_ref[bi, rows, lanes]
        k = k_ref[bi, :width, lanes]
        v = v_ref[bi, :width, lanes]
        lse = lse_ref[bi, hh, rows][:, :1]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=1, keepdims=True)
        s = _dot(q, k, ((1,), (1,))) * scale
        if lo is not None:
            s = _mask_from(s, lo, qi * block_q + r0, ki * block_k, off)
        p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
        dp = _dot(do, v, ((1,), (1,)))
        pv = p
        if rate > 0.0:
            keepf = _dropout_keep(seed_ref, bg * bb + bi, hg * hp + hh,
                                  qi * block_q + r0, ki * block_k,
                                  p.shape, rate)
            pv = p * keepf
            dp = dp * keepf
        # two products read ds: split for the MXU once
        ds = _mxu_parts(p * (dp - delta) * scale, k.dtype)
        dq_rows = pl.ds(pl.multiple_of(qi * block_q + r0, 128), n)
        dq_scr[bi, hh, dq_rows] += _dot(ds, k, ((1,), (0,)))
        dk_scr[bi, hh, :width] += _dot(ds, q, ((0,), (0,)))
        dv_scr[bi, hh, :width] += _dot(pv, do, ((0,), (0,)))

    _causal_tiles(causal, plan, qi * block_q + off - ki * block_k,
                  block_q, block_k, bb, hp, _rows)

    @pl.when(qi == nq - 1)
    def _write_dkv():
        for bi in range(bb):
            dk_ref[bi] = jnp.concatenate(
                [dk_scr[bi, hh] for hh in range(hp)],
                axis=1).astype(dk_ref.dtype)
            dv_ref[bi] = jnp.concatenate(
                [dv_scr[bi, hh] for hh in range(hp)],
                axis=1).astype(dv_ref.dtype)

    @pl.when((ki == nk - 1) & (qi == nq - 1))
    def _write_dq():
        for bi in range(bb):
            dq_ref[bi] = jnp.concatenate(
                [dq_scr[bi, hh] for hh in range(hp)],
                axis=1).astype(dq_ref.dtype)


def _bwd2(q, k, v, o, lse, do, scale, causal, block_q, block_k, hp, width,
          seed=None, rate=0.0, block_b=2):
    """q,k,v,o,do: [B, S, E]; lse: [B, H, Sq, 8]. Returns dq, dk, dv."""
    B, Sq, E = q.shape
    Sk = k.shape[1]
    D = width // hp
    H = E // D
    nq, nk = Sq // block_q, Sk // block_k
    while B % block_b:
        block_b //= 2
    bb = max(block_b, 1)
    plan = _plan_of("flash_bwd", causal, B, H, Sq, Sk, block_q, block_k)

    qs = pl.BlockSpec((bb, block_q, width), lambda b, h, j, i: (b, i, h))
    ks = pl.BlockSpec((bb, block_k, width), lambda b, h, j, i: (b, j, h))
    rowq = pl.BlockSpec((bb, hp, block_q, 8),
                        lambda b, h, j, i: (b, h, i, 0))
    in_specs = []
    args = []
    if rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    in_specs += [qs, ks, ks, qs, qs, rowq]
    args += [q, k, v, do, o, lse]

    def kern(*refs):
        if rate > 0.0:
            seed_ref, refs = refs[0], refs[1:]
        else:
            seed_ref = None
        return _bwd2_kernel(seed_ref, *refs, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, off=Sk - Sq,
                            rate=rate, bb=bb, hp=hp, D=D, plan=plan)

    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(B // bb, H // hp, nk, nq),
        in_specs=in_specs,
        out_specs=[
            # dq: one full-sequence window per (b, h) program set — the
            # index is constant over the (ki, qi) sweep so it flushes
            # exactly once, after the final accumulation step
            pl.BlockSpec((bb, Sq, width), lambda b, h, j, i: (b, 0, h)),
            pl.BlockSpec((bb, block_k, width), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((bb, block_k, width), lambda b, h, j, i: (b, j, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, E), q.dtype),
            jax.ShapeDtypeStruct((B, Sk, E), k.dtype),
            jax.ShapeDtypeStruct((B, Sk, E), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, hp, Sq, D), jnp.float32),
            pltpu.VMEM((bb, hp, block_k, D), jnp.float32),
            pltpu.VMEM((bb, hp, block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_bwd",
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
               lse_ref, dq_ref, acc_scr, *, scale, causal, block_q,
               block_k, off, rate):
    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = ((qi * block_q + block_q - 1 + off >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _step():
        lse = lse_ref[0, 0][:, :1]                       # [bq, 1]
        # delta = rowsum(dO * O), recomputed from the blocks already in
        # VMEM (D is small) — cheaper than an XLA precompute that writes
        # and lane-broadcasts a [B, H, S, 128] array through HBM
        delta = jnp.sum(do_ref[0, 0].astype(jnp.float32)
                        * o_ref[0, 0].astype(jnp.float32),
                        axis=1, keepdims=True)           # [bq, 1]
        s = _dot(q_ref[0, 0], k_ref[0, 0], ((1,), (1,))) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, off)
        # fully-masked row (lse = NEG_INF): shift by 0 so exp(-1e30) -> 0
        p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))  # [bq, bk]
        dp = _dot(do_ref[0, 0], v_ref[0, 0], ((1,), (1,)))
        if rate > 0.0:
            dp = dp * _dropout_keep(seed_ref, b, h, qi * block_q,
                                    ki * block_k, p.shape, rate)
        ds = p * (dp - delta) * scale
        acc_scr[:] += _dot(ds, k_ref[0, 0], ((1,), (0,)))

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                lse_ref, dk_ref, dv_ref, db_ref, dk_scr, dv_scr, db_scr, *,
                scale, causal, block_q, block_k, off, rate):
    b, h = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)          # k outer, q inner
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if db_scr is not None:
            db_scr[:] = jnp.zeros_like(db_scr)

    run = ((qi * block_q + block_q - 1 + off >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _step():
        lse = lse_ref[0, 0][:, :1]
        delta = jnp.sum(do_ref[0, 0].astype(jnp.float32)
                        * o_ref[0, 0].astype(jnp.float32),
                        axis=1, keepdims=True)           # [bq, 1]
        s = _dot(q_ref[0, 0], k_ref[0, 0], ((1,), (1,))) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, off)
        # fully-masked row (lse = NEG_INF): shift by 0 so exp(-1e30) -> 0
        p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))  # [bq, bk]
        pv = p
        dp = _dot(do_ref[0, 0], v_ref[0, 0], ((1,), (1,)))
        if rate > 0.0:
            # same (b, h, qi, ki) fold as the forward: identical mask
            keepf = _dropout_keep(seed_ref, b, h, qi * block_q,
                                  ki * block_k, p.shape, rate)
            pv = p * keepf
            dp = dp * keepf
        dv_scr[:] += _dot(pv, do_ref[0, 0], ((0,), (0,)))  # p~^T dO
        ds = p * (dp - delta) * scale
        dk_scr[:] += _dot(ds, q_ref[0, 0], ((0,), (0,)))  # ds^T q
        if db_scr is not None:
            # d(bias): ds summed over query rows (scale undone: bias adds to
            # the raw scores AFTER the q@k scaling)
            db_scr[:1] += jnp.sum(ds / scale, axis=0, keepdims=True)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)
        if db_ref is not None:
            db_ref[0, 0] = db_scr[:1].astype(db_ref.dtype)


def _mk_dkv_kernel(has_bias, has_seed=False, **kw):
    def wrapped(*refs):
        if has_seed:
            seed_ref, refs = refs[0], refs[1:]
        else:
            seed_ref = None
        if has_bias:
            return _dkv_kernel(seed_ref, *refs, **kw)
        q, k, v, do, o, lse, dk, dv, dk_scr, dv_scr = refs
        return _dkv_kernel(seed_ref, q, k, v, None, do, o, lse, dk, dv,
                           None, dk_scr, dv_scr, None, **kw)

    return wrapped


def _bwd_v1(q, k, v, bias, o, lse, do, scale, causal, block_q, block_k,
            seed=None, rate=0.0):
    """lse arrives as the forward's [B, H, Sq, 8] narrow-tile output
    and is fed straight to the kernels; delta = rowsum(dO*O) is computed
    in-kernel from the dO/O blocks (no XLA precompute, no HBM round-trip
    for either per-row vector)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // block_q, Sk // block_k

    qs = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    ks_j = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0))
    rowq = pl.BlockSpec((1, 1, block_q, 8), lambda b, h, i, j: (b, h, i, 0))

    seed_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  if rate > 0.0 else [])
    seed_args = [seed] if rate > 0.0 else []
    dq_in_specs = seed_specs + [qs, ks_j, ks_j]
    dq_args = seed_args + [q, k, v]
    if bias is not None:
        dq_in_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                        lambda b, h, i, j: (b, 0, 0, j)))
        dq_args.append(bias)
    dq_in_specs += [qs, qs, rowq]
    dq_args += [do, o, lse]

    dq = pl.pallas_call(
        _mk_kernel(_dq_kernel, bias is not None, has_seed=rate > 0.0,
                   scale=scale, causal=causal, block_q=block_q,
                   block_k=block_k, off=Sk - Sq, rate=rate),
        grid=(B, H, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_dq_v1",
    )(*dq_args)

    # dkv: grid (B, H, nk, nq) — i indexes k blocks, j indexes q blocks
    qs_j = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, j, 0))
    ks_i = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, i, 0))
    rowq_j = pl.BlockSpec((1, 1, block_q, 8),
                          lambda b, h, i, j: (b, h, j, 0))
    dkv_in_specs = seed_specs + [qs_j, ks_i, ks_i]
    dkv_args = seed_args + [q, k, v]
    if bias is not None:
        dkv_in_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                         lambda b, h, i, j: (b, 0, 0, i)))
        dkv_args.append(bias)
    dkv_in_specs += [qs_j, qs_j, rowq_j]
    dkv_args += [do, o, lse]

    dkv_out_specs = [
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, i, 0)),
    ]
    dkv_out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((block_k, D), jnp.float32),
        pltpu.VMEM((block_k, D), jnp.float32),
    ]
    if bias is not None:
        # per-(b, h) bias gradient rows; summed over heads below
        dkv_out_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                          lambda b, h, i, j: (b, h, 0, i)))
        dkv_out_shape.append(
            jax.ShapeDtypeStruct((B, H, 1, Sk), jnp.float32))
        dkv_scratch.append(pltpu.VMEM((8, block_k), jnp.float32))

    outs = pl.pallas_call(
        _mk_dkv_kernel(bias is not None, has_seed=rate > 0.0, scale=scale,
                       causal=causal, block_q=block_q, block_k=block_k,
                       off=Sk - Sq, rate=rate),
        grid=(B, H, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shape,
        scratch_shapes=dkv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_dkv_v1",
    )(*dkv_args)
    if bias is not None:
        dk, dv, db_h = outs
        db = jnp.sum(db_h, axis=1, keepdims=True)        # [B, 1, 1, Sk]
        return dq, dk, dv, db
    dk, dv = outs
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# routing + public entry (custom VJP over [B, S, H, D])
# ---------------------------------------------------------------------------


def _seed_arr(seed_f):
    """f32-bitcast seed words back to int32 (seed travels as a float arg so
    the custom_vjp can hand back a plain zero cotangent)."""
    return jax.lax.bitcast_convert_type(seed_f, jnp.int32)


# VMEM budgets (bytes) for picking how many batch rows one v2 program
# processes: the unrolled (bi, hh) loop keeps ~1 score tile live per
# iteration in the forward and ~3 (s/dp/ds) in the backward, and the fused
# backward additionally carries a full-Sq f32 dq scratch. The TPU scoped
# vmem limit is 16M; stay well under it.
_V2_FWD_TILE_BUDGET = 4 * 1024 * 1024
_V2_BWD_TILE_BUDGET = 8 * 1024 * 1024
# the fused backward carries a full-Sq f32 dq scratch AND a full-Sq dq
# output window; beyond this they crowd out the score tiles (measured:
# S=8192/D=64/hp=2 overflows the 16M scoped limit), so longer sequences
# route to the v1 split kernels, which tile everything
_V2_SCRATCH_CAP = 2 * 1024 * 1024


def _v2_plan(q, bias, block_q, block_k):
    """(hp, width, bb_fwd, bb_bwd) when the v2 layout-native kernels
    apply; None routes to v1."""
    B, Sq, H, D = q.shape
    if bias is not None:
        return None
    hp, width = _heads_per_block(D, H)
    if hp is None:
        return None
    tile = block_q * block_k * 4

    def pick(budget_tiles, scratch_per_b):
        bb = 8
        while bb > 1 and (B % bb or bb * hp * tile > budget_tiles
                          or bb * scratch_per_b > _V2_SCRATCH_CAP):
            bb //= 2
        return bb

    bb_fwd = pick(_V2_FWD_TILE_BUDGET, 0)
    bb_bwd = pick(_V2_BWD_TILE_BUDGET // 3, hp * Sq * D * 4)
    if hp * Sq * D * 4 > _V2_SCRATCH_CAP:
        return None
    return hp, width, bb_fwd, bb_bwd


def _fwd(q, k, v, bias, scale, causal, block_q, block_k,
         save_residuals=True, seed=None, rate=0.0):
    """Route [B, S, H, D] inputs to the layout-native v2 kernels (no
    transpose materializes) or the v1 [B, H, S, D] kernels (bias case;
    grouped K/V heads that share a lane block). ``k``/``v`` may hold
    fewer heads than ``q``: query head ``h`` reads K/V head
    ``h // (H / Hkv)``, through the K/V blocks' index maps — nothing is
    repeated in HBM."""
    kv_group = q.shape[2] // k.shape[2]
    plan = _v2_plan(q, bias, block_q, block_k)
    if plan is not None and kv_group > 1 and plan[0] != 1:
        plan = None
    if plan is not None:
        hp, width, bb_fwd, _ = plan
        B, Sq, H, D = q.shape
        E = H * D
        o, lse = _fwd2(q.reshape(B, Sq, E), k.reshape(B, k.shape[1], -1),
                       v.reshape(B, v.shape[1], -1), scale, causal, block_q,
                       block_k, hp, width, save_residuals=save_residuals,
                       seed=seed, rate=rate, block_b=bb_fwd,
                       kv_group=kv_group)
        return o.reshape(q.shape), lse
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    o, lse = _fwd_v1(qt, kt, vt, bias, scale, causal, block_q, block_k,
                     save_residuals=save_residuals, seed=seed, rate=rate,
                     kv_group=kv_group)
    return jnp.swapaxes(o, 1, 2), lse


def _bwd_impl(q, k, v, bias, o, lse, do, scale, causal, block_q, block_k,
              seed=None, rate=0.0):
    plan = _v2_plan(q, bias, block_q, block_k)
    if plan is not None:
        hp, width, _, bb_bwd = plan
        B, Sq, H, D = q.shape
        E = H * D
        r3 = lambda x: x.reshape(B, x.shape[1], E)
        dq, dk, dv = _bwd2(r3(q), r3(k), r3(v), r3(o), lse, r3(do), scale,
                           causal, block_q, block_k, hp, width, seed=seed,
                           rate=rate, block_b=bb_bwd)
        return (dq.reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape), None)
    qt, kt, vt, ot, dot_ = (jnp.swapaxes(x, 1, 2)
                            for x in (q, k, v, o, do))
    dq, dk, dv, db = _bwd_v1(qt, kt, vt, bias, ot, lse, dot_, scale, causal,
                             block_q, block_k, seed=seed, rate=rate)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2), db)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, bias, seed_f, scale, causal, block_q, block_k, rate):
    o, _ = _fwd(q, k, v, bias, scale, causal, block_q, block_k,
                save_residuals=False, seed=_seed_arr(seed_f), rate=rate)
    return o


def _flash_fwd(q, k, v, bias, seed_f, scale, causal, block_q, block_k,
               rate):
    o, lse = _fwd(q, k, v, bias, scale, causal, block_q, block_k,
                  seed=_seed_arr(seed_f), rate=rate)
    # What the backward keeps is NAMED, so that a remat policy can keep
    # it too (FLASH_RESIDUAL_NAMES). The named output is also what the
    # rest of the block reads: a recomputed block then needs nothing
    # that only the kernel can give. `o` is named as [B, S, E], the
    # kernel's own layout (as [B, S, H, D] XLA:TPU kept the layers'
    # stack S-minor and relaid 16 MB a layer out each way). Of the
    # log-sum-exp tile's 8 equal columns one is kept: [B, H, S] lies
    # dense in HBM, where the TPU layout pads 8 lanes to 128 (0.5 MB a
    # layer at B=8, S=1024, H=16 against 64).
    o = checkpoint_name(o.reshape(o.shape[:2] + (-1,)),
                        FLASH_RESIDUAL_NAMES[0]).reshape(o.shape)
    lse = checkpoint_name(lse[..., 0], FLASH_RESIDUAL_NAMES[1])
    return o, (q, k, v, bias, seed_f, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, rate, res, do):
    q, k, v, bias, seed_f, o, lse = res
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (8,))
    dq, dk, dv, db = _bwd_impl(q, k, v, bias, o, lse, do, scale, causal,
                               block_q, block_k, seed=_seed_arr(seed_f),
                               rate=rate)
    if bias is not None:
        db = db.astype(bias.dtype)
    return dq, dk, dv, db, jnp.zeros_like(seed_f)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _env_block(var: str, default: int) -> int:
    """Validated block-size override from the environment; the error names
    the env var so a bad value is traceable to its source (a bare int()
    ValueError at every flash call gave no hint an env var was the cause)."""
    import os
    raw = os.environ.get(var)
    if not raw:
        return default
    try:
        b = int(raw)
    except ValueError:
        raise ValueError(
            f"{var}={raw!r}: the flash-attention block override must be an "
            f"integer number of rows (a multiple of 128)") from None
    if b <= 0 or b % 128:
        raise ValueError(
            f"{var}={b}: the flash-attention block override must be a "
            f"positive multiple of 128 (the TPU lane tile)")
    return b


def _pick_block(seq_len: int, requested: int) -> int:
    """Largest multiple of 128 that divides seq_len, capped at `requested`
    (so 768 -> 384 with the 512 default rather than failing)."""
    if seq_len % 128:
        raise ValueError(f"flash attention needs seq_len % 128 == 0, "
                         f"got {seq_len}")
    start = (min(requested, seq_len) // 128) * 128
    for b in range(start, 127, -128):
        if seq_len % b == 0:
            return b
    return 128


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK,
                    block_k: int = DEFAULT_BLOCK,
                    dropout_rate: float = 0.0, dropout_key=None):
    """Flash attention over [B, S, H, D] inputs (framework layout).

    bias: optional additive mask broadcastable to [B, 1, 1, Sk]
    (e.g. key padding: 0 keep, -1e30 masked).
    dropout_rate/dropout_key: in-kernel attention dropout via a stateless
    counter-based hash (works on TPU and in the interpreter); masks are
    regenerated from the seed in the backward, nothing is stored.
    ``k``/``v`` may be ``[B, Sk, Hkv, D]`` with ``Hkv`` dividing ``H``
    (grouped-query attention: query head ``h`` reads K/V head
    ``h // (H / Hkv)``): the forward alone, for serving — no gradient,
    no dropout.
    Returns [B, S, H, D].
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    grouped = k.shape[2] != H
    if grouped and (H % k.shape[2] or v.shape[2] != k.shape[2]
                    or dropout_rate):
        raise ValueError(
            f"flash attention: {H} query heads over {k.shape[2]} K and "
            f"{v.shape[2]} V heads (dropout {dropout_rate}): grouped heads "
            "need Hkv | H, the same for K and V, and no dropout")
    # tuning override without touching call sites (block sweeps on real
    # hardware). Only applied when the caller left the block size at its
    # default — an explicit block_q/block_k argument always wins over the
    # environment.
    if block_q == DEFAULT_BLOCK:
        block_q = _env_block("PTPU_FLASH_BLOCK_Q", block_q)
    if block_k == DEFAULT_BLOCK:
        block_k = _env_block("PTPU_FLASH_BLOCK_K", block_k)
    block_q = _pick_block(Sq, block_q)
    block_k = _pick_block(Sk, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if bias is not None:
        bias = jnp.broadcast_to(jnp.asarray(bias, jnp.float32),
                                (B, 1, 1, Sk))
    rate = float(dropout_rate)
    if rate >= 1.0:
        # everything dropped: defined all-zeros output (matches the XLA
        # composition); avoids 0/0 from the 1/(1-rate) scaling
        return jnp.zeros_like(q)
    if rate > 0.0:
        if dropout_key is None:
            raise ValueError("dropout_rate > 0 needs dropout_key")
        words = jax.random.key_data(dropout_key).ravel()[:2]
        seed_f = jax.lax.bitcast_convert_type(
            words.astype(jnp.uint32), jnp.float32)
    else:
        seed_f = jnp.zeros((2,), jnp.float32)
    if grouped:
        return _fwd(q, k, v, bias, float(scale), bool(causal), int(block_q),
                    int(block_k), save_residuals=False)[0]
    return _flash(q, k, v, bias, seed_f, float(scale), bool(causal),
                  int(block_q), int(block_k), rate)
