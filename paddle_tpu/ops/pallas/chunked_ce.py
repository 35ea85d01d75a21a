"""Pallas fused chunked (streamed-vocab) cross-entropy (TPU).

The kernel form of :mod:`paddle_tpu.nn.chunked_ce`'s hard-label path —
the TPU-native replacement for the reference's fused CUDA
``softmax_with_cross_entropy`` op (reference:
paddle/fluid/operators/softmax_with_cross_entropy_op.cu).

Why a kernel when the XLA streaming loop already avoids the full-vocab
f32 materialization: the ``fori_loop`` body is a sequence of separate
HLO ops (dynamic-slice → convert → reduce → …) that XLA schedules as
individual HBM round trips per chunk, and the backward's
read-modify-write ``dynamic_update_slice`` forces a full extra
read+write of the gradient buffer. Here each ``[block_n, chunk]`` tile
is VMEM-resident for its whole fwd (online (m, s) logsumexp recurrence)
or bwd (``(softmax - onehot) * g``) pass: the logits are read exactly
once forward and once backward, the dlogits tile is written exactly
once, and the row statistics ride a narrow 8-lane tile like
flash_attention's lse.

Semantics are pinned to ``nn.chunked_ce._ce_hard``: f32 accumulation,
loss = lse - logits[n, label[n]] in f32, dlogits = (p - onehot) * g in
the logits dtype. ignore_index / class weights / reductions stay in the
differentiable epilogue OUTSIDE the kernel (nn/functional.py), so the
public ``F.cross_entropy`` semantics are untouched. Soft labels keep
the XLA streaming path.

Grid/blocking: ``(ceil(N / block_n), ceil(V / chunk))`` with the vocab
sweep innermost (``arbitrary``); ``block_n`` rows per program
(``PTPU_CE_BLOCK_N``, default 128; the backward halves it until its
two-tile footprint fits the scoped VMEM, see ``_bwd_block_n``), chunk
width from ``FLAGS_chunked_ce_chunk`` (multiples of 128 keep Mosaic lane
tiles exact; any tail is masked in-kernel, never padded in HBM).

Tests run these kernels on CPU via the Pallas interpreter
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

__all__ = ["chunked_ce_loss", "DEFAULT_BLOCK_N"]

DEFAULT_BLOCK_N = 128
NEG_INF = -1e30


def _block_n() -> int:
    """Row-block override following the PTPU_FLASH_BLOCK_Q/K convention."""
    raw = os.environ.get("PTPU_CE_BLOCK_N")
    if not raw:
        return DEFAULT_BLOCK_N
    try:
        b = int(raw)
    except ValueError:
        raise ValueError(
            f"PTPU_CE_BLOCK_N={raw!r}: the chunked-CE row-block override "
            f"must be a positive integer number of rows") from None
    if b <= 0 or b % 8:
        raise ValueError(
            f"PTPU_CE_BLOCK_N={b}: the chunked-CE row-block override "
            f"must be a positive multiple of 8 (the TPU sublane tile) — "
            f"Mosaic would reject the block shape with an error that "
            f"never names this variable")
    return b


def _col_ids(j, block_n: int, chunk: int):
    """Absolute vocab column ids of chunk ``j``, [block_n, chunk]."""
    return j * chunk + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, chunk), 1)


# ---------------------------------------------------------------------------
# forward: online logsumexp over the vocab sweep
# ---------------------------------------------------------------------------


def _lse_kernel(logits_ref, lse_ref, m_scr, s_scr, *, block_n, chunk, V):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        s_scr[:] = jnp.zeros_like(s_scr)

    sl = logits_ref[...].astype(jnp.float32)             # [bn, chunk]
    # tail chunk of a non-multiple vocab: mask the overhang columns
    sl = jnp.where(_col_ids(j, block_n, chunk) < V, sl, NEG_INF)
    m_prev = m_scr[:, :1]                                # [bn, 1]
    m_new = jnp.maximum(m_prev, jnp.max(sl, axis=1, keepdims=True))
    # fully-masked tile: m_new stays NEG_INF; shift by 0 to avoid inf-inf
    shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(sl - shift)                              # masked cols -> 0
    s_scr[:] = jnp.broadcast_to(
        s_scr[:, :1] * jnp.exp(m_prev - shift)
        + jnp.sum(p, axis=1, keepdims=True), s_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nj - 1)
    def _finish():
        m = m_scr[:, :1]
        s = s_scr[:, :1]
        safe_s = jnp.where(s == 0.0, 1.0, s)
        lse = jnp.where(s == 0.0, NEG_INF, m + jnp.log(safe_s))
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _online_lse(logits, block_n: int, chunk: int):
    """Row logsumexp of [N, V] logits; returns the narrow [N, 8] f32
    row-stat tile (column 0 is the value — same convention as
    flash_attention's lse output)."""
    N, V = logits.shape
    ni, nj = pl.cdiv(N, block_n), pl.cdiv(V, chunk)
    return pl.pallas_call(
        functools.partial(_lse_kernel, block_n=block_n, chunk=chunk, V=V),
        grid=(ni, nj),
        in_specs=[pl.BlockSpec((block_n, chunk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_n, 8), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 8), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_n, 8), jnp.float32),
            pltpu.VMEM((block_n, 8), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="chunked_ce_lse",
    )(logits)


# ---------------------------------------------------------------------------
# backward: dlogits = (softmax - onehot) * g, one pass, no accumulation
# ---------------------------------------------------------------------------


def _dlogits_kernel(logits_ref, lab_ref, lse_ref, g_ref, dl_ref, *,
                    block_n, chunk, V):
    j = pl.program_id(1)
    sl = logits_ref[...].astype(jnp.float32)             # [bn, chunk]
    lse = lse_ref[:, :1]                                 # [bn, 1]
    cols = _col_ids(j, block_n, chunk)
    # fully-padded row (grid overhang): lse = NEG_INF -> shift by 0 so
    # exp stays finite; the row's write is dropped by the grid bounds
    p = jnp.exp(sl - jnp.where(lse == NEG_INF, 0.0, lse))
    onehot = (cols == lab_ref[:, :1]).astype(jnp.float32)
    d = (p - onehot) * g_ref[:, :1]
    d = jnp.where(cols < V, d, 0.0)
    dl_ref[...] = d.astype(dl_ref.dtype)


#: Mosaic's default scoped-VMEM allowance is 16 MiB; the backward keeps
#: its estimated tile footprint under this much of it
_BWD_VMEM_BUDGET = 14 << 20


def _bwd_block_n(block_n: int, chunk: int, itemsize: int) -> int:
    """Rows per backward program. The backward holds a logits tile AND a
    dlogits tile (each double-buffered by the pipeline) plus about two
    f32 ``[block_n, chunk]`` temporaries, twice the forward's footprint:
    at the forward's 128 rows x 8192 columns the v5e compiler refuses it
    (16.25 MiB bf16 / 20.38 MiB f32 against the 16 MiB scoped limit). So
    the row block is halved until the estimate fits; the forward's block
    is unchanged."""
    # halving keeps a multiple of the 8-row sublane tile
    while (block_n % 16 == 0
           and block_n * chunk * (4 * itemsize + 8) > _BWD_VMEM_BUDGET):
        block_n //= 2
    return block_n


def _dlogits(logits, labels, lse, g, block_n: int, chunk: int):
    N, V = logits.shape
    block_n = _bwd_block_n(block_n, chunk, logits.dtype.itemsize)
    ni, nj = pl.cdiv(N, block_n), pl.cdiv(V, chunk)
    row8 = pl.BlockSpec((block_n, 8), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_dlogits_kernel, block_n=block_n, chunk=chunk,
                          V=V),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((block_n, chunk), lambda i, j: (i, j)),
            row8,                                        # labels [N, 8]
            row8,                                        # lse    [N, 8]
            row8,                                        # g      [N, 8]
        ],
        out_specs=pl.BlockSpec((block_n, chunk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, V), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
        name="chunked_ce_dlogits",
    )(logits, labels, lse, g)


def _row8(x, dtype):
    """Broadcast a [N] per-row vector to the narrow 8-lane tile the
    kernels consume (Mosaic's minimum lane width; 16x less HBM than a
    128-lane broadcast)."""
    return jnp.broadcast_to(x.astype(dtype)[:, None], (x.shape[0], 8))


# ---------------------------------------------------------------------------
# custom VJP (same signature/semantics as nn.chunked_ce._ce_hard)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ce(block_n: int, chunk: int, logits, labels):
    loss, _ = _ce_fwd(block_n, chunk, logits, labels)
    return loss


def _ce_fwd(block_n: int, chunk: int, logits, labels):
    lse8 = _online_lse(logits, block_n, chunk)
    tgt = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    loss = lse8[:, 0] - tgt.astype(jnp.float32)
    return loss, (logits, labels, lse8)


def _ce_bwd(block_n: int, chunk: int, res, g):
    logits, labels, lse8 = res
    grad = _dlogits(logits, _row8(labels, jnp.int32), lse8,
                    _row8(g, jnp.float32), block_n, chunk)
    return grad, np.zeros(labels.shape, dtype=jax.dtypes.float0)


_ce.defvjp(_ce_fwd, _ce_bwd)


def chunked_ce_loss(logits, labels, chunk: int):
    """Fused streamed hard-label NLL: ``logits [N, V]``, ``labels [N]``
    int32 class ids (the caller maps ignore_index to a safe id and masks
    the result — same contract as ``nn.chunked_ce.hard_nll``). Returns
    f32 ``[N]`` per-row losses; differentiable in ``logits``."""
    N, V = logits.shape
    chunk = max(1, min(int(chunk), V))
    # cap at N rounded UP to the sublane tile: a short batch gets one
    # 8-aligned block (grid-overhang rows are masked/dropped in-kernel)
    block_n = min(_block_n(), max(8, -(-N // 8) * 8))
    return _ce(int(block_n), int(chunk), logits,
               labels.astype(jnp.int32))
