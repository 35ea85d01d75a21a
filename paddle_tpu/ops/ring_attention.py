"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism (SURVEY §2.3: "Absent... must be
designed new") — long sequences there lean on recompute + fused kernels.
This module is the TPU-first design SURVEY §7.8 prescribes:

- **Ring attention**: Q stays put; K/V blocks rotate around the 'sp' mesh
  axis via `ppermute` (ICI neighbor exchanges). Each step computes local
  block attention and merges into a running (out, lse) with the numerically
  stable log-sum-exp combine — the cross-device generalization of the flash
  kernel's online softmax. Peak memory is O(S_local), enabling sequences
  n_sp times longer than one chip could hold.
- **Ulysses**: all-to-all swaps the sharded axis (sequence <-> heads), runs
  FULL-sequence attention on 1/n of the heads locally (dispatching to the
  Pallas flash kernel on TPU), and swaps back. Cheaper collectives for
  moderate S; requires num_heads % n == 0.

Both are plain functions over arrays, designed to run inside `shard_map`
over the mesh's 'sp' axis; `jax.grad` differentiates through them
(ppermute/all_to_all have registered transposes), so no custom VJP needed.

Block attention is computed in f32 with the framework matmul policy; causal
ring steps pick full/causal/skip per K/V-block origin with `lax.switch`.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.flags import matmul_precision

__all__ = ["ring_attention", "ulysses_attention", "block_attention"]

NEG_INF = -1e30


def _pcast_varying(x, axis_name):
    """Mark ``x`` as device-varying over ``axis_name`` for shard_map's VMA
    type checking."""
    return lax.pcast(x, (axis_name,), to="varying")


def block_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Local attention returning (out, lse) for cross-block merging.

    q: [B, Sq, H, D], k/v: [B, Sk, H, D] -> out [B, Sq, H, D],
    lse [B, Sq, H] (f32). The XLA composition; block sizes inside the ring
    are S_local so XLA's fusion handles them well.
    """
    B, Sq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    prec = matmul_precision()
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * scale
    s = s.astype(jnp.float32)
    if causal:
        Sk = k.shape[1]
        cmask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(cmask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(m <= NEG_INF, 0.0, m)
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= NEG_INF, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bkhd->bqhd", (p / l_safe).astype(q.dtype), v,
                   precision=prec)
    lse = jnp.where(l[..., 0] == 0.0, NEG_INF, m[..., 0] + jnp.log(l_safe[..., 0]))
    return o, jnp.swapaxes(lse, 1, 2)      # lse -> [B, Sq, H]


def _merge(o_a, lse_a, o_b, lse_b):
    """Combine two attention partials over disjoint key sets.

    The accumulator (o_a) stays f32 across ring steps — casting back to
    bf16 every step would compound ~n rounding truncations."""
    m = jnp.maximum(lse_a, lse_b)
    m_safe = jnp.where(m <= NEG_INF, 0.0, m)
    wa = jnp.exp(lse_a - m_safe)
    wb = jnp.exp(lse_b - m_safe)
    denom = wa + wb
    denom = jnp.where(denom == 0.0, 1.0, denom)
    o = (o_a.astype(jnp.float32) * (wa / denom)[..., None]
         + o_b.astype(jnp.float32) * (wb / denom)[..., None])
    lse = m + jnp.log(denom)
    lse = jnp.where(m <= NEG_INF, NEG_INF, lse)
    return o, lse


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over a sharded sequence (call inside shard_map).

    q/k/v: LOCAL shards [B, S_local, H, D]; the sequence axis is sharded
    over ``axis_name``. K/V rotate n times by `ppermute`; causal masking is
    exact: earlier-rank blocks attend fully, the home block causally, later
    blocks are skipped (they contribute -inf lse).
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]   # kv moves to next rank

    def step(carry, _):
        o_acc, lse_acc, kb, vb, src = carry
        # kb/vb originated at rank `src`
        def full(_):
            return block_attention(q, kb, vb, causal=False, scale=scale)

        def diag(_):
            return block_attention(q, kb, vb, causal=True, scale=scale)

        def skip(_):
            z = jnp.full(q.shape[:2] + (q.shape[2],), NEG_INF, jnp.float32)
            return (jnp.zeros_like(q),
                    _pcast_varying(z, axis_name))

        if causal:
            rel = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            o_i, lse_i = lax.switch(rel, [full, diag, skip], None)
        else:
            o_i, lse_i = full(None)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_i, lse_i)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        src = (src - 1) % n                     # our kv now came from src-1
        return (o_acc, lse_acc, kb, vb, src), None

    o0 = jnp.zeros(q.shape, jnp.float32)   # f32 accumulator (see _merge)
    lse0 = jnp.full(q.shape[:2] + (q.shape[2],), NEG_INF, jnp.float32)
    # mark the constant initial carries as device-varying so the scan carry
    # type matches the per-device outputs under shard_map's vma checking
    o0 = _pcast_varying(o0, axis_name)
    lse0 = _pcast_varying(lse0, axis_name)
    (o, lse, _, _, _), _ = lax.scan(step, (o0, lse0, k, v, my), None,
                                    length=n)
    return o.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None, use_flash: bool = True):
    """Ulysses SP (call inside shard_map): all-to-all seq<->heads, full
    attention on the local head slice, all-to-all back.

    q/k/v: LOCAL shards [B, S_local, H, D] with H % n == 0. After the first
    all_to_all each device holds [B, S_full, H/n, D].
    """
    n = lax.psum(1, axis_name)

    def seq_to_heads(x):
        # [B, S/n, H, D] -> gather seq, scatter heads -> [B, S, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    from .attention import sdpa_array
    of = sdpa_array(qf, kf, vf, mask=None, dropout_p=0.0, is_causal=causal,
                    use_flash=use_flash)
    return heads_to_seq(of.astype(q.dtype))
