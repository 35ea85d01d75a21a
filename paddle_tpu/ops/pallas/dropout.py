"""Fused dropout kernel (TPU).

reference parity: the reference's dropout op generates a mask with
curand, stores it, and multiplies (operators/dropout_op.cu); under XLA
the same composition materializes the random bits, the keep mask, and
the product as separate HBM round-trips (~4x the minimal traffic on a
BERT-base step).

TPU-native: ONE pass — the kernel reads x, computes the keep decision
from a stateless murmur3-finalizer hash over the absolute element index
(same construction as the flash kernel's in-kernel dropout), and writes
x * keep / (1-p). Nothing else touches HBM. The backward REGENERATES the
identical mask from the seed (custom_vjp), so no mask is ever stored.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["fused_dropout"]

_LANES = 128
_ROWS = 512            # rows per program: 512x128 f32 tile = 256KB


def _keep_mask(idx, seed0, seed1, rate):
    """Keep decision over absolute element indices (shared fmix32)."""
    from .rng import fmix32, keep_threshold
    x = fmix32(idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
               ^ seed0.astype(jnp.uint32)
               ^ (seed1.astype(jnp.uint32) << 1))
    return x >= keep_threshold(rate)


def _drop_kernel(seed_ref, x_ref, o_ref, *, rate):
    i = pl.program_id(0)
    rows, lanes = x_ref.shape
    base = i * rows * lanes
    idx = base + (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
                  * lanes
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    keep = _keep_mask(idx, seed_ref[0], seed_ref[1], rate)
    inv = 1.0 / (1.0 - rate)
    x = x_ref[...]
    o_ref[...] = jnp.where(keep, x * jnp.asarray(inv, x.dtype),
                           jnp.zeros_like(x))


def _run(x2d, seed, rate):
    R, C = x2d.shape
    # bound the BLOCK jointly over rows x lane-width: keep in+out blocks
    # around 256KB f32 each regardless of C (wide activations otherwise
    # blow the ~16M VMEM with 512-row blocks). rb is a power of two >= 8
    # (sublane multiple) that divides R (caller guarantees R % 8 == 0).
    budget = max(8, _ROWS * _LANES // C)
    rb = 8
    while rb * 2 <= budget and R % (rb * 2) == 0:
        rb *= 2
    nb = R // rb
    return pl.pallas_call(
        functools.partial(_drop_kernel, rate=rate),
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((rb, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=_interpret(),
        name="fused_dropout",
    )(seed, x2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dropout(x2d, seed_f, rate):
    return _run(x2d, jax.lax.bitcast_convert_type(seed_f, jnp.int32), rate)


def _dropout_fwd(x2d, seed_f, rate):
    return _dropout(x2d, seed_f, rate), seed_f


def _dropout_bwd(rate, seed_f, g):
    # identical mask regenerated from the seed: d(drop(x))/dx = mask/(1-p)
    dg = _run(g, jax.lax.bitcast_convert_type(seed_f, jnp.int32), rate)
    return dg, jnp.zeros_like(seed_f)


_dropout.defvjp(_dropout_fwd, _dropout_bwd)


def fused_dropout(x, rate: float, key):
    """Single-pass dropout over an array of any shape (upscale_in_train).

    Pads the flattened input to a whole number of (512, 128) tiles; the
    pad cost is bounded by one tile (64K elements)."""
    rate = float(rate)
    if rate <= 0.0:
        return x
    if rate >= 1.0:
        return jnp.zeros_like(x)
    words = jax.random.key_data(key).ravel()[:2].astype(jnp.uint32)
    seed_f = jax.lax.bitcast_convert_type(words, jnp.float32)
    n = x.size
    # natural 2D view when the trailing dim is lane-aligned: the reshape
    # [..., C] -> [n//C, C] is a free bitcast (no relayout copies)
    C = x.shape[-1] if (x.ndim >= 2 and x.shape[-1] % _LANES == 0
                        and x.shape[-1] <= 4096) else _LANES
    if n % C == 0 and (n // C) % 8 == 0:
        out = _dropout(x.reshape(n // C, C), seed_f, rate)
        return out.reshape(x.shape)
    tile = _ROWS * _LANES
    padded = (n + tile - 1) // tile * tile
    flat = x.reshape(-1)
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    out = _dropout(flat.reshape(padded // _LANES, _LANES), seed_f, rate)
    return out.reshape(-1)[:n].reshape(x.shape)
