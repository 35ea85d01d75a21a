"""Sequence-parallel attention tests on the 8-device CPU mesh.

No reference analogue exists (SURVEY §2.3: the reference has no SP) —
gold standard is single-device full attention; the sharded ring/Ulysses
runs must match it.
"""

import jax
from paddle_tpu.distributed.env import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.ops.attention import _sdpa_xla
from paddle_tpu.ops.ring_attention import (block_attention, ring_attention,
                                           ulysses_attention)

N = 8
B, S, H, D = 2, 64, 8, 16      # S sharded 8 ways -> 8 tokens per device


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("sp",))


def _qkv(seed):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D)  # noqa: E731
                             .astype(np.float32) * 0.5)
    return mk(), mk(), mk()


def _gold(q, k, v, causal):
    with jax.default_matmul_precision("highest"):
        return _sdpa_xla(q, k, v, None, 0.0, causal, None)


def test_block_attention_matches_sdpa():
    q, k, v = _qkv(0)
    o, lse = block_attention(q, k, v, causal=True)
    ref = _gold(q, k, v, True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert lse.shape == (B, S, H)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    q, k, v = _qkv(1)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    ring = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    out = ring(q, k, v)
    ref = _gold(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_ring_attention_grads_match_full():
    q, k, v = _qkv(2)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    def ring_loss(q, k, v):
        # check_vma off, as at the framework's own shard_map call sites
        out = shard_map(
            lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    def full_loss(q, k, v):
        return jnp.sum(_gold(q, k, v, True) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_full, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    q, k, v = _qkv(3)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    uly = jax.jit(shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sp", causal=causal,
                                          use_flash=False),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    out = uly(q, k, v)
    ref = _gold(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_ring_long_sequence_memory_shape():
    # 8x the single-shard length: each device only ever holds S/8 keys
    q, k, v = _qkv(4)
    mesh = _mesh()
    spec = P(None, "sp", None, None)
    out = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))(q, k, v)
    assert out.shape == (B, S, H, D)
    # sharding preserved on the sequence axis (trailing Nones normalized)
    assert out.sharding.spec[1] == "sp"
