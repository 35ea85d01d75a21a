"""Fused Pallas dropout (ops/pallas/dropout.py) — interpreter-run on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.dropout import fused_dropout

# the kernel is called directly: the marker selects the interpreter
pytestmark = pytest.mark.pallas


@pytest.mark.parametrize("shape", [(512, 128), (48, 33, 77), (70000,)])
def test_mask_statistics_and_scaling(shape):
    key = jax.random.key(7)
    x = jnp.ones(shape, jnp.float32)
    out = np.asarray(fused_dropout(x, 0.3, key))
    kept = out != 0.0
    # kept values scaled by 1/(1-p)
    np.testing.assert_allclose(out[kept], 1.0 / 0.7, rtol=1e-6)
    # keep rate ~ 1-p
    assert abs(kept.mean() - 0.7) < 0.02, kept.mean()


def test_backward_regenerates_identical_mask():
    key = jax.random.key(3)
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(256, 512)).astype(np.float32))

    def loss(a):
        return jnp.sum(fused_dropout(a, 0.4, key) * 2.0)

    out = fused_dropout(x, 0.4, key)
    g = jax.grad(loss)(x)
    # gradient = 2/(1-p) exactly where the forward kept the element
    kept = np.asarray(out) != 0.0
    np.testing.assert_allclose(np.asarray(g)[kept], 2.0 / 0.6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g)[~kept], 0.0)


def test_different_keys_different_masks():
    x = jnp.ones((512, 128), jnp.float32)
    a = np.asarray(fused_dropout(x, 0.5, jax.random.key(0)))
    b = np.asarray(fused_dropout(x, 0.5, jax.random.key(1)))
    assert (a != b).any()


def test_edge_rates():
    x = jnp.ones((8, 8), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fused_dropout(x, 0.0, jax.random.key(0))), 1.0)
    np.testing.assert_allclose(
        np.asarray(fused_dropout(x, 1.0, jax.random.key(0))), 0.0)


def test_bf16_dtype_preserved():
    x = jnp.ones((512, 128), jnp.bfloat16)
    out = fused_dropout(x, 0.2, jax.random.key(2))
    assert out.dtype == jnp.bfloat16


def test_wide_activation_block_bounded():
    """Review regression: wide trailing dims must shrink the row block
    (512-row blocks at C=4096 would blow VMEM on TPU)."""
    key = jax.random.key(5)
    x = jnp.ones((256, 4096), jnp.float32)
    out = np.asarray(fused_dropout(x, 0.25, key))
    kept = out != 0.0
    assert abs(kept.mean() - 0.75) < 0.02


def test_F_dropout_dispatches_to_fused(monkeypatch):
    """F.dropout routes eligible arrays to the fused kernel (gate wiring
    covered without TPU hardware by faking the backend check)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops.pallas import dropout as fd

    calls = {}
    real = fd.fused_dropout

    def spy(a, rate, key):
        calls["rate"] = rate
        calls["shape"] = tuple(a.shape)
        return real(a, rate, key)

    monkeypatch.setattr(fd, "fused_dropout", spy)
    # fake the gate's backend; the marker keeps the kernel interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paddle.seed(0)
    x = paddle.to_tensor(np.ones((64, 1024), np.float32))
    x.stop_gradient = False
    y = F.dropout(x, p=0.3, training=True)
    assert calls == {"rate": 0.3, "shape": (64, 1024)}
    y.sum().backward()
    g = np.asarray(x.grad._data)
    out = y.numpy()
    # mask consistency through the tape: grad nonzero exactly where kept
    np.testing.assert_array_equal(g != 0, out != 0)
