"""Activation-recompute parity tests.

Analogue of the reference's recompute tests
(reference: test_dygraph_recompute.py — loss/grad parity with and without
recompute, RNG consistency with dropout). Here jax.checkpoint does the
rematerialization; grads must be bit-comparable either way.
"""

import jax
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.distributed.fleet.utils import recompute


def _mlp():
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 16))


def test_eager_grad_parity():
    paddle.seed(7)
    blk = _mlp()
    x_np = np.random.RandomState(0).randn(4, 16).astype(np.float32)

    x = paddle.to_tensor(x_np)
    x.stop_gradient = False
    loss = blk(x).sum()
    loss.backward()
    ref_grads = {k: np.asarray(p.grad._data)
                 for k, p in blk.named_parameters()}
    ref_gx = np.asarray(x.grad._data)

    blk.clear_gradients()
    x2 = paddle.to_tensor(x_np)
    x2.stop_gradient = False
    loss2 = recompute(blk, x2).sum()
    loss2.backward()
    np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-6)
    for k, p in blk.named_parameters():
        np.testing.assert_allclose(ref_grads[k], np.asarray(p.grad._data),
                                   rtol=1e-6, err_msg=k)
    np.testing.assert_allclose(ref_gx, np.asarray(x2.grad._data), rtol=1e-6)


def test_closure_captured_layer_gets_grads():
    paddle.seed(8)
    blk = nn.Linear(8, 8)
    x = paddle.to_tensor(np.random.RandomState(1).randn(4, 8)
                         .astype(np.float32))
    x.stop_gradient = False
    loss = recompute(lambda t: F.relu(blk(t)), x).sum()
    loss.backward()
    assert blk.weight.grad is not None
    assert blk.bias.grad is not None
    assert x.grad is not None


def test_dropout_mask_consistent_between_fwd_and_remat():
    # the rematerialized forward must replay the SAME dropout mask the
    # primal forward drew (keys are split at trace time)
    paddle.seed(9)
    blk = nn.Sequential(nn.Linear(16, 16), nn.Dropout(0.5))
    x = paddle.to_tensor(np.random.RandomState(2).randn(4, 16)
                         .astype(np.float32))
    x.stop_gradient = False
    out = recompute(blk, x)
    loss = out.sum()
    loss.backward()
    # if masks diverged, grad wrt x would not match the dropout pattern of
    # the forward output: zeros in out must imply zero grad columns through
    # the dropped units — check grad finite and nonzero overall instead of
    # brittle elementwise structure:
    g = np.asarray(x.grad._data)
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_recompute_sequential_param_grads():
    from paddle_tpu.distributed.fleet.utils import recompute_sequential

    paddle.seed(12)
    layers = [nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 8), nn.ReLU(),
              nn.Linear(8, 4)]
    x = paddle.to_tensor(np.random.RandomState(5).randn(4, 8)
                         .astype(np.float32))
    out = recompute_sequential({"segments": 2}, layers, x)
    out.sum().backward()
    for lyr in layers:
        for _, p in lyr.named_parameters():
            assert p.grad is not None, "segment params lost from grad path"
            assert np.isfinite(np.asarray(p.grad._data)).all()


def test_jitted_trainstep_with_recompute_converges():
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTPretrainingCriterion)
    from paddle_tpu.optimizer import AdamW

    paddle.seed(10)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                    max_position_embeddings=64, use_recompute=True)
    m = GPTForPretraining(cfg)
    m.train()
    crit = GPTPretrainingCriterion()
    step = TrainStep(m, lambda l, i, t: crit(l(i), t),
                     AdamW(learning_rate=1e-3, parameters=m.parameters()))
    ids = np.random.RandomState(3).randint(0, 128, (2, 32)).astype(np.int32)
    losses = [float(step(ids, ids)) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_recompute_vs_plain_jit_loss_parity():
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTPretrainingCriterion)
    from paddle_tpu.optimizer import AdamW

    losses = {}
    for use_rc in (False, True):
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=64,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        use_recompute=use_rc)
        m = GPTForPretraining(cfg)
        m.train()
        crit = GPTPretrainingCriterion()
        step = TrainStep(m, lambda l, i, t: crit(l(i), t),
                         AdamW(learning_rate=1e-3,
                               parameters=m.parameters()))
        ids = np.random.RandomState(4).randint(0, 128, (2, 32)) \
            .astype(np.int32)
        losses[use_rc] = [float(step(ids, ids)) for _ in range(3)]
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5)


# ---------------------------------------------------------------------------
# a remat never re-runs a flash forward (ISSUE 34)
# ---------------------------------------------------------------------------

def test_resolver_keeps_the_flash_residuals_under_every_policy_but_full():
    """One predicate a policy name (nn.scan keys its trace cache on its
    identity); ``"full"`` and ``"nothing_saveable"`` are jax's own."""
    from paddle_tpu.distributed.fleet.utils.recompute import (
        resolve_checkpoint_policy as resolve)
    from paddle_tpu.ops.pallas import FLASH_RESIDUAL_NAMES
    assert resolve(None) is resolve(None)
    assert resolve("save_dots") is resolve("dots_saveable")
    assert resolve("full") is jax.checkpoint_policies.nothing_saveable
    assert resolve("nothing_saveable") is resolve("full")
    own = jax.checkpoint_policies.dots_saveable
    assert resolve(own) is own
    with pytest.raises(ValueError, match="unknown recompute policy"):
        resolve("save_only_these_names")

    @jax.custom_vjp
    def kernel(x):                       # a stand-in with a named residual
        return jax.numpy.exp(x)

    def kernel_fwd(x):
        y = checkpoint_name(jax.numpy.exp(x), FLASH_RESIDUAL_NAMES[0])
        return y, y

    kernel.defvjp(kernel_fwd, lambda y, g: (g * y,))

    def runs(policy):
        body = jax.checkpoint(lambda x: jax.numpy.tanh(kernel(x)) @ x,
                              policy=resolve(policy))
        text = str(jax.make_jaxpr(jax.grad(lambda x: body(x).sum()))(
            jax.numpy.ones((4, 4))))
        return text.count(" exp ")

    assert runs("full") == 2
    assert runs(None) == runs("dots_saveable") == runs("none") == 1


def _flash_calls(jaxpr, kernel):
    """pallas_calls named ``kernel`` in a jaxpr and everything it holds
    (the scan body is traced once, so one a layer BODY)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += (eqn.primitive.name == "pallas_call"
              and eqn.params["name"] == kernel)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _flash_calls(sub, kernel)
    return n


@pytest.fixture
def flash_on_cpu(monkeypatch, request):
    """The flash gate's backend check faked (the ``pallas`` marker keeps
    the kernel interpreted); with ``mesh`` a dp2 x mp2 mesh active, so
    the kernel runs inside ``shard_kernel``'s shard_map."""
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed.spmd import make_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if request.param == "mesh":
        dist_env.set_mesh(make_mesh({"dp": 2, "sharding": 1, "mp": 2},
                                    jax.devices()[:4]))
    yield request.param
    dist_env.reset()


def _gpt_loss(**cfg):
    """(loss(params, ids), params, ids) of a 2-layer GPT whose attention
    the flash kernel takes (S=256, heads of 64), AMP O1, dropout 0.1."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.functional import functional_call, param_arrays
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion, gpt_tiny)
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny(
        hidden_size=256, num_heads=4, max_position_embeddings=256,
        hidden_dropout_prob=0.1, attention_dropout_prob=0.1, **cfg))
    crit = GPTPretrainingCriterion()

    def loss(params, ids):
        with paddle.amp.auto_cast(level="O1"):
            logits, _ = functional_call(model, params, ids, training=True,
                                        rng=jax.random.key(5))
            return crit(Tensor(logits), Tensor(ids))._data

    ids = np.random.default_rng(0).integers(0, 256, (2, 256)).astype("int32")
    return loss, param_arrays(model), ids


@pytest.mark.pallas
@pytest.mark.parametrize("flash_on_cpu", ["plain", "mesh"], indirect=True)
@pytest.mark.parametrize("policy,forwards", [
    (None, 1), ("dots_with_no_batch_dims_saveable", 1), ("full", 2)],
    ids=["default", "dots", "full"])
def test_recomputed_body_runs_the_flash_forward_once(flash_on_cpu, policy,
                                                     forwards):
    """In the gradient of a recomputed layer body the flash forward
    appears once (its output and log-sum-exp are kept) under the default
    and under a dots policy, which keeps no custom call's output by
    itself; twice under ``"full"``; the backward kernel once."""
    loss, params, ids = _gpt_loss(use_recompute=True,
                                  recompute_policy=policy)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, ids).jaxpr
    assert _flash_calls(jaxpr, "flash_fwd") == forwards
    assert _flash_calls(jaxpr, "flash_bwd") == 1


@pytest.mark.pallas
@pytest.mark.parametrize("flash_on_cpu", ["plain", "mesh"], indirect=True)
def test_kept_flash_residuals_change_no_bit(flash_on_cpu):
    """Loss and every gradient leaf under the default are what ``"full"``
    and no recompute at all give, bit for bit, dropout on: the kept
    output is the one the second run would write, and the kept column of
    the log-sum-exp tile is the one the backward kernel reads."""
    got = {}
    for tag, cfg in (("default", dict(use_recompute=True)),
                     ("full", dict(use_recompute=True,
                                   recompute_policy="full")),
                     ("off", dict(use_recompute=False))):
        loss, params, ids = _gpt_loss(**cfg)
        got[tag] = jax.jit(jax.value_and_grad(loss))(params, ids)
    loss0, grads0 = got["default"]
    assert np.isfinite(float(loss0))
    for tag in ("full", "off"):
        loss1, grads1 = got[tag]
        assert float(loss1) == float(loss0), tag
        assert set(grads1) == set(grads0)
        for k in grads0:
            assert np.array_equal(np.asarray(grads0[k]),
                                  np.asarray(grads1[k])), (tag, k)
        assert any(float(abs(g).max()) > 0 for g in grads1.values())
