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


def _assert_policies_agree(**compile_options):
    """Loss and every gradient leaf of the 2-layer GPT step under the
    default policy, under ``"full"`` and with no recompute at all, bit
    for bit, compiled with ``compile_options`` (none: as a user's step
    is)."""
    got = {}
    for tag, cfg in (("default", dict(use_recompute=True)),
                     ("full", dict(use_recompute=True,
                                   recompute_policy="full")),
                     ("off", dict(use_recompute=False))):
        loss, params, ids = _gpt_loss(**cfg)
        step = jax.jit(jax.value_and_grad(loss))
        if compile_options:
            step = step.lower(params, ids).compile(
                compiler_options=compile_options)
        got[tag] = step(params, ids)
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


@pytest.mark.pallas
@pytest.mark.parametrize("flash_on_cpu", ["plain", "mesh"], indirect=True)
def test_kept_flash_residuals_change_no_bit(flash_on_cpu):
    """Loss and every gradient leaf under the default are what ``"full"``
    and no recompute at all give, bit for bit, dropout on: the kept
    output is the one the second run would write, the kept column of the
    log-sum-exp tile is the one the backward kernel reads, and the kept
    block values (the QKV and FFN-in products, the attention branch
    after its dropout) are the forward's own. (Keeping the mid-layer
    residual SUM instead parts the bits: XLA may carry the unstored
    bfloat16 sum at float32 into ``ln2``'s fusion, and the losses then
    differ in their 6th digit.)"""
    _assert_policies_agree()


@pytest.mark.pallas
@pytest.mark.parametrize("flash_on_cpu", ["plain", "mesh"], indirect=True)
def test_kept_values_change_no_bit_without_excess_precision(flash_on_cpu):
    """The same bit-equality with XLA's excess precision off, where
    every bfloat16 value is rounded where the program says: the kept
    values carry no rounding the recomputed ones would not."""
    _assert_policies_agree(xla_allow_excess_precision=False)


# ---------------------------------------------------------------------------
# a remat re-runs no product and no all-reduce
# ---------------------------------------------------------------------------

def _runs_again(policy, name):
    """Whether a remat under ``policy`` computes a value tagged ``name``
    a second time: the backward of ``y * y`` reads ``y = sin(x)``, and
    the backward of ``sin`` needs no ``sin``, so ``sin`` appears twice in
    the gradient exactly when ``y`` is recomputed."""
    def fn(x):
        y = checkpoint_name(jax.numpy.sin(x), name)
        return y * y

    body = jax.checkpoint(fn, policy=policy)
    text = str(jax.make_jaxpr(jax.grad(lambda x: body(x).sum()))(
        jax.numpy.ones((4,))))
    return text.count(" sin ") == 2


@pytest.mark.parametrize("name", ["flash_attention_o", "flash_attention_lse",
                                  "attn_branch", "ffn_in_product",
                                  "qkv_product"])
def test_every_policy_but_full_keeps_the_named_values(name):
    """The default and a dots policy keep the flash residuals and the
    GPT block's costly values; ``"full"`` keeps nothing; the pipeline
    stage's policy keeps the flash residuals alone."""
    from paddle_tpu.distributed.fleet.utils.recompute import (
        LAYER_RESIDUAL_NAMES, flash_residuals_policy,
        resolve_checkpoint_policy as resolve)
    from paddle_tpu.ops.pallas import FLASH_RESIDUAL_NAMES
    assert name in FLASH_RESIDUAL_NAMES + LAYER_RESIDUAL_NAMES
    assert not _runs_again(resolve(None), name)
    assert not _runs_again(resolve("dots_with_no_batch_dims_saveable"), name)
    assert _runs_again(resolve("full"), name)
    assert _runs_again(flash_residuals_policy(), name) == (
        name not in FLASH_RESIDUAL_NAMES)


def test_pipeline_stage_keeps_only_the_flash_residuals(monkeypatch):
    """The fill-drain pipeline's stage remat is not the default: a stage
    holds its residuals for every microbatch in flight, so it keeps the
    flash kernels' output and log-sum-exp and no block value."""
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed.fleet.utils.recompute import (
        flash_residuals_policy)
    from paddle_tpu.distributed.meta_parallel.spmd_pipeline import (
        PipelineStageStack)
    from paddle_tpu.distributed.spmd import make_mesh

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 8)

        def forward(self, x):
            return x + F.tanh(self.fc(x))

    seen = []
    wrap = jax.checkpoint

    def spy(fn, policy=None, **kw):
        seen.append(policy)
        return wrap(fn, policy=policy, **kw)

    stack = PipelineStageStack(Block, 2, num_microbatches=2)
    monkeypatch.setattr(jax, "checkpoint", spy)
    try:
        stack._pipe_program(make_mesh({"pp": 2}, jax.devices()[:2]), 2, 2, 4)
    finally:
        dist_env.reset()
    assert seen == [flash_residuals_policy()]


@pytest.mark.pallas
def test_gpt_block_names_its_values_only_in_a_training_forward():
    """A differentiated training forward names the block's three costly
    values once a body; an evaluation forward (what serving runs) holds
    no ``name`` equation at all."""
    from paddle_tpu.distributed.fleet.utils.recompute import (
        LAYER_RESIDUAL_NAMES)
    loss, params, ids = _gpt_loss(use_recompute=True)
    text = str(jax.make_jaxpr(jax.grad(loss))(params, ids))
    for name in LAYER_RESIDUAL_NAMES:
        assert f"name={name}" in text, name

    from paddle_tpu.jit.functional import functional_call, param_arrays
    from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
    model = GPTForPretraining(gpt_tiny(use_recompute=True))
    model.eval()
    ids = np.zeros((1, 16), "int32")
    text = str(jax.make_jaxpr(
        lambda p: functional_call(model, p, ids, training=False)[0])(
            param_arrays(model)))
    assert "name[name=" not in text
    assert not any(name in text for name in LAYER_RESIDUAL_NAMES)


@pytest.mark.pallas
@pytest.mark.parametrize("flash_on_cpu", ["plain", "mesh"], indirect=True)
@pytest.mark.parametrize("policy", [None, "full"], ids=["default", "full"])
def test_recomputed_body_runs_no_product(flash_on_cpu, policy):
    """By ``aot.products``' reading of the compiled CPU step: under the
    default a recomputed layer body runs no MXU product and, on the
    dp2 x mp2 mesh, no all-reduce; under ``"full"`` it runs the QKV,
    out-projection and FFN-in products again (the interpreted flash
    forward's products besides), and on the mesh the out-projection's
    tensor-parallel all-reduce."""
    from paddle_tpu.jit import aot
    loss, params, ids = _gpt_loss(use_recompute=True, recompute_policy=policy)
    module = aot.index_program(
        jax.jit(jax.grad(loss)).lower(params, ids).compile().as_text())
    again = aot.products(module, phase="remat")
    assert aot.products(module, phase="fwd")
    if policy is None:
        assert again == []
        return
    assert len(again) >= 3
    assert any(n.startswith("all-reduce") for n in again) == (
        flash_on_cpu == "mesh")


@pytest.mark.pallas
@pytest.mark.parametrize("flash_on_cpu", ["plain"], indirect=True)
def test_zero_step_after_its_heal_regathers_only_the_norms(flash_on_cpu):
    """A ZeRO mesh step (sharding2 x mp2) is built again after its first
    call, when the updated parameters come back sharded over
    ``sharding``. In that program a recomputed body runs no product and
    no all-reduce either; what it does run again is the all-gather of
    the two norms' parameters its norms read (the same all-gathers the
    body runs without the kept values, beside three products and the
    out-projection's all-reduce)."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import env as dist_env, fleet
    from paddle_tpu.jit import aot
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion, gpt_tiny)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(dp_degree=1, mp_degree=2, pp_degree=1,
                                   sharding_degree=2)
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(0)
        model = GPTForPretraining(gpt_tiny(
            hidden_size=256, num_heads=4, max_position_embeddings=256,
            use_recompute=True))
        crit = GPTPretrainingCriterion()

        def loss_fn(layer, ids, labels):
            with paddle.amp.auto_cast(level="O1"):
                return crit(layer(ids), labels)

        step = paddle.jit.TrainStep(
            model, loss_fn, paddle.optimizer.AdamW(
                learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters()),
            mesh=fleet.get_hybrid_communicate_group().mesh,
            data_spec=P(("dp", "sharding")), zero_axis="sharding")
        ids = np.random.default_rng(0).integers(0, 256, (8, 256)) \
            .astype("int32")
        step(ids, ids)
        assert aot.products("jit_train_step", phase="remat") == []
        step(ids, ids)
    finally:
        fleet.reset()
        dist_env.reset()
    again = aot.products("jit_train_step", phase="remat")
    index = aot.scopes("jit_train_step")
    assert again and all(n.startswith("all-gather") for n in again), again
    assert {index[n] for n in again} == {("norm", "remat")}
