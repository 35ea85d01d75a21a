"""1F1B pipeline-schedule tests: parity, compile counts, fault paths.

The schedule contract (docs/PARALLELISM.md): 1F1B and fill-drain are
SCHEDULES over one stacked-parameter layout — they may only reorder which
device computes a microbatch, so loss trajectories must agree with each
other and with single-device execution to float-reassociation tolerance
(1e-6, ISSUE 9 acceptance), state_dicts stay bit-exact across schedule
choice, each (schedule, mesh-shape) compiles exactly one program, a hung
stage handoff raises structured under the PR 5 collective watchdog, and
elastic restart resumes bit-exact from the PR 5 CheckpointManager.

Everything runs on the 8-device virtual CPU mesh the conftest forces;
1F1B itself requires a pp-only mesh on XLA:CPU (manual_collectives_ok) —
mixed dp/mp meshes pin the counted fallback instead.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import env as dist_env, fleet
from paddle_tpu.distributed.meta_parallel import spmd_pipeline as sp
from paddle_tpu.distributed.meta_parallel.spmd_pipeline import (
    PipelineStageStack, bubble_fraction, pipeline_comm_model,
    resolve_schedule, schedule_slots, schedule_timetable)
from paddle_tpu.jit.to_static import TrainStep
from paddle_tpu.optimizer import AdamW
from paddle_tpu.testing import chaos

H = 16


class Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(H, H)

    def forward(self, x):
        return x + paddle.nn.functional.tanh(self.fc(x))


class PipeNet(nn.Layer):
    """Toy pipelined net: stacked residual blocks + a linear regression
    head driven through ``PipelineStageStack.train_loss`` (the
    schedule-aware path TrainStep differentiates through)."""

    def __init__(self, num_layers=4, num_microbatches=4, schedule=None):
        super().__init__()
        self.blocks = PipelineStageStack(
            Block, num_layers, num_microbatches=num_microbatches,
            schedule=schedule)
        self.head = nn.Linear(H, 1)

    def loss(self, x, tgt):
        leaves = [p for _, p in self.head.named_parameters()]

        def head_apply(hl, y, t):
            w, b = hl[0], hl[1]
            pred = y @ w + b
            d = (pred - t).astype(jnp.float32)
            return jnp.sum(d * d), jnp.float32(d.size)

        return self.blocks.train_loss(
            x, head_apply, leaves, [tgt], head_token=("toy", id(self)))


def _pp_mesh(dp=1, pp=2, mp=1):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "pp_degree": pp,
                               "mp_degree": mp}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group().mesh


def _toy_batch(B=8):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((B, H)).astype(np.float32)
    tgt = rng.standard_normal((B, 1)).astype(np.float32)
    return x, tgt


def _run_toy(schedule, steps=3, use_mesh=True):
    """3 AdamW steps of PipeNet under one schedule; returns the loss
    trajectory. use_mesh=False = the single-device reference."""
    fleet.reset()
    dist_env.reset()
    mesh = _pp_mesh(pp=2) if use_mesh else None
    paddle.seed(21)
    model = PipeNet(schedule=schedule)
    opt = AdamW(learning_rate=1e-2, weight_decay=0.01)

    def loss_fn(layer, x, tgt):
        return layer.loss(x, tgt)

    kw = dict(mesh=mesh) if use_mesh else {}
    step = TrainStep(model, loss_fn, opt, **kw)
    x, tgt = _toy_batch()
    return [float(np.asarray(step(Tensor(x), Tensor(tgt))._data))
            for _ in range(steps)]


# -- schedule math ----------------------------------------------------------

def test_schedule_slots_and_bubble():
    assert schedule_slots("fill_drain", 4, 8) == 11
    assert schedule_slots("1f1b", 4, 8) == 22
    assert schedule_slots("1f1b", 1, 8) == 8       # no pipeline, no bubble
    assert bubble_fraction("1f1b", 4, 8) == pytest.approx(3 / 11)
    assert bubble_fraction("fill_drain", 1, 8) == 0.0
    m = pipeline_comm_model("1f1b", 4, 8, boundary_bytes=1024)
    assert m["slots"] == 22 and m["bytes"] == m["ops"] * 1024


def test_timetable_matches_canonical_and_is_causal():
    """The measured (implemented-predicate) timetable reproduces the
    canonical bubble EXACTLY and respects dataflow causality: stage s+1's
    forward of microbatch m runs after stage s's, backward starts after
    the last stage's forward, and cotangents flow S-1 -> 0."""
    for S, M in [(2, 4), (4, 8), (2, 2), (8, 8)]:
        for sched in ("fill_drain", "1f1b"):
            tt = schedule_timetable(sched, S, M)
            assert tt["bubble_fraction"] == pytest.approx(
                bubble_fraction(sched, S, M)), (sched, S, M)
        tt = schedule_timetable("1f1b", S, M)
        fwd_slot = {}
        bwd_slot = {}
        for s in range(S):
            f_slots = np.flatnonzero(tt["fwd"][s])
            b_slots = np.flatnonzero(tt["bwd"][s])
            assert len(f_slots) == M and len(b_slots) == M
            for m, t in enumerate(f_slots):
                fwd_slot[(s, m)] = t
            for m, t in enumerate(b_slots):
                bwd_slot[(s, m)] = t
        for m in range(M):
            for s in range(S - 1):
                assert fwd_slot[(s, m)] < fwd_slot[(s + 1, m)]
                assert bwd_slot[(s + 1, m)] < bwd_slot[(s, m)]
            assert bwd_slot[(S - 1, m)] > fwd_slot[(S - 1, m)]
        # steady state is strictly one-forward-one-backward: no stage is
        # ever asked to do both in one slot
        assert not np.any(tt["fwd"] & tt["bwd"])


def test_schedule_resolution_precedence():
    # default comes from the fleet strategy's pipeline_configs (1F1B)
    assert resolve_schedule(None) == "1f1b"
    # explicit arg (reference spellings normalize) beats the strategy
    assert resolve_schedule("F-then-B") == "fill_drain"
    assert resolve_schedule("gpipe") == "fill_drain"
    assert resolve_schedule("1F1B") == "1f1b"
    # the global flag is the kill switch: beats the explicit arg
    with flag_scope("pipeline_schedule", "fill_drain"):
        assert resolve_schedule("1f1b") == "fill_drain"
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        resolve_schedule("zb-h1")
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        PipelineStageStack(Block, 2, schedule="nope")


# -- numerics parity (the acceptance pin) -----------------------------------

@pytest.mark.multichip
def test_toy_1f1b_vs_fill_drain_vs_single_device():
    """ISSUE 9 acceptance: loss Δ ≤ 1e-6 between 1F1B, fill-drain and the
    single-device loop across 3 optimizer steps (fwd+bwd+AdamW through
    TrainStep — schedules only reorder which device computes what)."""
    l_1f1b = _run_toy("1f1b")
    l_fd = _run_toy("fill_drain")
    l_seq = _run_toy(None, use_mesh=False)
    assert all(np.isfinite(l_1f1b)), l_1f1b
    for a, b in zip(l_1f1b, l_fd):
        assert abs(a - b) <= 1e-6, (l_1f1b, l_fd)
    for a, b in zip(l_1f1b, l_seq):
        assert abs(a - b) <= 1e-6, (l_1f1b, l_seq)


@pytest.mark.multichip
def test_gpt_1f1b_three_step_parity():
    """GPT end-to-end acceptance pin: GPTForPretrainingPipe.pretraining_loss
    under 1F1B on a pp-only 8-device virtual mesh matches fill-drain AND
    single-device execution (Δ ≤ 1e-6) over 3 optimizer steps."""
    from paddle_tpu.models.gpt import GPTForPretrainingPipe, gpt_tiny

    def run(schedule, use_mesh=True):
        fleet.reset()
        dist_env.reset()
        mesh = _pp_mesh(pp=2) if use_mesh else None
        paddle.seed(1234)
        cfg = gpt_tiny()
        model = GPTForPretrainingPipe(cfg, num_microbatches=2,
                                      schedule=schedule)
        if use_mesh:
            model = fleet.distributed_model(model)
        opt = AdamW(learning_rate=1e-3, weight_decay=0.01)

        def loss_fn(layer, ids, labels, mask):
            base = layer._layers if hasattr(layer, "_layers") else layer
            return base.pretraining_loss(ids, labels, mask)

        kw = (dict(mesh=mesh, data_spec=P("dp")) if use_mesh else {})
        step = TrainStep(model, loss_fn, opt, **kw)
        rng = np.random.default_rng(0)
        B, S = 4, 32
        ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        mask = np.ones((B, S), np.float32)
        return [float(np.asarray(
            step(Tensor(ids), Tensor(labels), Tensor(mask))._data))
            for _ in range(3)]

    l_1f1b = run("1f1b")
    l_fd = run("fill_drain")
    l_seq = run(None, use_mesh=False)
    assert all(np.isfinite(l_1f1b)), l_1f1b
    for a, b in zip(l_1f1b, l_fd):
        assert abs(a - b) <= 1e-6, (l_1f1b, l_fd)
    for a, b in zip(l_1f1b, l_seq):
        assert abs(a - b) <= 1e-6, (l_1f1b, l_seq)


@pytest.mark.multichip
def test_schedule_parity_holds_with_dropout():
    """Kill-switch contract for STOCHASTIC models: both schedules derive
    stage RNG from the same (microbatch, stage) fold, so dropout masks —
    and therefore loss trajectories — are schedule-invariant (Δ ≤ 1e-6
    over 2 optimizer steps with dropout 0.1 everywhere)."""
    from paddle_tpu.models.gpt import GPTForPretrainingPipe, gpt_tiny

    def run(schedule):
        fleet.reset()
        dist_env.reset()
        mesh = _pp_mesh(pp=2)
        paddle.seed(77)
        cfg = gpt_tiny(hidden_dropout_prob=0.1,
                       attention_dropout_prob=0.1)
        model = fleet.distributed_model(
            GPTForPretrainingPipe(cfg, num_microbatches=2,
                                  schedule=schedule))
        opt = AdamW(learning_rate=1e-3)

        def loss_fn(layer, ids, labels, mask):
            base = layer._layers if hasattr(layer, "_layers") else layer
            return base.pretraining_loss(ids, labels, mask)

        step = TrainStep(model, loss_fn, opt, mesh=mesh,
                         data_spec=P("dp"))
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        labels = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        mask = np.ones((4, 16), np.float32)
        return [float(np.asarray(
            step(Tensor(ids), Tensor(labels), Tensor(mask))._data))
            for _ in range(2)]

    l_1f1b = run("1f1b")
    l_fd = run("fill_drain")
    assert all(np.isfinite(l_1f1b)), l_1f1b
    for a, b in zip(l_1f1b, l_fd):
        assert abs(a - b) <= 1e-6, (l_1f1b, l_fd)


@pytest.mark.multichip
def test_1f1b_eval_mode_uses_fill_drain():
    """Forward-only consumers (eval) never see the combined fwd+bwd
    program: train_loss in eval mode equals the plain forward + head."""
    _pp_mesh(pp=2)
    paddle.seed(5)
    model = PipeNet(schedule="1f1b")
    model.eval()
    x, tgt = _toy_batch()
    built0 = sp.PIPELINE_STATS["programs_built"]
    loss = model.loss(Tensor(x), Tensor(tgt))
    # the fill-drain forward program was built, not the 1f1b one
    out = model.blocks(Tensor(x))
    pred = out._data @ model.head.weight._data + model.head.bias._data
    want = float(np.mean((np.asarray(pred) - tgt) ** 2))
    assert float(np.asarray(loss._data)) == pytest.approx(want, rel=1e-5)
    assert sp.PIPELINE_STATS["programs_built"] == built0 + 1


# -- state_dict + compile-count pins ----------------------------------------

@pytest.mark.multichip
def test_state_dict_bit_exact_roundtrip_across_schedules():
    """state_dict names/values are schedule-independent and roundtrip
    bit-exact: a 1F1B-trained model's state loads into a fill-drain model
    and the next loss is IDENTICAL (the checkpoint-manifest compatibility
    claim of docs/PARALLELISM.md)."""
    _pp_mesh(pp=2)

    def build(schedule):
        paddle.seed(33)
        return PipeNet(schedule=schedule)

    model_a = build("1f1b")
    opt = AdamW(learning_rate=1e-2)
    step = TrainStep(model_a, lambda l, x, t: l.loss(x, t), opt)
    x, tgt = _toy_batch()
    step(Tensor(x), Tensor(tgt))
    step.sync_to_layer()          # the step donated the layer's arrays

    sd = model_a.state_dict()
    # per-layer views keep template names (state_dict manifest contract)
    per_layer = model_a.blocks.layer_state_dict(0)
    assert set(per_layer) == {"fc.weight", "fc.bias"}

    model_b = build("fill_drain")
    model_b.set_state_dict({k: Tensor(jnp.asarray(np.asarray(v._data)))
                            for k, v in sd.items()})
    for (k, pa), (_, pb) in zip(model_a.named_parameters(),
                                model_b.named_parameters()):
        np.testing.assert_array_equal(np.asarray(pa._data),
                                      np.asarray(pb._data), err_msg=k)
    la = float(np.asarray(model_a.loss(Tensor(x), Tensor(tgt))._data))
    lb = float(np.asarray(model_b.loss(Tensor(x), Tensor(tgt))._data))
    # same stacked values through two schedules: ≤ reassociation noise
    assert abs(la - lb) <= 1e-6


@pytest.mark.multichip
def test_one_program_per_schedule_and_mesh_shape():
    """Compile-count pin: M microbatches run in ONE pipelined program per
    (schedule, mesh shape) — program builds don't scale with M, and
    repeat calls with fresh data trace nothing new."""
    from paddle_tpu.utils import CompileCounter

    _pp_mesh(pp=2)
    paddle.seed(3)
    model = PipeNet(num_microbatches=4, schedule="1f1b")
    x, tgt = _toy_batch()
    assert sp.PIPELINE_STATS["programs_built"] == 0
    float(np.asarray(model.loss(Tensor(x), Tensor(tgt))._data))
    assert sp.PIPELINE_STATS["programs_built"] == 1   # one, not one per M
    with CompileCounter() as c:
        x2 = x + 1.0
        float(np.asarray(model.loss(Tensor(x2), Tensor(tgt))._data))
    assert sp.PIPELINE_STATS["programs_built"] == 1
    assert c.jaxpr_traces == 0, "warm 1f1b call re-traced"
    # switching schedule builds exactly one more program
    model.blocks.schedule = "fill_drain"
    float(np.asarray(model.loss(Tensor(x), Tensor(tgt))._data))
    assert sp.PIPELINE_STATS["programs_built"] == 2


# -- fallbacks + ZeRO interaction -------------------------------------------

@pytest.mark.multichip
def test_1f1b_counted_fallback_on_tp_mesh_and_zero_parity():
    """On XLA:CPU a nontrivial mp axis cannot run the manual-pp program:
    train_loss degrades to fill-drain with a one-time RuntimeWarning and
    a counted fallback — and the ZeRO-sharded TrainStep over that mesh
    still matches single-device numerics (the ZeRO re-shard interaction
    pin; on TPU the same config runs the real 1F1B program)."""

    def run(use_mesh):
        fleet.reset()
        dist_env.reset()
        mesh = _pp_mesh(dp=2, pp=2, mp=1) if use_mesh else None
        paddle.seed(11)
        model = PipeNet(schedule="1f1b")
        opt = AdamW(learning_rate=1e-2)
        kw = (dict(mesh=mesh, data_spec=P("dp"), zero_axis="dp")
              if use_mesh else {})
        step = TrainStep(model, lambda l, a, b: l.loss(a, b), opt, **kw)
        x, tgt = _toy_batch()
        return [float(np.asarray(step(Tensor(x), Tensor(tgt))._data))
                for _ in range(3)]

    sp.reset_pipeline_stats()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        l_mesh = run(True)
    assert sp.PIPELINE_STATS["fallbacks"] >= 1
    assert any("degraded to sequential" in str(x.message) for x in w)
    l_seq = run(False)
    for a, b in zip(l_mesh, l_seq):
        assert abs(a - b) <= 5e-6, (l_mesh, l_seq)

    # exactly ONE count per degraded dispatch: the 1f1b schedule pick in
    # train_loss probes WITHOUT counting, forward()'s own check records
    # the fallback (one trace = one degraded dispatch = one count)
    fleet.reset()
    dist_env.reset()
    _pp_mesh(dp=2, pp=2, mp=1)
    paddle.seed(2)
    m2 = PipeNet(schedule="1f1b")
    x, tgt = _toy_batch()
    sp.reset_pipeline_stats()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        float(np.asarray(m2.loss(Tensor(x), Tensor(tgt))._data))
    assert sp.PIPELINE_STATS["fallbacks"] == 1, sp.PIPELINE_STATS


# -- fault tolerance through the pipeline dispatch path ---------------------

@pytest.mark.multichip
@pytest.mark.chaos
def test_chaos_hang_in_pipeline_dispatch_raises_structured():
    """A chaos-hung stage handoff in the EAGER pipeline dispatch raises
    CollectiveTimeoutError naming the pipeline program, within the
    FLAGS_collective_timeout_s budget. (Autograd-recorded eager calls jit
    the whole op — there the guard sits on TrainStep's step dispatch
    instead — so the eager watchdog path is the no_grad one.)"""
    from paddle_tpu.core.tensor import no_grad
    from paddle_tpu.distributed import collective as C

    _pp_mesh(pp=2)
    paddle.seed(7)
    stack = PipelineStageStack(Block, num_layers=4, num_microbatches=2)
    x, _ = _toy_batch(B=4)
    with no_grad():
        out = stack(Tensor(x))           # compile OUTSIDE the budget
        assert np.all(np.isfinite(np.asarray(out._data)))
        assert sp.PIPELINE_STATS["dispatches"] >= 1
        with flag_scope("collective_timeout_s", 1.0):
            out = stack(Tensor(x + 1.0))  # healthy warm guarded dispatch
            assert np.all(np.isfinite(np.asarray(out._data)))
            chaos.arm("collective.hang", at=1)
            with pytest.raises(C.CollectiveTimeoutError) as exc:
                stack(Tensor(x + 2.0))
    assert exc.value.op == "pipeline.fill_drain"
    assert exc.value.timeout_s == 1.0


@pytest.mark.multichip
@pytest.mark.chaos
def test_chaos_hang_in_trainstep_pipeline_step_raises():
    """TrainStep applies the same watchdog to its whole step program when
    the model carries a pipeline: a hang at the step dispatch raises
    structured instead of stalling the controller."""
    from paddle_tpu.distributed import collective as C

    _pp_mesh(pp=2)
    paddle.seed(7)
    model = PipeNet(schedule="1f1b")
    step = TrainStep(model, lambda l, a, b: l.loss(a, b),
                     AdamW(learning_rate=1e-2))
    assert step._pp_degree == 2
    x, tgt = _toy_batch()
    # compile + first dispatch outside the watchdog budget
    float(np.asarray(step(Tensor(x), Tensor(tgt))._data))
    with flag_scope("collective_timeout_s", 1.0):
        float(np.asarray(step(Tensor(x), Tensor(tgt))._data))  # healthy
        chaos.arm("collective.hang", at=1)
        with pytest.raises(C.CollectiveTimeoutError) as exc:
            step(Tensor(x), Tensor(tgt))
    assert exc.value.op == "pipeline_step"


@pytest.mark.multichip
def test_checkpoint_resume_1f1b_bit_exact(tmp_path):
    """Elastic-restart acceptance: a 1F1B training run killed after an
    interval save resumes from the PR 5 CheckpointManager and continues
    BIT-EXACT vs the uninterrupted run."""
    from paddle_tpu.distributed.checkpoint import CheckpointManager

    root = str(tmp_path / "ckpt")
    x, tgt = _toy_batch()

    def build_step():
        fleet.reset()
        dist_env.reset()
        mesh = _pp_mesh(pp=2)
        paddle.seed(99)
        model = PipeNet(schedule="1f1b")
        return TrainStep(model, lambda l, a, b: l.loss(a, b),
                         AdamW(learning_rate=1e-2), mesh=mesh)

    # uninterrupted reference: 4 steps
    step = build_step()
    ref = [float(np.asarray(step(Tensor(x), Tensor(tgt))._data))
           for _ in range(4)]

    # run A: 2 steps, synchronous interval save at step 2
    step_a = build_step()
    with CheckpointManager(step_a, root, interval_steps=2,
                           asynchronous=False) as mgr:
        for i in range(2):
            step_a(Tensor(x), Tensor(tgt))
            mgr.on_step(dataloader_state={"offset": i + 1})
    # run B: fresh process-equivalent, resume + 2 more steps
    step_b = build_step()
    with CheckpointManager(step_b, root, interval_steps=2,
                           asynchronous=False) as mgr:
        info = mgr.resume()
        assert info and info["dataloader"]["offset"] == 2
        cont = [float(np.asarray(step_b(Tensor(x), Tensor(tgt))._data))
                for _ in range(2)]
    assert cont == ref[2:], (cont, ref)


# -- topology validation (satellite) ----------------------------------------

def test_topology_validation_named_errors():
    from paddle_tpu.distributed.fleet import (HybridCommunicateGroup,
                                              MeshTopologyError,
                                              validate_topology)

    n = len(jax.devices())
    assert n == 8
    # legal: exact factor and sub-mesh prefix
    assert validate_topology({"dp": 2, "pp": 2, "mp": 2}, 8) == 8
    assert validate_topology({"pp": 4}, 8) == 4
    with pytest.raises(MeshTopologyError, match="needs 16 devices"):
        validate_topology({"dp": 8, "mp": 2}, 8)
    with pytest.raises(MeshTopologyError, match="does not factor"):
        validate_topology({"dp": 3, "mp": 2}, 8)
    with pytest.raises(MeshTopologyError, match=">= 1"):
        validate_topology({"dp": 0, "mp": 2}, 8)
    # the named error surfaces from the user-facing constructor too —
    # not a shape error deep inside make_mesh
    with pytest.raises(MeshTopologyError, match="does not factor"):
        HybridCommunicateGroup(dp_degree=3, mp_degree=2)
    with pytest.raises(MeshTopologyError, match="needs"):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 16}
        fleet.init(is_collective=True, strategy=strategy)


# -- tooling ----------------------------------------------------------------

def test_monitor_report_comms_render():
    """tools/monitor_report.py --comms renders the schedule comm model."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import monitor_report

    rows = [{"name": "pipeline_bubble_fraction", "type": "gauge",
             "value": 0.2, "labels": {"op": "ppermute", "schedule": "1f1b",
                                      "pp": 2, "microbatches": 4}}]
    out = monitor_report.render(rows, comms=True)
    assert "Pipeline schedule comm model" in out
    assert "pipeline_bubble_fraction" in out
    # without --comms the gauge lands in the generic table instead
    out2 = monitor_report.render(rows, comms=False)
    assert "Pipeline schedule comm model" not in out2
