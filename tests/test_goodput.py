"""Training goodput ledger + per-layer model health (ISSUE 19,
docs/OBSERVABILITY.md "Training goodput & model health").

Pins the three contracts the feature ships on:

- **exhaustiveness**: every second of trainer wall-clock lands in
  exactly ONE exclusive bucket — sum(buckets) == elapsed by
  construction (``host_other`` is the derived residual), including
  across chaos faults and a SIGTERM → resume restart;
- **zero overhead off**: with ``FLAGS_train_goodput`` unset no ledger
  is ever allocated (``GOODPUT_STATS['ledgers_allocated']`` stays 0),
  no registry series appear, and the compiled step program — and
  therefore the loss trajectory — is bit-identical;
- **attribution**: each chaos drill's wall-clock shows up in its
  designated bucket (``ckpt.write.torn`` → checkpoint_stall,
  ``grad.nonfinite`` → nonfinite_rollback, ``collective.hang`` →
  host_other), and ``train_goodput_pct`` reconstructs bit-consistently
  across preemption via the CheckpointManager sidecar.
"""

import json
import os
import signal
import sys
import time
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.distributed.checkpoint import (CheckpointManager,
                                               PreemptionSignal)
from paddle_tpu.distributed.checkpoint.manager import MANAGER_STATE_NAME
from paddle_tpu.jit.to_static import TrainStep, _layer_key
from paddle_tpu.monitor import flight_recorder as flight
from paddle_tpu.monitor import goodput, scoped_registry
from paddle_tpu.monitor import trace as trace_mod
from paddle_tpu.monitor.goodput import (BADPUT_BUCKETS, BUCKETS,
                                        GOODPUT_STATS, GoodputLedger,
                                        LayerHealthMonitor)
from paddle_tpu.monitor.metrics import MetricsRegistry
from paddle_tpu.monitor.server import AdminServer
from paddle_tpu.testing import chaos

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def _build_step(**kwargs):
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    return TrainStep(model, lambda l, a, b: F.cross_entropy(l(a), b),
                     paddle.optimizer.Adam(learning_rate=1e-2,
                                           parameters=model.parameters()),
                     **kwargs)


def _batch(i):
    rng = np.random.default_rng(50 + i)
    return (rng.standard_normal((8, 8)).astype(np.float32),
            rng.integers(0, 4, (8,)).astype(np.int64))


def _ref_losses(n):
    step = _build_step()
    return [float(step(*_batch(i))) for i in range(n)]


# ---------------------------------------------------------------------------
# GoodputLedger unit behaviour
# ---------------------------------------------------------------------------

def test_bucket_taxonomy():
    assert BUCKETS[0] == "productive_dispatch"
    assert set(BADPUT_BUCKETS) == set(BUCKETS) - {"productive_dispatch"}
    for b in ("compile", "data_wait", "checkpoint_stall",
              "nonfinite_rollback", "restart_gap", "host_other"):
        assert b in BADPUT_BUCKETS
    led = GoodputLedger()
    with pytest.raises(ValueError, match="unknown goodput bucket"):
        with led.measure("coffee_break"):
            pass


def test_bucket_sum_equals_elapsed():
    """The exhaustiveness invariant: measured buckets plus the derived
    host_other residual account for ALL elapsed wall-clock."""
    led = GoodputLedger()
    with led.measure("compile"):
        time.sleep(0.02)
    with led.measure("productive_dispatch"):
        time.sleep(0.03)
    time.sleep(0.01)            # unmeasured host time -> residual
    snap = led.snapshot()
    total = sum(snap["buckets"].values())
    assert total == pytest.approx(snap["elapsed_s"], rel=1e-6)
    # the acceptance band (1%) is therefore trivially met
    assert abs(total - snap["elapsed_s"]) <= 0.01 * snap["elapsed_s"]
    assert snap["buckets"]["compile"] >= 0.015
    assert snap["buckets"]["productive_dispatch"] >= 0.025
    assert snap["buckets"]["host_other"] >= 0.005
    assert 0.0 < snap["goodput_pct"] < 100.0


def test_nested_measures_never_double_count():
    """The exclusivity cursor clips overlap: an inner interval already
    accounted is never charged again to the outer bucket."""
    led = GoodputLedger()
    t_begin = time.perf_counter()
    with led.measure("host_other"):
        with led.measure("compile"):
            time.sleep(0.02)
        time.sleep(0.01)
    wall = time.perf_counter() - t_begin
    assert led._seconds["compile"] >= 0.015
    # outer gets only its own tail, inner only its own body; together
    # they can never exceed the real wall-clock of the nest
    assert (led._seconds["compile"] + led._seconds["host_other"]
            <= wall + 1e-6)


def test_measure_on_error_attributes_and_reraises():
    led = GoodputLedger()
    with pytest.raises(RuntimeError, match="boom"):
        with led.measure("productive_dispatch", on_error="host_other"):
            time.sleep(0.01)
            raise RuntimeError("boom")
    assert led._seconds["host_other"] >= 0.005
    assert led._seconds["productive_dispatch"] == 0.0


def test_reattribute_last_moves_seconds_once():
    led = GoodputLedger()
    assert led.reattribute_last("nonfinite_rollback") == 0.0
    with led.measure("productive_dispatch"):
        time.sleep(0.01)
    moved = led.reattribute_last("nonfinite_rollback")
    assert moved >= 0.005
    assert led._seconds["productive_dispatch"] == pytest.approx(0.0,
                                                                abs=1e-12)
    assert led._seconds["nonfinite_rollback"] == pytest.approx(moved)
    assert GOODPUT_STATS["reattributions"] == 1
    # idempotent when the interval already lives in the target bucket
    assert led.reattribute_last("nonfinite_rollback") == \
        pytest.approx(moved)
    assert GOODPUT_STATS["reattributions"] == 1


def test_restore_is_bit_consistent_and_names_the_gap():
    a = GoodputLedger()
    with a.measure("productive_dispatch"):
        time.sleep(0.02)
    with a.measure("compile"):
        time.sleep(0.01)
    saved = a.state()
    assert saved["version"] == 1 and saved["wall"] > 0
    saved = json.loads(json.dumps(saved))     # the sidecar round-trip
    time.sleep(0.05)                          # the restart dead time
    b = GoodputLedger()
    gap = b.restore(saved)
    assert gap > 0.0
    assert GOODPUT_STATS["restores"] == 1
    for bucket in BUCKETS:
        if bucket != "restart_gap":
            assert b._carry[bucket] == saved["buckets"][bucket]
    assert b._carry["restart_gap"] == \
        saved["buckets"]["restart_gap"] + gap
    assert b._restarts == saved["restarts"] + 1
    snap = b.snapshot()
    assert snap["restarts"] == 1
    # productive seconds carried bit-exactly, invariant intact
    assert snap["buckets"]["productive_dispatch"] == \
        saved["buckets"]["productive_dispatch"]
    assert sum(snap["buckets"].values()) == \
        pytest.approx(snap["elapsed_s"], rel=1e-6)


def test_restore_without_wall_stamp_adds_no_gap():
    b = GoodputLedger()
    gap = b.restore({"buckets": {"compile": 1.0}, "elapsed_s": 2.0,
                     "restarts": 0})
    assert gap == 0.0
    assert b._carry["compile"] == 1.0
    assert b._carry["restart_gap"] == 0.0


def test_publish_emits_monotonic_counter_deltas():
    led = GoodputLedger()
    reg = MetricsRegistry()
    with led.measure("compile"):
        time.sleep(0.01)
    led.publish(reg)
    ctr = reg.get("train_badput_seconds_total")
    first = ctr.value(bucket="compile")
    assert first >= 0.005
    with led.measure("compile"):
        time.sleep(0.01)
    led.publish(reg)
    assert ctr.value(bucket="compile") > first   # delta, not re-set
    assert reg.get("train_goodput_pct") is not None


# ---------------------------------------------------------------------------
# LayerHealthMonitor + layer grouping
# ---------------------------------------------------------------------------

def test_layer_key_groups_by_first_numeric_component():
    assert _layer_key("layers.0.attn.qkv_weight") == "layers.0"
    assert _layer_key("layers.11.mlp.w2") == "layers.11"
    assert _layer_key("embed.weight") == "embed"
    assert _layer_key("0.weight") == "0"
    assert _layer_key("bias") == "bias"


def test_health_monitor_spikes_after_warmup_then_rearms():
    mon = LayerHealthMonitor(alpha=0.3, factor=10.0, warmup=3)
    for _ in range(4):
        assert mon.observe({"fc": {"grad_norm": 1.0}}) == []
    assert mon.observe({"fc": {"grad_norm": 50.0}}) == ["fc"]
    # the EWMA keeps tracking: a genuine regime change stops alerting
    for _ in range(12):
        mon.observe({"fc": {"grad_norm": 50.0}})
    assert mon.observe({"fc": {"grad_norm": 50.0}}) == []


def test_health_monitor_nonfinite_always_spikes():
    mon = LayerHealthMonitor()
    assert mon.observe({"a": {"grad_norm": float("nan")}}) == ["a"]


# ---------------------------------------------------------------------------
# Zero-overhead pin (flags off — the default)
# ---------------------------------------------------------------------------

def test_zero_overhead_when_flags_off():
    """FLAGS_train_goodput unset: no ledger allocation, no accounting,
    no registry series, no stats section, no statusz section."""
    step = _build_step()
    with scoped_registry() as reg:
        for i in range(2):
            step(*_batch(i))
    with goodput.measure("compile"):       # the seam form: a no-op
        pass
    assert GOODPUT_STATS["ledgers_allocated"] == 0
    assert GOODPUT_STATS["intervals_accounted"] == 0
    assert goodput.get_ledger() is None
    assert goodput.active_ledger() is None
    assert goodput.statusz_section() is None
    assert "goodput" not in step.stats()
    assert reg.write_count == 0
    assert reg.get("train_goodput_pct") is None
    assert reg.get("train_badput_seconds_total") is None


def test_flag_on_keeps_loss_trajectory_bit_identical():
    """The ledger only brackets host seams: dispatch args and the
    compiled program are untouched, so losses match bit-for-bit."""
    ref = _ref_losses(3)
    with flag_scope("train_goodput", True):
        step = _build_step()
        got = [float(step(*_batch(i))) for i in range(3)]
    assert got == ref
    assert GOODPUT_STATS["ledgers_allocated"] == 1


# ---------------------------------------------------------------------------
# TrainStep integration
# ---------------------------------------------------------------------------

def test_trainstep_stats_carry_goodput_snapshot():
    with flag_scope("train_goodput", True):
        step = _build_step()
        for i in range(3):
            step(*_batch(i))
        snap = step.stats()["goodput"]
        assert snap["buckets"]["compile"] > 0.0
        assert snap["buckets"]["productive_dispatch"] > 0.0
        assert 0.0 < snap["goodput_pct"] < 100.0
        assert sum(snap["buckets"].values()) == \
            pytest.approx(snap["elapsed_s"], rel=0.01)
    # flag off again: the section disappears (ledger object survives)
    assert "goodput" not in step.stats()
    assert goodput.get_ledger() is not None


def test_monitor_mode_publishes_goodput_series():
    with flag_scope("train_goodput", True), flag_scope("monitor", True):
        with scoped_registry() as reg:
            step = _build_step()
            for i in range(2):
                step(*_batch(i))
    assert reg.get("train_goodput_pct") is not None
    prom = reg.to_prometheus()
    assert "train_goodput_pct" in prom
    assert "train_badput_seconds_total" in prom
    assert 'bucket="compile"' in prom


def test_statusz_renders_goodput_section():
    with flag_scope("train_goodput", True):
        led = goodput.active_ledger()
        with led.measure("compile"):
            time.sleep(0.005)
        srv = AdminServer(port=0).start()
        try:
            srv.register_status("goodput", goodput.statusz_section)
            with urllib.request.urlopen(srv.url + "/statusz",
                                        timeout=10) as r:
                doc = json.loads(r.read())
        finally:
            srv.close()
    sec = doc["sections"]["goodput"]
    assert sec["buckets"]["compile"] > 0
    assert "goodput_pct" in sec and "elapsed_s" in sec


def test_data_wait_span_attaches_to_step_trace():
    """The wait for a step's batch closes before its trace exists; the
    ledger parks the interval and TrainStep attaches it retroactively
    as an explicit-timestamp span on the same perf_counter timeline."""
    from paddle_tpu.io import DataLoader, Dataset

    class _DS(Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, i):
            rng = np.random.default_rng(i)
            return (rng.standard_normal(8).astype(np.float32),
                    np.int64(i % 4))

    with flag_scope("train_goodput", True), flag_scope("trace", True), \
            flag_scope("trace_sample", 1.0):
        step = _build_step()
        loader = DataLoader(_DS(), batch_size=8, drop_last=True)
        xb, yb = next(iter(loader))
        step(xb, yb)
        kept = [t for t in trace_mod.get_tracer().retained()
                if t.name == "train.step"]
    assert kept
    names = [s.name for s in kept[-1].spans]
    assert "data_wait" in names and "train.dispatch" in names
    dw = [s for s in kept[-1].spans if s.name == "data_wait"][0]
    assert dw.t1 is not None and dw.t1 >= dw.t0
    # consumed on attach: nothing pending for the next step
    assert goodput.get_ledger().pop_pending_data_wait() is None
    assert goodput.get_ledger().snapshot()["buckets"]["data_wait"] > 0


# ---------------------------------------------------------------------------
# Per-layer model health in the compiled step
# ---------------------------------------------------------------------------

def test_health_gauges_and_last_vector():
    with flag_scope("train_goodput", True), \
            flag_scope("train_health_every", 1), \
            flag_scope("monitor", True):
        with scoped_registry() as reg:
            step = _build_step()
            for i in range(2):
                step(*_batch(i))
    lh = goodput.last_layer_health()
    assert lh is not None and lh["step"] == 2
    # nn.Sequential param names are index-rooted: layers "0" and "2"
    assert set(lh["layers"]) == {"0", "2"}
    for vals in lh["layers"].values():
        assert set(vals) == {"grad_norm", "param_norm", "update_ratio"}
        assert all(np.isfinite(v) and v >= 0 for v in vals.values())
    prom = reg.to_prometheus()
    assert "train_layer_grad_norm" in prom and 'layer="0"' in prom
    assert "train_layer_param_norm" in prom
    assert "train_layer_update_ratio" in prom


def test_health_program_preserves_trajectory():
    """Health side-outputs only ADD f32 scalars to the step program —
    params/opt-state math is byte-for-byte the same computation."""
    ref = _ref_losses(3)
    with flag_scope("train_health_every", 1):
        step = _build_step()
        got = [float(step(*_batch(i))) for i in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    assert step.stats()["compiles"] == 1     # one program, health fused


def test_health_publish_respects_cadence():
    with flag_scope("train_health_every", 2):
        step = _build_step()
        step(*_batch(0))
        assert goodput.last_layer_health() is None     # step 1: skipped
        step(*_batch(1))
        lh = goodput.last_layer_health()
        assert lh is not None and lh["step"] == 2


def test_health_spike_marks_trace_and_flight():
    assert "health_spike" in trace_mod.ANOMALY_REASONS
    assert "health_spike" in flight.RECOVERY_EVENTS
    step = _build_step()
    mon = LayerHealthMonitor(warmup=0)
    for _ in range(2):
        mon.observe({"0": {"grad_norm": 1.0}})
    step._health_mon = mon
    hvec = {"0": {"grad_norm": np.float32(1e6),
                  "param_norm": np.float32(1.0),
                  "update_ratio": np.float32(1e-3)}}
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0), \
            flag_scope("flight_recorder", True):
        tr = trace_mod.get_tracer().start_trace("train.step")
        with trace_mod.activate(tr):
            step._publish_health(hvec, False)
        trace_mod.get_tracer().finish_trace(tr)
        events = flight.get_flight_recorder().events
    assert tr.anomaly == "health_spike"
    assert step.stats()["health_spikes"] == 1
    spikes = [e for e in events if e["event"] == "health_spike"]
    assert spikes and spikes[0]["layers"] == ["0"]


def test_flight_dump_attaches_goodput_and_layer_health():
    """Satellite: every flight-recorder dump carries the goodput
    snapshot and the last layer-health vector; --flight renders them."""
    import monitor_report
    with flag_scope("train_goodput", True):
        led = goodput.active_ledger()
        with led.measure("compile"):
            time.sleep(0.005)
        goodput.note_layer_health(
            {"0": {"grad_norm": 1.5, "param_norm": 2.0,
                   "update_ratio": 3e-4}}, step=7)
        doc = flight.get_flight_recorder().doc(reason="test")
    assert doc["goodput"]["buckets"]["compile"] > 0
    assert doc["layer_health"]["step"] == 7
    assert doc["layer_health"]["layers"]["0"]["param_norm"] == 2.0
    out = monitor_report.render_flight(doc)
    assert "goodput:" in out
    assert "Goodput buckets at dump (seconds)" in out
    assert "Last layer-health vector (step 7)" in out


# ---------------------------------------------------------------------------
# Windowed rendering (monitor_report --goodput, monitor_top pane)
# ---------------------------------------------------------------------------

def test_monitor_report_goodput_section(tmp_path):
    import monitor_report
    from paddle_tpu.monitor import load_jsonl
    reg = MetricsRegistry()
    led = GoodputLedger()
    with led.measure("data_wait"):
        time.sleep(0.01)
    with led.measure("productive_dispatch"):
        time.sleep(0.01)
    led.publish(reg)
    reg.gauge("train_layer_grad_norm", "h").set(3.5, layer="layers.0")
    reg.gauge("train_layer_update_ratio", "h").set(2e-3, layer="layers.0")
    reg.counter("train_health_spikes_total", "h").inc(layer="layers.0")
    p = str(tmp_path / "m.jsonl")
    reg.dump_jsonl(p)
    out = monitor_report.render(load_jsonl(p), goodput=True)
    assert "Training goodput (FLAGS_train_goodput)" in out
    assert "Badput by bucket" in out and "data_wait" in out
    assert "Per-layer model health" in out and "layers.0" in out
    # empty dump: a hint, not a crash
    assert "no goodput series" in monitor_report.render([], goodput=True)


def test_monitor_top_goodput_pane():
    import monitor_top
    from paddle_tpu.monitor.timeseries import (TimeseriesRing,
                                               parse_prometheus)

    class _Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = _Clock()
    ring = TimeseriesRing(clock=clock)
    reg = MetricsRegistry()
    reg.gauge("train_goodput_pct", "h").set(87.5)
    reg.counter("train_badput_seconds_total", "h").inc(
        1.0, bucket="data_wait")
    reg.gauge("train_layer_grad_norm", "h").set(4.0, layer="0")
    reg.gauge("train_layer_update_ratio", "h").set(1e-3, layer="0")
    ring.ingest_rows(parse_prometheus(reg.to_prometheus()))
    clock.t += 2.0
    reg.counter("train_badput_seconds_total", "h").inc(
        0.5, bucket="data_wait")
    ring.ingest_rows(parse_prometheus(reg.to_prometheus()))
    frame = monitor_top.render_frame(ring, "http://h/metrics")
    assert "goodput" in frame and "87.5% productive" in frame
    assert "badput/s" in frame and "data_wait" in frame
    assert "layers" in frame and "|g|=" in frame


# ---------------------------------------------------------------------------
# Chaos drills: every fault's wall-clock lands in its designated bucket
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_nonfinite_lands_in_rollback_bucket():
    """A chaos-NaN step trips the watchdog: its dispatch seconds are
    re-attributed from productive_dispatch to nonfinite_rollback (a
    rolled-back update made no progress) and the trip handling itself
    is accounted there too."""
    with flag_scope("train_goodput", True):
        chaos.configure("grad.nonfinite@2")
        step = _build_step(skip_nonfinite_budget=2)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for i in range(3):
                step(*_batch(i))
        chaos.reset()
        snap = goodput.get_ledger().snapshot()
    assert step.stats()["nonfinite_skips"] == 1
    assert snap["buckets"]["nonfinite_rollback"] > 0.0
    assert GOODPUT_STATS["reattributions"] >= 1
    assert sum(snap["buckets"].values()) == \
        pytest.approx(snap["elapsed_s"], rel=0.01)


@pytest.mark.chaos
def test_chaos_torn_checkpoint_write_lands_in_stall_bucket(tmp_path):
    """A torn write corrupts silently (save() does not raise) — its
    wall-clock still shows up as checkpoint_stall, never vanishing."""
    with flag_scope("train_goodput", True):
        step = _build_step()
        step(*_batch(0))
        before = goodput.get_ledger().snapshot()["buckets"][
            "checkpoint_stall"]
        mgr = CheckpointManager(step, str(tmp_path / "ck"),
                                interval_steps=1, asynchronous=False)
        try:
            chaos.configure("ckpt.write.torn@1")
            mgr.save()
            chaos.reset()
        finally:
            mgr.close()
        after = goodput.get_ledger().snapshot()["buckets"][
            "checkpoint_stall"]
    assert after > before


@pytest.mark.chaos
def test_chaos_hung_collective_is_host_other_badput():
    """The dispatch seam measures with on_error='host_other': a
    chaos-hung collective that dies as CollectiveTimeoutError inside
    the dispatch window is named badput, never productive time."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import collective as C
    with flag_scope("train_goodput", True), \
            flag_scope("collective_timeout_s", 1.0):
        g = C.new_group([0, 1])
        chaos.arm("collective.hang", at=1)
        with pytest.raises(C.CollectiveTimeoutError):
            with goodput.measure("productive_dispatch",
                                 on_error="host_other"):
                C.all_reduce(jnp.ones((2, 4), jnp.float32), group=g)
        chaos.reset()
        snap = goodput.get_ledger().snapshot()
    assert snap["buckets"]["host_other"] >= 0.9     # ~the 1s timeout
    assert snap["buckets"]["productive_dispatch"] == 0.0


# ---------------------------------------------------------------------------
# SIGTERM → resume: goodput reconstructs across the restart
# ---------------------------------------------------------------------------

def test_goodput_survives_sigterm_resume(tmp_path):
    """Acceptance: the ledger rides the CheckpointManager sidecar
    through a preemption — bucket totals restore bit-exactly, the dead
    time between the final commit and the new process is attributed to
    restart_gap, and published counters stay monotonic."""
    root = str(tmp_path / "ckpts")
    with flag_scope("train_goodput", True):
        step_a = _build_step()
        with pytest.raises(PreemptionSignal) as exc:
            with CheckpointManager(step_a, root, interval_steps=2,
                                   keep_n=2) as mgr:
                for i in range(4):
                    step_a(*_batch(i))
                    if i == 2:
                        os.kill(os.getpid(), signal.SIGTERM)
                    mgr.on_step(dataloader_state={"offset": i + 1})
        assert exc.value.step == 3
        with open(os.path.join(exc.value.path, MANAGER_STATE_NAME)) as f:
            saved = json.load(f)["goodput"]
        assert saved["wall"] > 0 and saved["restarts"] == 0
        assert saved["buckets"]["productive_dispatch"] > 0
        assert sum(saved["buckets"].values()) == \
            pytest.approx(saved["elapsed_s"], rel=0.01)

        # "new process": module state dropped, then resume restores the
        # sidecar into a freshly allocated ledger
        goodput.reset()
        time.sleep(0.05)
        step_b = _build_step()
        with CheckpointManager(step_b, root, interval_steps=2,
                               keep_n=2) as mgr2:
            info = mgr2.resume()
        assert info["step"] == 3
        led = goodput.get_ledger()
        assert led is not None and GOODPUT_STATS["restores"] == 1
        for b in BUCKETS:
            if b != "restart_gap":
                assert led._carry[b] == saved["buckets"][b]
        gap = led._carry["restart_gap"] - saved["buckets"]["restart_gap"]
        assert gap > 0.0
        snap = led.snapshot()
        assert snap["restarts"] == 1
        # bit-consistent reconstruction: the productive numerator is
        # exactly the saved one, and the invariant still holds with the
        # restart gap folded in
        assert snap["buckets"]["productive_dispatch"] == \
            saved["buckets"]["productive_dispatch"]
        assert snap["buckets"]["restart_gap"] >= gap
        assert sum(snap["buckets"].values()) == \
            pytest.approx(snap["elapsed_s"], rel=0.01)
        # a restarted process publishes to a fresh registry: its first
        # publish carries the restored totals forward, so the fleet
        # aggregate never drops below what the dead process durably
        # exported in the sidecar
        reg_b = MetricsRegistry()
        led.publish(reg_b)
        ctr = reg_b.get("train_badput_seconds_total")
        for b in BADPUT_BUCKETS:
            assert ctr.value(bucket=b) >= saved["buckets"][b] - 1e-9
