"""Serving resilience layer (ISSUE 8): deadlines + cancellation,
admission control / load shedding, graceful drain, fault isolation,
decode watchdog, chaos-verified SLOs."""

import os
import signal
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.core.tensor import no_grad
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.monitor import scoped_registry
from paddle_tpu.serving import (DecodeWatchdogError, EngineDrained,
                                LoadSpec, OverloadDetector, Request,
                                ServerOverloaded, ServingConfig,
                                ServingEngine, TokenBucket,
                                build_requests, load_drain_snapshot,
                                requests_from_snapshot, run_open_loop)
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.scheduler import (TERMINAL_OUTCOMES, BucketTable,
                                          Scheduler)
from paddle_tpu.testing import chaos

pytestmark = [pytest.mark.serve, pytest.mark.chaos]


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    return GPTForPretraining(gpt_tiny())


class ManualClock:
    """Controllable clock for deadline/overload tests (engine +
    scheduler share it; latencies then measure virtual time)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _engine(model, clock=None, **kw):
    cfg = dict(max_batch_slots=3, block_size=4, max_context_len=64,
               prefill_buckets=(8, 16), batch_buckets=(1, 2))
    cfg.update(kw)
    kw2 = {"clock": clock} if clock is not None else {}
    return ServingEngine(model, ServingConfig(**cfg), **kw2)


def _golden(model, prompt, n):
    """Re-derive every generated token by full uncached forwards."""
    seq = np.asarray(prompt, np.int32)
    for _ in range(n):
        with no_grad():
            lg = model(paddle.to_tensor(seq[None, :])).numpy()
        seq = np.concatenate([seq, [np.int32(lg[0, -1].argmax())]])
    return seq


def _prompts(rng, n, lo=4, hi=10):
    return [rng.integers(2, 250,
                         (int(rng.integers(lo, hi + 1)),)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# deadlines + cancellation
# ---------------------------------------------------------------------------


def test_queued_deadline_expires_before_any_slot(tiny_model):
    clock = ManualClock()
    eng = _engine(tiny_model, clock=clock, max_batch_slots=1)
    rng = np.random.default_rng(0)
    # slot is busy with a long request; the deadlined one waits
    busy = eng.submit(Request(rng.integers(2, 250, (5,)),
                              max_new_tokens=8))
    doomed = eng.submit(Request(rng.integers(2, 250, (5,)),
                                max_new_tokens=4, deadline_s=0.5))
    eng.step()
    clock.advance(1.0)                     # deadline passes in the queue
    with scoped_registry() as reg:
        eng.run()
    assert doomed.outcome == "expired"
    assert doomed.generated == []          # never touched a slot
    assert doomed.slot is None
    assert busy.outcome == "completed"
    assert reg.get("serve_requests_total").value(event="expired") == 1
    assert eng.scheduler.stats["expired_queued"] == 1   # shed-like
    assert eng.cache.allocator.pages_in_use == 0


def test_inflight_deadline_cancelled_at_boundary_pages_freed(tiny_model):
    clock = ManualClock()
    eng = _engine(tiny_model, clock=clock)
    rng = np.random.default_rng(1)
    p_keep = rng.integers(2, 250, (6,)).astype(np.int32)
    keep = eng.submit(Request(p_keep, max_new_tokens=6))
    doomed = eng.submit(Request(rng.integers(2, 250, (6,)),
                                max_new_tokens=6, deadline_s=0.5))
    eng.step()                             # both admitted, first tokens
    assert len(doomed.generated) >= 1
    in_use = eng.cache.allocator.pages_in_use
    clock.advance(1.0)
    eng.step()                             # boundary sweep expires it
    assert doomed.outcome == "expired"
    # admitted and decoded: counts against availability, never as shed
    assert eng.scheduler.stats["expired_queued"] == 0
    assert eng.cache.allocator.pages_in_use < in_use   # freed immediately
    eng.run()
    assert keep.outcome == "completed"     # survivor streams on, exact
    np.testing.assert_array_equal(
        np.concatenate([p_keep, keep.generated]),
        _golden(tiny_model, p_keep, 6))


def test_deadline_slack_histogram_only_for_deadline_requests(tiny_model):
    eng = _engine(tiny_model)
    rng = np.random.default_rng(2)
    with scoped_registry() as reg:
        eng.generate([rng.integers(2, 250, (5,))], max_new_tokens=2)
        assert reg.get("serve_deadline_slack_seconds") is None
        eng.submit(Request(rng.integers(2, 250, (5,)),
                           max_new_tokens=2, deadline_s=60.0))
        eng.run()
        h = reg.get("serve_deadline_slack_seconds")
        assert h is not None and h.count() == 1


def test_cancel_queued_and_inflight(tiny_model):
    eng = _engine(tiny_model, max_batch_slots=1)
    rng = np.random.default_rng(3)
    stream = []
    running = eng.submit(Request(
        rng.integers(2, 250, (5,)), max_new_tokens=8,
        on_token=lambda r, t, txt: stream.append(t)))
    queued = eng.submit(Request(rng.integers(2, 250, (5,)),
                                max_new_tokens=8))
    eng.step()
    assert eng.cancel(queued.request.request_id)   # queued: immediate
    assert queued.outcome == "cancelled"
    assert eng.cancel(running.request.request_id)  # in-flight: latched
    assert running.outcome is None
    n_at_cancel = len(stream)
    eng.run()
    assert running.outcome == "cancelled"
    assert len(stream) == n_at_cancel              # stream stopped
    assert eng.cache.allocator.pages_in_use == 0
    assert not eng.cancel(queued.request.request_id)   # already terminal
    assert not eng.cancel(987654)                      # unknown id


# ---------------------------------------------------------------------------
# admission control + load shedding
# ---------------------------------------------------------------------------


def _host_scheduler(policy="reject-new", max_queue=2, max_slots=2,
                    num_pages=12, on_event=None, clock=None):
    cache = PagedKVCache(1, 1, 4, num_pages=num_pages, block_size=4,
                         max_slots=max_slots, max_blocks_per_slot=6)
    kw = {"clock": clock} if clock is not None else {}
    return Scheduler(cache, BucketTable((8, 16, 24), (1, 2)),
                     max_queue=max_queue, policy=policy,
                     on_event=on_event, **kw)


def _fill(sched, n=2):
    """Occupy all slots so new submits stay queued."""
    sts = [sched.submit(Request([1, 2, 3], max_new_tokens=4))
           for _ in range(n)]
    sched.plan_admissions()
    return sts


def test_policy_reject_new():
    sched = _host_scheduler(policy="reject-new", max_queue=2)
    _fill(sched)
    q = [sched.submit(Request([1, 2], max_new_tokens=2))
         for _ in range(2)]
    with pytest.raises(ServerOverloaded) as ei:
        sched.submit(Request([1, 2], max_new_tokens=2))
    assert ei.value.reason == "queue_full"
    assert all(st.outcome is None for st in q)     # nobody else harmed


def test_policy_drop_oldest():
    events = []
    sched = _host_scheduler(policy="drop-oldest", max_queue=2,
                            on_event=lambda ev, st: events.append((ev, st)))
    _fill(sched)
    old = sched.submit(Request([1, 2], max_new_tokens=2))
    mid = sched.submit(Request([3, 4], max_new_tokens=2))
    new = sched.submit(Request([5, 6], max_new_tokens=2))  # sheds `old`
    assert old.outcome == "shed"
    assert mid.outcome is None and new.outcome is None
    assert sched.queue_depth == 2
    assert ("shed", old) in events
    assert sched.stats["shed"] == 1


def test_policy_priority_lanes():
    sched = _host_scheduler(policy="priority", max_queue=2)
    _fill(sched)
    low = sched.submit(Request([1, 2], max_new_tokens=2, priority=0))
    high = sched.submit(Request([3, 4], max_new_tokens=2, priority=5))
    # queue ordered by priority lane (high first) regardless of arrival
    assert sched.waiting[0] is high
    # a higher-priority newcomer sheds the lowest-priority waiter...
    vip = sched.submit(Request([5, 6], max_new_tokens=2, priority=9))
    assert low.outcome == "shed"
    assert sched.waiting[0] is vip
    # ...but an equal-or-lower one is rejected instead
    with pytest.raises(ServerOverloaded):
        sched.submit(Request([7, 8], max_new_tokens=2, priority=5))
    assert high.outcome is None


def test_expired_waiters_do_not_hold_queue_capacity():
    """A dead (already-expired) waiter must neither reject a live
    submit nor get mis-shed: submit sweeps expiries before the
    capacity check."""
    clock = ManualClock()
    sched = _host_scheduler(policy="reject-new", max_queue=2,
                            clock=clock)
    _fill(sched)
    dead = [sched.submit(Request([1, 2], max_new_tokens=2,
                                 deadline_s=0.5))
            for _ in range(2)]
    clock.advance(1.0)             # both waiters past their deadline
    live = sched.submit(Request([3, 4], max_new_tokens=2))
    assert all(st.outcome == "expired" for st in dead)   # not "shed"
    assert live.outcome is None and live in sched.waiting
    assert sched.stats["expired"] == 2
    assert sched.stats["shed"] == 0


def test_overload_detector_hysteresis():
    det = OverloadDetector(threshold_s=1.0, alpha=1.0, exit_frac=0.5)
    assert det.observe(0.2) is None and not det.overloaded
    assert det.observe(1.5) == "enter" and det.overloaded
    assert det.observe(1.2) is None          # still above exit band
    assert det.observe(0.7) is None          # inside the hysteresis band
    assert det.observe(0.3) == "exit" and not det.overloaded


def test_overload_shedding_state_on_engine(tiny_model):
    clock = ManualClock()
    eng = _engine(tiny_model, clock=clock, max_batch_slots=1,
                  overload_threshold_s=1.0, overload_alpha=1.0)
    rng = np.random.default_rng(4)
    with scoped_registry() as reg:
        eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=3))
        stuck = eng.submit(Request(rng.integers(2, 250, (5,)),
                                   max_new_tokens=3))
        eng.step()
        clock.advance(5.0)                  # head-of-queue delay blows up
        eng.step()
        assert eng._overload.overloaded
        assert reg.get("serve_overload").value() == 1.0
        with pytest.raises(ServerOverloaded) as ei:
            eng.submit(Request(rng.integers(2, 250, (4,)),
                               max_new_tokens=2))
        assert ei.value.reason == "overload"
        assert reg.get("serve_requests_total").value(
            event="rejected") == 1
        eng.run()                           # queue drains -> delay 0
        assert stuck.outcome == "completed"
        for _ in range(8):                  # EWMA decays below exit
            eng.step()
        assert not eng._overload.overloaded
        assert reg.get("serve_overload").value() == 0.0
        assert reg.get("serve_overload_transitions_total").value(
            state="enter") == 1
        assert reg.get("serve_overload_transitions_total").value(
            state="exit") == 1
    # recovered: admission works again
    eng.submit(Request(rng.integers(2, 250, (4,)), max_new_tokens=2))
    eng.run()


def test_overload_recovers_on_idle_engine(tiny_model):
    """A tripped detector must not latch forever once the engine goes
    idle: drivers only call step() while there is work, so submit()
    itself folds the empty-queue delay sample in while overloaded."""
    clock = ManualClock()
    eng = _engine(tiny_model, clock=clock, max_batch_slots=1,
                  overload_threshold_s=1.0, overload_alpha=0.3)
    rng = np.random.default_rng(6)
    eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=2))
    stuck = eng.submit(Request(rng.integers(2, 250, (5,)),
                               max_new_tokens=2))
    eng.step()
    clock.advance(5.0)
    eng.step()                              # head-of-queue delay trips
    assert eng._overload.overloaded
    eng.run()                               # drains; engine now idle
    assert stuck.outcome == "completed"
    assert not eng.scheduler.has_work
    # the EWMA is still above the exit band: the first idle submit is
    # refused, but each refusal decays the detector...
    with pytest.raises(ServerOverloaded):
        eng.submit(Request(rng.integers(2, 250, (4,)), max_new_tokens=2))
    st = None
    for _ in range(16):
        try:
            st = eng.submit(Request(rng.integers(2, 250, (4,)),
                                    max_new_tokens=2))
            break
        except ServerOverloaded:
            pass
    # ...so the idle engine recovers WITHOUT a single step() call
    assert st is not None and not eng._overload.overloaded
    eng.run()
    assert st.outcome == "completed"


def test_oldest_waiting_under_priority_lanes():
    """The overload detector samples the OLDEST waiter; under the
    priority policy that is not waiting[0] (the head of the highest
    lane), or starving low-priority requests could never trip it."""
    clock = ManualClock()
    sched = _host_scheduler(policy="priority", max_queue=4, clock=clock)
    _fill(sched)
    old_low = sched.submit(Request([1, 2], max_new_tokens=2, priority=0))
    clock.advance(3.0)
    fresh_high = sched.submit(Request([3, 4], max_new_tokens=2,
                                      priority=5))
    assert sched.waiting[0] is fresh_high   # lane order
    assert sched.oldest_waiting_t() == old_low.submitted_t


def test_run_open_loop_gives_up_on_persistent_watchdog_trips():
    """A backend that hangs on EVERY retry is down, not slow: the
    open-loop driver re-raises instead of looping forever (each retry
    would abandon another live dispatch thread)."""
    class _HungEngine:
        class scheduler:
            has_work = True

        def submit(self, request):
            return None

        def step(self):
            raise DecodeWatchdogError("decode", 0.1, 1, 1)

    spec = LoadSpec(num_requests=1, rate_rps=1e6, prompt_len_range=(4, 4),
                    max_new_range=(2, 2), vocab_size=64, seed=0)
    with pytest.raises(DecodeWatchdogError):
        run_open_loop(_HungEngine(), spec)


# ---------------------------------------------------------------------------
# fault isolation
# ---------------------------------------------------------------------------


def test_poisoned_request_fails_alone(tiny_model):
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, 3, 5, 8)
    golden = [_golden(tiny_model, p, 4) for p in prompts]
    with flag_scope("flight_recorder", True), \
            chaos.chaos_scope("serve.request.poison@2"):
        eng = _engine(tiny_model)
        sts = [eng.submit(Request(p, max_new_tokens=4)) for p in prompts]
        eng.run()
        from paddle_tpu.monitor import flight_recorder as fr
        events = [e for e in fr.get_flight_recorder().events
                  if e.get("event") == "request_failed"]
    assert sts[1].poisoned and sts[1].outcome == "failed"
    assert "non-finite" in sts[1].failure
    assert len(events) == 1
    assert events[0]["request_id"] == sts[1].request.request_id
    # the rest of the batch streamed on, token-exact
    for i in (0, 2):
        assert sts[i].outcome == "completed"
        np.testing.assert_array_equal(
            np.concatenate([prompts[i], sts[i].generated]), golden[i])
    assert eng.cache.allocator.pages_in_use == 0


def test_detokenizer_exception_fails_only_its_request(tiny_model):
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, 2, 5, 7)
    golden = [_golden(tiny_model, p, 4) for p in prompts]
    with chaos.chaos_scope("serve.detok.raise@2"):
        eng = _engine(tiny_model)
        sts = [eng.submit(Request(p, max_new_tokens=4,
                                  on_token=lambda r, t, txt: None))
               for p in prompts]
        eng.run()
    outcomes = sorted(st.outcome for st in sts)
    assert outcomes == ["completed", "failed"]
    survivor = next(i for i, st in enumerate(sts)
                    if st.outcome == "completed")
    np.testing.assert_array_equal(
        np.concatenate([prompts[survivor], sts[survivor].generated]),
        golden[survivor])
    assert eng.cache.allocator.pages_in_use == 0


def test_malformed_stop_condition_fails_request(tiny_model):
    rng = np.random.default_rng(7)
    eng = _engine(tiny_model)

    def bad_stop(generated):
        raise TypeError("malformed stop condition")

    st_bad = eng.submit(Request(rng.integers(2, 250, (5,)),
                                max_new_tokens=4, stop=bad_stop))
    st_ok = eng.submit(Request(rng.integers(2, 250, (5,)),
                               max_new_tokens=4,
                               stop=lambda g: len(g) >= 2))
    eng.run()
    assert st_bad.outcome == "failed"
    assert "TypeError" in st_bad.failure
    assert st_ok.outcome == "completed"
    assert len(st_ok.generated) == 2       # custom stop honoured
    assert eng.cache.allocator.pages_in_use == 0


def test_pages_exhaust_chaos_forces_exact_preemption(tiny_model):
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, 2, 6, 8)
    golden = [_golden(tiny_model, p, 6) for p in prompts]
    with chaos.chaos_scope("serve.pages.exhaust@3"):
        eng = _engine(tiny_model)
        outs = eng.generate(prompts, max_new_tokens=6)
    assert eng.stats()["preemptions"] >= 1
    for out, g in zip(outs, golden):
        np.testing.assert_array_equal(out, g)
    assert eng.cache.allocator.pages_in_use == 0


def test_pages_exhaust_preempts_newest_not_slot0_occupant():
    """Slot 0 holding the NEWEST request (normal after slot turnover)
    must not shield it: the chaos dry-pool drill preempts the newest
    admitted — the same victim order as the real dry-pool path."""
    clock = ManualClock()
    sched = _host_scheduler(max_queue=4, clock=clock)
    a, b = _fill(sched)                  # a -> slot 0, b -> slot 1
    clock.advance(1.0)
    sched.finish(a)                      # slot 0 frees
    newer = sched.submit(Request([5, 6, 7], max_new_tokens=4))
    sched.plan_admissions()              # newer reuses slot 0
    assert newer.slot == 0 and b.slot == 1
    assert newer.admitted_t > b.admitted_t
    with chaos.chaos_scope("serve.pages.exhaust@1"):
        sched.ensure_decode_capacity()
    assert newer.outcome is None and newer.slot is None  # preempted
    assert sched.waiting[0] is newer     # requeued at the front
    assert b.slot == 1                   # the older request survives


def test_latched_cancel_survives_preemption_no_readmission():
    """A cancel latched on an in-flight request that is then preempted
    back to the queue must still cancel at admission time — never
    re-allocate pages and burn a prefill dispatch on a client that
    already disconnected."""
    clock = ManualClock()
    sched = _host_scheduler(max_queue=4, clock=clock)
    a = sched.submit(Request([1, 2, 3], max_new_tokens=4))
    sched.plan_admissions()
    clock.advance(0.5)
    b = sched.submit(Request([4, 5, 6], max_new_tokens=4))
    sched.plan_admissions()              # b strictly newest-admitted
    assert sched.cancel(b.request.request_id)   # latched, b in-flight
    with chaos.chaos_scope("serve.pages.exhaust@1"):
        sched.ensure_decode_capacity()   # preempts b, latch and all
    assert b.outcome is None and b in sched.waiting
    assert sched.plan_admissions() == []  # honoured, not re-admitted
    assert b.outcome == "cancelled" and b not in sched.waiting
    assert a.slot is not None and a.outcome is None


# ---------------------------------------------------------------------------
# decode watchdog
# ---------------------------------------------------------------------------


def test_watchdog_converts_hang_into_structured_error(tiny_model):
    rng = np.random.default_rng(9)
    with flag_scope("serve_watchdog_s", 0.4), \
            flag_scope("flight_recorder", True), \
            chaos.chaos_scope("serve.decode.hang@1"):
        eng = _engine(tiny_model)
        st = eng.submit(Request(rng.integers(2, 250, (5,)),
                                max_new_tokens=4))
        with scoped_registry() as reg:
            with pytest.raises(DecodeWatchdogError) as ei:
                eng.run()
            assert ei.value.kind == "decode"
            assert ei.value.timeout_s == pytest.approx(0.4)
            assert ei.value.active_slots == 1
            assert reg.get("serve_watchdog_trips_total").value(
                kind="decode") == 1
        from paddle_tpu.monitor import flight_recorder as fr
        names = [e.get("event")
                 for e in fr.get_flight_recorder().events]
        assert "decode_watchdog" in names
        assert "trip" in names             # dump recorded forensics
        # the hang was host-side (program never ran): retrying the step
        # continues the stream token-exactly
        eng.run()
    assert st.outcome == "completed"
    p = st.request.prompt
    np.testing.assert_array_equal(
        np.concatenate([p, st.generated]), _golden(tiny_model, p, 4))


def test_hang_without_watchdog_budget_is_loud(tiny_model):
    rng = np.random.default_rng(10)
    with chaos.chaos_scope("serve.decode.hang@1"):
        eng = _engine(tiny_model)
        eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=2))
        with pytest.raises(RuntimeError, match="serve_watchdog_s"):
            eng.run()


def test_watchdog_reuses_one_dispatcher_thread(tiny_model):
    """The armed watchdog must not put thread creation on the per-token
    hot path: every guarded dispatch of a healthy run rides ONE
    long-lived worker."""
    rng = np.random.default_rng(23)
    with flag_scope("serve_watchdog_s", 30.0):
        eng = _engine(tiny_model)
        st = eng.submit(Request(rng.integers(2, 250, (5,)),
                                max_new_tokens=4))
        eng.run()
    assert st.outcome == "completed"
    dispatches = (eng._stats["prefill_dispatches"]
                  + eng._stats["decode_dispatches"])
    assert dispatches >= 3
    assert len(eng._watchdog_threads) == 1
    assert eng._watchdog_worker is not None \
        and eng._watchdog_worker.usable
    eng.shutdown()
    assert eng._watchdog_worker is None


def test_prefill_trip_rolls_back_every_unprefilled_group(tiny_model):
    """A watchdog trip in the FIRST of several planned admission groups
    un-admits the later groups too: their slots were assigned but never
    prefilled, so a retried step() would otherwise decode slots with no
    token to feed."""
    rng = np.random.default_rng(24)
    with flag_scope("serve_watchdog_s", 0.4):
        eng = _engine(tiny_model)
        # different len buckets (8 vs 16) => two admission groups
        short = eng.submit(Request(rng.integers(2, 250, (5,)),
                                   max_new_tokens=3))
        long = eng.submit(Request(rng.integers(2, 250, (12,)),
                                  max_new_tokens=3))
        real_get, tripped = eng._get_prefill, []

        def slow_get(nb, sp):
            prog = real_get(nb, sp)

            def wrapper(*a):
                if not tripped:
                    tripped.append(sp)
                    time.sleep(1.5)        # blows the 0.4s budget
                return prog(*a)
            return wrapper

        eng._get_prefill = slow_get
        with pytest.raises(DecodeWatchdogError) as ei:
            eng.step()
        assert ei.value.kind == "prefill" and ei.value.retry_safe
        # BOTH groups rolled back: nothing holds a slot, nothing was
        # mis-counted as a page-pressure preemption
        assert short.slot is None and long.slot is None
        assert short.outcome is None and long.outcome is None
        assert len(eng.scheduler.waiting) == 2
        assert eng.scheduler.stats["preemptions"] == 0
        eng._get_prefill = real_get
        eng.run()                          # retried plan re-prefills
    assert short.outcome == long.outcome == "completed"
    p = short.request.prompt
    np.testing.assert_array_equal(
        np.concatenate([p, short.generated]), _golden(tiny_model, p, 3))


def test_reset_tears_down_abandoned_watchdog_thread(tiny_model):
    import paddle_tpu.serving as serving
    rng = np.random.default_rng(11)
    with flag_scope("serve_watchdog_s", 0.2):
        chaos.configure("serve.decode.hang@1")
        eng = _engine(tiny_model)
        eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=2))
        with pytest.raises(DecodeWatchdogError):
            eng.run()
        threads = list(eng._watchdog_threads)
        assert threads and threads[0].is_alive()   # abandoned in the hang
        serving.reset()                    # must not rely on chaos.reset
        threads[0].join(timeout=2.0)
        assert not threads[0].is_alive()
        assert eng._watchdog_threads == []


def test_reset_restores_drain_signal_handler(tiny_model, tmp_path):
    import paddle_tpu.serving as serving
    before = signal.getsignal(signal.SIGTERM)
    eng = _engine(tiny_model)
    eng.enable_drain(str(tmp_path / "drain"))
    assert signal.getsignal(signal.SIGTERM) is not before
    serving.reset()
    assert signal.getsignal(signal.SIGTERM) is before
    assert eng._drain_latch is None


# ---------------------------------------------------------------------------
# graceful drain (acceptance)
# ---------------------------------------------------------------------------


def test_sigterm_drain_zero_lost_and_backlog_rerun(tiny_model, tmp_path):
    root = str(tmp_path / "drain")
    rng = np.random.default_rng(12)
    prompts = _prompts(rng, 5, 5, 8)
    golden = [_golden(tiny_model, p, 6) for p in prompts]
    eng = _engine(tiny_model, max_batch_slots=2)
    eng.enable_drain(root, budget_s=0.0)   # snapshot in-flight too
    sts = [eng.submit(Request(p, max_new_tokens=6)) for p in prompts]
    eng.step()                             # 2 in flight, 3 queued
    os.kill(os.getpid(), signal.SIGTERM)   # cloud preemption
    with pytest.raises(EngineDrained) as ei:
        eng.run()
    report = ei.value.report
    # zero silently-lost requests: everything completed or snapshotted
    outcomes = [st.outcome for st in sts]
    assert all(o in ("completed", "drained") for o in outcomes)
    assert outcomes.count("drained") == report.snapshotted
    assert report.snapshotted >= 1 and report.path
    assert eng.cache.allocator.pages_in_use == 0
    with pytest.raises(ServerOverloaded):  # admission stays closed
        eng.submit(Request([1, 2], max_new_tokens=2))
    # a fresh engine re-runs the snapshotted backlog to completion —
    # greedy continuations are token-exact with the never-drained run
    path, specs = load_drain_snapshot(root)
    assert path == report.path and len(specs) == report.snapshotted
    eng2 = _engine(tiny_model, max_batch_slots=2)
    by_id = {st.request.request_id: i for i, st in enumerate(sts)}
    resub = requests_from_snapshot(specs)
    sts2 = [eng2.submit(r) for r in resub]
    eng2.run()
    for spec, st2 in zip(specs, sts2):
        assert st2.outcome == "completed"
        i = by_id[spec["request_id"]]
        full = np.concatenate([spec["prompt"], spec["generated"],
                               st2.generated]).astype(np.int32)
        np.testing.assert_array_equal(full, golden[i])


def test_drain_grace_budget_finishes_inflight(tiny_model, tmp_path):
    root = str(tmp_path / "drain")
    rng = np.random.default_rng(13)
    eng = _engine(tiny_model, max_batch_slots=2)
    sts = [eng.submit(Request(p, max_new_tokens=3))
           for p in _prompts(rng, 2, 5, 7)]
    eng.step()
    report = eng.drain(snapshot_dir=root, budget_s=60.0)
    # nothing was queued and the budget covered the tails: all finished
    assert report.completed == 2 and report.snapshotted == 0
    assert report.path is None
    assert all(st.outcome == "completed" for st in sts)


def test_drain_honours_latched_cancel_not_snapshotted(tiny_model,
                                                      tmp_path):
    """A request the client disconnected from ends 'cancelled' at drain
    time — never resurrected on the successor engine as drained work."""
    root = str(tmp_path / "drain")
    rng = np.random.default_rng(26)
    eng = _engine(tiny_model, max_batch_slots=2)
    keep = eng.submit(Request(rng.integers(2, 250, (5,)),
                              max_new_tokens=8))
    gone = eng.submit(Request(rng.integers(2, 250, (5,)),
                              max_new_tokens=8))
    eng.step()                               # both in-flight
    assert eng.cancel(gone.request.request_id)   # latched
    report = eng.drain(snapshot_dir=root, budget_s=0.0)
    assert gone.outcome == "cancelled"
    assert keep.outcome == "drained"
    assert report.snapshotted == 1           # only the live request


def test_drain_refuses_to_discard_without_snapshot_dir(tiny_model):
    rng = np.random.default_rng(14)
    eng = _engine(tiny_model)
    eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=4))
    with pytest.raises(ValueError, match="snapshot_dir"):
        eng.drain(budget_s=0.0)


def test_drain_snapshot_commit_is_atomic_under_torn_write(
        tiny_model, tmp_path):
    root = str(tmp_path / "drain")
    rng = np.random.default_rng(15)
    # first drain commits a valid snapshot
    eng1 = _engine(tiny_model)
    eng1.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=4))
    r1 = eng1.drain(snapshot_dir=root, budget_s=0.0)
    assert r1.snapshotted == 1
    # second drain's commit is torn mid-write (chaos) — the torn dir
    # must never read as a snapshot; the previous one still loads
    eng2 = _engine(tiny_model)
    eng2.submit(Request(rng.integers(2, 250, (6,)), max_new_tokens=4))
    with chaos.chaos_scope("ckpt.write.torn@1"):
        r2 = eng2.drain(snapshot_dir=root, budget_s=0.0)
    assert r2.path.endswith("drain_2")
    path, specs = load_drain_snapshot(root)
    assert path == r1.path                  # fallback to the valid commit
    assert len(specs) == 1


# ---------------------------------------------------------------------------
# chaos SLO (acceptance)
# ---------------------------------------------------------------------------


def test_chaos_slo_availability_and_token_exactness(tiny_model):
    rng = np.random.default_rng(16)
    prompts = _prompts(rng, 10, 4, 9)
    max_new = 5
    # golden = the UNINJECTED run (batching invariance is pinned by the
    # PR 6 parity suite)
    golden = _engine(tiny_model).generate(prompts, max_new_tokens=max_new)
    spec = ("serve.request.poison:0.1,serve.decode.hang@4,"
            "serve.pages.exhaust:0.2")
    with flag_scope("serve_watchdog_s", 1.0), scoped_registry() as reg, \
            chaos.chaos_scope(spec, seed=3):
        eng = _engine(tiny_model, max_batch_slots=2)
        sts = [eng.submit(Request(p, max_new_tokens=max_new))
               for p in prompts]
        guard = 0
        while eng.scheduler.has_work:
            try:
                eng.step()
            except DecodeWatchdogError:
                pass                       # structured, survivable
            guard += 1
            assert guard < 500, "chaos run failed to converge"
        assert chaos.fired(), "chaos plan never fired"
        # no request ends without a terminal outcome event
        assert all(st.outcome in TERMINAL_OUTCOMES for st in sts)
        ctr = reg.get("serve_requests_total")
        terminal = sum(ctr.value(event=e) for e in TERMINAL_OUTCOMES)
        assert terminal == ctr.value(event="submitted") == len(sts)
    poisoned = [st for st in sts if st.poisoned]
    clean = [(i, st) for i, st in enumerate(sts) if not st.poisoned]
    for st in poisoned:
        assert st.outcome == "failed"
    # SLO: >= 95% of non-poisoned requests complete token-exactly
    exact = 0
    for i, st in clean:
        if st.outcome == "completed" and np.array_equal(
                np.concatenate([prompts[i], st.generated]), golden[i]):
            exact += 1
    assert exact / max(len(clean), 1) >= 0.95
    assert eng.cache.allocator.pages_in_use == 0


# ---------------------------------------------------------------------------
# zero-overhead pin
# ---------------------------------------------------------------------------


def test_resilience_off_adds_no_registry_series_or_dispatches(tiny_model):
    """With deadlines/watchdog/chaos off, the hot path writes no new
    registry series and the dispatch counts match the PR 6 schedule
    (repeat traffic: one bucketed prefill + max_new-1 decode steps)."""
    rng = np.random.default_rng(17)
    prompts = [rng.integers(2, 250, (6,)).astype(np.int32)
               for _ in range(2)]
    with scoped_registry() as reg:
        eng = _engine(tiny_model)
        eng.generate(prompts, max_new_tokens=4)
        names = set(reg.names())
    banned = ("serve_overload", "serve_deadline_slack_seconds",
              "serve_watchdog_trips_total",
              "serve_overload_transitions_total")
    assert not any(n.startswith(b) for n in names for b in banned)
    events = {d["event"] for d
              in reg.get("serve_requests_total").labels_seen()}
    assert events == {"submitted", "completed"}
    s = eng.stats()
    assert s["prefill_dispatches"] == 1    # both rode one bucket
    assert s["decode_dispatches"] == 3     # tokens 2..4
    assert chaos.occurrences("serve.pages.exhaust") == 0  # probes inert


# ---------------------------------------------------------------------------
# scheduler fuzz (satellite): invariants under random interleavings
# ---------------------------------------------------------------------------


def test_scheduler_fuzz_invariants():
    clock = ManualClock()
    events = []
    sched = _host_scheduler(policy="reject-new", max_queue=32,
                            max_slots=3, num_pages=12,
                            on_event=lambda ev, st: events.append((ev, st)),
                            clock=clock)
    cache = sched.cache
    rng = np.random.default_rng(1234)
    submitted = []

    def check_invariants():
        # no slot double-assignment; slot back-pointers consistent
        active = [(i, st) for i, st in enumerate(sched.slots)
                  if st is not None]
        assert len({id(st) for _, st in active}) == len(active)
        for i, st in active:
            assert st.slot == i and st.outcome is None
        # every allocated page accounted exactly once (disjoint slots,
        # no duplicate in the free list => no double-free, no leak)
        alloc = cache.allocator
        free = list(alloc._free)
        assert len(free) == len(set(free))
        pages = [p for lst in cache._slot_pages for p in lst]
        assert len(pages) == len(set(pages))
        assert not set(pages) & set(free)
        assert alloc.pages_in_use == len(pages)
        # terminal exclusivity: exactly one outcome, finished <=>
        # completed, terminal requests hold nothing
        for st in submitted:
            if st.outcome is not None:
                assert st.outcome in TERMINAL_OUTCOMES
                assert st.finished == (st.outcome == "completed")
                assert st.slot is None and st not in sched.waiting
            else:
                assert (st in sched.waiting) ^ (st.slot is not None)

    for it in range(260):
        op = rng.integers(0, 7)
        clock.advance(float(rng.random()) * 0.2)
        if op == 0:                                   # submit
            plen = int(rng.integers(1, 9))
            deadline = (float(rng.uniform(0.1, 3.0))
                        if rng.random() < 0.3 else None)
            try:
                st = sched.submit(Request(
                    rng.integers(1, 99, (plen,)),
                    max_new_tokens=int(rng.integers(1, 9)),
                    deadline_s=deadline))
                submitted.append(st)
            except ServerOverloaded:
                pass
        elif op == 1:
            sched.plan_admissions()
        elif op == 2:                                 # decode-ish step
            sched.ensure_decode_capacity()
            for _, st in list(sched.active()):
                st.generated.append(int(rng.integers(1, 99)))
                if st.is_done():
                    sched.finish(st)
        elif op == 3 and submitted:                   # cancel random
            st = submitted[int(rng.integers(0, len(submitted)))]
            sched.cancel(st.request.request_id)
        elif op == 4:                                 # expiry sweeps
            sched.expire_queued()
            sched.sweep_active()
        elif op == 5:                                 # fault isolation
            act = sched.active()
            if act:
                _, st = act[int(rng.integers(0, len(act)))]
                sched.fail(st, "fuzz")
        elif op == 6:                                 # drain release
            pool = sched.waiting + [st for _, st in sched.active()]
            if pool and rng.random() < 0.2:
                sched.drain_release(
                    pool[int(rng.integers(0, len(pool)))])
        check_invariants()

    # converge: everything reaches a terminal outcome, pool fully free
    guard = 0
    while sched.has_work:
        sched.plan_admissions()
        sched.ensure_decode_capacity()
        for _, st in list(sched.active()):
            st.generated.append(1)
            if st.is_done():
                sched.finish(st)
        sched.expire_queued()
        sched.sweep_active()
        check_invariants()
        guard += 1
        assert guard < 2000
    assert all(st.outcome is not None for st in submitted)
    assert cache.allocator.pages_in_use == 0
    # the event hook saw exactly the terminal transitions
    assert len(events) == len(submitted)
    st_counts = {e: 0 for e in TERMINAL_OUTCOMES}
    for ev, _ in events:
        st_counts[ev] += 1
    assert st_counts == {e: sched.stats[e] for e in TERMINAL_OUTCOMES}


# ---------------------------------------------------------------------------
# loadgen: bursty arrivals, deadline sampling, token bucket
# ---------------------------------------------------------------------------


def test_loadgen_bursty_modes_deterministic_and_mean_preserving():
    base = dict(num_requests=1500, rate_rps=50.0,
                prompt_len_range=(4, 8), max_new_range=(2, 4),
                vocab_size=256, seed=9)
    pois = build_requests(LoadSpec(**base))
    for mode, kw in (("gamma", dict(burstiness=4.0)),
                     ("mmpp", dict(burstiness=3.0, mmpp_switch=0.2))):
        spec = LoadSpec(arrival=mode, **kw, **base)
        a = build_requests(spec)
        b = build_requests(spec)
        assert [t for t, _ in a] == [t for t, _ in b]   # seeded replay
        for (_, ra), (_, rb) in zip(a, b):
            np.testing.assert_array_equal(ra.prompt, rb.prompt)
        gaps = np.diff([t for t, _ in a])
        assert (gaps >= 0).all()
        # same mean rate as the poisson schedule, within sampling noise
        # (mmpp gaps are serially correlated, so the band is generous —
        # but it would still catch a broken mean-rate rescale)
        mean = float(np.mean(gaps))
        assert 0.75 / 50.0 < mean < 1.35 / 50.0
        assert [t for t, _ in a] != [t for t, _ in pois]
    # burstier gaps have a heavier tail than poisson at the same rate
    g = np.diff([t for t, _ in build_requests(
        LoadSpec(arrival="gamma", burstiness=8.0, **base))])
    p = np.diff([t for t, _ in pois])
    assert np.std(g) > 1.5 * np.std(p)


def test_loadgen_deadline_and_priority_sampling():
    spec = LoadSpec(num_requests=40, rate_rps=100.0,
                    prompt_len_range=(4, 8), max_new_range=(2, 4),
                    vocab_size=256, seed=11,
                    deadline_range=(0.5, 2.0),
                    priority_choices=(0, 5))
    reqs = [r for _, r in build_requests(spec)]
    assert all(0.5 <= r.deadline_s <= 2.0 for r in reqs)
    assert {r.priority for r in reqs} == {0, 5}
    # unchanged default: no deadline draws -> None
    plain = [r for _, r in build_requests(LoadSpec(
        num_requests=4, vocab_size=256, seed=11))]
    assert all(r.deadline_s is None and r.priority == 0 for r in plain)


def test_token_bucket():
    tb = TokenBucket(rate=1.0, burst=2)
    assert tb.admit(0.0) and tb.admit(0.0)
    assert not tb.admit(0.0)               # burst spent
    assert tb.admit(1.05)                  # refilled one token
    assert not tb.admit(1.06)
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=2)


def test_run_open_loop_counts_rejections_and_throttle(tiny_model):
    eng = _engine(tiny_model, max_batch_slots=1, max_queue=1)
    spec = LoadSpec(num_requests=5, rate_rps=1e5,
                    prompt_len_range=(4, 8), max_new_range=(2, 3),
                    vocab_size=256, seed=12)
    summary = run_open_loop(eng, spec)
    # nothing is silently lost: every offered request either completed
    # or was counted as a client-visible refusal
    s = eng.scheduler.stats
    accounted = (summary["requests_completed"]
                 + summary["requests_rejected"] + s["shed"]
                 + s["expired"] + s["failed"])
    assert accounted == 5
    assert summary["requests_rejected"] >= 1       # queue of 1 overflowed
    assert summary["watchdog_trips"] == 0
    # client-side token bucket throttles instead of submitting
    eng2 = _engine(tiny_model, max_batch_slots=1)
    summary2 = run_open_loop(eng2, spec,
                             token_bucket=TokenBucket(rate=1.0, burst=2))
    assert summary2["requests_throttled"] >= 1
    assert (summary2["requests_completed"]
            + summary2["requests_throttled"]
            + summary2["requests_rejected"]) == 5


# ---------------------------------------------------------------------------
# tooling: monitor_report --serve
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(tools, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_monitor_report_outcomes_and_overload_timeline(
        tiny_model, tmp_path):
    clock = ManualClock()
    path = str(tmp_path / "serve.jsonl")
    with scoped_registry() as reg:
        eng = _engine(tiny_model, clock=clock, max_batch_slots=1,
                      overload_threshold_s=1.0, overload_alpha=1.0)
        rng = np.random.default_rng(18)
        eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=2))
        doomed = eng.submit(Request(rng.integers(2, 250, (5,)),
                                    max_new_tokens=2, deadline_s=0.1))
        # deadline-free straggler keeps the queue non-empty so the
        # overload detector sees the stuck head-of-queue delay
        eng.submit(Request(rng.integers(2, 250, (5,)), max_new_tokens=2))
        eng.step()
        clock.advance(5.0)
        eng.step()                          # expiry + overload enter
        assert doomed.outcome == "expired"
        reg.dump_jsonl(path)
        eng.run()
        for _ in range(8):
            eng.step()                      # overload exit
        reg.dump_jsonl(path)
    mod = _load_tool("monitor_report")
    from paddle_tpu.monitor import load_jsonl
    out = mod.render(load_jsonl(path), serve=True)
    assert "Request outcomes" in out
    assert "expired" in out and "completed" in out
    assert "Overload state timeline" in out
    assert "OVERLOADED (shedding)" in out and "normal" in out
