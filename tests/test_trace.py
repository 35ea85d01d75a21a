"""Structured tracing + SLO burn rate (paddle_tpu.monitor.trace / .slo,
ISSUE 11): span trees, tail-based anomaly sampling, Perfetto export,
exemplars, trace-context survival across preemption and drain/resume,
and the zero-overhead contract."""

import json

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.jit.to_static import TrainStep
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.monitor import scoped_registry
from paddle_tpu.monitor import trace as trace_mod
from paddle_tpu.monitor.slo import SLOTracker
from paddle_tpu.optimizer import SGD
from paddle_tpu.serving import (Request, ServingConfig, ServingEngine,
                                load_drain_snapshot,
                                requests_from_snapshot)
from paddle_tpu.testing import chaos


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    return GPTForPretraining(gpt_tiny())


def _engine(model, **kw):
    cfg = dict(max_batch_slots=3, block_size=4, max_context_len=64,
               prefill_buckets=(8, 16), batch_buckets=(1, 2))
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _spans(tr):
    return [(s.name, s.parent_id) for s in tr.spans]


def _span_names(tdoc_or_trace):
    spans = (tdoc_or_trace.get("spans")
             if isinstance(tdoc_or_trace, dict)
             else [s.to_dict() for s in tdoc_or_trace.spans])
    return [s["name"] for s in spans]


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_tree_ids_parents_and_durations():
    t = trace_mod.Tracer(capacity=8, seed=0)
    with flag_scope("trace_sample", 1.0):
        tr = t.start_trace("unit", foo="bar")
    assert tr.root.parent_id is None and tr.root.span_id == 0
    a = tr.start_span("a")
    b = tr.start_span("b", parent=a)
    assert a.parent_id == 0 and b.parent_id == a.span_id
    tr.end_span(b)
    tr.end_span(a)
    assert b.duration is not None and b.duration >= 0
    ev = tr.event("marker", outcome="x")
    assert ev.duration == 0.0
    assert t.finish_trace(tr) is True
    d = tr.to_dict()
    assert d["trace_id"] == tr.trace_id
    assert [s["name"] for s in d["spans"]] == ["unit", "a", "b",
                                               "marker"]
    assert d["spans"][0]["attrs"]["foo"] == "bar"
    # idempotent finish
    assert t.finish_trace(tr) is True
    assert len(t.retained()) == 1


def test_head_and_tail_sampling_decisions():
    t = trace_mod.Tracer(capacity=32, seed=0)
    with flag_scope("trace_sample", 0.0):
        healthy = t.start_trace("h")
        assert t.finish_trace(healthy) is False        # dropped
        weird = t.start_trace("w")
        weird.mark_anomaly("chaos", site="x")
        assert weird.anomaly == "chaos"
        weird.mark_anomaly("failed")                   # first wins
        assert weird.anomaly == "chaos"
        assert t.finish_trace(weird) is True           # tail-kept
    with flag_scope("trace_sample", 1.0):
        head = t.start_trace("s")
        assert t.finish_trace(head) is True
    assert {tr.name for tr in t.retained()} == {"w", "s"}
    assert trace_mod.TRACE_STATS["tail_retained"] == 1
    assert trace_mod.TRACE_STATS["traces_dropped"] == 1


def test_retained_ring_is_bounded():
    t = trace_mod.Tracer(capacity=3)
    with flag_scope("trace_sample", 1.0):
        traces = [t.start_trace(f"t{i}") for i in range(5)]
        for tr in traces:
            t.finish_trace(tr)
    kept = t.retained()
    assert len(kept) == 3
    assert [tr.name for tr in kept] == ["t2", "t3", "t4"]


def test_trace_off_allocates_nothing():
    assert trace_mod.start_trace("x") is None
    with trace_mod.span("y"):
        pass
    assert trace_mod.TRACE_STATS["spans_allocated"] == 0
    assert trace_mod.TRACE_STATS["traces_started"] == 0


def test_activate_and_span_attach():
    t = trace_mod.Tracer(capacity=4)
    with flag_scope("trace_sample", 1.0):
        tr = t.start_trace("step")
    assert trace_mod.current_trace() is None
    with trace_mod.activate(tr):
        assert trace_mod.current_trace() is tr
        with trace_mod.span("inner", k=1) as sp:
            assert sp.child is not None \
                and sp.child.trace_id == tr.trace_id
    assert trace_mod.current_trace() is None
    assert "inner" in _span_names(tr)


def test_perfetto_export_valid_json_monotonic_tracks(tmp_path):
    t = trace_mod.Tracer(capacity=8)
    with flag_scope("trace_sample", 1.0):
        for i in range(2):
            tr = t.start_trace(f"r{i}")
            with tr.span("a"):
                with tr.span("b"):
                    pass
            t.finish_trace(tr)
    path = str(tmp_path / "perfetto.json")
    trace_mod.export_perfetto(path, traces=t.snapshot())
    with open(path) as f:
        doc = json.load(f)                      # valid JSON, the pin
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert events, "no duration events exported"
    per_track = {}
    for e in events:
        per_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for ts_list in per_track.values():
        assert ts_list == sorted(ts_list)       # monotonic per track
    names = {e["name"] for e in events}
    assert {"r0", "r1", "a", "b"} <= names
    # metadata names the tracks
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               for e in doc["traceEvents"])


def test_flight_recorder_dump_carries_traces(tmp_path):
    from paddle_tpu.monitor import flight_recorder as fr
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        tr = trace_mod.start_trace("inflight", request_id=9)
        assert tr is not None                   # provider registered
        rec = fr.FlightRecorder(capacity=8, dump_dir=str(tmp_path))
        path = rec.dump(reason="explicit")
    with open(path) as f:
        doc = json.load(f)
    ids = [t["trace_id"] for t in doc.get("traces", [])]
    assert tr.trace_id in ids                   # live trace attached
    trace_mod.get_tracer().finish_trace(tr)


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------


def test_histogram_exemplar_round_trip(tmp_path):
    from paddle_tpu.monitor import load_jsonl
    with scoped_registry() as reg:
        h = reg.histogram("ex_seconds", "t", buckets=(0.1, 1.0, 10.0))
        h.observe(0.05, exemplar="tid-1")
        h.observe(0.5)                          # no exemplar: kept old
        h.observe(0.7, exemplar="tid-2")
        h.observe(100.0, exemplar="tid-inf")    # past the last bucket
        ex = h.exemplars()
        assert ex["0.1"]["trace_id"] == "tid-1"
        assert ex["1.0"]["trace_id"] == "tid-2"
        assert ex["+Inf"]["trace_id"] == "tid-inf"
        p = str(tmp_path / "m.jsonl")
        reg.dump_jsonl(p)
    rows = [r for r in load_jsonl(p) if r["name"] == "ex_seconds"]
    assert rows and rows[0]["exemplars"]["1.0"]["trace_id"] == "tid-2"
    assert rows[0]["count"] == 4                # histogram itself intact


# ---------------------------------------------------------------------------
# SLO burn rate
# ---------------------------------------------------------------------------


def _clocked_tracker(**kw):
    now = [0.0]
    t = SLOTracker("t", kw.pop("objective", 0.99),
                   windows=kw.pop("windows", (60.0, 600.0)),
                   clock=lambda: now[0], **kw)
    return t, now


def test_burn_rate_arithmetic():
    t, now = _clocked_tracker(objective=0.99)    # budget = 1%
    for i in range(99):
        now[0] = float(i)
        t.record(good=1)
    now[0] = 99.0
    t.record(bad=1)
    # window 600s covers everything: error ratio 1% -> burn exactly 1.0
    assert t.error_ratio(600.0) == pytest.approx(0.01)
    assert t.burn_rate(600.0) == pytest.approx(1.0)
    # 60s window sees the tail: 59 good (t>=40..98) + 1 bad
    r60 = t.error_ratio(60.0)
    assert t.burn_rate(60.0) == pytest.approx(r60 / 0.01)
    assert t.burn_rate(60.0) > 1.0
    # budget: 1 bad / 100 total on a 1% budget = fully consumed
    assert t.budget_remaining() == pytest.approx(0.0)
    # no-traffic window burns nothing
    now[0] = 10_000.0
    assert t.burn_rate(60.0) == 0.0


def test_burn_alert_needs_both_windows():
    t, now = _clocked_tracker(objective=0.999, windows=(60.0, 3600.0))
    # old burst (bad), then a long quiet good period: the long window
    # still shows burn but the short one has recovered -> no alert
    now[0] = 0.0
    t.record(bad=50)
    for i in range(1, 120):
        now[0] = float(i * 25)
        t.record(good=10)
    pairs = ((3600.0, 60.0, 10.0),)
    assert t.burn_rate(3600.0) > 10.0
    assert t.burn_rate(60.0) < 10.0
    assert t.should_alert(pairs) == []
    # fresh burst: both windows fire
    t.record(bad=50)
    firing = t.should_alert(pairs)
    assert len(firing) == 1 and firing[0]["threshold"] == 10.0


def test_slo_validation_and_publish():
    with pytest.raises(ValueError):
        SLOTracker("x", 1.5)
    with pytest.raises(ValueError):
        SLOTracker("x", 0.99, windows=())
    t, now = _clocked_tracker(objective=0.9, windows=(60.0,))
    now[0] = 1.0
    t.record(good=8, bad=2)
    with scoped_registry() as reg:
        t.publish(registry=reg)
        burn = reg.get("slo_burn_rate")
        assert burn.value(slo="t", window="60s") == pytest.approx(2.0)
        assert reg.get("slo_error_budget_remaining").value(
            slo="t") == pytest.approx(-1.0)
        assert reg.get("slo_objective").value(slo="t") == \
            pytest.approx(0.9)
    snap = t.snapshot()
    assert snap["burn_60s"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# serving lifecycle traces
# ---------------------------------------------------------------------------


@pytest.mark.serve
def test_serving_request_lifecycle_trace(tiny_model):
    with scoped_registry() as reg, flag_scope("trace", True), \
            flag_scope("trace_sample", 1.0):
        eng = _engine(tiny_model)
        eng.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=3)
        kept = trace_mod.get_tracer().retained()
        assert len(kept) == 2
        ids = {tr.trace_id for tr in kept}
        assert len(ids) == 2                    # one trace per request
        for tr in kept:
            assert tr.anomaly is None and tr.finished
            names = _span_names(tr)
            assert names[0] == "serve.request"
            for expected in ("queued", "admitted", "prefill",
                             "decode[1]", "decode[2]", "terminal"):
                assert expected in names, (expected, names)
            term = [s for s in tr.spans if s.name == "terminal"][0]
            assert term.attrs["outcome"] == "completed"
            assert tr.root.attrs["outcome"] == "completed"
            # decode spans nest under admitted, which nests under root
            adm = [s for s in tr.spans if s.name == "admitted"][0]
            dec = [s for s in tr.spans if s.name.startswith("decode")]
            assert all(d.parent_id == adm.span_id for d in dec)
        # exemplars link the latency histograms to these traces
        ex = reg.get("serve_ttft_seconds").exemplars()
        assert any(v["trace_id"] in ids for v in ex.values())


@pytest.mark.serve
def test_zero_overhead_with_flags_off(tiny_model):
    """Both flags off ⇒ zero span allocations, zero trace/slo registry
    series over a 50-request serve run (the acceptance probe)."""
    with scoped_registry() as reg:
        eng = _engine(tiny_model)
        for i in range(50):
            eng.submit(Request([1 + (i % 7), 2, 3], max_new_tokens=2))
        eng.run()
        assert eng.scheduler.stats["completed"] == 50
    assert trace_mod.TRACE_STATS["spans_allocated"] == 0
    assert trace_mod.TRACE_STATS["traces_started"] == 0
    assert trace_mod._tracer is None or not \
        trace_mod._tracer.retained()
    assert not [n for n in reg.names()
                if n.startswith(("trace_", "slo_"))]


@pytest.mark.serve
@pytest.mark.chaos
def test_chaos_drill_tail_keeps_only_anomalies(tiny_model):
    """Head sample 0.0 + serve.request.poison: the poisoned request
    retains a COMPLETE span tree with its failure reason; healthy
    requests retain zero traces (the acceptance drill)."""
    chaos.configure("serve.request.poison@2", seed=0)
    with flag_scope("trace", True), flag_scope("trace_sample", 0.0):
        eng = _engine(tiny_model)
        for i in range(4):
            eng.submit(Request([1, 2, 3, 4], max_new_tokens=2))
        eng.run()
        assert eng.scheduler.stats["failed"] == 1
        assert eng.scheduler.stats["completed"] == 3
        kept = trace_mod.get_tracer().retained()
        assert len(kept) == 1                   # ONLY the anomaly
        tr = kept[0]
        assert tr.anomaly == "chaos"
        names = _span_names(tr)
        assert {"queued", "admitted", "terminal"} <= set(names)
        term = [s for s in tr.spans if s.name == "terminal"][0]
        assert term.attrs["outcome"] == "failed"
        assert "non-finite" in term.attrs["reason"]
        assert trace_mod.TRACE_STATS["tail_retained"] == 1


@pytest.mark.serve
@pytest.mark.chaos
def test_watchdog_trip_tail_keeps_inflight_traces(tiny_model):
    from paddle_tpu.serving import DecodeWatchdogError
    chaos.configure("serve.decode.hang@1", seed=0)
    with flag_scope("trace", True), flag_scope("trace_sample", 0.0), \
            flag_scope("serve_watchdog_s", 2.0):
        eng = _engine(tiny_model)
        eng.submit(Request([1, 2, 3], max_new_tokens=2))
        with pytest.raises(DecodeWatchdogError):
            eng.run()
        chaos.cancel_hangs()
        eng.run()                               # post-trip retry
        assert eng.scheduler.stats["completed"] == 1
        kept = trace_mod.get_tracer().retained()
        assert len(kept) == 1
        assert kept[0].anomaly == "watchdog"
        assert kept[0].root.attrs["outcome"] == "completed"


@pytest.mark.serve
@pytest.mark.chaos
def test_trace_survives_recompute_preemption(tiny_model):
    # probe #1 (admission) passes, probe #2 (decode capacity) forces a
    # recompute-preemption of the newest-admitted request
    chaos.configure("serve.pages.exhaust@2", seed=0)
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        eng = _engine(tiny_model)
        a = eng.submit(Request([1, 2, 3], max_new_tokens=3))
        b = eng.submit(Request([4, 5, 6], max_new_tokens=3))
        eng.run()
        assert eng.scheduler.stats["preemptions"] == 1
        assert eng.scheduler.stats["completed"] == 2
        victim = b if b.preemptions else a
        assert victim.preemptions == 1
        kept = {t.trace_id: t for t in trace_mod.get_tracer().retained()}
        tr = kept[victim.trace.trace_id]        # same trace object/id
        names = _span_names(tr)
        assert names.count("queued") == 2       # both residencies
        assert names.count("admitted") == 2
        requeued = [s for s in tr.spans
                    if s.name == "queued" and s.attrs.get("reason")]
        assert requeued and requeued[0].attrs["reason"] == "preemption"
        assert tr.root.attrs["outcome"] == "completed"


@pytest.mark.serve
def test_trace_id_survives_drain_resume(tiny_model, tmp_path):
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        eng = _engine(tiny_model, drain_dir=str(tmp_path))
        st1 = eng.submit(Request([1, 2, 3], max_new_tokens=8))
        st2 = eng.submit(Request([4, 5, 6], max_new_tokens=8))
        eng.step()                              # admit + first tokens
        report = eng.drain(budget_s=0.0)        # snapshot, don't finish
        assert report.snapshotted == 2
        orig_ids = {st.request.request_id: st.trace.trace_id
                    for st in (st1, st2)}
        path, specs = load_drain_snapshot(str(tmp_path))
        assert path is not None and len(specs) == 2
        by_req = {s["request_id"]: s for s in specs}
        for rid, tid in orig_ids.items():
            assert by_req[rid]["trace_id"] == tid
        # successor engine resumes the SAME trace ids — and a resumed
        # identity is kept even when the head coin would drop it (the
        # first half may already be retained; a re-flip must not orphan
        # the continuation)
        with flag_scope("trace_sample", 0.0):
            eng2 = _engine(tiny_model)
            states = [eng2.submit(r)
                      for r in requests_from_snapshot(specs)]
            eng2.run()
        resumed_ids = {st.trace.trace_id for st in states}
        assert resumed_ids == set(orig_ids.values())
        kept_ids = {t.trace_id
                    for t in trace_mod.get_tracer().retained()}
        assert resumed_ids <= kept_ids
        for st in states:
            assert st.trace.root.attrs["resumed"] is True
            assert st.trace.root.attrs["outcome"] == "completed"


@pytest.mark.serve
def test_serving_slo_trackers(tiny_model):
    with scoped_registry() as reg:
        eng = _engine(tiny_model, slo_availability=0.99,
                      slo_deadline=0.95, slo_windows=(60.0, 600.0))
        eng.generate([[1, 2, 3]], max_new_tokens=2)
        assert eng._slo_avail.total_good == 1
        assert eng._slo_avail.total_bad == 0
        assert reg.get("slo_burn_rate").value(
            slo="serve_availability", window="60s") == 0.0
        assert reg.get("slo_error_budget_remaining").value(
            slo="serve_availability") == pytest.approx(1.0)
        # a queued expiry spends availability AND deadline budget
        eng2 = _engine(tiny_model, slo_availability=0.99,
                       slo_deadline=0.95)
        st = eng2.submit(Request([1, 2], max_new_tokens=2,
                                 deadline_s=1e-6))
        import time as _time
        _time.sleep(0.01)
        eng2.scheduler.expire_queued()
        assert st.outcome == "expired"
        assert eng2._slo_avail.total_bad == 1
        assert eng2._slo_deadline.total_bad == 1


@pytest.mark.serve
def test_spans_follow_injected_engine_clock(tiny_model):
    """Every span of a serving trace lives in the ENGINE clock domain
    (injectable), never the tracer's wall clock — one time base per
    trace."""
    fake = [1000.0]

    def clock():
        fake[0] += 0.25
        return fake[0]

    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        eng = ServingEngine(tiny_model, ServingConfig(
            max_batch_slots=2, block_size=4, max_context_len=64,
            prefill_buckets=(8,), batch_buckets=(1, 2)),
            clock=clock)
        eng.generate([[1, 2, 3]], max_new_tokens=2)
        tr = trace_mod.get_tracer().retained()[0]
    for s in tr.spans:
        assert 1000.0 <= s.t0 <= fake[0], (s.name, s.t0)
        assert s.t1 is not None and s.t1 <= fake[0], s.name
        assert s.t1 >= s.t0, (s.name, s.t0, s.t1)


@pytest.mark.serve
def test_requeue_closes_open_queued_span(tiny_model):
    """A watchdog rollback of a never-prefilled state must close its
    ORIGINAL queued span before opening the new one (no open-span
    leak)."""
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        eng = _engine(tiny_model)
        st = eng.submit(Request([1, 2, 3], max_new_tokens=2))
        first_q = st.trace_spans["queued"]
        assert first_q.t1 is None
        eng._trace_requeue(st, "watchdog_rollback")
        assert first_q.t1 is not None               # closed, not leaked
        assert first_q.attrs["requeued"] == "watchdog_rollback"
        second_q = st.trace_spans["queued"]
        assert second_q is not first_q and second_q.t1 is None
        eng.run()
        assert all(s.t1 is not None
                   for s in st.trace.spans), "open span leaked"


def test_flight_dump_survives_nonfinite_span_attrs(tmp_path):
    from paddle_tpu.monitor import flight_recorder as fr
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        tr = trace_mod.start_trace("weird")
        tr.mark_anomaly("nonfinite", loss=float("nan"))
        rec = fr.FlightRecorder(capacity=4, dump_dir=str(tmp_path))
        path = rec.dump(reason="explicit")   # allow_nan=False must hold
    doc = json.load(open(path))
    root = doc["traces"][0]["spans"][0]
    assert root["attrs"]["loss"] == "nan"
    trace_mod.get_tracer().finish_trace(tr)


# ---------------------------------------------------------------------------
# training-step traces
# ---------------------------------------------------------------------------


def _train_step():
    paddle.seed(7)
    m = nn.Linear(8, 4)
    opt = SGD(learning_rate=0.1, parameters=m.parameters())
    return m, TrainStep(m, lambda layer, x, y: F.mse_loss(layer(x), y),
                        opt)


def test_train_step_trace_and_zero_overhead():
    _, step = _train_step()
    x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
    y = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
    with scoped_registry() as reg:
        w0 = reg.write_count
        for _ in range(3):
            step(x, y)
        # monitor AND trace off: zero registry writes, zero spans
        assert reg.write_count == w0
    assert trace_mod.TRACE_STATS["spans_allocated"] == 0
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        step(x, y)
    kept = trace_mod.get_tracer().retained()
    assert len(kept) == 1
    tr = kept[0]
    assert tr.name == "train.step"
    assert "train.dispatch" in _span_names(tr)
    assert tr.anomaly is None


def test_train_step_nonfinite_tail_retains():
    _, step = _train_step()
    step._check_numerics = "warn"
    x = paddle.to_tensor(
        np.full((4, 8), np.nan, dtype="float32"))
    y = paddle.to_tensor(np.zeros((4, 4), dtype="float32"))
    with flag_scope("trace", True), flag_scope("trace_sample", 0.0):
        with pytest.warns(RuntimeWarning):
            step(x, y)
    kept = trace_mod.get_tracer().retained()
    assert len(kept) == 1 and kept[0].anomaly == "nonfinite"


def test_checkpoint_commit_span_attaches(tmp_path):
    from paddle_tpu.serving.resilience import save_drain_snapshot
    t = trace_mod.Tracer(capacity=4)
    old = trace_mod.set_tracer(t)
    try:
        with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
            tr = trace_mod.start_trace("train.step")
            with trace_mod.activate(tr):
                save_drain_snapshot(str(tmp_path / "d"), [])
            t.finish_trace(tr)
        assert "checkpoint.commit" in _span_names(tr)
    finally:
        trace_mod.set_tracer(old)


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


def test_monitor_report_trace_render(tmp_path):
    import tools.monitor_report as report
    t = trace_mod.Tracer(capacity=8)
    with flag_scope("trace_sample", 0.0):
        tr = t.start_trace("serve.request", request_id=1)
        with tr.span("queued"):
            pass
        adm = tr.start_span("admitted")
        with tr.span("prefill", parent=adm):
            pass
        with tr.span("decode[1]", parent=adm):
            pass
        tr.end_span(adm)
        tr.event("terminal", outcome="failed", reason="boom")
        tr.mark_anomaly("failed")
        t.finish_trace(tr)
    path = t.dump(str(tmp_path / "traces.json"))
    out = report.render_traces(trace_mod.load_trace_dump(path))
    assert "ANOMALY: failed" in out
    assert "[tail-kept]" in out
    assert "decode[1]" in out and "terminal" in out
    assert "Exclusive time by span" in out
    assert "*" in out                           # critical path marked
    # the CLI path parses the same file
    assert report.main(["--trace", path]) == 0


def test_monitor_report_fallbacks_render():
    import tools.monitor_report as report
    rows = [
        {"name": "scan_fallback_total", "type": "counter",
         "labels": {"reason": "kv_cache"}, "value": 2},
        {"name": "pallas_fallback_total", "type": "counter",
         "labels": {"kernel": "chunked_ce", "reason": "cpu_backend"},
         "value": 5},
        {"name": "pipeline_fallback_total", "type": "counter",
         "labels": {"reason": "tp_mesh"}, "value": 1},
        {"name": "moe_fallback_total", "type": "counter",
         "labels": {"reason": "mixed_mesh"}, "value": 3},
    ]
    out = report.render(rows, fallbacks=True)
    assert "Fallbacks / degradations (11 total)" in out
    for sub in ("scan", "pallas", "pipeline", "moe"):
        assert sub in out
    assert "reason=kv_cache" in out
    # counters claimed by the section do not re-render below
    assert "Other metrics" not in out
    empty = report.render([], fallbacks=True)
    assert "no *_fallback_total counters" in empty


def test_recovery_events_single_source():
    """Satellite pin: the tool imports the canonical RECOVERY_EVENTS;
    its standalone fallback copy can never drift."""
    import tools.monitor_report as report
    from paddle_tpu.monitor.flight_recorder import RECOVERY_EVENTS
    assert report._recovery_events() is RECOVERY_EVENTS
    assert report._RECOVERY_EVENTS_FALLBACK == RECOVERY_EVENTS
