"""The step-level span ring (monitor/trace.py `span` / `spans` /
`record`, ISSUE 26): one call on both clocks, the phases inside
`engine.step()` and `TrainStep.__call__`, the stall report, and the
block scopes on device operations with the index `jit/aot.py` keeps of
them."""

import re
import sys
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.jit import aot
from paddle_tpu.models.gpt import (GPTForPretraining,
                                   GPTPretrainingCriterion, gpt_tiny)
from paddle_tpu.monitor import flight_recorder, scoped_registry
from paddle_tpu.monitor import trace as trace_mod
from paddle_tpu.nn.layer import BLOCKS
from paddle_tpu.serving import Request, ServingConfig, ServingEngine

NAME, T0, T1, ID, PARENT, STEP, ATTRS = range(7)

#: the names one `engine.step()` with one prefill group and one decode
#: dispatch yields (ISSUE 26's table; docs/OBSERVABILITY.md)
SERVE_STEP_SPANS = {
    "serve.step", "serve.sweep", "serve.admit", "serve.queued",
    "serve.prefill", "serve.prefill.build", "serve.prefill.dispatch",
    "serve.prefill.readback", "serve.prefill.accept",
    "serve.decode", "serve.decode.build", "serve.decode.dispatch",
    "serve.decode.readback", "serve.decode.accept", "serve.publish"}


@pytest.fixture(autouse=True)
def clear_ring():
    """Every test starts and leaves with an empty ring; one that has to
    forget its own warm-up takes the fixture and calls it."""
    trace_mod._ring.clear()
    yield trace_mod._ring.clear
    trace_mod._ring.clear()


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    return GPTForPretraining(gpt_tiny())


def _engine(model, **kw):
    cfg = dict(max_batch_slots=3, block_size=4, max_context_len=64,
               prefill_buckets=(8, 16), batch_buckets=(1, 2))
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _gpt_step(**cfg):
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny(**cfg))
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def loss_fn(layer, ids, labels):
        with paddle.amp.auto_cast(level="O1"):
            return crit(layer(ids), labels)

    return paddle.jit.TrainStep(model, loss_fn, opt)


def _ids(shape=(2, 16)):
    return np.random.default_rng(0).integers(0, 100, shape).astype("int32")


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


def test_span_records_parent_links_and_step_ids():
    with trace_mod.span("outer", step=7, k=1) as outer:
        with trace_mod.span("inner") as inner:
            trace_mod.record("waited", outer.t0 - 1.0, outer.t0, who="x")
        outer.set(late=2)
    with trace_mod.span("alone"):
        pass
    recs = {r[NAME]: r for r in trace_mod.spans()}
    assert [r[NAME] for r in trace_mod.spans()] == [
        "waited", "inner", "outer", "alone"]       # appended as they close
    assert recs["outer"][PARENT] is None and recs["outer"][STEP] == 7
    assert recs["inner"][PARENT] == outer.span_id
    assert recs["inner"][STEP] == 7                # inherited
    assert recs["waited"][PARENT] == inner.span_id
    assert recs["waited"][ATTRS] == {"who": "x"}
    assert recs["outer"][ATTRS] == {"k": 1, "late": 2}
    assert recs["inner"][ATTRS] is None            # no dict unless given
    assert recs["alone"][PARENT] is None and recs["alone"][STEP] is None
    assert recs["outer"][T0] <= recs["inner"][T0] <= recs["inner"][T1] \
        <= recs["outer"][T1]
    assert outer.span_id < inner.span_id < recs["waited"][ID]


def test_spans_filters_by_name_and_time():
    for name in ("a", "b", "a"):
        with trace_mod.span(name):
            pass
    a = trace_mod.spans(name="a")
    assert [r[NAME] for r in a] == ["a", "a"]
    assert trace_mod.spans(since=a[1][T0]) == trace_mod.spans()[2:]
    assert trace_mod.spans(until=a[0][T1]) == trace_mod.spans()[:1]
    assert trace_mod.spans(since=a[1][T1] + 1.0) == []


def test_ring_is_bounded():
    for _ in range(trace_mod.SPAN_RING_CAPACITY + 50):
        with trace_mod.span("tick"):
            pass
    assert len(trace_mod.spans()) == trace_mod.SPAN_RING_CAPACITY


def test_exception_closes_the_span_and_propagates():
    with pytest.raises(ValueError):
        with trace_mod.span("boom"):
            with trace_mod.span("inner"):
                raise ValueError("x")
    assert [r[NAME] for r in trace_mod.spans()] == ["inner", "boom"]
    with trace_mod.span("after"):
        pass
    assert trace_mod.spans(name="after")[0][PARENT] is None   # stack unwound


def test_trace_annotation_entered_only_while_a_profiler_is_on(monkeypatch):
    entered = []

    class FakeAnnotation:
        on = False

        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        @classmethod
        def is_enabled(cls):
            return cls.on

        def __enter__(self):
            entered.append((self.name, self.kw))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "TraceAnnotation", FakeAnnotation)
    with trace_mod.span("quiet", step=1):
        pass
    assert entered == []
    FakeAnnotation.on = True
    with trace_mod.span("loud", step=3):
        with trace_mod.span("child"):
            pass
    with trace_mod.span("stepless"):
        pass
    assert entered == [("loud", {"step": 3}), ("child", {"step": 3}),
                       ("exit", "child"), ("exit", "loud"),
                       ("stepless", {}), ("exit", "stepless")]
    # the ring record carries the same (name, step): the join key
    assert {(r[NAME], r[STEP]) for r in trace_mod.spans()} >= {
        ("loud", 3), ("child", 3)}


def test_failing_annotation_leaves_no_stale_parent(monkeypatch):
    class Broken:
        @staticmethod
        def is_enabled():
            return True

        def __init__(self, name, **kw):
            raise RuntimeError("profiler went away")

    with trace_mod.span("outer"):
        monkeypatch.setattr(trace_mod, "TraceAnnotation", Broken)
        with pytest.raises(RuntimeError):
            with trace_mod.span("never_entered"):
                pass
        monkeypatch.undo()
        with trace_mod.span("sibling") as sib:
            pass
    recs = {r[NAME]: r for r in trace_mod.spans()}
    assert "never_entered" not in recs              # no record, no parent
    assert recs["sibling"][PARENT] == recs["outer"][ID]
    assert sib.parent_id == recs["outer"][ID]
    assert getattr(trace_mod._open, "stack") == []


@pytest.mark.parametrize("seconds, median, stall", [
    (0.9, 0.01, False),          # under the floor, whatever the median
    (1.4, 0.18, True),           # the serve cell's stall: 1.4 s over 184 ms
    (1.4, 0.30, False),          # under 5 x the median
    (6.0, 1.5, False), (8.0, 1.5, True)])
def test_the_stall_rule(seconds, median, stall):
    assert trace_mod.stalled(seconds, median) is stall


def test_stall_phase_follows_the_largest_children():
    recs = [("serve.step", 0.0, 3.0, 1, None, 1, None),
            ("serve.sweep", 0.0, 0.1, 2, 1, 1, None),
            ("serve.queued", -2.0, 0.2, 3, 1, 1, None),    # began before
            ("serve.decode", 0.2, 2.9, 4, 1, 1, None),
            ("serve.decode.build", 0.2, 0.3, 5, 4, 1, None),
            ("serve.decode.readback", 0.4, 2.8, 6, 4, 1, None)]
    assert trace_mod.stall_phase(recs[0], recs) == "serve.decode.readback"
    assert trace_mod.stall_phase(recs[1], recs) == "serve.sweep"
    assert trace_mod.phase_table(recs[0], recs) == pytest.approx({
        "serve.sweep": 0.1, "serve.decode": 2.7, "serve.decode.build": 0.1,
        "serve.decode.readback": 2.4})


def test_span_lies_on_the_profilers_clock(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_mod.span("serve.step", step=41):
            with trace_mod.span("serve.decode"):
                pass
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    seen = {e.name: dict(e.stats) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("serve.")}
    assert seen["serve.step"]["step"] == 41
    assert seen["serve.decode"]["step"] == 41


def test_span_attaches_nested_children_to_the_current_trace():
    t = trace_mod.Tracer(capacity=4)
    with flag_scope("trace_sample", 1.0):
        tr = t.start_trace("train.step")
    with trace_mod.span("outside"):
        pass
    assert trace_mod.TRACE_STATS["spans_allocated"] == 1      # the root
    with trace_mod.activate(tr):
        with trace_mod.span("a", k=1) as a:
            with trace_mod.span("b") as b:
                pass
    assert a.child.parent_id == tr.root.span_id
    assert b.child.parent_id == a.child.span_id
    assert a.child.attrs == {"k": 1} and b.child.t1 is not None


def test_spans_allocate_no_span_objects_with_the_flag_off(tiny_model):
    """The `TRACE_STATS["spans_allocated"] == 0` pins of test_trace.py,
    over both instrumented hot paths."""
    eng = _engine(tiny_model)
    eng.generate([[1, 2, 3]], max_new_tokens=3)
    eng.shutdown()
    step = _gpt_step()
    step(_ids(), _ids())
    assert trace_mod.spans(name="serve.step")
    assert trace_mod.spans(name="train.step")
    assert trace_mod.TRACE_STATS["spans_allocated"] == 0
    assert trace_mod.TRACE_STATS["traces_started"] == 0


def test_record_event_is_the_span(tmp_path):
    from paddle_tpu import profiler as prof
    assert issubclass(prof.RecordEvent, trace_mod.span)
    with prof.RecordEvent("quiet"):
        pass
    assert "quiet" not in prof._events          # table only while profiling
    prof.start_profiler(log_dir=None)
    try:
        with prof.RecordEvent("outer"):
            with prof.RecordEvent("inner"):
                pass
    finally:
        prof.stop_profiler()
    assert prof._events["outer"][0] == 1 and prof._events["inner"][0] == 1
    recs = {r[NAME]: r for r in trace_mod.spans()}
    assert recs["inner"][PARENT] == recs["outer"][ID]
    assert recs["quiet"][PARENT] is None


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_one_engine_step_yields_the_span_table(tiny_model, clear_ring):
    eng = _engine(tiny_model)
    eng.warmup()
    clear_ring()
    eng.submit(Request(np.arange(5, dtype=np.int32), max_new_tokens=4))
    eng.step()
    recs = trace_mod.spans()
    assert {r[NAME] for r in recs} == SERVE_STEP_SPANS
    assert len(recs) == len(SERVE_STEP_SPANS)          # each exactly once
    by_id = {r[ID]: r for r in recs}
    by_name = {r[NAME]: r for r in recs}
    root = by_name["serve.step"]
    assert root[PARENT] is None and root[STEP] == 1
    assert root[ATTRS] == {"n_active": 1, "n_groups": 1}
    assert by_name["serve.prefill"][ATTRS] == {"nb": 1, "sp": 8,
                                               "chunk": (5,), "ctx": (0,)}
    assert by_name["serve.decode"][ATTRS] == {"n_active": 1}
    for r in recs:
        assert r[STEP] == 1
        if r is root:
            continue
        parent = by_id[r[PARENT]]
        want = r[NAME].rsplit(".", 1)[0] if r[NAME].count(".") == 2 \
            else "serve.admit" if r[NAME] == "serve.queued" else "serve.step"
        assert parent[NAME] == want, (r[NAME], parent[NAME])
        if r[NAME] != "serve.queued":                 # began before the step
            assert parent[T0] <= r[T0] <= r[T1] <= parent[T1]
    q = by_name["serve.queued"]
    assert q[ATTRS] == {"request_id": q[ATTRS]["request_id"], "prompt_len": 5}
    assert q[T0] <= root[T0] <= q[T1]                 # submitted, then admitted
    # the phases account for the step
    kids = [r for r in recs if r[PARENT] == root[ID]]
    assert sum(r[T1] - r[T0] for r in kids) >= 0.9 * (root[T1] - root[T0])
    # later steps: decode only, one serve.step per call, step ids count up
    eng.run()
    steps = trace_mod.spans(name="serve.step")
    assert [r[STEP] for r in steps] == list(range(1, len(steps) + 1))
    assert len(trace_mod.spans(name="serve.queued")) == 1
    assert len(trace_mod.spans(name="serve.prefill")) == 1
    eng.shutdown()


def test_serve_queued_once_per_admission_and_again_after_preemption(tiny_model, clear_ring):
    # 4 usable pages of 4 positions: two prompts of 6 take 2 pages each
    # and neither can grow — the newer is preempted at the first decode,
    # requeued, and admitted a second time once the older one is done
    eng = _engine(tiny_model, num_pages=5, max_batch_slots=2)
    eng.warmup()
    clear_ring()
    states = [eng.submit(Request(np.arange(1, 7, dtype=np.int32),
                                 max_new_tokens=5)) for _ in range(2)]
    eng.run()
    queued = trace_mod.spans(name="serve.queued")
    n_pre = sum(st.preemptions for st in states)
    assert n_pre >= 1
    assert len(queued) == 2 + n_pre == eng.scheduler.stats["admitted"]
    by_req = {}
    for r in queued:
        by_req.setdefault(r[ATTRS]["request_id"], []).append(r)
    for st in states:
        waits = by_req[st.request.request_id]
        assert len(waits) == 1 + st.preemptions
        assert waits[0][T0] == st.submitted_t
        for earlier, later in zip(waits, waits[1:]):
            assert later[T0] >= earlier[T1]           # requeued after admission
    eng.shutdown()


def test_verify_spans_under_speculation(tiny_model):
    with flag_scope("serve_spec_k", 3):
        eng = _engine(tiny_model)
    eng.generate([[3, 4, 5, 3, 4, 5, 3, 4]], max_new_tokens=12)
    assert eng._stats["verify_dispatches"] > 0
    names = {r[NAME] for r in trace_mod.spans()}
    assert {"serve.verify", "serve.verify.build", "serve.verify.dispatch",
            "serve.verify.readback", "serve.verify.accept"} <= names
    eng.shutdown()


def test_ring_is_thread_safe_under_two_engine_threads(tiny_model, clear_ring):
    engines = [_engine(tiny_model) for _ in range(2)]
    for eng in engines:
        eng.warmup()
    clear_ring()
    errors = []

    def serve(eng):
        try:
            eng.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
        except BaseException as e:                     # surfaced below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=serve, args=(e,)) for e in engines]
        for t in threads:
            t.start()
        reads = 0
        while any(t.is_alive() for t in threads):
            trace_mod.spans()                          # a reader beside writers
            reads += 1
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    recs = trace_mod.spans()
    ids = [r[ID] for r in recs]
    assert len(set(ids)) == len(ids)                   # no id given twice
    by_id = {r[ID]: r for r in recs}
    steps = [r for r in recs if r[NAME] == "serve.step"]
    assert len(steps) == sum(e._step_seq for e in engines)
    # a thread's spans nest under that thread's own step, never the other's
    for r in recs:
        if r[NAME] == "serve.queued" or r[PARENT] is None:
            continue
        parent = by_id[r[PARENT]]
        assert parent[T0] <= r[T0] and r[T1] <= parent[T1], (r, parent)
    for eng in engines:
        eng.shutdown()


def test_stalled_step_is_counted_and_recorded(tiny_model, monkeypatch):
    eng = _engine(tiny_model)
    eng.warmup()
    eng.generate([[1, 2, 3]], max_new_tokens=8)        # ordinary steps: a median
    real = eng._guarded_dispatch
    clock = {"skew": 0.0}
    base = trace_mod.time.perf_counter

    def slow_dispatch(kind, prog, args, hang=False):
        clock["skew"] += 3.0                           # the "runtime" sat 3 s
        return real(kind, prog, args, hang=hang)

    monkeypatch.setattr(eng, "_guarded_dispatch", slow_dispatch)
    monkeypatch.setattr(trace_mod.time, "perf_counter",
                        lambda: base() + clock["skew"])
    with scoped_registry() as reg, flag_scope("flight_recorder", True):
        flight_recorder.get_flight_recorder().clear()
        eng.submit(Request(np.arange(4, dtype=np.int32), max_new_tokens=1))
        eng.step()
        assert reg.counter("serve_step_stalls_total").value(
            phase="serve.prefill.dispatch") == 1
        events = [e for e in flight_recorder.get_flight_recorder().events
                  if e["event"] == "serve_step_stall"]
    assert len(events) == 1
    ev = events[0]
    assert ev["phase"] == "serve.prefill.dispatch" and ev["seconds"] >= 3.0
    assert ev["phases_ms"]["serve.prefill.dispatch"] >= 3000.0
    assert set(ev["phases_ms"]) >= {"serve.sweep", "serve.admit",
                                    "serve.prefill", "serve.publish"}
    assert "serve.queued" not in ev["phases_ms"]
    assert "serve_step_stall" in flight_recorder.RECOVERY_EVENTS
    eng.shutdown()


def test_cold_bucket_compile_is_a_span_and_no_stall(tiny_model, monkeypatch):
    """An engine that was not warmed builds a bucket's program inside
    `serve.prefill.build`: the build is a `serve.compile` span, and the
    step, however long, is not counted as a stall."""
    eng = _engine(tiny_model)
    eng.generate([[1, 2, 3]], max_new_tokens=8)        # bucket 1x8, decode
    assert ("prefill", 1, 16) not in eng._programs
    clock = {"skew": 0.0}
    base = trace_mod.time.perf_counter
    real = aot.AOTProgram.compile

    def slow_compile(self, example_args):
        clock["skew"] += 3.0                           # the compiler sat 3 s
        return real(self, example_args)

    monkeypatch.setattr(aot.AOTProgram, "compile", slow_compile)
    monkeypatch.setattr(trace_mod.time, "perf_counter",
                        lambda: base() + clock["skew"])
    with scoped_registry() as reg, flag_scope("flight_recorder", True):
        flight_recorder.get_flight_recorder().clear()
        eng.submit(Request(np.arange(12, dtype=np.int32), max_new_tokens=1))
        eng.step()
        assert reg.counter("serve_step_stalls_total").samples() == []
        assert not [e for e in flight_recorder.get_flight_recorder().events
                    if e["event"] == "serve_step_stall"]
    assert ("prefill", 1, 16) in eng._programs
    step = trace_mod.spans(name="serve.step")[-1]
    assert step[T1] - step[T0] >= 3.0
    by_id = {r[ID]: r for r in trace_mod.spans()}
    compile_ = trace_mod.spans(name="serve.compile")[-1]
    assert compile_[ATTRS] == {"kind": "serve_prefill_b1_s16"}
    assert compile_[T1] - compile_[T0] >= 3.0
    assert by_id[compile_[PARENT]][NAME] == "serve.prefill.build"
    assert compile_[STEP] == step[STEP]
    eng.shutdown()


def test_latched_drain_steps_are_steps_of_their_own(tiny_model, tmp_path):
    """The drain latch is honoured before `serve.step` opens: the
    drain's own steps are top-level records, and no step spans the
    drain."""
    from paddle_tpu.serving import EngineDrained
    eng = _engine(tiny_model)
    eng.warmup()
    latch = eng.enable_drain(str(tmp_path), budget_s=60.0, signals=())
    eng.submit(Request(np.arange(5, dtype=np.int32), max_new_tokens=6))
    eng.step()
    before = eng._step_seq
    latch.trigger()
    with scoped_registry() as reg:
        with pytest.raises(EngineDrained) as drained:
            eng.step()
        assert reg.counter("serve_step_stalls_total").samples() == []
    assert drained.value.report.completed == 1
    steps = trace_mod.spans(name="serve.step")
    assert len(steps) == eng._step_seq > before       # the drain's steps only
    assert [r[STEP] for r in steps] == list(range(1, eng._step_seq + 1))
    assert all(r[PARENT] is None for r in steps)
    for earlier, later in zip(steps, steps[1:]):
        assert earlier[T1] <= later[T0]               # none inside another
    eng.shutdown()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def test_train_step_call_yields_step_and_its_children(clear_ring):
    step = _gpt_step()
    step(_ids(), _ids())                               # compiles
    clear_ring()
    step(_ids(), _ids())
    recs = trace_mod.spans()
    assert [r[NAME] for r in recs] == [
        "train.place_batch", "train.args", "train.dispatch", "train.step"]
    root = recs[-1]
    assert root[STEP] == 2 and root[PARENT] is None
    for r in recs[:-1]:
        assert r[PARENT] == root[ID] and r[STEP] == 2
        assert root[T0] <= r[T0] <= r[T1] <= root[T1]
    assert len(recs) <= 5                              # ISSUE 26's budget


def test_train_compile_span_when_it_happens():
    step = _gpt_step()
    step(_ids(), _ids())
    (compile_,) = trace_mod.spans(name="train.compile")
    (root,) = trace_mod.spans(name="train.step")
    assert compile_[PARENT] == root[ID]
    assert compile_[ATTRS] == {"kind": "step"}


@pytest.mark.parametrize("num_workers, buffered", [(0, True), (0, False)])
def test_data_wait_span_in_both_loader_iterators(num_workers, buffered):
    class DS(paddle.io.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.full((4,), i, np.float32)

    loader = paddle.io.DataLoader(DS(), batch_size=4, num_workers=num_workers,
                                  use_buffer_reader=buffered)
    batches = list(loader)
    assert len(batches) == 2
    waits = trace_mod.spans(name="train.data_wait")
    assert len(waits) >= 2 and all(r[T1] >= r[T0] for r in waits)


def test_train_step_trace_tree_under_flags_trace():
    """With FLAGS_trace on the same span calls attach to the step trace,
    under the names the ring has."""
    step = _gpt_step()
    step(_ids(), _ids())
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        step(_ids(), _ids())
    (tr,) = trace_mod.get_tracer().retained()
    names = [s.name for s in tr.spans]
    assert names[0] == "train.step"
    assert {"train.place_batch", "train.args", "train.dispatch"} <= set(names)
    assert names.count("train.step") == 1              # the root, no twin


# ---------------------------------------------------------------------------
# block scopes and the index
# ---------------------------------------------------------------------------


def test_resolve_paths():
    r = aot._resolve
    pre = "jit(train_step)/jit(main)/"
    assert r(pre + "jvp(attn)/dot_general") == ("attn", "fwd")
    assert r(pre + "transpose(jvp(attn))/dot_general") == ("attn", "bwd")
    assert r(pre + "transpose(jvp())/while/body/closed_call/checkpoint/"
             "rematted_computation/ffn/mul") == ("ffn", "remat")
    assert r(pre + "transpose(jvp())/while/body/closed_call/checkpoint/"
             "norm/mul") == ("norm", "bwd")
    assert r(pre + "kv_write/while/body/attn/dot_general") == ("attn", "fwd")
    assert r(pre + "kv_write/while/body/dynamic_slice") == ("kv_write", "fwd")
    assert r(pre + "attn/kv_write/scatter") == ("kv_write", "fwd")
    assert r(pre + "optimizer/mul") == ("optimizer", "fwd")
    assert r(pre + "while/body/dynamic_update_slice") is None
    assert r("norm") is None                # the last part is the operation
    assert r(pre + "mla/select/top_k") == ("select", "fwd")
    assert r(pre + "indexer/while/body/dot_general") == ("indexer", "fwd")
    assert set(BLOCKS) == {"embed", "attn", "ffn", "moe", "norm", "loss",
                           "optimizer", "sampling", "kv_write", "mla",
                           "indexer", "select", "mhc", "conv"}


def test_parse_scopes_takes_an_instructions_own_scope_and_guesses_none():
    text = '''HloModule jit_toy, is_scheduled=true

%fused_computation.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4] parameter(0)
  %mul.1 = f32[4] multiply(%p0, %p0), metadata={op_name="jit(toy)/ffn/mul"}
  ROOT %dus.1 = f32[4] dynamic-update-slice(%mul.1, %p0), metadata={op_name="jit(toy)/while/body/dynamic_update_slice"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %flash_fwd.2 = f32[4] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/jvp(attn)/pallas_call"}
  %dus_fusion.3 = f32[4] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(toy)/while/body/dynamic_update_slice"}
  %copy.4 = f32[4] copy(%flash_fwd.2)
  ROOT %add.6 = f32[4] add(%copy.4, %a), metadata={op_name="jit(toy)/transpose(jvp(norm))/add"}
}
'''
    module, table = aot.parse_scopes(text)
    assert module == "jit_toy"
    # by its own `op_name` and nothing else: a fusion named after a
    # plumbing root and a copy without metadata stay unscoped, whatever
    # their fused instructions or operands carry
    assert table == {"mul.1": ("ffn", "fwd"), "flash_fwd.2": ("attn", "fwd"),
                     "add.6": ("norm", "bwd")}


def test_kernel_calls_are_the_mosaic_calls_read_by_scope():
    """`aot.kernel_calls`: the instructions that are Mosaic custom calls,
    all of a program's or those the scope index places in a block and a
    phase (ISSUE 34: what a recomputed body runs of attention AGAIN)."""
    text = '''HloModule jit_toy_kernels, is_scheduled=true

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %flash_fwd.2 = f32[4] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/jvp()/while/body/closed_call/attn/flash_fwd/pallas_call"}
  %flash_fwd.3 = f32[4] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn/flash_fwd/pallas_call"}
  %fused_dropout.5 = f32[4] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/ffn/fused_dropout/pallas_call"}
  %flash_bwd.4 = f32[4] custom-call(%flash_fwd.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/transpose(jvp())/while/body/closed_call/checkpoint/attn/flash_bwd/pallas_call"}
  %alloc.7 = f32[4] custom-call(), custom_call_target="AllocateBuffer"
  %loose.8 = f32[4] custom-call(%a), custom_call_target="tpu_custom_call"
  ROOT %add.6 = f32[4] add(%flash_bwd.4, %a), metadata={op_name="jit(toy)/transpose(jvp(norm))/add"}
}
'''
    assert aot.kernel_calls("jit_toy_kernels") is None
    assert aot.index_program(text) == "jit_toy_kernels"
    assert aot.scopes("jit_toy_kernels")["add.6"] == ("norm", "bwd")
    assert aot.kernel_calls("jit_toy_kernels") == [
        "flash_bwd.4", "flash_fwd.2", "flash_fwd.3", "fused_dropout.5",
        "loose.8"]
    assert aot.kernel_calls("jit_toy_kernels", "attn") == [
        "flash_bwd.4", "flash_fwd.2", "flash_fwd.3"]
    assert aot.kernel_calls("jit_toy_kernels", "attn", "remat") \
        == ["flash_fwd.3"]
    assert aot.kernel_calls("jit_toy_kernels", phase="remat") == [
        "flash_fwd.3", "fused_dropout.5"]
    assert aot.kernel_calls("jit_toy_kernels", "loss") == []
    # an interpreted or XLA-path program has an index and no call
    step = _gpt_step(use_recompute=True)
    step(_ids(), _ids())
    assert aot.kernel_calls("jit_train_step") == []


def test_products_are_the_mxu_products_and_collectives_read_by_scope():
    """`aot.products`: a fusion whose computation holds a `dot` or a
    `convolution` (through a nested fusion too), such an instruction
    unfused, and the collectives by their one `-start` (or plain) form;
    never what sits inside a fused computation, never a `-done`."""
    text = '''HloModule jit_toy_products, is_scheduled=true

%inner.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8] parameter(0)
  ROOT %convolution.1 = bf16[8,8] convolution(%p0, %p0), dim_labels=bf_io->bf
}

%outer.2 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8] parameter(0)
  ROOT %fusion.9 = bf16[8,8] fusion(%p0), kind=kOutput, calls=%inner.1
}

%plain.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %add.1 = f32[8] add(%p0, %p0)
}

%sum.4 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x, %y)
}

ENTRY %main (a: bf16[8,8], b: f32[8]) -> f32[8] {
  %a = bf16[8,8]{1,0:T(8,128)(2,1)} parameter(0)
  %b = f32[8] parameter(1)
  %convolution_fusion.5 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%a), kind=kOutput, calls=%outer.2, metadata={op_name="jit(toy)/transpose(jvp())/while/body/checkpoint/rematted_computation/ffn/dot_general"}
  %dot.6 = bf16[8,8] dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(toy)/jvp()/attn/dot_general"}
  %add_fusion.7 = f32[8] fusion(%b), kind=kLoop, calls=%plain.3, metadata={op_name="jit(toy)/transpose(jvp())/while/body/checkpoint/rematted_computation/ffn/add"}
  %all-reduce.8 = f32[8] all-reduce(%add_fusion.7), replica_groups={{0,1}}, to_apply=%sum.4, metadata={op_name="jit(toy)/transpose(jvp())/while/body/checkpoint/rematted_computation/attn/psum"}
  %collective-permute-start.9 = (f32[8], f32[8]) collective-permute-start(%b), source_target_pairs={{0,1}}
  %collective-permute-done.10 = f32[8] collective-permute-done(%collective-permute-start.9)
  ROOT %add.11 = f32[8] add(%all-reduce.8, %collective-permute-done.10)
}
'''
    assert aot.products("jit_toy_products") is None
    assert aot.index_program(text) == "jit_toy_products"
    assert aot.products("jit_toy_products") == [
        "all-reduce.8", "collective-permute-start.9", "convolution_fusion.5",
        "dot.6"]
    assert aot.products("jit_toy_products", phase="remat") == [
        "all-reduce.8", "convolution_fusion.5"]
    assert aot.products("jit_toy_products", "attn") == [
        "all-reduce.8", "dot.6"]


def test_scopes_of_a_toy_gpt_step():
    step = _gpt_step(use_recompute=True)
    step(_ids(), _ids())
    (prog,) = step.aot_programs()
    text = prog.compiled.as_text()
    assert text.startswith("HloModule jit_train_step")
    table = aot.scopes("jit_train_step")
    assert table is aot.SCOPES["jit_train_step"]
    assert aot.scopes("jit_no_such_program") is None
    op_names = dict(re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"', text, re.M))
    assert op_names

    def block_of(pattern):
        hits = {table.get(n) for n, path in op_names.items()
                if re.search(pattern, path)}
        assert hits, pattern
        return hits

    # the attention products (the flash kernel's XLA twin on the CPU),
    # forward, backward through transpose(jvp(...)), and recomputed
    # under checkpoint
    assert block_of(r"/jvp\(\)/while/body/.*bqhd,bkhd->bhqk/dot_general") \
        == {("attn", "fwd")}
    assert block_of(r"transpose\(jvp\(\)\).*/checkpoint/attn/.*dot_general") \
        == {("attn", "bwd")}
    assert block_of(r"rematted_computation/attn/.*dot_general") \
        == {("attn", "remat")}
    assert block_of(r"/jvp\(\)/while/body/.*/ffn/dot_general") == {("ffn", "fwd")}
    assert block_of(r"transpose\(jvp\(loss\)\)/bse,ve->bsv/dot_general") \
        == {("loss", "bwd")}
    assert block_of(r"jvp\(embed\)/") == {("embed", "fwd")}
    # the AdamW update
    assert block_of(r"/optimizer/") == {("optimizer", "fwd")}
    assert {p for _, p in table.values()} == {"fwd", "bwd", "remat"}
    assert {b for b, _ in table.values()} >= {"embed", "attn", "ffn", "norm",
                                               "loss", "optimizer"}


def test_serving_programs_are_named_and_scoped(tiny_model):
    eng = _engine(tiny_model)
    eng.warmup()
    names = {p.compiled.as_text().split(",", 1)[0].split()[1]
             for p in eng._programs.values()}
    assert "jit_serve_decode" in names
    assert {"jit_serve_prefill_1x8", "jit_serve_prefill_2x16"} <= names
    blocks = {b for b, _ in aot.scopes("jit_serve_decode").values()}
    assert {"sampling", "kv_write", "attn", "ffn", "norm", "embed"} <= blocks
    eng.shutdown()


def _instructions(hlo: str) -> list:
    """The module's instruction lines without their metadata."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo.splitlines()
            if re.match(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", line)]


@pytest.fixture
def no_compile_cache():
    """The persistent cache keys an executable without its metadata: a
    hit would hand back the first build's text for the second."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_block_scopes_change_metadata_only(monkeypatch, no_compile_cache):
    """The optimized program of the toy step with `block` unset and set
    differs in metadata only."""
    import contextlib

    def compiled_text():
        step = _gpt_step(use_recompute=True)
        step(_ids(), _ids())
        (prog,) = step.aot_programs()
        return prog.compiled.as_text(), prog.compiled.memory_analysis()

    scoped, mem_scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, mem_bare = compiled_text()
    assert "/attn/" in scoped and "/attn/" not in bare
    assert "train_step)/optimizer/" in scoped
    assert "train_step)/optimizer/" not in bare
    assert len(_instructions(scoped)) > 500
    assert _instructions(scoped) == _instructions(bare)
    assert mem_scoped.temp_size_in_bytes == mem_bare.temp_size_in_bytes
    assert mem_scoped.argument_size_in_bytes == mem_bare.argument_size_in_bytes


def test_scopes_do_not_retrace_the_eager_path(monkeypatch):
    """`Layer.__call__` enters `jax.named_scope` on eager calls too:
    the warm eager forward compiles nothing and does the same host-side
    tracing work as with no scope at all (ISSUE 26: were it not so, the
    scope would be entered only under a tracer)."""
    import contextlib

    from paddle_tpu.utils import CompileCounter
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    model.eval()
    ids = paddle.to_tensor(_ids())

    def warm_forward():
        model(ids)                                     # warm the eager caches
        model(ids)
        with CompileCounter() as c:
            model(ids)
        assert c.backend_compiles == 0
        return c.jaxpr_traces

    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: entered.append(name) or real(name))
    with_scopes = warm_forward()
    assert {"embed", "attn", "ffn", "norm"} <= set(entered)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert with_scopes == warm_forward()
