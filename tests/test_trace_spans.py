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
def _clean_ring():
    trace_mod.clear_spans()
    yield
    trace_mod.clear_spans()


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


def test_one_engine_step_yields_the_span_table(tiny_model):
    eng = _engine(tiny_model)
    eng.warmup()
    trace_mod.clear_spans()
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
    assert by_name["serve.prefill"][ATTRS] == {"nb": 1, "sp": 8}
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


def test_serve_queued_once_per_admission_and_again_after_preemption(tiny_model):
    # 4 usable pages of 4 positions: two prompts of 6 take 2 pages each
    # and neither can grow — the newer is preempted at the first decode,
    # requeued, and admitted a second time once the older one is done
    eng = _engine(tiny_model, num_pages=5, max_batch_slots=2)
    eng.warmup()
    trace_mod.clear_spans()
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


def test_ring_is_thread_safe_under_two_engine_threads(tiny_model):
    engines = [_engine(tiny_model) for _ in range(2)]
    for eng in engines:
        eng.warmup()
    trace_mod.clear_spans()
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


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def test_train_step_call_yields_step_and_its_children():
    step = _gpt_step()
    step(_ids(), _ids())                               # compiles
    trace_mod.clear_spans()
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
    assert set(BLOCKS) == {"embed", "attn", "ffn", "moe", "norm", "loss",
                           "optimizer", "sampling", "kv_write"}


def test_parse_scopes_fusion_votes_and_operand_hop():
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
  %copy.5 = f32[4] copy(%copy.4)
  ROOT %add.6 = f32[4] add(%copy.5, %a), metadata={op_name="jit(toy)/add"}
}
'''
    module, table = aot.parse_scopes(text)
    assert module == "jit_toy"
    assert table["flash_fwd.2"] == ("attn", "fwd")
    assert table["dus_fusion.3"] == ("ffn", "fwd")     # its computation's vote
    assert table["copy.4"] == ("attn", "fwd")          # its operand's, one hop
    assert "copy.5" not in table and "add.6" not in table and "a" not in table


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
    phases = {p for _, p in table.values()}
    assert phases == {"fwd", "bwd", "remat"}
    assert {b for b, _ in table.values()} >= {"embed", "attn", "ffn", "norm",
                                               "loss", "optimizer"}
    assert aot.SCOPE_PARSE_SECONDS["jit_train_step"] < 5.0


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

    from paddle_tpu.nn import layer as layer_mod

    def compiled_text():
        step = _gpt_step(use_recompute=True)
        step(_ids(), _ids())
        (prog,) = step.aot_programs()
        return prog.compiled.as_text(), prog.compiled.memory_analysis()

    scoped, mem_scoped = compiled_text()
    monkeypatch.setattr(layer_mod, "block_scope",
                        lambda block, *inputs: contextlib.nullcontext())
    for mod in ("paddle_tpu.models.gpt", "paddle_tpu.nn.scan"):
        monkeypatch.setattr(sys.modules[mod], "block_scope",
                            layer_mod.block_scope)
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


def test_eager_calls_enter_no_scope_and_do_not_retrace(monkeypatch):
    import contextlib

    from paddle_tpu.nn import layer as layer_mod
    from paddle_tpu.nn.layer import block_scope
    from paddle_tpu.utils import CompileCounter
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    assert isinstance(block_scope("attn", x), contextlib.nullcontext)
    seen = []

    @jax.jit
    def traced(a):
        seen.append(type(block_scope("attn", a)))
        return a

    traced(x._data)
    assert seen and seen[0] is not contextlib.nullcontext
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

    with_scopes = warm_forward()
    monkeypatch.setattr(layer_mod, "block_scope",
                        lambda block, *inputs: contextlib.nullcontext())
    monkeypatch.setattr(sys.modules["paddle_tpu.models.gpt"], "block_scope",
                        layer_mod.block_scope)
    # the eager path does the same host-side tracing work with the
    # blocks set as without any scope at all
    assert with_scopes == warm_forward()
