"""The readers of the program's own spans and scope index
(`harness/program_spans.py`): the window, self time, the by-block join
on `recorded_trace.json` with a hand-made scope table, and None — never
an error — where the data is missing (a program without the ring, a run
without a device trace)."""
import json
import os
import types

import pytest

from benchmark.harness import program_spans as ps
from benchmark.harness import trace_reduce as tr
from benchmark.harness.result import Run

HERE = os.path.dirname(os.path.abspath(__file__))


def make_run(**kw):
    run = Run(cell="c", config={}, mix={}, system={}, chips=1, seed=0,
              seconds=10.0, traced=True, rehearse=False, t_start=100.0,
              deadline=1e9)
    run.e2e["setup_s"] = 20.0                 # window: 120 .. 130
    run.counts["window_s"] = 10.0
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def rec(name, t0, t1, sid, parent=None, step=None, attrs=None):
    return (name, t0, t1, sid, parent, step, attrs)


class Ring:
    """The ring's reading side over hand-made records; the step walk and
    the stall rule are the program's own (`monitor/trace.py`)."""

    def __init__(self, recs):
        self.recs = recs
        trace = pytest.importorskip("paddle_tpu.monitor.trace")
        self.phase_table = trace.phase_table
        self.stalled = trace.stalled
        self.stall_phase = trace.stall_phase

    def spans(self, since=None, until=None, name=None):
        return [r for r in self.recs
                if (name is None or r[0] == name)
                and (since is None or r[2] >= since)
                and (until is None or r[1] <= until)]


#: two decode-only steps inside the window, one that straddles its start,
#: one stalled step with a prefill, and three queue waits
RECS = [
    rec("serve.step", 119.9, 120.1, 1, step=1),
    rec("serve.step", 121.0, 121.2, 10, step=2),
    rec("serve.sweep", 121.0, 121.01, 11, 10, 2),
    rec("serve.decode", 121.02, 121.19, 12, 10, 2),
    rec("serve.decode.dispatch", 121.03, 121.05, 13, 12, 2),
    rec("serve.decode.readback", 121.05, 121.18, 14, 12, 2),
    rec("serve.step", 122.0, 122.3, 20, step=3),
    rec("serve.decode", 122.0, 122.3, 21, 20, 3),
    rec("serve.decode.dispatch", 122.0, 122.04, 22, 21, 3),
    rec("serve.decode.readback", 122.04, 122.29, 23, 21, 3),
    rec("serve.step", 123.0, 126.0, 30, step=4),
    rec("serve.admit", 123.0, 123.1, 31, 30, 4),
    rec("serve.queued", 118.0, 123.05, 32, 31, 4),        # began before
    rec("serve.prefill", 123.1, 123.5, 33, 30, 4),
    rec("serve.prefill.readback", 123.2, 123.5, 34, 33, 4),
    rec("serve.decode", 123.5, 125.9, 35, 30, 4),
    rec("serve.decode.dispatch", 123.5, 123.6, 36, 35, 4),
    rec("serve.decode.readback", 123.6, 125.8, 37, 35, 4),
    rec("serve.queued", 119.0, 119.5, 40, None, 0),       # ended before
    rec("serve.queued", 124.0, 124.5, 41, None, 5),
    rec("serve.queued", 129.5, 131.0, 42, None, 9),       # ended after
]


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(ps, "_ring", lambda: Ring(RECS))
    monkeypatch.setattr(ps, "_reported", set())


def test_window_and_selection(ring):
    run = make_run()
    assert ps.window(run) == (120.0, 130.0)
    # wholly inside: the straddling step 1 is left out
    assert ps.durations(run, "serve.step") == pytest.approx([0.2, 0.3, 3.0])
    assert ps.median_ms(run, "serve.step") == pytest.approx(300.0)
    assert ps.median_ms(run, "serve.decode.dispatch") == pytest.approx(40.0)
    assert ps.median_ms(run, "no.such.span") is None
    # a wait counts where it ENDS: 32 (5.05 s) and 41 (0.5 s)
    assert ps.ending_in_window_ms(run, "serve.queued", 100) == pytest.approx(5050.0)
    assert ps.ending_in_window_ms(run, "serve.queued", 0) == pytest.approx(500.0)


def test_self_time_and_minus_descendants(ring):
    run = make_run()
    recs = ps.records(run)
    kids = ps.children(recs)
    step2 = next(r for r in recs if r[ps.ID] == 10)
    assert ps.self_time(step2, kids[10]) == pytest.approx(0.2 - 0.01 - 0.17)
    step4 = next(r for r in recs if r[ps.ID] == 30)
    # the queue wait recorded under serve.admit began before the step:
    # not a phase of it, and clipped out of its parent's self time
    assert set(ps._ring().phase_table(step4, recs)) == {
        "serve.admit", "serve.prefill", "serve.prefill.readback",
        "serve.decode", "serve.decode.dispatch", "serve.decode.readback"}
    admit = next(r for r in recs if r[ps.ID] == 31)
    assert ps.self_time(admit, kids[31]) == pytest.approx(0.1 - 0.05)
    # serve.step minus every .readback under it: 0.07, 0.05, 0.5
    assert ps.minus_descendants_ms(run, "serve.step", ".readback") \
        == pytest.approx(70.0)


def test_report_prints_once_and_names_the_stall(ring, capsys):
    run = make_run()
    ps.report(run)
    ps.report(run)
    out = capsys.readouterr().out
    assert out.count("program spans inside the window") == 1
    assert ("STALLED serve.step step 4 at 3.000s into the window: 3000.0 ms, "
            "sat in serve.decode.readback") in out
    assert "serve.decode.readback 2200.0" in out
    assert "serve.queued ending in the window: 2 admissions" in out
    assert "children cover" in out


def test_module_ms():
    run = make_run(trace={"modules": {
        "jit_serve_decode": (10, 1.73, 1.9),
        "jit_serve_prefill_1x256": (2, 0.30, 0.30),
        "jit_serve_prefill_4x256": (1, 0.12, 0.2),
        "jit_serve_prefill_4x768": (0, 0.0, 0.1)}, "by_op": {}})
    assert ps.module_ms(run, "jit_serve_decode") == pytest.approx(173.0)
    assert ps.module_ms(run, "jit_serve_prefill") == pytest.approx(140.0)
    assert ps.module_ms(run, "jit_serve_verify") is None


def test_by_block_on_the_recorded_trace(monkeypatch, capsys):
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recd = json.load(f)
    trace = tr.Trace(devices={0: [tuple(e) for e in recd["device0_ops"]]},
                     spans=[tuple(e) for e in recd["host_spans"]],
                     modules={0: [(recd["window"][0], recd["window"][1], "jit_train_step")]},
                     mosaic=set(recd["mosaic"]))
    summary = tr.reduce(trace, window=tuple(recd["window"]))
    ops = sorted(summary["by_op"])
    # a hand-made index: the flash kernels are attention, the dropout
    # kernel belongs to the ffn half, everything else has no block
    index = {"flash_fwd.19": ("attn", "remat"), "flash_bwd.10": ("attn", "bwd"),
             "fused_dropout.48": ("ffn", "bwd"), "not_in_the_window.1": ("loss", "fwd")}
    monkeypatch.setattr(ps, "_scopes", lambda module: index
                        if module == "jit_train_step" else None)
    monkeypatch.setattr(ps, "_reported", set())
    run = make_run(trace=summary)
    split = ps.by_block(run)
    busy = sum(summary["by_op"].values())
    assert split["module"] == "jit_train_step"
    assert split["busy_s"] == pytest.approx(busy)
    assert busy == pytest.approx(recd["expected"]["busy_s"], rel=1e-6)
    k = recd["expected"]["kernel_s"]
    assert split["blocks"]["attn"]["remat"] == pytest.approx(k["flash_fwd"], rel=1e-9)
    assert split["blocks"]["attn"]["bwd"] == pytest.approx(k["flash_bwd"], rel=1e-9)
    assert "loss" not in split["blocks"]
    assert split["kinds"]["attn"] == pytest.approx(
        {"flash_fwd": k["flash_fwd"], "flash_bwd": k["flash_bwd"]}, rel=1e-9)
    dropout = summary["by_op"]["fused_dropout.48"]
    assert ps.block_pct(run, ("attn",)) == pytest.approx(
        100.0 * (k["flash_fwd"] + k["flash_bwd"]) / busy)
    assert ps.block_pct(run, ("ffn", "moe")) == pytest.approx(100.0 * dropout / busy)
    assert ps.block_pct(run, ("loss",)) == 0.0
    shares = [ps.block_pct(run, (b,)) for b in ("attn", "ffn")] + [ps.block_pct(run, ())]
    assert sum(shares) == pytest.approx(100.0)
    assert ops and split["unscoped_top"][0][0] not in index
    out = capsys.readouterr().out
    assert "device time by block, chip 0, program jit_train_step" in out
    assert "(unscoped)" in out and "flash_bwd " in out


def test_none_on_missing_data(monkeypatch):
    monkeypatch.setattr(ps, "_reported", set())
    # a program without the ring or the index (the parent of PR 26)
    monkeypatch.setattr(ps, "_ring", lambda: None)
    monkeypatch.setattr(ps, "_scopes", lambda module: None)
    run = make_run(trace={"by_op": {"fusion.1": 1.0},
                          "modules": {"jit_step": (3, 1.0, 1.0)}})
    assert ps.records(run) == [] and ps.durations(run, "serve.step") == []
    assert ps.median_ms(run, "train.dispatch") is None
    assert ps.minus_descendants_ms(run, "serve.step", ".readback") is None
    assert ps.ending_in_window_ms(run, "serve.queued", 95) is None
    assert ps.by_block(run) is None and ps.block_pct(run, ("attn",)) is None
    assert ps.module_ms(run, "jit_serve_decode") is None
    # no device trace (--rehearse on the CPU), no window yet
    bare = make_run(trace=None)
    bare.e2e.clear()
    monkeypatch.setattr(ps, "_ring", lambda: Ring(RECS))
    assert ps.window(bare) is None and ps.records(bare) == []
    assert ps.median_ms(bare, "serve.step") is None
    assert ps.ending_in_window_ms(bare, "serve.queued", 95) is None
    assert ps.by_block(bare) is None and ps.module_ms(bare, "jit_") is None
    ps.report(bare)                                   # prints nothing, raises nothing


def test_every_new_reader_returns_none_without_data(monkeypatch):
    import importlib.util
    monkeypatch.setattr(ps, "_ring", lambda: None)
    monkeypatch.setattr(ps, "_scopes", lambda module: None)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(os.path.dirname(HERE), "run.py"))
    bench_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_run)
    run = make_run(trace=None)
    for metric in ("engine.host_ms", "engine.dispatch_ms", "engine.readback_wait_ms",
                   "engine.queue_wait_ms", "step.device_ms.decode",
                   "step.device_ms.prefill", "trainstep.place_ms",
                   "trainstep.dispatch_ms", "input.queue_wait_ms",
                   "block.attention_pct.train", "block.ffn_pct.train",
                   "block.loss_pct.train", "block.optimizer_pct.train",
                   "block.unscoped_pct.train"):
        assert bench_run.load_reader(metric).read(run) is None, metric


def test_against_the_programs_own_ring():
    """The real ring and index, when the program has them."""
    trace = pytest.importorskip("paddle_tpu.monitor.trace")
    if not hasattr(trace, "spans"):
        pytest.skip("the program has no span ring")
    import time
    t0 = time.perf_counter()
    with trace.span("train.step", step=1):
        with trace.span("train.dispatch"):
            pass
    run = types.SimpleNamespace(t_start=t0 - 1.0, e2e={"setup_s": 1.0},
                                counts={"window_s": time.perf_counter() - t0 + 1.0},
                                trace=None)
    assert len(ps.durations(run, "train.dispatch")) >= 1
    assert ps.median_ms(run, "train.dispatch") >= 0.0
