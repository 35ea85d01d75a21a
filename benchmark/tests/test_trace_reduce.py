"""The reduction from trace to numbers, on intervals written by hand and
on a small recorded trace of the chip (`recorded_trace.json`: the first
events of chip 0's `XLA Ops` line and the `bench.*` host spans of a
traced run of `gpt2_345m.train.b8s1024`, PR 25)."""
import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0                                     # ns in a microsecond


def test_union_and_gaps():
    ivs = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (40, 45, "d")]
    assert tr.union(ivs) == [(0, 20), (30, 45)]
    assert tr.gaps(tr.union(ivs), -5, 50) == [(-5, 0), (20, 30), (45, 50)]


def test_self_time_of_nested_events():
    # a while op covering its body: two fusions and a kernel
    ivs = [(0, 100, "while.1"), (10, 30, "fusion.1"), (30, 70, "flash_fwd.2"),
           (80, 90, "fusion.1"), (120, 130, "copy.3")]
    st = tr.self_times(ivs)
    assert st == {"while.1": 30, "fusion.1": 30, "flash_fwd.2": 40, "copy.3": 10}
    assert sum(st.values()) == sum(e - s for s, e in tr.union(ivs))


def test_reduce_names_gaps_by_host_span():
    dev = {0: [(100 * US, 200 * US, "fusion.1"), (300 * US, 400 * US, "paged_decode.1")],
           1: [(100 * US, 150 * US, "fusion.1")]}
    spans = [(0, 500 * US, "bench.window"), (190 * US, 310 * US, "bench.engine_step"),
             (400 * US, 500 * US, "bench.idle_sleep"), (0, 90 * US, "bench.submit")]
    mods = {0: [(100 * US, 200 * US, "jit_decode_fn"), (300 * US, 400 * US, "jit_decode_fn"),
                (450 * US, 600 * US, "jit_decode_fn")]}
    out = tr.reduce(tr.Trace(devices=dev, spans=spans, modules=mods,
                             mosaic={"paged_decode.1"}))
    assert out["mosaic_s"] == pytest.approx(100e-6)
    assert out["modules"]["jit_decode_fn"] == pytest.approx((2, 200e-6, 250e-6))
    per_run, runs = tr.main_program(out)
    assert per_run == pytest.approx(100e-6) and runs == pytest.approx(2.5)
    assert out["window_s"] == pytest.approx(500e-6)
    assert out["busy_s_device0"] == pytest.approx(200e-6)
    assert out["busy_s"] == pytest.approx((200e-6 + 50e-6) / 2)
    assert out["span_counts"]["bench.engine_step"] == 1
    assert tr.time_in(out["by_op"], ("paged_decode",)) == pytest.approx(100e-6)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["bench.engine_step"] == pytest.approx(100e-6)   # 200..300
    assert gaps["bench.idle_sleep"] == pytest.approx(100e-6)    # 400..500
    assert gaps["bench.submit"] == pytest.approx(100e-6)        # 0..100, mostly submit
    assert dict(out["breakdown"]["device_ops"]) == pytest.approx(
        {"fusion": 100e-6, "paged_decode": 100e-6})


def test_no_device_op_is_no_trace():
    assert tr.reduce(tr.Trace()) is None
    assert tr.reduce(tr.Trace(devices={0: []}, spans=[(0, 10, "bench.window")])) is None


def test_base_name():
    assert tr.op_name("%fusion.123 = bf16[8]{0} fusion(bf16[8]{0} %flash_fwd.2)") == "fusion.123"
    assert tr.base_name("fusion.123") == "fusion"
    assert tr.base_name("flash_fwd.2") == "flash_fwd"
    assert tr.base_name("all-reduce-start.1") == "all-reduce-start"


@pytest.mark.skipif(not os.path.exists(os.path.join(HERE, "recorded_trace.json")),
                    reason="no recorded trace")
def test_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    trace = tr.Trace(devices={0: [tuple(e) for e in rec["device0_ops"]]},
                     spans=[tuple(e) for e in rec["host_spans"]],
                     mosaic=set(rec["mosaic"]))
    out = tr.reduce(trace, window=tuple(rec["window"]))
    exp = rec["expected"]
    assert out["mosaic_s"] == pytest.approx(exp["mosaic_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    for name, sec in exp["kernel_s"].items():
        assert tr.time_in(out["by_op"], (name,)) == pytest.approx(sec, rel=1e-9)
    assert 0.0 < out["busy_s"] <= out["window_s"]
