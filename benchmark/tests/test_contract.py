"""BENCHMARK.json against the driver's contract, as far as a file can
show it, and against the files the harness finds by name."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    RAW = f.read()
BENCH = json.loads(RAW)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def cells_of(metric):
    return set(metric.get("workloads", [w["name"] for w in BENCH["workloads"]]))


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert len(RAW) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"] and len(BENCH["command"]) <= 32
    assert os.path.exists(os.path.join(ROOT, BENCH["command"][1]))


def test_configs_and_cells():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert 1 <= len(configs) <= 24 and len(configs) == len(BENCH["configs"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        for kind, name in (("traffic", w["traffic"]), ("workloads", w["name"])):
            assert os.path.exists(os.path.join(ROOT, "benchmark", kind, name + ".json"))
    assert {w["config"] for w in cells} == set(configs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
        # the metric it should move is reported wherever it is
        assert cells_of(m) <= cells_of(e2e[m["moves"]])
        parts = m["name"].split(".")
        assert any(os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", ".".join(parts[:n]) + ".py"))
            for n in range(len(parts), 0, -1)), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        mine = [m for m in BENCH["end_to_end"] if w["name"] in cells_of(m)]
        assert len(mine) >= 2
        assert any(w["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_layers_are_perf_md_layers():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in BENCH["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]
        assert f"`{m['name']}`" in perf or m["name"].rsplit(".", 1)[0] in perf


def test_a_split_metric_shares_the_reader_of_its_prefix():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    for m in BENCH["per_layer"]:
        assert callable(run.load_reader(m["name"]).read)
    assert (run.load_reader("hbm_peak_gib.train").__file__
            == run.load_reader("hbm_peak_gib.serve").__file__)
    assert run.load_reader("step.device_ms.train").__file__.endswith(
        "step.device_ms.train.py")
    try:
        run.load_reader("no_such_metric.train")
    except FileNotFoundError:
        pass
    else:
        raise AssertionError("a metric with no reader has to be an error")
