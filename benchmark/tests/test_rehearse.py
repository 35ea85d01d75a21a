"""`--rehearse` of every cell of BENCHMARK.json, end to end on the CPU
(toy widths, kernels interpreted, four virtual devices for a four-chip
cell): the path, the arguments and every check but the chip's own. And
what the command does with no chip."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def run_cell(*extra, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, **(env or {})})


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearse(cell, trace):
    p = run_cell("--workload", cell, "--seed", str(2**31 + 17), "--seconds", "3",
                 "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal only: no result line (correct=True")
    assert "FAILED" not in p.stdout


def test_no_accelerator_no_result_line():
    cell = BENCH["workloads"][0]["name"]
    p = run_cell("--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0",
                 env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no accelerator" in p.stderr
