"""`benchmark/run.py --rehearse` of every cell of BENCHMARK.json.

A cell's runner, its probe against the reference and its per-layer
readers call into the program by name (`eng._chunk`, `eng._programs`,
`step.aot_programs()`, span attributes, counters). A rehearsal walks
that whole path on the CPU at toy widths, so a program change that
breaks a runner or a reader fails here and not on the chip. `--trace 1`
is the superset: it also runs the trace readers. The cells are read
from the file, so a later cell is covered the day it lands.

One file, because under `--dist loadfile` a file is one worker's: the
rehearsals run one after another beside the other workers' tests.
"""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_rehearse import (  # noqa: E402,F401
    test_no_accelerator_no_result_line)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]

#: a hung cell fails its own case, not the suite
TIMEOUT_S = 300


def _own_env():
    """The environment a rehearsal by hand has: conftest.py's eight
    virtual devices are for the tests of this process, and run.py asks
    for the devices a cell needs itself."""
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("cell", CELLS)
def test_rehearse(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 17), "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=TIMEOUT_S,
        env=_own_env())
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal only: no result line (correct=True")
    assert "FAILED" not in p.stdout
