"""The yardstick's own unit tests, as part of tier-1.

`benchmark/run.py` reaches into the program by name (engine internals,
span and counter names, the scope index), and `benchmark/tests/` holds
the tests of its readers, its arithmetic and its contract with
`BENCHMARK.json`. The driver's command is `pytest tests/`, so this
module takes those test functions in as they stand: no copy to drift.
The rehearsals of every cell are in `test_benchmark_rehearse.py`.
"""
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

UNIT_MODULES = ("test_arithmetic", "test_arithmetic_cohere2_moe",
                "test_arithmetic_xing4", "test_arithmetic_lfm2_moe",
                "test_contract", "test_loadgen", "test_program_spans",
                "test_trace_reduce")


def _take_in(names):
    """Every test function and fixture of benchmark/tests/<name>.py
    becomes an attribute of this module, where pytest collects it."""
    taken = {}
    for name in names:
        dotted = f"benchmark.tests.{name}"
        pytest.register_assert_rewrite(dotted)
        mod = importlib.import_module(dotted)
        for attr, obj in vars(mod).items():
            is_test = attr.startswith("test_") and callable(obj)
            is_fixture = (type(obj).__module__ == "_pytest.fixtures"
                          or hasattr(obj, "_pytestfixturefunction"))
            if not (is_test or is_fixture):
                continue
            assert attr not in taken, (
                f"{attr} is defined in both {taken[attr]} and {dotted}")
            taken[attr] = dotted
            globals()[attr] = obj


_take_in(UNIT_MODULES)
