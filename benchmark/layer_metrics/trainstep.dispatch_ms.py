"""Median milliseconds of `train.dispatch`: the call of the compiled
step program until the runtime's execute call returns."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.median_ms(run, "train.dispatch")
