"""Median milliseconds of `serve.decode.dispatch`: from the call of the
decode program until the runtime's execute call returns (the device
runs on; the host does not wait here unless the runtime makes it)."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.decode.dispatch")
