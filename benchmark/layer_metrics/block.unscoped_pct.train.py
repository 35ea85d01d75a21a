"""Share of chip 0's busy time in instructions that carry no block: the
health of the instrumentation itself (lower is better)."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.block_pct(run, ())
