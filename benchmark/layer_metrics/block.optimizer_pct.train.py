"""Share of chip 0's busy time in instructions of the `optimizer` block
(clipping and the parameter update)."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.block_pct(run, ("optimizer",))
