"""Share of chip 0's busy time in instructions of the `loss` block (the
vocabulary projection and the cross-entropy, forward and backward)."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.block_pct(run, ("loss",))
