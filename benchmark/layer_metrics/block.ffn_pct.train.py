"""Share of chip 0's busy time in instructions of the `ffn` and `moe`
blocks (the feed-forward halves of the decoder blocks)."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.block_pct(run, ("ffn", "moe"))
