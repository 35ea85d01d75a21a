"""Share of chip 0's busy time in instructions of the `attn` block
(attention halves of the decoder blocks: QKV and output products, the
flash kernels, their dropout and residual add), forward, backward and
recomputed."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.block_pct(run, ("attn",))
