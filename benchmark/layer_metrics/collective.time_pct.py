"""Chip 0's time in all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all ops over the traced window."""
from benchmark.harness import trace_reduce


def read(run):
    tr = run.trace
    if not tr:
        return None
    return 100.0 * trace_reduce.time_in(
        tr["by_op"], trace_reduce.COLLECTIVES) / tr["window_s"]
