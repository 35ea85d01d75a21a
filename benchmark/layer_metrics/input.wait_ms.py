"""Median host-clock milliseconds a step waited in `next(loader)`."""
import statistics


def read(run):
    xs = run.spans.get("bench.input_wait")
    return statistics.median(xs) * 1e3 if xs else None
