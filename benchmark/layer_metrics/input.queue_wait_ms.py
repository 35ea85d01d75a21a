"""Median milliseconds of `train.data_wait`: the loader's `__next__` as
the program times it (pop of the prefetched batch and the refill)."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.median_ms(run, "train.data_wait")
