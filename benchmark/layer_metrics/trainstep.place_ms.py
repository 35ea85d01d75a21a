"""Median milliseconds of `train.place_batch`: the batch's arrays
placed on the device or the mesh (`device_put` per data spec) and
flattened, inside `TrainStep.__call__`."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.median_ms(run, "train.place_batch")
