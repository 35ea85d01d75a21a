"""Median host-clock milliseconds of the call `step(ids, labels)` until
it returns (it does not block on the device)."""
import statistics


def read(run):
    xs = run.spans.get("bench.step_call")
    return statistics.median(xs) * 1e3 if xs else None
