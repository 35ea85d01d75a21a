"""Median host-clock milliseconds of one `engine.step()`: scheduler,
dispatch, the read-back of the tokens, sampling, callbacks."""
import statistics


def read(run):
    xs = run.spans.get("bench.engine_step")
    return statistics.median(xs) * 1e3 if xs else None
