"""Device busy milliseconds (union of op intervals, mean over the chips)
per training step dispatched inside the traced window."""


def read(run):
    tr = run.trace
    steps = tr and tr["span_counts"].get("bench.step_call")
    return tr["busy_s"] / steps * 1e3 if steps else None
