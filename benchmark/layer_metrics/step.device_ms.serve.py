"""Device busy milliseconds per `engine.step()` call inside the traced
window."""


def read(run):
    tr = run.trace
    steps = tr and tr["span_counts"].get("bench.engine_step")
    return tr["busy_s"] / steps * 1e3 if steps else None
