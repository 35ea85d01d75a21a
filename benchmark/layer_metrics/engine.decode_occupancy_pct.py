"""Mean slots decoded per decode dispatch inside the window, over the
engine's slots (`metrics_summary()` counts, window delta)."""


def read(run):
    d = run.counts.get("decode_dispatches")
    if not d:
        return None
    return 100.0 * run.counts["decode_slot_steps"] / d / run.counts["slots"]
