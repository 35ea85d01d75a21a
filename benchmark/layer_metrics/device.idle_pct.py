"""1 - device busy union over the traced window, mean over the chips.
Serves `device.idle_pct.train` and `device.idle_pct.serve`."""


def read(run):
    tr = run.trace
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None
