"""Pages the window lifetime's slots HELD in the decode steps of the
window, as a share of what they would hold if nothing were ever freed:
the program's own counters `serve_kv_pages_live_total{lifetime=window}`
over `serve_kv_pages_unwindowed_total` (a row an active slot a decode
step; window delta). 100 while every context is shorter than the model's
window; the lower, the more the second page lifetime saves."""


def read(run):
    whole = run.counts.get("window.serve_kv_pages_unwindowed_total")
    if not whole:
        return None
    return 100.0 * run.counts.get(
        "window.serve_kv_pages_live_total{lifetime=window}", 0) / whole
