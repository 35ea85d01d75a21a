"""Positions the decode steps of the window attended over, as a share
of the positions their slots had cached: the program's own counters
`serve_dsa_selected_total` over `serve_dsa_available_total` (a row an
active slot a decode step; window delta). 100 while every context is
shorter than `index_topk`; the lower, the more the selection discards."""


def read(run):
    have = run.counts.get("window.serve_dsa_available_total")
    if not have:
        return None
    return 100.0 * run.counts.get("window.serve_dsa_selected_total", 0) / have
