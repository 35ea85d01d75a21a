"""What the fullest chip held in the WINDOW, GiB (harness/result.py
`hbm_account`): the arrays of the process (`bytes_in_use`) plus what the
runtime had reserved for the loaded programs' temporaries
(`bytes_reserved`) — the peak of that sum if the window raised it, else
what was held when the window opened and closed. Serves
`hbm_peak_gib.train` and `hbm_peak_gib.serve`; the whole process's peak,
set-up and its checks included, is the result line's
`memory_peak_bytes`."""


def read(run):
    b = run.counts.get("hbm_window_bytes")
    return b / 2**30 if b else None
