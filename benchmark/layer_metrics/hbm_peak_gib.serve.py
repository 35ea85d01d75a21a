"""Peak HBM on the fullest chip after the window, GiB: the allocator's
`peak_bytes_in_use` plus the temporaries of the largest program that ran
(harness/result.py `hbm_peak_bytes`). One process per run, so it is this
cell's peak."""


def read(run):
    b = run.counts.get("hbm_peak_bytes")
    return b / 2**30 if b else None
