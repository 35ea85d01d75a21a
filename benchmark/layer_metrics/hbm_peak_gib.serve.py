"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip after the
window, GiB: the arrays the process held at its peak. A program's
temporaries are not in it (harness/result.py `hbm_peak_bytes`). One
process per run, so it is this cell's peak."""


def read(run):
    b = run.counts.get("hbm_peak_bytes")
    return b / 2**30 if b else None
