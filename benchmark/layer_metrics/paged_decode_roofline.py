"""K and V bytes the decode steps of the traced window had to read — for
every token a decode step produced, the positions it attended over (the
benchmark's own record of each request) times the bytes a position holds
(models/<family>.py `kv_bytes_per_token`) — over the device time of the
`paged_decode` kernel, as a share of the chip's peak HBM bytes/s.
Bandwidth-bound: one multiply-add per K/V element read."""
from benchmark.harness import peaks, trace_reduce


def read(run):
    tr = run.trace
    if not tr:
        return None
    seconds = trace_reduce.time_in(tr["by_op"], ("paged_decode",))
    kv = run.counts.get("traced_decode_kv_bytes")
    if seconds <= 0 or not kv:
        return None
    return 100.0 * kv / seconds / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
