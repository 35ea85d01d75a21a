"""K and V bytes the decode steps of the traced window had to read — the
program's own `serve.decode` spans inside the traced part carry the
positions a step reads in a full layer (`read_full`: pos + 1 a slot) and
in a window layer (`read_window`: min(pos + 1, window)), and
models/<family>.py `decode_read_bytes` turns them into bytes — over the
device time of the `paged_decode` kernel, as a share of the chip's peak
HBM bytes/s. Bandwidth-bound: one multiply-add per K/V element read.
Nothing where the program has no such attributes (its parent), the
family no such function, or the run no trace.

NOT an entry of BENCHMARK.json at present: it moves `tpot_p90_ms`, and
the one cell that has something for it to read is judged on
`ttft_p95_ms` alone until the serve bounds are set anew (PERF.md §7,
ROADMAP Speed 0 (i)); 43.08 on the chip by hand (PERF.md §6)."""
from benchmark.harness import peaks, program_spans, trace_reduce


def read(run):
    tr, t0 = run.trace, run.counts.get("trace_t0")
    if not tr or t0 is None or not hasattr(run.model, "decode_read_bytes"):
        return None
    full = window = 0
    for rec in program_spans.records(run, "serve.decode"):
        attrs = rec[program_spans.ATTRS] or {}
        if rec[program_spans.T0] >= t0 and "read_full" in attrs:
            full += attrs["read_full"]
            window += attrs["read_window"]
    seconds = trace_reduce.time_in(tr["by_op"], ("paged_decode",))
    if seconds <= 0 or not full:
        return None
    sz = run.model.sizes(run.config, run.rehearse)
    need = run.model.decode_read_bytes(
        sz, full, window, run.system["engine"]["cache_dtype"])
    return 100.0 * need / seconds \
        / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
