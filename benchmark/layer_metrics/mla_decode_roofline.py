"""Latent bytes the decode steps of the traced part had to read — the
program's own `serve.decode` spans inside it carry the positions a step
attends over in a layer (`read_latent`: pos + 1 a slot), and
models/<family>.py `decode_read_bytes` turns them into bytes (576 values
a position a layer, ONCE: keys and values are one row) — over the device
time of the `paged_mla_decode` kernel, as a share of the chip's peak HBM
bytes/s. By the bytes it is bandwidth-bound (a latent value is read once
and meets 32 heads' queries and probabilities); the kernel's three-part
probabilities make its MXU time comparable, so the share says how far
the sweep is from the bandwidth floor, not that bandwidth binds it.
Nothing where the program has no such attribute (its parent), the family
no such function, or the run no trace.

NOT an entry of BENCHMARK.json at present: it moves `tpot_p90_ms`, and
the one cell that has something for it to read is judged on
`ttft_p95_ms` alone (PERF.md §6 and §7, PR 35: the cell's two sets of
six did not hold half the bound on `tpot_p90_ms`, which sits between
two levels of gaps there); 33.5-34 on the chip by hand (PERF.md §6)."""
from benchmark.harness import peaks, program_spans, trace_reduce


def read(run):
    tr, t0 = run.trace, run.counts.get("trace_t0")
    if not tr or t0 is None or not hasattr(run.model, "decode_read_bytes"):
        return None
    positions = 0
    for rec in program_spans.records(run, "serve.decode"):
        attrs = rec[program_spans.ATTRS] or {}
        if rec[program_spans.T0] >= t0 and "read_latent" in attrs:
            positions += attrs["read_latent"]
    seconds = trace_reduce.time_in(tr["by_op"], ("paged_mla_decode",))
    if seconds <= 0 or not positions:
        return None
    sz = run.model.sizes(run.config, run.rehearse)
    need = run.model.decode_read_bytes(
        sz, positions, run.system["engine"]["cache_dtype"])
    return 100.0 * need / seconds \
        / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
