"""FLOPs the flash forward and backward need for the steps of the traced
window (models/<family>.py `flash_flops_per_step`, this chip's share)
over the device time of the `flash_fwd` + `flash_bwd` kernels on chip 0,
as a share of the chip's peak FLOP/s. Compute-bound: the kernel's bytes
(q, k, v, o once) are two orders under its FLOPs at S=1024."""
from benchmark.harness import peaks, trace_reduce


def read(run):
    tr = run.trace
    steps = tr and tr["span_counts"].get("bench.step_call")
    if not steps:
        return None
    seconds = trace_reduce.time_in(tr["by_op"], ("flash_fwd", "flash_bwd"))
    if seconds <= 0:
        return None
    flops = run.counts["flash_flops_per_step"] * steps / run.chips
    return 100.0 * flops / seconds / peaks.peaks_for(run.device_kind)["flops_per_s"]
