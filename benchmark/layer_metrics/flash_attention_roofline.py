"""FLOPs the flash forward and backward need for the steps the traced
window held (models/<family>.py `flash_flops_per_step`, this chip's
share; steps that straddle the window's edges counted as parts) over the
device time of the `flash_fwd` + `flash_bwd` kernels on chip 0, as a
share of the chip's peak FLOP/s. Compute-bound: the kernel's bytes (q, k,
v, o once) are two orders under its FLOPs at S=1024."""
from benchmark.harness import peaks, trace_reduce


def read(run):
    prog = run.trace and trace_reduce.main_program(run.trace)
    if not prog:
        return None
    seconds = trace_reduce.time_in(run.trace["by_op"], ("flash_fwd", "flash_bwd"))
    if seconds <= 0:
        return None
    flops = run.counts["flash_flops_per_step"] * prog[1] / run.chips
    return 100.0 * flops / seconds / peaks.peaks_for(run.device_kind)["flops_per_s"]
