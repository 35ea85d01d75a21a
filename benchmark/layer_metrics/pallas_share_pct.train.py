"""Device time in the program's named Pallas kernels over device busy
time, chip 0, traced window. The names are the program's own
(`paddle_tpu.ops.pallas.kernels()` rows plus the kernels of the flash
and chunked-CE modules as they appear in the HLO)."""
from benchmark.harness import trace_reduce

KERNELS = ("flash_fwd", "flash_bwd", "chunked_ce_lse", "chunked_ce_dlogits",
           "fused_dropout", "paged_decode", "bgmv", "int8_matmul")


def read(run):
    tr = run.trace
    if not tr or tr["busy_s_device0"] <= 0:
        return None
    return 100.0 * trace_reduce.time_in(tr["by_op"], KERNELS) / tr["busy_s_device0"]
