"""Median milliseconds of one `engine.step()` minus the time it spent
blocked on the chip: the program's `serve.step` span minus its
`.readback` descendants — scheduler, host arrays, transfers, the
execute call, sampling bookkeeping, callbacks. The host's own work."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.minus_descendants_ms(run, "serve.step", ".readback")
