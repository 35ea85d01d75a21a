"""Device milliseconds of one execution of the decode program
(`jit_serve_decode`) on chip 0, over its executions wholly inside the
traced window."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.module_ms(run, "jit_serve_decode")
