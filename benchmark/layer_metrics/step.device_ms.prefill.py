"""Device milliseconds of one execution of a prefill program (every
`jit_serve_prefill*`: the bucketed and the context ones) on chip 0,
seconds over executions wholly inside the traced window."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.module_ms(run, "jit_serve_prefill")
