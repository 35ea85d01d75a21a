"""Device milliseconds one execution of the step program takes on chip 0
(its `XLA Modules` events wholly inside the traced window, mean)."""
from benchmark.harness import trace_reduce


def read(run):
    prog = run.trace and trace_reduce.main_program(run.trace)
    return prog[0] * 1e3 if prog else None
