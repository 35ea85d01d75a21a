"""Median milliseconds of `serve.decode.readback`: the host blocked in
`np.asarray(toks)` until the chip has the decode step's tokens."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.median_ms(run, "serve.decode.readback")
