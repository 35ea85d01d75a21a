"""Share of chip 0's busy time in the traced part that went to the
short convolutions: the self time of the instructions whose block is
`conv` (the in-projection, the gating, the three taps with the state's
read and write, the out-projection) in EVERY program of the traced part,
chunks and decode steps both, over busy time, %. The split is
`harness/xing_serve_runner.py by_block_by_program` (an instruction's
name is unique in its program only, so each event resolves through the
scope index of the program execution that holds it). Nothing where the
run has no trace or the program no `conv` block."""


def read(run):
    tr = run.trace
    sec = run.counts.get("conv_device_s")
    if not tr or not sec or not tr.get("busy_s_device0"):
        return None
    return 100.0 * sec / tr["busy_s_device0"]
