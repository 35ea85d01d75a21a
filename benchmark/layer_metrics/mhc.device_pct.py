"""Share of chip 0's busy time in the traced part that went to the
residual path: the self time of the instructions whose block is `mhc`
(the three mappings' projection, the Sinkhorn iterations, `H_pre X`,
`H_res X + H_post^T y`, the streams' first copy and last sum) in EVERY
program of the traced part, chunks and decode steps both, over busy
time, %. The split is `harness/xing_serve_runner.py
by_block_by_program` (an instruction's name is unique in its program
only, so each event resolves through the scope index of the program
execution that holds it). Nothing where the run has no trace or the
program no `mhc` block."""


def read(run):
    tr = run.trace
    sec = run.counts.get("mhc_device_s")
    if not tr or not sec or not tr.get("busy_s_device0"):
        return None
    return 100.0 * sec / tr["busy_s_device0"]
