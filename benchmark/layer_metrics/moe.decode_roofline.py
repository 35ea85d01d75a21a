"""The expert layers' share of the HBM roofline in the decode steps of
the traced part: the expert weights those steps had to read — the
program's counter `serve_moe_experts_read_total` (the experts given at
least one pair, summed over the expert layers and the steps; its delta
over the traced part) times models/<family>.py `expert_bytes` (gate, up
and down: 3 x 2,048 x 1,536 x 2 B at the published widths) — over the
self time of block `moe` in the `jit_serve_decode` executions of chip 0
(`harness/xing_serve_runner.py by_block_by_program`), as a share of the
chip's peak HBM bytes/s. A decode step at 8 pairs an expert is bound by
those bytes; the block's time also holds the router, the sort and the
gathers. Nothing where the program has no such counter (its parent),
the family no such function, or the run no trace (or a CPU's)."""
from benchmark.harness import peaks


def read(run):
    sec = run.counts.get("moe_decode_device_s")
    experts = run.counts.get("traced.serve_moe_experts_read_total")
    if not run.trace or not sec or not experts or run.rehearse \
            or not hasattr(run.model, "expert_bytes"):
        return None
    sz = run.model.sizes(run.config, run.rehearse)
    need = experts * run.model.expert_bytes(sz, run.system["weights_dtype"])
    return 100.0 * need / sec \
        / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
