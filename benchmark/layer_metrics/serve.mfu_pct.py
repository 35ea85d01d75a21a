"""Model FLOPs of the window's prompt and reply tokens over window x
the chip's peak, %: what the tokens NEED (models/<family>.py: 2 x the
matmul parameters a token passes, attention over the selected
positions, index scores over the cached ones — by context length), not
what the program executed. Prompt tokens from the program's
`serve.prefill` spans inside the window (their `chunk` and `ctx`
attributes), reply tokens from its decode counters (selected and
available positions a slot-step, window delta)."""
from benchmark.harness import peaks, program_spans


def read(run):
    fam, win = run.model, program_spans.window(run)
    if win is None or not hasattr(fam, "prefill_flops"):
        return None
    sz = fam.sizes(run.config, run.rehearse)
    flops = 0.0
    for rec in program_spans.inside(
            program_spans.records(run, "serve.prefill"), win):
        attrs = rec[program_spans.ATTRS] or {}
        if "chunk" not in attrs:
            return None
        flops += sum(fam.prefill_flops(sz, int(pos), int(n))
                     for n, pos in zip(attrs["chunk"], attrs["ctx"]))
    steps = run.counts.get("decode_slot_steps", 0)
    flops += steps * 2.0 * fam.matmul_params_per_token(sz, True)
    flops += fam.attention_flops(
        sz, run.counts.get("window.serve_dsa_selected_total", 0),
        run.counts.get("window.serve_dsa_available_total", 0))
    if flops <= 0 or run.rehearse:
        return None
    peak = peaks.peaks_for(run.device_kind)["flops_per_s"] * run.chips
    return 100.0 * flops / (win[1] - win[0]) / peak
