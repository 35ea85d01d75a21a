"""Runner `lfm2_serve`: a model that keeps a state a slot beside its
pages (gated short convolutions beside GQA attention) and every expert
held, behind `inference.create_serving_engine`, under the traffic of a
mix — the window is `serve_runner._drive`, the set-up and the probe are
this file's.

Set-up (counted in `setup_s`): the model built in the cell's weight
dtype from `--seed` (the family is imported before anything is drawn:
a program without it fails at once), the engine through the public
entry point with chunked prefill at the cell's chunk, every serving
program compiled or loaded from the cache (`warmup()`), the probe
below, each program executed once, then `warm_s` seconds of the mix.
Window: `--seconds` of that traffic going on.

The probe, at the timed sizes: ONE prompt into `slot_lens` slots of the
engine's OWN cache (pages from its allocator, the state array's rows of
those slots), each slot a cut of the prompt, through the engine's own
forward at its prefill shapes (1 x bucket rows: the plain path at
position 0, the context path after; a cut chunk's padded tail behind
its state write), then decode steps at its decode shape with all those
slots live at their different lengths. Slot 0, the whole prompt,
against the reference's full forward over the same tokens on the same
weights: the last row's logits after each chunk and step, each row's
chosen experts (judged, then taken over). Of every live slot's last
rows, the router's scores ANEW in float32 from the system's own
operand. And, in every run, two CONTROLS through the same checks, which
have to refuse them: the reference in bfloat16 throughout, and the
reference whose short convolutions start from zero at every chunk and
decode step (a system that carried no state).

After the window this file checks that both kinds of row ran (fresh
rows at position 0 and rows from a carried state, the fresh ones
exactly the window's first chunks), and in a traced run splits chip 0's
self time by block over EVERY program of the traced part
(`xing_serve_runner.by_block_by_program`) for `conv.device_pct` and
`moe.decode_roofline`.
"""

from __future__ import annotations

import time

from . import program_spans, serve_runner
from .glm_serve_runner import _counters
from .loadgen import ServeTraffic
from .result import BenchFailure, Run, rel_err, say
from .xing_serve_runner import by_block_by_program

#: `--rehearse`: an engine, a chunk and a probe a toy model on the CPU
#: can serve (the probe's second slot is cut inside a chunk)
_REHEARSE_ENGINE = dict(max_batch_slots=8, block_size=4, max_context_len=64,
                        num_pages=None, prefill_buckets=(4, 8),
                        batch_buckets=(1, 4), cache_dtype="float32",
                        prefill_token_budget=8)
_REHEARSE_CHUNK = 8
#: a toy model in float32 reads ~1e-6 in logits; the conv-reset control
#: moves them by far more
_REHEARSE_PROBE = dict(probe_prompt_len=16, slot_lens=[16, 13, 8, 4],
                       logits_rel_tol=0.005)
_REHEARSE_SCALE = dict(prompt_div=128, prompt_max=40, output_div=16,
                       output_max=16)


def probe_system(eng, model, ids, lens, steps: int) -> dict:
    """The prompt `ids[:lens[j]]` into slot j in chunks, then `steps`
    decode steps with every such slot live, slot j fed `ids[lens[j] +
    s]`. Returns what `judge_probe` takes. Of SLOT 0, the whole prompt:
    `rows` and `logits` (a sample a chunk and a decode step: the last
    row's position in `ids`, its logits), `starts` (the first position
    of each of its programs: where a system that carried no state would
    start its convolutions again) and `routing` (every row's chosen
    experts, an expert layer). Of EVERY live slot's last rows:
    `router_probe` (a list of samples an expert layer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sc, chunk, n = eng.config, eng._chunk, len(lens)
    cache, slots = eng.cache, sc.max_batch_slots
    total = lens[0] + steps

    def tapped(params, tokens, pools, tbl, pos, real, ctx, live):
        model.taps = {}
        try:
            logits, pools, _ = eng._forward(params, tokens, pools, tbl, pos,
                                            ctx=ctx, lens=real)
            taps = model.taps
        finally:
            model.taps = None
        last = jnp.take_along_axis(
            logits, (real - 1)[:, None, None], axis=1)[:live, 0]
        return (last.astype(jnp.float32),
                [r[:live] for r in taps["router_topk"]],
                [{k: v[:live] for k, v in d.items()}
                 for d in taps["router_probe"]], pools)

    first = jax.jit(lambda p, pools, tbl, t, pos, real: tapped(
        p, t, pools, tbl, pos, real, False, 1), donate_argnums=(1,))
    later = jax.jit(lambda p, pools, tbl, t, pos, real: tapped(
        p, t, pools, tbl, pos, real, True, 1), donate_argnums=(1,))
    decode = jax.jit(lambda p, pools, tbl, t, pos, real: tapped(
        p, t, pools, tbl, pos, real, False, n), donate_argnums=(1,))
    got = {"rows": [], "logits": [], "starts": [], "routing": None,
           "router_probe": None}

    def keep(out, starts, n_rows, record=True):
        logits, topk, rprobe, pools = out
        cache.update(*pools)
        if not record:
            return
        if got["routing"] is None:
            got["routing"] = [np.zeros((total, r.shape[-1]), np.int32)
                              for r in topk]
            got["router_probe"] = [[] for _ in rprobe]
        for mine, theirs in zip(got["routing"], topk):
            mine[starts[0]:starts[0] + n_rows] = np.asarray(
                theirs[0])[:n_rows]
        got["rows"].append(starts[0] + n_rows - 1)
        got["starts"].append(starts[0])
        got["logits"].append(logits[0])
        for j in range(len(starts)):
            for mine, theirs in zip(got["router_probe"], rprobe):
                mine.append({k: v[j] for k, v in theirs.items()})

    for j, plen in enumerate(lens):
        if not cache.alloc_slot(j, plen + steps):
            raise BenchFailure(f"the probe's slot {j} found no pages")
    try:
        for j, plen in enumerate(lens):
            for at in range(0, plen, chunk):
                clen = min(chunk, plen - at)
                bucket = min(b for b in sc.prefill_buckets if b >= clen)
                if clen < bucket and j == 0:
                    raise BenchFailure("slot 0's prompt is not whole buckets")
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :clen] = ids[at:at + clen]
                keep((later if at else first)(
                    eng.params, cache.pool_args(), cache.table_array([j]),
                    jnp.asarray(toks), jnp.full((1,), at, jnp.int32),
                    jnp.full((1,), clen, jnp.int32)),
                    [at], clen, record=j == 0)
        for s in range(steps):
            toks = np.zeros((slots, 1), np.int32)
            pos = np.zeros((slots,), np.int32)
            for j, plen in enumerate(lens):
                toks[j, 0], pos[j] = ids[plen + s], plen + s
            rows = list(range(n)) + [None] * (slots - n)
            keep(decode(eng.params, cache.pool_args(),
                        cache.table_array(rows), jnp.asarray(toks),
                        jnp.asarray(pos), jnp.ones((slots,), jnp.int32)),
                 [plen + s for plen in lens], 1)
    finally:
        for j in range(n):
            cache.free_slot(j)
    return got


def control_system(reference, weights, ids, sz, got, **how) -> dict:
    """What `probe_system` returns, of the reference computed as `how`
    says (`dtype=`, `conv_from=`): a system the checks have to refuse."""
    rows = got["rows"]
    out = reference.forward(weights, ids, sz, rows=rows, **how)
    return {"rows": list(rows), "starts": list(got["starts"]),
            "logits": list(out["logits"]), "routing": out["routing"],
            "router_probe": [[{k: v[i] for k, v in d.items()}
                              for i in range(len(rows))]
                             for d in out["router_probe"]]}


def judge_probe(check, tol, got, reference, weights, ids, sz) -> dict:
    """A system's probe `got` against the reference in float32, which
    judges the chosen experts by its own scores, goes on with the
    system's (reference/lfm2_moe.py says why), and computes the router's
    scores anew from the system's own operands. `check(name, ok,
    detail)` is called once a check; returns the readings."""
    import jax.numpy as jnp
    import numpy as np
    ref = reference.forward(weights, ids, sz, rows=got["rows"],
                            forced={"routing": got["routing"]})
    errs = [rel_err(g, w) for g, w in zip(got["logits"], ref["logits"])]
    rou = [{k: float(v) for k, v in j.items()} for j in ref["routing_judged"]]
    experts = [f"layers.{li}.moe.router.weight"
               for li, m in enumerate(sz["mlp_layer_types"]) if m != "dense"]
    router_err = lambda smp, w: float(jnp.max(jnp.abs(
        smp["scores"].astype(jnp.float32)
        - reference.router_scores_of(smp["x"][None], weights[w])[0])))
    rtr = [max(router_err(smp, w) for smp in layer)
           for layer, w in zip(got["router_probe"], experts)]
    n_rows = got["routing"][0].shape[0]
    check("reference_logits",
          all(np.isfinite(e) and e <= tol["logits_rel_tol"] for e in errs),
          f"last-row logits of {len(errs)} programs (rows {got['rows']}), "
          f"the reference going on with the system's chosen experts: "
          f"max|diff|/max|ref| = {[f'{e:.2e}' for e in errs]} "
          f"(tol {tol['logits_rel_tol']:g})")
    check("reference_routing",
          all(j["sizes_equal"]
              and j["min_overlap"] >= tol["router_min_overlap"]
              and j["worst_miss"] <= tol["router_margin"] for j in rou),
          f"the chosen experts of each of {n_rows} rows, an expert layer: "
          f"{rou} (min overlap {tol['router_min_overlap']:g}, margin "
          f"{tol['router_margin']:g} on s + b)")
    check("reference_router_scores",
          all(np.isfinite(e) and e <= tol["router_score_tol"] for e in rtr),
          f"sigmoid scores of {len(got['router_probe'][0])} rows against "
          f"float32 from the same operand, an expert layer: max|diff| = "
          f"{[f'{e:.2e}' for e in rtr]} (tol {tol['router_score_tol']:g})")
    return {"rows": [int(r) for r in got["rows"]], "logits_rel_err": errs,
            "routing_judged": rou, "router_score_err": rtr}


def probe_against_reference(run: Run, eng, model, reference, sz,
                            vocab: int) -> None:
    """The system's probe through `judge_probe` into `run.check`; then
    the two controls through the same function, which has to refuse
    each by the checks the cell names for it."""
    import jax.numpy as jnp
    import numpy as np
    tol = dict(run.system["correct"])
    if run.rehearse:
        tol.update(_REHEARSE_PROBE)
    plen, steps = int(tol["probe_prompt_len"]), int(tol["decode_steps"])
    lens = [int(n) for n in tol["slot_lens"]]
    if lens[0] != plen or plen % eng._chunk:
        raise BenchFailure(f"probe prompt {plen}: slot 0 takes all of it, in "
                           f"whole chunks of {eng._chunk}")
    rng = np.random.default_rng([run.seed, 11])
    ids = rng.integers(0, vocab, (plen + steps,)).astype(np.int32)
    got = probe_system(eng, model, ids, lens, steps)
    run.notes["reference"] = dict(
        judge_probe(run.check, tol, got, reference, eng.params, ids, sz),
        probe_prompt_len=plen, decode_steps=steps, slot_lens=lens)
    for name, how, refusing in (
            ("low_precision", dict(dtype=jnp.dtype(tol["control_dtype"])),
             tol["control_refused_by"]),
            ("conv_reset", dict(conv_from=got["starts"]),
             tol["reset_control_refused_by"])):
        verdict = {}
        low = control_system(reference, eng.params, ids, sz, got, **how)
        readings = judge_probe(
            lambda check, ok, detail: verdict.update({check: ok}),
            tol, low, reference, eng.params, ids, sz)
        run.notes["control." + name] = dict(readings, passed=verdict)
        run.check(f"control_refused.{name}",
                  not any(verdict[c] for c in refusing),
                  f"the reference, {name}, through the same checks (ok?): "
                  f"{verdict}; each of {refusing} has to refuse it. Its "
                  f"readings: logits {max(readings['logits_rel_err']):.3g}, "
                  f"router scores {max(readings['router_score_err']):.3g}")


def execute_each_program_once(eng) -> None:
    """Every resident serving program executed once before the mix, on
    the arguments it was compiled against: every row padded, so K and V
    go to the scratch page and the state to slot 0's row, which no
    admission reads (a row at position 0 reads zeros). A program's FIRST
    execution also reserves its temporaries, after the probe's reference
    has held the memory: in a cold run one warm-phase step took over a
    second there and `serve_runner._drive`'s stall dump crashed the
    process (exit 139, on a TPU v5e). The probe's closures are
    collected first."""
    import gc
    import jax
    gc.collect()
    build = {"decode": lambda key: eng._decode_program(),
             "prefill": lambda key: eng._prefill_program(*key[1:]),
             "prefill_ctx": lambda key: eng._prefill_ctx_program(*key[1:])}
    for key, prog in list(eng._programs.items()):
        _, args = build[key[0]](key)
        out = prog(*args)
        eng.cache.update(*out[2])
        jax.block_until_ready(out)


def first_chunks(run: Run) -> int:
    """Rows at position 0 in the program's `serve.prefill` spans that
    began inside the window (their `ctx` attribute: each row's first
    position); None where the program keeps no such span."""
    win = program_spans.window(run)
    if win is None:
        return None
    n = 0
    for rec in program_spans.records(run, "serve.prefill"):
        attrs = rec[program_spans.ATTRS] or {}
        if rec[program_spans.T0] >= win[0] and "ctx" in attrs:
            n += sum(1 for at in attrs["ctx"] if int(at) == 0)
    return n


def run(run: Run, ledger, reference) -> None:
    import jax
    from paddle_tpu import inference
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.ops import pallas as pallas_ops
    from paddle_tpu.serving import ServingConfig

    mix, sysc, fam = run.mix, run.system, run.model
    sz = fam.sizes(run.config, run.rehearse)
    vocab = sz["vocab_size"]
    pallas_ops.reset_pallas_stats()

    t = time.perf_counter()
    dtype = "float32" if run.rehearse else sysc["weights_dtype"]
    model = fam.build_model(run.config, run.seed, rehearse=run.rehearse,
                            dtype=dtype)
    jax.block_until_ready([p._data for p in model.parameters()])
    say(f"  model built in {time.perf_counter() - t:.1f}s ({dtype})")

    eng_kw = dict(sysc["engine"])
    chunk = int(sysc["prefill_chunk"])
    if run.rehearse:
        eng_kw.update(_REHEARSE_ENGINE)
        chunk = _REHEARSE_CHUNK
    for key in ("prefill_buckets", "batch_buckets"):
        eng_kw[key] = tuple(eng_kw[key])
    with flag_scope("serve_prefill_chunk", chunk):
        eng = inference.create_serving_engine(model, ServingConfig(**eng_kw))
    try:
        t = time.perf_counter()
        n_prog = eng.warmup([(nb, sp) for nb in eng_kw["batch_buckets"]
                             for sp in eng_kw["prefill_buckets"]])
        say(f"  {n_prog} serving programs resident after warmup "
            f"({time.perf_counter() - t:.1f}s): plain and context prefill "
            f"{eng_kw['prefill_buckets']} x batch {eng_kw['batch_buckets']} + "
            f"decode; chunk {chunk}; weights {dtype}, cache "
            f"{eng_kw['cache_dtype']}; pages {eng.cache.allocator.num_pages}")
        t = time.perf_counter()
        probe_against_reference(run, eng, model, reference, sz, vocab)
        say(f"  reference probe took {time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        execute_each_program_once(eng)
        say(f"  each program executed once ({time.perf_counter() - t:.1f}s)")
        run.counts["slots"] = eng.config.max_batch_slots
        run.counts["kv_bytes_per_token"] = fam.kv_bytes_per_token(
            sz, eng_kw["cache_dtype"])
        run.counts["state_bytes_per_slot"] = fam.state_bytes_per_slot(
            sz, eng_kw["cache_dtype"])
        if run.counts["kv_bytes_per_token"] != eng.cache.kv_bytes_per_token() \
                or run.counts["state_bytes_per_slot"] \
                != eng.cache.state_bytes_per_slot():
            raise BenchFailure("kv bytes per token or state bytes per slot: "
                               "the benchmark's arithmetic and the engine's "
                               "disagree")
        traffic = ServeTraffic(mix, vocab, run.seed,
                               _REHEARSE_SCALE if run.rehearse else None)

        # the engine's counters when the window opens (`_drive` notes
        # `setup_s` at that moment, before the window's first step), and
        # when the profiler starts (before the traced part's first step)
        opened, traced = {}, {}
        step = eng.step

        def stepping(*a, **kw):
            if not opened and "setup_s" in run.e2e:
                opened.update(_counters(eng))
            if run.traced and "trace_t0" not in run.counts \
                    and jax.profiler.TraceAnnotation.is_enabled():
                run.counts["trace_t0"] = time.perf_counter()
                traced.update(_counters(eng))
            return step(*a, **kw)

        eng.step = stepping
        serve_runner._drive(run, ledger, eng, traffic,
                            jax.devices()[:run.chips])
        closed = _counters(eng)
        for name, v in closed.items():
            run.counts["window." + name] = v - opened.get(name, 0)
        win = lambda name: run.counts.get("window." + name, 0)
        if traced:
            run.counts["traced.serve_moe_experts_read_total"] = (
                closed.get("serve_moe_experts_read_total", 0)
                - traced.get("serve_moe_experts_read_total", 0))
        if run.traced and run.trace:
            split = by_block_by_program(run)
            run.notes["by_block_by_program"] = split
            run.counts["conv_device_s"] = sum(
                blocks.get("conv", 0.0) for blocks in split.values())
            run.counts["moe_decode_device_s"] = sum(
                blocks.get("moe", 0.0) for module, blocks in split.items()
                if module.startswith("jit_serve_decode"))

        fallbacks = {f"{k[0]}:{k[1]}": v
                     for k, v in pallas_ops.PALLAS_STATS.items()}
        run.notes["pallas_fallbacks"] = fallbacks
        fresh = win("serve_conv_state_fresh_total")
        carried = {p: win("serve_conv_state_carried_total{program=%s}" % p)
                   for p in ("prefill_ctx", "decode")}
        firsts = first_chunks(run)
        run.counts["first_chunks"] = firsts
        run.check("no_preemption", run.counts["preemptions"] == 0,
                  f"{run.counts['preemptions']} in the window")
        run.check("state_path_live",
                  fresh > 0 and all(v > 0 for v in carried.values())
                  and fresh == firsts,
                  f"the window's rows from a fresh state: {fresh} (its first "
                  f"chunks, by the prefill spans: {firsts}); from a carried "
                  f"state: {carried}")
        run.check("every_expert_held",
                  win("serve_moe_skipped_pairs_total") == 0
                  and win("serve_moe_experts_read_total") > 0,
                  f"the decode steps of the window read "
                  f"{win('serve_moe_experts_read_total')} experts (over the "
                  f"layers) and skipped {win('serve_moe_skipped_pairs_total')}"
                  f" pairs (every expert is held: 0)")
        if not run.rehearse:
            row = {r["kernel"]: r for r in pallas_ops.kernels()}["paged_decode"]
            run.check("paged_decode_live",
                      row["live"] and "paged_decode" in
                      eng._get_decode().compiled.as_text(), str(row))
            run.check("no_unexpected_fallback",
                      set(fallbacks) <= set(sysc["expect"]["fallbacks"]),
                      f"recorded {fallbacks}")
    finally:
        eng.shutdown()
