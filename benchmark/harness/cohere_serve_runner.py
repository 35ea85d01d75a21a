"""Runner `cohere_serve`: a model whose pages live two lifetimes (a full
layer's as long as the slot, a window layer's as long as a query can
reach them) behind `inference.create_serving_engine`, under the traffic
of a mix — the window is `serve_runner._drive`, the set-up and the probe
are this file's.

Set-up (counted in `setup_s`): the model built in the cell's weight
dtype from `--seed`, the engine through the public entry point with
chunked prefill at the cell's chunk, every serving program compiled or
loaded from the cache (`warmup()`), the probe below, then `warm_s`
seconds of the mix. Window: `--seconds` of that traffic going on.

The probe, at the timed sizes: ONE prompt into `probe_slots` slots of
the engine's OWN cache — pages from its allocators, both lifetimes'
tables kept by `cache.advance` as the engine keeps them, so that window
pages HAVE been freed by the time the later chunks run — each slot a
cut of the prompt, through the engine's own forward at its prefill
shape (1 x chunk rows: the plain path at position 0, the context path
after) and then decode steps at its decode shape with all those slots
live at their different lengths. Slot 0, the whole prompt, against the
reference's full forward over the same tokens on the same weights: the
last row's logits after each chunk and step, each row's chosen experts,
the last row's attention before `W_o` in each layer; every live slot's
router scores against float32 from its own operand. And, in every run,
two CONTROLS through the same checks, which have to refuse them: the
reference in bfloat16 throughout, and the reference with the window
taken off its window layers.
"""

from __future__ import annotations

import time

from . import serve_runner
from .glm_serve_runner import _counters
from .loadgen import ServeTraffic
from .result import BenchFailure, Run, rel_err, say

#: `--rehearse`: an engine, a chunk and a probe a toy model on the CPU
#: can serve, with contexts past its window of 8
_REHEARSE_ENGINE = dict(max_batch_slots=8, block_size=4, max_context_len=64,
                        num_pages=None, prefill_buckets=(8,),
                        batch_buckets=(1,), cache_dtype="float32",
                        prefill_token_budget=8)
_REHEARSE_CHUNK = 8
_REHEARSE_PROBE = dict(probe_prompt_len=40, slot_lens=[40, 24, 16, 4])
_REHEARSE_SCALE = dict(prompt_div=512, prompt_max=40, output_div=128,
                       output_max=16)


def probe_system(eng, model, ids, lens, steps: int) -> dict:
    """The prompt `ids[:lens[j]]` into slot j in chunks, then `steps`
    decode steps with every such slot live, slot j fed `ids[lens[j] +
    s]`. Returns what `judge_probe` takes. Of SLOT 0, the whole prompt:
    `rows` and `logits` (a sample a chunk and a decode step: the last
    row's position in `ids`, its logits), `routing` (every row's chosen
    experts, a layer) and `attn_out` (a list a layer of the samples'
    attention rows before `W_o`). Slot 0 alone, because a row has to
    have ONE version: a shorter slot's decode step computes anew a row
    that slot 0 prefilled, with choices of its own. Of EVERY live slot:
    `router_probe` (a list of samples a layer). And `window_entries`,
    the live entries of slot 0's window tables when the probe ends."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sc, chunk, n = eng.config, eng._chunk, len(lens)
    cache, slots = eng.cache, sc.max_batch_slots
    total = lens[0] + steps

    def tapped(params, tokens, pools, tbl, pos, ctx, live):
        model.taps = {}
        try:
            logits, pools, _ = eng._forward(params, tokens, pools, tbl, pos,
                                            ctx=ctx)
            taps = model.taps
        finally:
            model.taps = None
        return (logits[:live, -1].astype(jnp.float32),
                [r[:live] for r in taps["router_topk"]],
                [{k: v[:live] for k, v in d.items()}
                 for d in taps["router_probe"]],
                [a[:live].astype(jnp.float32) for a in taps["attn_out"]],
                pools)

    first = jax.jit(lambda p, pools, tbl, t, pos: tapped(
        p, t, pools, tbl, pos, False, 1), donate_argnums=(1,))
    later = jax.jit(lambda p, pools, tbl, t, pos: tapped(
        p, t, pools, tbl, pos, True, 1), donate_argnums=(1,))
    decode = jax.jit(lambda p, pools, tbl, t, pos: tapped(
        p, t, pools, tbl, pos, False, n), donate_argnums=(1,))
    got = {"rows": [], "logits": [], "routing": None, "attn_out": None,
           "router_probe": None}

    def keep(out, starts, n_rows, record=True):
        logits, topk, rprobe, attn, pools = out
        cache.update(*pools)
        if not record:
            return
        if got["routing"] is None:
            got["routing"] = [np.zeros((total, r.shape[-1]), np.int32)
                              for r in topk]
            got["router_probe"] = [[] for _ in rprobe]
            got["attn_out"] = [[] for _ in attn]
        for mine, theirs in zip(got["routing"], topk):
            mine[starts[0]:starts[0] + n_rows] = np.asarray(
                theirs[0])[:n_rows]
        got["rows"].append(starts[0] + n_rows - 1)
        got["logits"].append(logits[0])
        for mine, theirs in zip(got["attn_out"], attn):
            mine.append(theirs[0])
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
                cache.advance(j, at, clen)
                toks = np.zeros((1, chunk), np.int32)
                toks[0, :clen] = ids[at:at + clen]
                if clen < chunk and j == 0:
                    raise BenchFailure("slot 0's prompt is not whole chunks")
                # a cut chunk's last row is not the row the bucket ends on:
                # its samples are not kept (only slot 0's are, anyway)
                keep((later if at else first)(
                    eng.params, cache.pool_args(), cache.table_array([j]),
                    jnp.asarray(toks), jnp.full((1,), at, jnp.int32)),
                    [at], clen, record=j == 0)
        for s in range(steps):
            toks = np.zeros((slots, 1), np.int32)
            pos = np.zeros((slots,), np.int32)
            for j, plen in enumerate(lens):
                toks[j, 0], pos[j] = ids[plen + s], plen + s
                cache.advance(j, plen + s, 1)
            rows = list(range(n)) + [None] * (slots - n)
            keep(decode(eng.params, cache.pool_args(),
                        cache.table_array(rows), jnp.asarray(toks),
                        jnp.asarray(pos)), [plen + s for plen in lens], 1)
        got["window_entries"] = [w.live_blocks(0) for w in cache.windows]
        got["window_bound"] = [w.pages_per_slot for w in cache.windows]
        got["window_freed"] = [w.freed for w in cache.windows]
    finally:
        for j in range(n):
            cache.free_slot(j)
    return got


def control_system(reference, weights, ids, sz, rows, **how) -> dict:
    """What `probe_system` returns, of the reference computed as `how`
    says (`dtype=`, `windowed=`): a system the checks have to refuse."""
    out = reference.forward(weights, ids, sz, rows=rows, **how)
    return {"rows": list(rows), "logits": list(out["logits"]),
            "routing": out["routing"],
            "attn_out": [list(a) for a in out["attn_out"]],
            "router_probe": [[{k: v[i] for k, v in d.items()}
                              for i in range(len(rows))]
                             for d in out["router_probe"]]}


def judge_probe(check, tol, got, reference, weights, ids, sz) -> dict:
    """A system's probe `got` against the reference in float32, which
    judges the chosen experts by its own scores, goes on with the
    system's (reference/cohere2_moe.py says why), and computes the
    router's scores anew from the system's own operand. `check(name,
    ok, detail)` is called once a check; returns the readings."""
    import jax.numpy as jnp
    import numpy as np
    ref = reference.forward(weights, ids, sz, rows=got["rows"],
                            forced={"routing": got["routing"]})
    errs = [rel_err(g, w) for g, w in zip(got["logits"], ref["logits"])]
    rou = [{k: float(v) for k, v in j.items()} for j in ref["routing_judged"]]
    window = [li for li, t in enumerate(sz["layer_types"])
              if t == "sliding_attention"]
    attn = [max(rel_err(jnp.asarray(g), w)
                for g, w in zip(got["attn_out"][li], ref["attn_out"][li]))
            for li in range(len(sz["layer_types"]))]
    router_err = lambda smp, w: float(jnp.max(jnp.abs(
        smp["scores"].astype(jnp.float32)
        - reference.router_scores_of(smp["x"][None], weights[w])[0])))
    rtr = [max(router_err(smp, f"layers.{li}.moe.router.weight")
               for smp in layer)
           for li, layer in enumerate(got["router_probe"])]
    n_rows = got["routing"][0].shape[0]
    check("reference_logits",
          all(np.isfinite(e) and e <= tol["logits_rel_tol"] for e in errs),
          f"last-row logits of {len(errs)} programs (rows {got['rows']}), "
          f"the reference going on with the system's chosen experts: "
          f"max|diff|/max|ref| = {[f'{e:.2e}' for e in errs]} "
          f"(tol {tol['logits_rel_tol']:g})")
    check("reference_window_attn",
          all(np.isfinite(attn[li]) and attn[li] <= tol["attn_rel_tol"]
              for li in window),
          f"the last rows' attention before W_o, a layer (window layers "
          f"{window}): max|diff|/max|ref| = {[f'{e:.2e}' for e in attn]} "
          f"(tol {tol['attn_rel_tol']:g} on the window layers)")
    check("reference_routing",
          all(j["sizes_equal"]
              and j["min_overlap"] >= tol["router_min_overlap"]
              and j["worst_miss"] <= tol["router_margin"] for j in rou),
          f"the chosen experts of each of {n_rows} rows, a layer: {rou} "
          f"(min overlap {tol['router_min_overlap']:g}, margin "
          f"{tol['router_margin']:g} on s)")
    check("reference_router_scores",
          all(np.isfinite(e) and e <= tol["router_score_tol"] for e in rtr),
          f"sigmoid scores of {len(got['router_probe'][0])} rows against "
          f"float32 from the same operand, a layer: max|diff| = "
          f"{[f'{e:.2e}' for e in rtr]} (tol {tol['router_score_tol']:g})")
    return {"rows": [int(r) for r in got["rows"]], "logits_rel_err": errs,
            "attn_out_rel_err": attn, "routing_judged": rou,
            "router_score_err": rtr}


def probe_against_reference(run: Run, eng, model, reference, sz,
                            vocab: int) -> None:
    """The system's probe through `judge_probe` into `run.check`; then
    the two controls through the same function, which has to refuse
    each by the check the cell names for it."""
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
        probe_prompt_len=plen, decode_steps=steps, slot_lens=lens,
        window_entries=got["window_entries"],
        window_bound=got["window_bound"], window_freed=got["window_freed"])
    run.check("window_pages_bounded",
              all(0 < e <= b for e, b in zip(got["window_entries"],
                                             got["window_bound"]))
              and all(f > 0 for f in got["window_freed"]),
              f"slot 0's window table holds {got['window_entries']} live "
              f"entries after {plen + steps} positions (bound "
              f"{got['window_bound']}: ceil((W + chunk) / block) + 1); "
              f"{got['window_freed']} pages were freed on the way")
    rows = got["rows"]
    del got
    for name, how, refusing in (
            ("low_precision", dict(dtype=jnp.dtype(tol["control_dtype"])),
             tol["control_refused_by"]),
            ("no_window", dict(windowed=False),
             tol["window_control_refused_by"])):
        verdict = {}
        low = control_system(reference, eng.params, ids, sz, rows, **how)
        readings = judge_probe(
            lambda check, ok, detail: verdict.update({check: ok}),
            tol, low, reference, eng.params, ids, sz)
        run.notes["control." + name] = dict(readings, passed=verdict)
        run.check(f"control_refused.{name}",
                  not any(verdict[c] for c in refusing),
                  f"the reference, {how}, through the same checks (ok?): "
                  f"{verdict}; each of {refusing} has to refuse it. Its "
                  f"readings: logits {max(readings['logits_rel_err']):.3g}, "
                  f"attention rows {max(readings['attn_out_rel_err']):.3g}, "
                  f"router scores {max(readings['router_score_err']):.3g}")


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
            f"{eng_kw['cache_dtype']}; pages: slot lifetime "
            f"{eng.cache.allocator.num_pages}, window lifetimes "
            f"{[(w.window, w.pages_per_slot, w.num_pages) for w in eng.cache.windows]}")
        t = time.perf_counter()
        probe_against_reference(run, eng, model, reference, sz, vocab)
        say(f"  reference probe took {time.perf_counter() - t:.1f}s")
        run.counts["slots"] = eng.config.max_batch_slots
        run.counts["kv_bytes_per_token"] = fam.kv_bytes_per_token(
            sz, eng_kw["cache_dtype"])
        if run.counts["kv_bytes_per_token"] != eng.cache.kv_bytes_per_token():
            raise BenchFailure("kv bytes per token: the benchmark's arithmetic "
                               "and the engine's disagree")
        traffic = ServeTraffic(mix, vocab, run.seed,
                               _REHEARSE_SCALE if run.rehearse else None)

        # the engine's counters when the window opens (`_drive` notes
        # `setup_s` at that moment, before the window's first step), and
        # the moment the profiler starts (before the traced part's first)
        opened = {}
        step = eng.step

        def stepping(*a, **kw):
            if not opened and "setup_s" in run.e2e:
                opened.update(_counters(eng))
            if run.traced and "trace_t0" not in run.counts \
                    and jax.profiler.TraceAnnotation.is_enabled():
                run.counts["trace_t0"] = time.perf_counter()
            return step(*a, **kw)

        eng.step = stepping
        serve_runner._drive(run, ledger, eng, traffic,
                            jax.devices()[:run.chips])
        closed = _counters(eng)
        for name, v in closed.items():
            run.counts["window." + name] = v - opened.get(name, 0)

        fallbacks = {f"{k[0]}:{k[1]}": v
                     for k, v in pallas_ops.PALLAS_STATS.items()}
        run.notes["pallas_fallbacks"] = fallbacks
        run.check("no_preemption", run.counts["preemptions"] == 0,
                  f"{run.counts['preemptions']} in the window")
        win = lambda name: run.counts.get("window." + name, 0)
        run.check("both_lifetimes_live",
                  win("serve_kv_pages_live_total{lifetime=slot}") > 0
                  and win("serve_kv_pages_live_total{lifetime=window}") > 0
                  and win("serve_kv_window_pages_freed_total") > 0
                  and any(k.startswith("window.serve_moe_routed_tokens_total")
                          and v > 0 for k, v in run.counts.items()),
                  "the decode steps of the window counted pages held in both "
                  "lifetimes, window pages freed, and tokens routed to held "
                  "experts")
        if not run.rehearse:
            row = {r["kernel"]: r for r in pallas_ops.kernels()}["paged_decode"]
            run.check("paged_decode_live", row["live"] and "paged_decode" in
                      eng._get_decode().compiled.as_text(), str(row))
            run.check("no_unexpected_fallback",
                      set(fallbacks) <= set(sysc["expect"]["fallbacks"]),
                      f"recorded {fallbacks}")
    finally:
        eng.shutdown()
