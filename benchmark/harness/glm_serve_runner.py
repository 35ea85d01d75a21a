"""Runner `glm_serve`: a model that declares its own page kinds behind
`inference.create_serving_engine`, under the traffic of a mix — the
window is `serve_runner._drive`, the set-up and the probe are this
file's (`serve_runner.run` unpacks a K/V pair).

Set-up (counted in `setup_s`): the model built in the cell's weight
dtype from `--seed`, the engine through the public entry point with
chunked prefill at the cell's chunk, every serving program compiled or
loaded from the cache (`warmup()`), the probe below, then `warm_s`
seconds of the mix. Window: `--seconds` of that traffic going on.

The probe, at the timed sizes: ONE prompt into `probe_slots` slots of
the engine's OWN pools, each slot on pages of its own (interleaved) and
a chunk shorter than the one before, through the engine's own forward
at its chunked-prefill shape (1 x chunk rows, the context path); then
decode steps at its decode shape with all those slots live at their
different lengths — the traced code of the serving programs, which
return a sampled token where the probe's two programs return the last
row's logits, every row's selected set and chosen experts, and the last
row's index and router scores beside the operands they were computed
from. Slot 0, the whole prompt, against the reference's full forward
over the same tokens on the same weights; every live slot's scores
against float32 from its own operands; and, in every run, the CONTROL:
the reference itself in the nearest precision below the
configuration's, which the same checks have to refuse.
"""

from __future__ import annotations

import time

from . import serve_runner
from .loadgen import ServeTraffic
from .result import BenchFailure, Run, rel_err, say

#: `--rehearse`: an engine, a chunk and a probe a toy model on the CPU
#: can serve, with contexts past its index_topk of 8
_REHEARSE_ENGINE = dict(max_batch_slots=8, block_size=4, max_context_len=64,
                        num_pages=None, prefill_buckets=(8,),
                        batch_buckets=(1,), cache_dtype="float32",
                        prefill_token_budget=8)
_REHEARSE_CHUNK, _REHEARSE_PROBE = 8, 24
_REHEARSE_SCALE = dict(prompt_div=640, prompt_max=40, output_div=64,
                       output_max=16)
#: the checks that hold what the configuration states as float32: the
#: control has to be refused by each
_SCORE_CHECKS = ("reference_index_scores", "reference_router_scores")


def probe_system(eng, model, ids, lens, steps: int) -> dict:
    """The prompt `ids[:lens[j]]` into slot j (whole chunks), then
    `steps` decode steps with every such slot live, slot j fed
    `ids[lens[j] + s]`. Returns what `judge_probe` takes. Of SLOT 0,
    the whole prompt: `rows` and `logits` (a sample a chunk and a decode
    step: the last row's position in `ids`, its logits), `selection`
    and `routing` (every row's choice, a layer). Slot 0 alone, because
    a row has to have ONE version: a shorter slot's decode step computes
    anew a row that slot 0 prefilled, with choices of its own at the
    cut, and the reference can go on with one of the two (with both in
    one forward, rows after it read the wrong one's index key: 0.21 of
    a row's range at position 2,048, my chip run, PR 28). Of EVERY live
    slot, whose operands say all there is to say: `probe_rows`,
    `index_probe` and `router_probe` (a list of samples a layer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.serving.kv_cache import blocks_needed
    sc, chunk, n = eng.config, eng._chunk, len(lens)
    total = lens[0] + steps
    slots, mb = sc.max_batch_slots, eng.cache.max_blocks_per_slot
    table = np.zeros((slots, mb), np.int32)        # others: scratch page 0
    for j, plen in enumerate(lens):
        need = blocks_needed(plen + steps, sc.block_size)
        table[j, :need] = 1 + j + n * np.arange(need)
    table = jnp.asarray(table)

    def tapped(params, tokens, pools, tbl, pos, ctx, live):
        model.taps = {"live": live}
        try:
            logits, pools, _ = eng._forward(params, tokens, pools, tbl, pos,
                                            ctx=ctx)
            taps = model.taps
        finally:
            model.taps = None
        ctx_cut = lambda d: {k: v[:live, :total] if k in ("scores", "keys")
                             else v[:live] for k, v in d.items()}
        return (logits[:live, -1].astype(jnp.float32),
                [m[:live, :, :total] for m in taps["selection"]],
                [r[:live] for r in taps["router_topk"]],
                [ctx_cut(d) for d in taps["index_probe"]],
                [{k: v[:live] for k, v in d.items()}
                 for d in taps["router_probe"]], pools)

    prefill = jax.jit(lambda p, pools, row, t, pos: tapped(
        p, t, pools, row, pos, True, 1), donate_argnums=(1,))
    decode = jax.jit(lambda p, pools, t, pos: tapped(
        p, t, pools, table, pos, False, n), donate_argnums=(1,))
    got = {"rows": [], "logits": [], "selection": None, "routing": None,
           "probe_rows": [], "index_probe": None, "router_probe": None}

    def keep(out, starts, record=True):
        logits, sel, topk, iprobe, rprobe, pools = out
        eng.cache.update(*pools)
        if not record:
            return
        if got["selection"] is None:
            got["selection"] = [np.zeros((total, total), bool) for _ in sel]
            got["routing"] = [np.zeros((total, r.shape[-1]), np.int32)
                              for r in topk]
            got["index_probe"] = [[] for _ in iprobe]
            got["router_probe"] = [[] for _ in rprobe]
        last = starts[0] + sel[0].shape[1] - 1
        for mine, theirs in zip(got["selection"] + got["routing"],
                                sel + topk):
            rows = np.asarray(theirs[0])
            mine[starts[0]:starts[0] + rows.shape[0]] = rows
        got["rows"].append(last)
        got["logits"].append(logits[0])
        for j, at in enumerate(starts):
            got["probe_rows"].append(at + sel[0].shape[1] - 1)
            for mine, theirs in zip(got["index_probe"] + got["router_probe"],
                                    iprobe + rprobe):
                mine.append({k: v[j] for k, v in theirs.items()})

    for j, plen in enumerate(lens):
        for at in range(0, plen, chunk):
            keep(prefill(eng.params, eng.cache.pool_args(), table[j:j + 1],
                         jnp.asarray(ids[None, at:at + chunk]),
                         jnp.full((1,), at, jnp.int32)), [at], record=j == 0)
    for s in range(steps):
        toks = np.zeros((slots, 1), np.int32)
        pos = np.zeros((slots,), np.int32)
        for j, plen in enumerate(lens):
            toks[j, 0], pos[j] = ids[plen + s], plen + s
        keep(decode(eng.params, eng.cache.pool_args(), jnp.asarray(toks),
                    jnp.asarray(pos)), [plen + s for plen in lens])
    return got


def control_system(reference, weights, ids, sz, rows, dtype) -> dict:
    """What `probe_system` returns, of the reference computed in
    `dtype` throughout: the system the checks have to refuse."""
    out = reference.forward(weights, ids, sz, rows=rows, dtype=dtype)
    samples = lambda d: [{k: v if k == "keys" else v[i]
                          for k, v in d.items()} for i in range(len(rows))]
    return {"rows": list(rows), "logits": list(out["logits"]),
            "selection": out["selection"], "routing": out["routing"],
            "probe_rows": list(rows),
            "index_probe": [samples(d) for d in out["index_probe"]],
            "router_probe": [samples(d) for d in out["router_probe"]]}


def judge_probe(check, tol, got, reference, weights, ids, sz) -> dict:
    """A system's probe `got` against the reference in float32, which
    judges each discrete choice by its own scores, goes on with the
    system's (reference/glm_moe_dsa.py says why), and computes the
    scores the configuration states as float32 anew from the system's
    own operands. `check(name, ok, detail)` is called once a check;
    returns the readings."""
    import jax.numpy as jnp
    import numpy as np
    ref = reference.forward(weights, ids, sz, rows=got["rows"],
                            forced={"selection": got["selection"],
                                    "routing": got["routing"]})
    errs = [rel_err(g, w) for g, w in zip(got["logits"], ref["logits"])]
    sel = [{k: float(v) for k, v in j.items()} for j in ref["selection_judged"]]
    rou = [{k: float(v) for k, v in j.items()} for j in ref["routing_judged"]]

    def index_err(row, smp):
        """max over s <= row of |I - I anew| over I anew's range."""
        anew = reference.index_scores_of(smp["q"][None], smp["w"][None],
                                         smp["keys"])[0, :row + 1]
        mine = smp["scores"][:row + 1].astype(jnp.float32)
        return float(jnp.max(jnp.abs(mine - anew))
                     / (jnp.max(anew) - jnp.min(anew) + 1e-30))

    experts = [f"layers.{li}.moe.router.weight"
               for li, m in enumerate(sz["mlp_layer_types"]) if m != "dense"]
    router_err = lambda smp, w: float(jnp.max(jnp.abs(
        smp["scores"].astype(jnp.float32)
        - reference.router_scores_of(smp["x"][None], weights[w])[0])))
    idx = [max(index_err(r, smp) for r, smp in zip(got["probe_rows"], layer))
           for layer in got["index_probe"]]
    rtr = [max(router_err(smp, w) for smp in layer)
           for layer, w in zip(got["router_probe"], experts)]
    n_rows = got["selection"][0].shape[0]
    check("reference_logits",
          all(np.isfinite(e) and e <= tol["logits_rel_tol"] for e in errs),
          f"last-row logits of {len(errs)} programs (rows {got['rows']}), "
          f"the reference going on with the system's choices: "
          f"max|diff|/max|ref| = {[f'{e:.2e}' for e in errs]} "
          f"(tol {tol['logits_rel_tol']:g})")
    check("reference_selection",
          all(j["sizes_equal"]
              and j["min_overlap"] >= tol["selection_min_overlap"]
              and j["worst_miss_of_range"] <= tol["selection_margin"]
              for j in sel),
          f"the selected set of each of {n_rows} rows, a `full` layer: "
          f"{sel} (min overlap {tol['selection_min_overlap']:g}, margin "
          f"{tol['selection_margin']:g} of the chosen scores' range)")
    check("reference_routing",
          all(j["sizes_equal"]
              and j["min_overlap"] >= tol["router_min_overlap"]
              and j["worst_miss"] <= tol["router_margin"] for j in rou),
          f"the chosen experts of each of {n_rows} rows, an expert layer: "
          f"{rou} (min overlap {tol['router_min_overlap']:g}, margin "
          f"{tol['router_margin']:g} on s + b)")
    check("reference_index_scores",
          all(np.isfinite(e) and e <= tol["index_score_tol"] for e in idx),
          f"I(t, .) of {len(got['probe_rows'])} rows against float32 from the "
          f"same queries, weights and cached keys, a `full` layer: max|diff| "
          f"over the row's range = {[f'{e:.2e}' for e in idx]} "
          f"(tol {tol['index_score_tol']:g})")
    check("reference_router_scores",
          all(np.isfinite(e) and e <= tol["router_score_tol"] for e in rtr),
          f"sigmoid scores of {len(got['probe_rows'])} rows against float32 "
          f"from the same operand, an expert layer: max|diff| = "
          f"{[f'{e:.2e}' for e in rtr]} (tol {tol['router_score_tol']:g})")
    return {"rows": [int(r) for r in got["rows"]],
            "probe_rows": [int(r) for r in got["probe_rows"]],
            "logits_rel_err": errs,
            "selection_judged": sel, "routing_judged": rou,
            "index_score_err": idx, "router_score_err": rtr}


def probe_against_reference(run: Run, eng, model, reference, sz,
                            vocab: int) -> None:
    """The system's probe through `judge_probe` into `run.check`; then
    the control through the same function, which has to refuse it."""
    import jax.numpy as jnp
    import numpy as np
    tol = run.system["correct"]
    chunk = eng._chunk
    plen = _REHEARSE_PROBE if run.rehearse else int(tol["probe_prompt_len"])
    steps = int(tol["decode_steps"])
    if plen % chunk:
        raise BenchFailure(f"probe prompt {plen} is not whole chunks of {chunk}")
    n = min(int(tol["probe_slots"]), plen // chunk)
    lens = [plen - j * chunk for j in range(n)]
    rng = np.random.default_rng([run.seed, 11])
    ids = rng.integers(0, vocab, (plen + steps,)).astype(np.int32)
    got = probe_system(eng, model, ids, lens, steps)
    run.notes["reference"] = dict(
        judge_probe(run.check, tol, got, reference, eng.params, ids, sz),
        probe_prompt_len=plen, decode_steps=steps, slot_lens=lens)
    rows = got["rows"]
    del got
    verdict = {}
    low = control_system(reference, eng.params, ids, sz, rows,
                         jnp.dtype(tol["control_dtype"]))
    run.notes["control"] = dict(
        judge_probe(lambda name, ok, detail: verdict.update({name: ok}),
                    tol, low, reference, eng.params, ids, sz),
        dtype=tol["control_dtype"], passed=verdict)
    run.check("control_refused",
              not any(verdict[name] for name in _SCORE_CHECKS),
              f"the reference in {tol['control_dtype']} throughout, through "
              f"the same checks (ok?): {verdict}; each of {_SCORE_CHECKS} "
              f"has to refuse it. Its readings: logits "
              f"{max(run.notes['control']['logits_rel_err']):.3g}, index "
              f"scores {max(run.notes['control']['index_score_err']):.3g}, "
              f"router scores "
              f"{max(run.notes['control']['router_score_err']):.3g}")


def _counters(eng) -> dict:
    """The engine's own counts a window is the difference of."""
    out = dict(eng._stats.get("model_counters") or {})
    out["prefill_tokens"] = eng._stats["prefill_tokens"]
    return out


def run(run: Run, ledger, reference) -> None:
    import jax
    from paddle_tpu import inference
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.ops import pallas as pallas_ops
    from paddle_tpu.serving import ServingConfig

    mix, sysc, fam = run.mix, run.system, run.model
    sz = fam.sizes(run.config, run.rehearse)
    # ids are drawn from the vocabulary's slice, not from its padding
    vocab = sz["vocab_size"] if run.rehearse else run.config["vocab_size"]
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
            f"{eng_kw['cache_dtype']}")
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
        # `setup_s` at that moment, before the window's first step)
        opened = {}
        step = eng.step

        def stepping(*a, **kw):
            if not opened and "setup_s" in run.e2e:
                opened.update(_counters(eng))
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
        run.check("sparse_path_live",
                  run.counts.get("window.serve_dsa_selected_total", 0) > 0
                  and any(k.startswith("window.serve_moe_routed_tokens_total")
                          and v > 0 for k, v in run.counts.items()),
                  "the decode steps of the window counted selected positions "
                  "and tokens routed to held experts")
        if not run.rehearse:
            run.check("no_unexpected_fallback",
                      set(fallbacks) <= set(sysc["expect"]["fallbacks"]),
                      f"recorded {fallbacks}")
    finally:
        eng.shutdown()
