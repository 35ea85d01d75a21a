"""Runner `xing_serve`: a model with a four-stream residual path, ONE
latent page kind read densely and every expert held, behind
`inference.create_serving_engine`, under the traffic of a mix — the
window is `serve_runner._drive`, the set-up and the probe are this
file's.

Set-up (counted in `setup_s`): the model built in the cell's weight
dtype from `--seed`, the engine through the public entry point with
chunked prefill at the cell's chunk, every serving program compiled or
loaded from the cache (`warmup()`), the probe below, then `warm_s`
seconds of the mix. Window: `--seconds` of that traffic going on.

The probe, at the timed sizes: ONE prompt into `slot_lens` slots of the
engine's OWN pool (pages from its allocator), each slot a cut of the
prompt, through the engine's own forward at its prefill shapes (1 x
bucket rows: the plain path at position 0, the context path after) and
then decode steps at its decode shape with all those slots live at
their different lengths. Slot 0, the whole prompt, against the
reference's full forward over the same tokens on the same weights: the
last row's logits after each chunk and step, each row's chosen experts
(judged, then taken over). Of every live slot's last rows, what the
family states as float32, ANEW in float32 from the system's own
operands: the router's scores and every sublayer's `H_res`. And, in
every run, two CONTROLS through the same checks, which have to refuse
them: the reference in bfloat16 throughout, and the reference with
`H_res` = identity.

After the window of a traced run this file also splits chip 0's self
time by block over EVERY program of the traced part
(`by_block_by_program`): `program_spans.by_block` joins the trace with
the scope index of the window's largest program alone, and the mHC
path's share (`mhc.device_pct`) is of chunks and decode steps both.
"""

from __future__ import annotations

import time
from collections import defaultdict

from . import serve_runner, trace_reduce
from .glm_serve_runner import _counters
from .loadgen import ServeTraffic
from .result import BenchFailure, Run, rel_err, say

#: `--rehearse`: an engine, a chunk and a probe a toy model on the CPU
#: can serve
_REHEARSE_ENGINE = dict(max_batch_slots=8, block_size=4, max_context_len=64,
                        num_pages=None, prefill_buckets=(8,),
                        batch_buckets=(1,), cache_dtype="float32",
                        prefill_token_budget=8)
_REHEARSE_CHUNK = 8
#: a toy model in float32 reads 3e-7 in logits, and two layers of 64
#: wide move them by 0.01-0.04 with `H_res` = identity: the limit of the
#: bf16 cell would let that control pass
_REHEARSE_PROBE = dict(probe_prompt_len=16, slot_lens=[16, 12, 8, 4],
                       logits_rel_tol=0.005)
_REHEARSE_SCALE = dict(prompt_div=256, prompt_max=40, output_div=64,
                       output_max=16)


def probe_system(eng, model, ids, lens, steps: int) -> dict:
    """The prompt `ids[:lens[j]]` into slot j in chunks, then `steps`
    decode steps with every such slot live, slot j fed `ids[lens[j] +
    s]`. Returns what `judge_probe` takes. Of SLOT 0, the whole prompt:
    `rows` and `logits` (a sample a chunk and a decode step: the last
    row's position in `ids`, its logits) and `routing` (every row's
    chosen experts, an expert layer). Slot 0 alone, because a row has to
    have ONE version: a shorter slot's decode step computes anew a row
    that slot 0 prefilled, with choices of its own. Of EVERY live slot's
    last rows: `router_probe` (a list of samples an expert layer) and
    `mhc_probe` (a list of samples a sublayer)."""
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
        cut = lambda ds: [{k: v[:live] for k, v in d.items()} for d in ds]
        return (logits[:live, -1].astype(jnp.float32),
                [r[:live] for r in taps["router_topk"]],
                cut(taps["router_probe"]), cut(taps["mhc_probe"]), pools)

    first = jax.jit(lambda p, pools, tbl, t, pos: tapped(
        p, t, pools, tbl, pos, False, 1), donate_argnums=(1,))
    later = jax.jit(lambda p, pools, tbl, t, pos: tapped(
        p, t, pools, tbl, pos, True, 1), donate_argnums=(1,))
    decode = jax.jit(lambda p, pools, tbl, t, pos: tapped(
        p, t, pools, tbl, pos, False, n), donate_argnums=(1,))
    got = {"rows": [], "logits": [], "routing": None, "router_probe": None,
           "mhc_probe": None}

    def keep(out, starts, n_rows, record=True):
        logits, topk, rprobe, mprobe, pools = out
        cache.update(*pools)
        if not record:
            return
        if got["routing"] is None:
            got["routing"] = [np.zeros((total, r.shape[-1]), np.int32)
                              for r in topk]
            got["router_probe"] = [[] for _ in rprobe]
            got["mhc_probe"] = [[] for _ in mprobe]
        for mine, theirs in zip(got["routing"], topk):
            mine[starts[0]:starts[0] + n_rows] = np.asarray(
                theirs[0])[:n_rows]
        got["rows"].append(starts[0] + n_rows - 1)
        got["logits"].append(logits[0])
        for j in range(len(starts)):
            for mine, theirs in zip(got["router_probe"] + got["mhc_probe"],
                                    rprobe + mprobe):
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
                # a cut chunk's last row is not the row the bucket ends
                # on: its samples are not kept (only slot 0's are, anyway)
                keep((later if at else first)(
                    eng.params, cache.pool_args(), cache.table_array([j]),
                    jnp.asarray(toks), jnp.full((1,), at, jnp.int32)),
                    [at], clen, record=j == 0)
        for s in range(steps):
            toks = np.zeros((slots, 1), np.int32)
            pos = np.zeros((slots,), np.int32)
            for j, plen in enumerate(lens):
                toks[j, 0], pos[j] = ids[plen + s], plen + s
            rows = list(range(n)) + [None] * (slots - n)
            keep(decode(eng.params, cache.pool_args(),
                        cache.table_array(rows), jnp.asarray(toks),
                        jnp.asarray(pos)), [plen + s for plen in lens], 1)
    finally:
        for j in range(n):
            cache.free_slot(j)
    return got


def control_system(reference, weights, ids, sz, rows, **how) -> dict:
    """What `probe_system` returns, of the reference computed as `how`
    says (`dtype=`, `h_res=`): a system the checks have to refuse."""
    out = reference.forward(weights, ids, sz, rows=rows, **how)
    samples = lambda ds: [[{k: v[i] for k, v in d.items()}
                           for i in range(len(rows))] for d in ds]
    return {"rows": list(rows), "logits": list(out["logits"]),
            "routing": out["routing"],
            "router_probe": samples(out["router_probe"]),
            "mhc_probe": samples(out["mhc_probe"])}


def judge_probe(check, tol, got, reference, weights, ids, sz) -> dict:
    """A system's probe `got` against the reference in float32, which
    judges the chosen experts by its own scores, goes on with the
    system's (reference/xing4.py says why), and computes the router's
    scores and every sublayer's `H_res` anew from the system's own
    operands. `check(name, ok, detail)` is called once a check; returns
    the readings."""
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
    subs = [f"layers.{li}.{which}_hc."
            for li in range(len(sz["mlp_layer_types"]))
            for which in ("attn", "ffn")]
    hc = dict(n=sz["hc_mult"], iters=sz["hc_sinkhorn_iters"],
              eps=sz["hc_eps"], clamp=tuple(sz["mhc_h_res_clamp"]))

    def h_res_err(layer, p):
        """max |H_res - H_res anew| over a sublayer's samples."""
        x = jnp.stack([smp["x"] for smp in layer])
        mine = jnp.stack([smp["h_res"] for smp in layer]).astype(jnp.float32)
        anew = reference.mhc_mappings_of(
            x, weights[p + "phi"], weights[p + "alpha"], weights[p + "b"],
            **hc)[2]
        return float(jnp.max(jnp.abs(mine - anew)))

    mhc = [h_res_err(layer, p) for layer, p in zip(got["mhc_probe"], subs)]
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
    check("reference_h_res",
          all(np.isfinite(e) and e <= tol["h_res_tol"] for e in mhc),
          f"H_res of {len(got['mhc_probe'][0])} rows against float32 from "
          f"the same residual rows, a sublayer: max|diff| = "
          f"{[f'{e:.2e}' for e in mhc]} (tol {tol['h_res_tol']:g})")
    return {"rows": [int(r) for r in got["rows"]], "logits_rel_err": errs,
            "routing_judged": rou, "router_score_err": rtr,
            "h_res_err": mhc}


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
    rows = got["rows"]
    del got
    for name, how, refusing in (
            ("low_precision", dict(dtype=jnp.dtype(tol["control_dtype"])),
             tol["control_refused_by"]),
            ("h_res_identity", dict(h_res="identity"),
             tol["identity_control_refused_by"])):
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
                  f"router scores {max(readings['router_score_err']):.3g}, "
                  f"H_res {max(readings['h_res_err']):.3g}")


def by_block_by_program(run: Run) -> dict:
    """Chip 0's self seconds of the traced part, a program: `{module:
    {block or "(unscoped)": seconds}}`. An instruction's name is unique
    in its program only, so each event goes to the program execution
    that holds it and resolves through THAT program's scope index
    (`paddle_tpu.jit.aot.scopes`); `{}` where the program keeps none or
    nothing was traced."""
    try:
        from paddle_tpu.jit import aot
        tr = trace_reduce.load(trace_reduce.find_xplane(run.trace_dir))
    except (ImportError, FileNotFoundError):
        return {}
    if not tr.devices or not hasattr(aot, "scopes"):
        return {}
    wins = [(s, e) for s, e, n in tr.spans if n == trace_reduce.WINDOW_SPAN]
    if not wins:
        return {}
    lo, hi = max(wins, key=lambda w: w[1] - w[0])
    first = min(tr.devices)
    mods = sorted(trace_reduce.clip(tr.modules.get(first, []), lo, hi))
    ops = sorted(trace_reduce.clip(tr.devices[first], lo, hi))
    held = defaultdict(list)
    at = 0
    for iv in ops:
        while at < len(mods) and mods[at][1] <= iv[0]:
            at += 1
        if at < len(mods) and mods[at][0] <= iv[0]:
            held[mods[at][2]].append(iv)
    out = {}
    for module, ivs in held.items():
        index = aot.scopes(module) or {}
        split = defaultdict(float)
        for op, ns in trace_reduce.self_times(ivs).items():
            split[index.get(op, ("(unscoped)",))[0]] += ns / 1e9
        out[module] = dict(split)
    return out


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
        win = lambda name: run.counts.get("window." + name, 0)
        # `serve.mfu_pct`'s reader hands the family's `attention_flops`
        # the decode steps' attended positions under the counters of the
        # family it was written for; this one attends over all it reads
        read = win("serve_attn_read_positions_total{lifetime=slot}")
        run.counts["window.serve_dsa_selected_total"] = read
        run.counts["window.serve_dsa_available_total"] = read
        if run.traced and run.trace:
            split = by_block_by_program(run)
            run.notes["by_block_by_program"] = split
            run.counts["mhc_device_s"] = sum(
                blocks.get("mhc", 0.0) for blocks in split.values())

        fallbacks = {f"{k[0]}:{k[1]}": v
                     for k, v in pallas_ops.PALLAS_STATS.items()}
        run.notes["pallas_fallbacks"] = fallbacks
        run.check("no_preemption", run.counts["preemptions"] == 0,
                  f"{run.counts['preemptions']} in the window")
        run.check("dense_path_live",
                  read > 0 and win("serve_moe_skipped_pairs_total") == 0
                  and any(k.startswith("window.serve_moe_routed_tokens_total")
                          and v > 0 for k, v in run.counts.items()),
                  f"the decode steps of the window read {read} latent "
                  f"positions a layer, routed tokens to the experts and "
                  f"skipped {win('serve_moe_skipped_pairs_total')} pairs "
                  f"(every expert is held: 0)")
        if not run.rehearse:
            row = {r["kernel"]: r for r in pallas_ops.kernels()}["paged_decode"]
            run.check("paged_mla_decode_live",
                      row["live"] and "paged_mla_decode" in
                      eng._get_decode().compiled.as_text(), str(row))
            run.check("no_unexpected_fallback",
                      set(fallbacks) <= set(sysc["expect"]["fallbacks"]),
                      f"recorded {fallbacks}")
    finally:
        eng.shutdown()
