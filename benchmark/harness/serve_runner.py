"""Runner `serve`: `inference.create_serving_engine` under the traffic of
a mix, driven from one thread.

Set-up (counted in `setup_s`): the model with weights from `--seed`, the
engine through the public entry point in the dtypes the cell states,
every serving program compiled or loaded from the cache (`warmup()`),
one probe prompt through prefill and a decode step against the plain
reference, then `warm_s` seconds of the same traffic, so the window
opens on a full engine. Window: `--seconds` of that traffic going on.

The clock is the client's: a request is timed from when it was due (in
a closed loop, the moment its caller's last request finished), a token
when `Request.on_token` delivers it. How late the loop submitted is
reported beside the latencies.
"""

from __future__ import annotations

import faulthandler
import gc
import heapq
import sys
import time
from dataclasses import dataclass, field
from typing import List

from . import trace_reduce
from .loadgen import ServeTraffic, percentile
from .result import (BenchFailure, Run, Timed, annotate, arm_deadline,
                     hbm_account, hbm_read, rel_err, say)

#: `--rehearse`: an engine and lengths a toy model on the CPU can serve
_REHEARSE_ENGINE = dict(max_batch_slots=8, block_size=4, max_context_len=64,
                        prefill_buckets=(8, 32), batch_buckets=(1, 4))
_REHEARSE_SCALE = dict(prompt_div=24, prompt_max=32, output_div=16,
                       output_max=16)
#: an `engine.step()` that takes longer than this has every thread's
#: stack written to stderr while it is stuck, so a stall can be named
#: (the longest ordinary step, a decode behind prefills, takes 0.56 s;
#: the stalls seen took 1.4 to 6 s: PERF.md, PR 25)
STALL_S = 1.0


@dataclass
class Rec:
    k: int
    due: float
    prompt_len: int
    max_new: int
    submit_t: float = 0.0
    times: List[float] = field(default_factory=list)
    state: object = None


def probe_against_reference(run: Run, eng, model, reference, vocab) -> None:
    """Prefill of one probe prompt, then ONE decode step, through the
    engine's own forward (pages, the paged kernel, the engine's weights
    and cache dtypes) against the reference's full forward over the same
    tokens and the same weights. Logits, not tokens: random weights have
    near-ties. Small pools of the engine's layout and dtype stand in for
    its own, which are too large to copy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.serving.kv_cache import blocks_needed
    tol = run.system["correct"]
    sc = eng.config
    plen = min(int(tol["probe_prompt_len"]), max(sc.prefill_buckets) - 1)
    rng = np.random.default_rng([run.seed, 11])
    prompt = rng.integers(0, vocab, (plen,)).astype(np.int32)
    nxt = int(rng.integers(0, vocab))
    slots, mb = sc.max_batch_slots, eng.cache.max_blocks_per_slot
    sp = min(b for b in sc.prefill_buckets if b >= plen)
    need = blocks_needed(plen + 1, sc.block_size)
    table = np.zeros((slots, mb), np.int32)        # others: scratch page 0
    table[0, :need] = 1 + np.arange(need)
    ids = np.zeros((1, sp), np.int32)
    ids[0, :plen] = prompt
    toks = np.zeros((slots,), np.int32)
    toks[0] = nxt
    pos = np.zeros((slots,), np.int32)
    pos[0] = plen
    L, _, bs, H, D = eng.cache.k.shape
    pool = jnp.zeros((L, need + 1, bs, H, D), eng.cache.k.dtype)

    @jax.jit
    def replay(params, k, v):
        _, k, v = eng._fwd(params, jnp.asarray(ids), k, v,
                           jnp.asarray(table[:1]), jnp.zeros((1,), jnp.int32))
        logits, _, _ = eng._fwd(params, jnp.asarray(toks)[:, None], k, v,
                                jnp.asarray(table), jnp.asarray(pos))
        return logits[0, -1]

    got = replay(eng.params, pool, pool).astype(jnp.float32)
    full = np.concatenate([prompt, [nxt]])[None].astype(np.int32)
    ref = reference.forward(eng.params, jnp.asarray(full))[0, -1]
    err = rel_err(got, ref)
    run.notes["reference"] = {
        "probe_prompt_len": plen, "logits_rel_err": err,
        "max_abs_ref_logit": float(jnp.max(jnp.abs(ref))),
        "argmax_system": int(jnp.argmax(got)), "argmax_reference": int(jnp.argmax(ref))}
    run.check("reference_decode_logits",
              np.isfinite(err) and err <= tol["logits_rel_tol"],
              f"prefill {plen} + 1 decode step: max|diff|/max|ref| = {err:.2e} "
              f"(tol {tol['logits_rel_tol']:g})")


def run(run: Run, ledger, reference) -> None:
    import jax
    import numpy as np
    from paddle_tpu import inference
    from paddle_tpu.ops import pallas as pallas_ops
    from paddle_tpu.serving import ServingConfig

    mix, sysc, fam = run.mix, run.system, run.model
    sz = fam.sizes(run.config, run.rehearse)
    vocab = sz["padded_vocab_size"]
    pallas_ops.reset_pallas_stats()

    t = time.perf_counter()
    model = fam.build_model(run.config, run.seed, rehearse=run.rehearse)
    jax.block_until_ready([p._data for p in model.parameters()])
    say(f"  model built in {time.perf_counter() - t:.1f}s")

    eng_kw = dict(sysc["engine"])
    if run.rehearse:
        eng_kw.update(_REHEARSE_ENGINE)
    for key in ("prefill_buckets", "batch_buckets"):
        eng_kw[key] = tuple(eng_kw[key])
    cfg = inference.Config.from_layer(model, input_spec=[])
    if sysc["weights_dtype"] == "bfloat16":
        cfg.enable_tpu_bf16()
    eng = inference.create_serving_engine(cfg, ServingConfig(**eng_kw))
    try:
        t = time.perf_counter()
        # the engine appends a max_context_len prefill bucket for
        # re-prefill after preemption; at full residency nothing is
        # preempted, so only the buckets the traffic uses are warmed
        n_prog = eng.warmup([(nb, sp) for nb in eng_kw["batch_buckets"]
                             for sp in eng_kw["prefill_buckets"]])
        say(f"  {n_prog} serving programs resident after warmup "
            f"({time.perf_counter() - t:.1f}s): prefill {eng_kw['prefill_buckets']} "
            f"x batch {eng_kw['batch_buckets']} + decode; weights "
            f"{sysc['weights_dtype']}, cache {eng_kw['cache_dtype']}")
        t = time.perf_counter()
        probe_against_reference(run, eng, model, reference, vocab)
        say(f"  reference probe took {time.perf_counter() - t:.1f}s")
        run.counts["slots"] = eng.config.max_batch_slots
        run.counts["kv_bytes_per_token"] = fam.kv_bytes_per_token(
            run.config if not run.rehearse else {**run.config, **sz},
            eng_kw["cache_dtype"])
        if run.counts["kv_bytes_per_token"] != eng.cache.kv_bytes_per_token():
            raise BenchFailure("kv bytes per token: the benchmark's arithmetic "
                               "and the engine's disagree")
        traffic = ServeTraffic(mix, vocab, run.seed,
                               _REHEARSE_SCALE if run.rehearse else None)
        _drive(run, ledger, eng, traffic, jax.devices()[:run.chips])

        row = {r["kernel"]: r for r in pallas_ops.kernels()}["paged_decode"]
        fallbacks = {f"{k[0]}:{k[1]}": v
                     for k, v in pallas_ops.PALLAS_STATS.items()}
        run.notes["pallas_fallbacks"] = fallbacks
        if not run.rehearse:
            run.check("paged_decode_live", row["live"] and "paged_decode" in
                      eng._get_decode().compiled.as_text(), str(row))
            run.check("no_unexpected_fallback",
                      set(fallbacks) <= set(sysc["expect"]["fallbacks"]),
                      f"recorded {fallbacks}")
    finally:
        eng.shutdown()


def _drive(run: Run, ledger, eng, traffic: ServeTraffic, devices) -> None:
    import numpy as np
    from paddle_tpu.serving import Request, SamplingParams
    from paddle_tpu.serving.resilience import ServerOverloaded

    mix, sysc = run.mix, run.system
    arrival = mix["arrival"]
    warm_s = float(mix["warm_s"]) if not run.rehearse else 2.0
    trace_s = min(float(sysc["trace_seconds"]), run.seconds) if run.traced else 0.0
    tracer = trace_reduce.WindowTrace(run.trace_dir) if run.traced else None
    clock = time.perf_counter
    recs: dict = {}
    pending: list = []                    # heap of due times (closed) / (due, k)
    think = float(arrival.get("think_s", 0.0))
    rejected = 0
    next_k = 0

    def on_token(req, token, text):
        t = clock()
        rec = recs[req.request_id]
        rec.times.append(t)
        if traffic.closed and len(rec.times) == rec.max_new:
            heapq.heappush(pending, t + think)

    slow_steps: list = []                 # (seconds into the window, wall, cpu)
    t_start = clock()
    if traffic.closed:
        n = int(arrival["clients"]) if not run.rehearse else 8
        stagger = float(arrival.get("stagger_s", 0.0)) if not run.rehearse else 1.0
        for i in range(n):
            heapq.heappush(pending, t_start + i * stagger / n)
    else:
        for due in traffic.dues:
            heapq.heappush(pending, t_start + float(due))
    t_w0 = t_start + warm_s
    t_end = t_w0 + run.seconds
    snap0 = summ0 = hbm0 = gc0 = None
    t_trace0 = None
    while True:
        now = clock()
        if now >= t_end:
            break
        if snap0 is None and now >= t_w0:
            t_w0 = now
            t_end = t_w0 + run.seconds
            snap0, summ0 = ledger.snap(), eng.metrics_summary()
            hbm0 = hbm_read(devices)
            gc0 = [g["collections"] for g in gc.get_stats()]
            run.e2e["setup_s"] = now - run.t_start
        if tracer and not tracer.started and now >= t_end - trace_s:
            tracer.start()
            t_trace0 = clock()
        while pending and pending[0] <= now and next_k < len(traffic):
            due = heapq.heappop(pending)
            with annotate(run, "bench.submit"):
                rec = Rec(next_k, due, 0, int(traffic.max_new[next_k]))
                prompt = traffic.prompt(next_k)
                rec.prompt_len = len(prompt)
                req = Request(prompt, max_new_tokens=rec.max_new,
                              sampling=SamplingParams(), on_token=on_token)
                recs[req.request_id] = rec
                rec.submit_t = clock()
                try:
                    rec.state = eng.submit(req)
                except ServerOverloaded:
                    rejected += 1
            next_k += 1
        if eng.scheduler.has_work:
            faulthandler.dump_traceback_later(STALL_S, file=sys.stderr)
            cpu0 = time.thread_time()
            with Timed(run, "bench.engine_step", keep=snap0 is not None) as span:
                eng.step()
            arm_deadline(run)
            if span.seconds > STALL_S:
                slow_steps.append((span.t0 - t_w0, span.seconds,
                                   time.thread_time() - cpu0))
        else:
            with annotate(run, "bench.idle_sleep"):
                wait = (pending[0] - clock()) if pending else 0.005
                time.sleep(min(max(wait, 0.0), 0.005))
    t_w1 = clock()
    gc1 = [g["collections"] for g in gc.get_stats()]
    if snap0 is None:
        raise BenchFailure("the window never opened")
    hbm_account(run, devices, hbm0, [p.compiled for p in eng._programs.values()])
    if tracer and tracer.started:
        run.trace = tracer.stop()
    snap1, summ1 = ledger.snap(), eng.metrics_summary()

    # -- what the clients saw inside [t_w0, t_w1] ---------------------------
    inside = lambda t: t_w0 <= t <= t_w1
    tokens = 0
    ttft, gaps, late, done_in = [], [], [], 0
    traced_kv_positions = 0
    for rec in recs.values():
        ts = rec.times
        tokens += sum(1 for t in ts if inside(t))
        if ts and inside(ts[0]):
            ttft.append((ts[0] - rec.due) * 1e3)
            late.append((rec.submit_t - rec.due) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]) if inside(b))
        if len(ts) == rec.max_new and inside(ts[-1]):
            done_in += 1
        if t_trace0 is not None:
            # token i >= 1 comes from a decode step that attended over
            # prompt_len + i cached positions
            traced_kv_positions += sum(rec.prompt_len + i
                                       for i, t in enumerate(ts)
                                       if i >= 1 and t >= t_trace0)
    win = t_w1 - t_w0
    if tokens:
        run.e2e["serve_tokens_per_s"] = tokens / win
    # every percentile a metric may name; BENCHMARK.json says which are
    # end-to-end metrics and which stand beside them, per layer
    for q in (50, 95):
        if ttft:
            run.e2e[f"ttft_p{q}_ms"] = percentile(ttft, q)
    for q in (50, 90, 95, 99):
        if gaps:
            run.e2e[f"tpot_p{q}_ms"] = percentile(gaps, q)
    delta = lambda k: (summ1[k] or 0) - (summ0[k] or 0)
    lost = {k: delta(k) for k in ("requests_shed", "requests_failed",
                                  "requests_expired", "requests_cancelled",
                                  "requests_drained") if delta(k)}
    if rejected:
        lost["requests_rejected"] = rejected
    run.attempted = done_in + sum(lost.values())
    run.failed = sum(lost.values())
    d1, d0 = summ1["decode_dispatches"], summ0["decode_dispatches"]
    run.counts["decode_dispatches"] = d1 - d0
    run.counts["decode_slot_steps"] = (
        (summ1["mean_decode_occupancy"] or 0) * d1
        - (summ0["mean_decode_occupancy"] or 0) * d0)
    run.counts["preemptions"] = delta("preemptions")
    run.counts["window_s"] = win
    run.counts["tokens"] = tokens
    run.counts["traced_decode_kv_bytes"] = (
        traced_kv_positions * run.counts["kv_bytes_per_token"])
    steps_ms = [x * 1e3 for x in run.spans.get("bench.engine_step", [])]
    run.notes["client"] = {
        "requests_completed_in_window": done_in,
        "requests_per_s": done_in / win,
        "ttft_samples": len(ttft), "tpot_samples": len(gaps),
        "tpot_mean_ms": float(np.mean(gaps)) if gaps else None,
        "gap_histogram_20ms": {int(k) * 20: int(v) for k, v in zip(
            *np.unique(np.asarray(gaps) // 20, return_counts=True))},
        "engine_step_p95_ms": percentile(steps_ms, 95),
        "engine_step_max_ms": max(steps_ms) if steps_ms else None,
        # steps of over STALL_S, warm phase included (negative times): when,
        # how long, and how much of it this thread spent on a CPU (near
        # all: host work; near none: it waited, for the chip or a lock)
        "engine_steps_stalled": [
            {"at_s": at, "wall_s": wall, "thread_cpu_s": cpu}
            for at, wall, cpu in slow_steps],
        # the collector runs as in any process (nothing is frozen): its
        # passes inside the window, youngest generation first
        "gc_collections_in_window": [c1 - c0 for c0, c1 in zip(gc0, gc1)],
        "submit_late_p50_ms": percentile(late, 50),
        "submit_late_p95_ms": percentile(late, 95),
        "submit_late_max_ms": max(late) if late else None,
        "requests_submitted_in_all": next_k,
        "mean_output_len": float(np.mean(traffic.max_new[:max(next_k, 1)])),
        "engine_steps_in_window": len(run.spans.get("bench.engine_step", [])),
    }

    # -- correct ---------------------------------------------------------------
    wrong = [r.k for r in recs.values()
             if r.state is not None and r.state.terminal
             and (r.state.outcome != "completed"
                  or len(r.state.generated) != r.max_new
                  or len(r.times) != r.max_new)]
    finished = sum(1 for r in recs.values()
                   if r.state is not None and r.state.terminal)
    run.check("requests_exact", not wrong and finished > 0,
              f"{finished} finished requests each returned the tokens it "
              f"asked for; wrong: {wrong[:8]}")
    run.check("none_lost", not lost, f"lost in the window: {lost}")
    run.check("no_compile_in_window", snap1 == snap0,
              f"(compiles, cache hits, cache misses) {snap0} -> {snap1}")
    run.check("traffic_sufficed", next_k < len(traffic),
              f"{next_k} of {len(traffic)} requests of the mix were used")
