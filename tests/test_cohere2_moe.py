"""`cohere2_moe` (Command A+'s family) at a small size on the CPU: the
serving form through the ENGINE's pages — two page lifetimes, grouped
K/V heads, the parallel block — against the plain reference."""
import importlib.util
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.incubate.moe import (gated_ffn, held_experts_ffn,
                                     sigmoid_topk_routing)
from paddle_tpu.models import cohere2_moe as cohere
from paddle_tpu.monitor import trace
from paddle_tpu.serving import ServingConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/cohere2_moe.py", "ref_cohere2_moe")

CHUNK, PAGE, W = 8, 4, 8


def sizes_of(cfg):
    """What the reference needs beside the weights."""
    return {k: getattr(cfg, k) for k in (
        "num_heads", "num_kv_heads", "num_experts_per_tok",
        "n_shared_experts", "experts_held", "layer_types", "sliding_window",
        "rope_theta", "layer_norm_eps", "logit_scale")}


def build(seed=3, slots=2, **kw):
    paddle.seed(seed)
    cfg = cohere.cohere2_moe_tiny(**kw)
    model = cohere.Cohere2MoeForCausalLM(cfg)
    with flag_scope("serve_prefill_chunk", CHUNK):
        eng = ServingEngine(model, ServingConfig(
            max_batch_slots=slots, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,)))
    return cfg, model, eng


def through_pages(eng, model, ids, plen, tap="router_topk"):
    """(logits, tap of every layer) of the last row of every program:
    the prompt through the engine's forward in chunks (the plain path at
    position 0, the context path after), then a decode step a token —
    in the engine's OWN pools, both lifetimes' tables kept by the cache
    as the engine keeps them, so window pages are freed on the way."""
    cache, slots = eng.cache, eng.config.max_batch_slots
    assert cache.alloc_slot(0, len(ids))
    out = []

    def fwd(tokens, rows, pos, ctx):
        model.taps = {}
        logits, pools, _ = eng._forward(
            eng.params, tokens, cache.pool_args(), cache.table_array(rows),
            jnp.asarray(pos, jnp.int32), ctx=ctx)
        cache.update(*pools)
        taps, model.taps = model.taps, None
        return np.asarray(logits[0]), [np.asarray(t[0]) for t in taps[tap]]

    for at in range(0, plen, CHUNK):
        clen = min(CHUNK, plen - at)
        cache.advance(0, at, clen)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :clen] = ids[at:at + clen]
        logits, taps = fwd(jnp.asarray(toks), [0], [at], at > 0)
        out.append((at + clen - 1, logits[clen - 1], taps))
    for i in range(plen, len(ids)):
        cache.advance(0, i, 1)
        toks = np.zeros((slots, 1), np.int32)
        toks[0, 0] = ids[i]
        pos = np.zeros((slots,), np.int32)
        pos[0] = i
        logits, taps = fwd(jnp.asarray(toks), [0] + [None] * (slots - 1),
                           pos, False)
        out.append((i, logits[-1], taps))
    return out


@pytest.mark.parametrize("plen,steps,pallas", [
    (16, 3, False), (29, 6, False), (40, 4, False), (29, 6, True)])
def test_chunks_then_decode_through_the_engines_pages(plen, steps, pallas):
    """Contexts past the window (8) and past the point where window
    pages are freed: the logits of every chunk's last row and of every
    decode step equal the reference's full forward, and so do the chosen
    experts; the window lifetime freed pages and holds no more than its
    bound. Once with the decode kernel interpreted, else its XLA twin."""
    with flag_scope("pallas_interpret", pallas):
        cfg, model, eng = build()
        ids = np.random.default_rng(plen).integers(
            0, cfg.vocab_size, (plen + steps,)).astype(np.int32)
        got = through_pages(eng, model, ids, plen)
    rows = [r for r, _, _ in got]
    want = ref.forward(eng.params, ids, sizes_of(cfg), rows=rows)
    for i, (t, logits, topk) in enumerate(got):
        np.testing.assert_allclose(logits, want["logits"][i], rtol=2e-4,
                                   atol=2e-5)
        for li in range(cfg.num_layers):
            assert set(topk[li][-1 if t >= plen else (t % CHUNK)].tolist()) \
                == set(np.asarray(want["routing"][li][t]).tolist())
    (win,) = eng.cache.windows
    assert win.freed > 0
    assert 0 < win.live_blocks(0) <= win.pages_per_slot == 5
    # the slot lifetime kept every page
    assert eng.cache.slot_blocks(0) == -(-len(ids) // PAGE)
    # every entry before the window's first page points at scratch again
    first = win.first_position(0) // PAGE
    assert first > 0 and not win.tables[0, :first].any()


def test_generate_is_the_references_argmax_token_by_token():
    """The whole engine (scheduler, admission, chunked prefill under a
    token budget, decode over two slots of different lengths): greedy
    tokens equal the argmax of the reference's full forward."""
    cfg, model, eng = build(slots=3)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (21, 5, 34)]
    outs = eng.generate(prompts, max_new_tokens=9)
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        want = ref.forward(eng.params, o, sizes_of(cfg))["logits"]
        np.testing.assert_array_equal(
            o[len(p):], np.asarray(jnp.argmax(want, -1))[len(p) - 1:-1])
    (win,) = eng.cache.windows
    assert win.freed > 0 and win.allocator.pages_in_use == 0
    assert eng.cache.allocator.pages_in_use == 0


def test_the_shares_of_all_chips_sum_to_the_uncut_layer():
    """8 experts over 4 chips of 2: every chip routes over all 8 and
    computes its own experts' part; the parts, with the averaged shared
    experts counted once, add up to the reference's uncut layer."""
    cfg = cohere.cohere2_moe_tiny()
    rng = np.random.default_rng(2)
    D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.n_routed_experts
    ns = cfg.n_shared_experts
    n = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    weights = {"router.weight": n(D, E),
               "experts.w_in": n(E, D, 2 * F), "experts.w_out": n(E, F, D),
               "shared.w_in": n(D, 2 * ns * F), "shared.w_out": n(ns * F, D)}
    x = n(37, D) * 5
    sz = sizes_of(cfg)
    whole, _, _ = ref.routed_part(x, weights, "", sz, experts=(0, E))
    whole = whole + ref.shared_part(x, weights, "", sz)
    routing = sigmoid_topk_routing(x, weights["router.weight"],
                                   jnp.zeros((E,)), cfg.num_experts_per_tok)
    # the program's form of the shared experts: ONE product, then 1/n
    total = gated_ffn(x, weights["shared.w_in"], weights["shared.w_out"]) / ns
    given = 0
    for first in range(0, E, 2):
        part, tokens, here = held_experts_ffn(
            x, routing, weights["experts.w_in"][first:first + 2],
            weights["experts.w_out"][first:first + 2], first)
        given += int(tokens.sum())
        total = total + part
    assert given == 37 * cfg.num_experts_per_tok            # no pair dropped
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def one_layer(kind):
    """A one-layer model of that kind of layer behind an engine."""
    paddle.seed(1)
    cfg = cohere.cohere2_moe_tiny(layer_types=(kind,))
    model = cohere.Cohere2MoeForCausalLM(cfg)
    with flag_scope("serve_prefill_chunk", CHUNK):
        eng = ServingEngine(model, ServingConfig(
            max_batch_slots=1, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,)))
    return cfg, model, eng


@pytest.mark.parametrize("kind", [cohere.WINDOW, cohere.FULL])
def test_rotary_on_window_layers_only(kind):
    """A full layer has no positional term at all: with the earlier
    tokens in another order, the last row sees the same SET of keys and
    gives the same logits. A window layer's keys are turned by their
    positions, and the logits move."""
    cfg, model, eng = one_layer(kind)
    ids = np.array([11, 22, 33, 44, 55, 66], np.int32)
    swapped = ids[[1, 0, 3, 2, 4, 5]]
    a = through_pages(eng, model, ids, 6)[0][1]
    eng.cache.free_slot(0)
    b = through_pages(eng, model, swapped, 6)[0][1]
    if kind == cohere.FULL:
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    else:
        assert np.max(np.abs(a - b)) > 1e-3


def test_the_parallel_block_reads_one_normed_row():
    """x' = x + attention(h) + experts(h), ONE h: a one-layer system's
    logits are those of the two halves computed from the reference's
    single norm, and not those of a sequential block, whose expert half
    would read the norm of x + attention."""
    cfg, model, eng = one_layer(cohere.WINDOW)
    sz, w, p = sizes_of(cfg), eng.params, "layers.0."
    ids = np.random.default_rng(4).integers(0, 256, (7,)).astype(np.int32)
    x = jnp.asarray(w["embed"])[ids].astype(jnp.float32)
    norm = lambda t: ref._norm(t, w[p + "norm.weight"],
                               eps=cfg.layer_norm_eps)
    experts = lambda t: ref.routed_part(t, w, p + "moe.", sz)[0] \
        + ref.shared_part(t, w, p + "moe.", sz)
    head = lambda t: ref._head(t[-1:], w["final_norm.weight"], w["embed"],
                               eps=cfg.layer_norm_eps, scale=1.0)[0]
    a, _ = ref._attention(
        norm(x), jnp.arange(7), w[p + "attn.wq"], w[p + "attn.wk"],
        w[p + "attn.wv"], w[p + "attn.wo"], n_heads=8, n_kv=2, window=W,
        theta=cfg.rope_theta, rotate=True, dt=jnp.float32)
    parallel = head(x + a + experts(norm(x)))
    sequential = head(x + a + experts(norm(x + a)))
    got = through_pages(eng, model, ids, 7)[0][1]
    np.testing.assert_allclose(got, parallel, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(sequential - parallel))) > 1e-3


def test_page_kinds_declare_two_lifetimes_and_one_donated_pool_tuple():
    cfg, model, eng = build()
    kinds = cfg.page_kinds()
    assert [(k.name, k.layers, k.heads, k.lifetime) for k in kinds] == [
        ("k", (3,), 2, "slot"), ("v", (3,), 2, "slot"),
        ("k_window", (0, 1, 2), 2, W), ("v_window", (0, 1, 2), 2, W)]
    (win,) = eng.cache.windows
    # ceil((W + C) / bs) + 1 a slot, derived, + the scratch page
    assert win.pages_per_slot == (W + CHUNK) // PAGE + 1
    assert win.num_pages == 2 * win.pages_per_slot + 1
    shapes = [p.shape for p in eng.cache.pool_args()]
    assert shapes == [(1, 33, 1, PAGE, 32)] * 2 + [(3, 11, 1, PAGE, 32)] * 2
    tables = eng.cache.table_array()
    assert isinstance(tables, tuple) and len(tables) == 2
    # K and V are cached at 2 heads, never repeated to 8
    assert eng.cache.kv_bytes_per_token() == 4 * 2 * 2 * 16 * 4


def test_counters_spans_and_freed_pages():
    cfg, model, eng = build()
    t0 = time.perf_counter()
    prompt = np.random.default_rng(0).integers(0, 256, (21,)).astype(np.int32)
    eng.generate([prompt], max_new_tokens=5)
    c = eng._stats["model_counters"]
    # decode steps at positions 21..24 (the first token came from prefill)
    pos = np.arange(21, 25)
    assert c["serve_attn_read_positions_total{lifetime=slot}"] == (pos + 1).sum()
    assert c["serve_attn_read_positions_total{lifetime=window}"] == 4 * W
    assert c["serve_kv_pages_unwindowed_total"] == (pos // PAGE + 1).sum()
    assert c["serve_kv_pages_live_total{lifetime=slot}"] == (pos // PAGE + 1).sum()
    assert c["serve_kv_pages_live_total{lifetime=window}"] == sum(
        p // PAGE - (p - W + 1) // PAGE + 1 for p in pos)
    assert c["serve_kv_window_pages_freed_total"] == eng.cache.windows[0].freed > 0
    routed = sum(v for k, v in c.items() if "routed_tokens" in k)
    pairs = 4 * 4 * cfg.num_experts_per_tok          # steps x layers x top-k
    assert routed + c["serve_moe_skipped_pairs_total"] == pairs
    recs = trace.spans(since=t0)
    decode = [r[6] for r in recs if r[0] == "serve.decode"]
    assert [(a["read_full"], a["read_window"]) for a in decode] == [
        (p + 1, W) for p in pos]
    freed = [r[6]["freed"] for r in recs if r[0] == "serve.kv.release"]
    assert sum(freed) == eng.cache.windows[0].freed
    assert [(r[6]["chunk"], r[6]["ctx"]) for r in recs
            if r[0] == "serve.prefill"] == [
        ((8,), (0,)), ((8,), (8,)), ((5,), (16,))]


@pytest.mark.parametrize("who", ["system", "low_precision", "no_window"])
def test_the_cells_checks_pass_the_system_and_refuse_both_controls(who):
    """The cell's own judge at the small size: the system passes every
    check; the reference in bfloat16 is refused by the router-score
    check and the reference without its window by the attention rows."""
    from benchmark.harness import cohere_serve_runner as runner
    cfg, model, eng = build(slots=4)
    sz = sizes_of(cfg)
    ids = np.random.default_rng(9).integers(0, 256, (44,)).astype(np.int32)
    tol = dict(logits_rel_tol=0.03, attn_rel_tol=0.1, router_min_overlap=0.5,
               router_margin=0.02, router_score_tol=2e-4)
    verdict = {}
    check = lambda name, ok, detail: verdict.update({name: ok})
    got = runner.probe_system(eng, model, ids, [40, 24, 16, 4], 4)
    assert got["window_entries"][0] <= got["window_bound"][0]
    if who == "system":
        runner.judge_probe(check, tol, got, ref, eng.params, ids, sz)
        assert all(verdict.values()), verdict
        return
    how = dict(dtype=jnp.bfloat16) if who == "low_precision" \
        else dict(windowed=False)
    low = runner.control_system(ref, eng.params, ids, sz, got["rows"], **how)
    runner.judge_probe(check, tol, low, ref, eng.params, ids, sz)
    refused = "reference_router_scores" if who == "low_precision" \
        else "reference_window_attn"
    assert not verdict[refused], verdict
