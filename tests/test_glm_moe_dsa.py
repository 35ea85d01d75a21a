"""The `glm_moe_dsa` family (MLA + learned sparse attention + held
experts) at a small size on the CPU, each piece against the plain
reference (benchmark/reference/glm_moe_dsa.py) on seeded weights; and
the page kinds that carry it through the one serving engine."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.incubate.moe import (gated_ffn, held_experts_ffn,
                                     sigmoid_topk_routing)
from paddle_tpu.models import glm_moe_dsa as glm
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.monitor import trace
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_cache import (PageKind, PagedCacheView,
                                         PagedKVCache, blocks_needed)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/glm_moe_dsa.py", "ref_glm_moe_dsa")

CHUNK, PAGE = 8, 4


def sizes_of(cfg):
    """What the reference needs beside the weights."""
    return {k: getattr(cfg, k) for k in (
        "num_heads", "qk_nope_head_dim", "qk_rope_head_dim", "index_n_heads",
        "index_topk", "num_experts_per_tok", "routed_scaling_factor",
        "experts_held", "mlp_layer_types", "indexer_types", "rope_theta",
        "rms_norm_eps")}


def build(seed=3, **kw):
    paddle.seed(seed)
    cfg = glm.glm_moe_dsa_tiny(**kw)
    model = glm.GlmMoeDsaForCausalLM(cfg)
    with flag_scope("serve_prefill_chunk", CHUNK):
        eng = ServingEngine(model, ServingConfig(
            max_batch_slots=2, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,)))
    return cfg, model, eng


def through_pages(eng, model, ids, plen):
    """Logits, selection and routing at positions plen-1 .. len(ids)-1:
    the prompt through the engine's forward in chunks, then a decode
    step a token, all in the engine's pools."""
    slots, mb = eng.config.max_batch_slots, eng.cache.max_blocks_per_slot
    need = blocks_needed(len(ids), PAGE)
    table = np.zeros((slots, mb), np.int32)
    table[0, :need] = 1 + np.arange(need)
    table = jnp.asarray(table)
    pools, out = eng.cache.pool_args(), []

    def fwd(tokens, tbl, pos, ctx):
        nonlocal pools
        model.taps = {}
        logits, pools, _ = eng._forward(eng.params, tokens, pools, tbl,
                                        jnp.asarray(pos, jnp.int32), ctx=ctx)
        taps, model.taps = model.taps, None
        # of the LAST full and the LAST expert layer, at the last row
        return (np.asarray(logits[0, -1]),
                np.asarray(taps["selection"][-1][0, -1]),
                np.asarray(taps["router_topk"][-1][0, -1]))

    assert plen % CHUNK == 0
    for at in range(0, plen, CHUNK):
        got = fwd(jnp.asarray(ids[None, at:at + CHUNK]), table[:1], [at],
                  at > 0)
    out.append(got)
    for i in range(plen, len(ids)):
        toks = np.zeros((slots, 1), np.int32)
        toks[0, 0] = ids[i]
        pos = np.zeros((slots,), np.int32)
        pos[0] = i
        out.append(fwd(jnp.asarray(toks), table, pos, False))
    return out


@pytest.mark.parametrize("plen,steps", [(16, 3), (24, 4), (40, 2)])
def test_chunked_prefill_then_decode_against_the_full_forward(plen, steps):
    """Contexts past index_topk (8), so the selection discards: logits,
    the selected set and the routing of prefill's last position and of
    every decode step equal the reference's full forward."""
    cfg, model, eng = build()
    ids = np.random.default_rng(plen).integers(0, cfg.vocab_size,
                                               (plen + steps,)).astype(np.int32)
    got = through_pages(eng, model, ids, plen)
    rows = list(range(plen - 1, plen + steps))
    want = ref.forward(eng.params, ids, sizes_of(cfg), rows=rows)
    for i, (logits, sel, topk) in enumerate(got):
        t = rows[i]
        np.testing.assert_allclose(logits, want["logits"][i], rtol=2e-4,
                                   atol=2e-5)
        assert sel[:t + 1].sum() == cfg.index_topk
        np.testing.assert_array_equal(sel[:t + 1], want["members"][i][:t + 1])
        assert not sel[t + 1:].any()
        assert set(topk.tolist()) == set(
            np.asarray(want["router_topk"][i]).tolist())


@pytest.mark.parametrize("flip", [False, True])
def test_a_shared_layer_uses_the_full_layers_set(flip):
    """Layers 1-3 have no indexer and read layer 0's set. With layer
    0's index weights negated (flip) ITS selection changes, the logits
    move, and they move exactly as the reference's do."""
    cfg, model, eng = build(seed=5)
    assert not hasattr(model.layers[1], "indexer")
    assert hasattr(model.layers[0], "indexer")
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (27,)).astype(np.int32)
    plain = through_pages(eng, model, ids, 24)[0][0]
    if flip:
        eng.params = dict(eng.params)
        eng.params["layers.0.indexer.w"] = -eng.params["layers.0.indexer.w"]
    got = through_pages(eng, model, ids, 24)
    want = ref.forward(eng.params, ids, sizes_of(cfg), rows=[23, 24, 25, 26])
    for (logits, _, _), w in zip(got, want["logits"]):
        np.testing.assert_allclose(logits, w, rtol=2e-4, atol=2e-5)
    assert (np.abs(got[0][0] - plain).max() > 1e-3) == flip


def test_mla_expanded_against_absorbed():
    """Prefill's form (K and V expanded from the latent, a block at a
    time, online softmax) and decode's (W_kvb folded into the query and
    the output, over gathered rows) are the same mathematics."""
    cfg = glm.glm_moe_dsa_tiny()
    rng = np.random.default_rng(0)
    B, ctx, H = 2, 23, cfg.num_heads
    mb = 8
    pool = jnp.asarray(rng.normal(size=(1 + B * mb, 1, PAGE, cfg.latent_width)),
                       jnp.float32)
    table = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(B, 1, H, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(B, 1, H, cfg.qk_rope_head_dim)), jnp.float32)
    w_kvb = jnp.asarray(rng.normal(size=(
        cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim))) * 0.2,
        jnp.float32)
    pos = jnp.full((B,), ctx - 1, jnp.int32)
    expanded = glm.mla_context_attention(q_nope, q_rope, pool, table, 0, pos,
                                         w_kvb, None, cfg)[:, 0]
    idx = jnp.broadcast_to(jnp.arange(ctx, dtype=jnp.int32), (B, ctx))
    absorbed = glm.mla_sparse_decode(q_nope[:, 0], q_rope[:, 0], pool, table, 0,
                                     idx, jnp.ones((B, ctx), bool), w_kvb, cfg)
    np.testing.assert_allclose(expanded, absorbed, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("k", [1, 5, 16])
def test_topk_members_is_exact_and_breaks_ties_by_position(k):
    rng = np.random.default_rng(k)
    s = rng.integers(-3, 4, (6, 256)).astype(np.float32)   # ties everywhere
    s[:, 200:] = -np.inf
    s[2, 3:] = -np.inf                                      # fewer than k finite
    got = np.asarray(glm.topk_members(jnp.asarray(s), k))
    order = np.argsort(-s, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    np.testing.assert_array_equal(got, (rank < k) & np.isfinite(s))


def test_sigmoid_topk_selects_by_s_plus_b_and_weighs_by_s():
    x = jnp.eye(4, dtype=jnp.float32)[:1]                   # picks row 0 of W
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3, jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0], jnp.float32)   # lifts the worst
    r = sigmoid_topk_routing(x, w, bias, top_k=2, scale=2.5)
    s = jax.nn.sigmoid(w[0])
    assert r.idx[0].tolist() == [3, 0]                      # by s + b
    want = 2.5 * jnp.asarray([s[3], s[0]]) / (s[3] + s[0])  # by s alone
    np.testing.assert_allclose(r.gates[0], want, rtol=1e-6)


def test_the_shares_of_all_chips_sum_to_the_uncut_layer():
    """8 experts over 4 chips of 2: every chip routes over all 8 and
    computes its own experts' part; the parts, with the shared expert
    counted once, add up to the reference's uncut layer."""
    cfg = glm.glm_moe_dsa_tiny()
    rng = np.random.default_rng(2)
    D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
    n = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    weights = {"router.weight": n(D, E), "router.bias": n(E) * 0.5,
               "experts.w_in": n(E, D, 2 * F), "experts.w_out": n(E, F, D),
               "shared.w_in": n(D, 2 * F), "shared.w_out": n(F, D)}
    x = n(37, D) * 5
    sz = sizes_of(cfg)
    whole, _, _ = ref.routed_part(x, weights, "", sz, experts=(0, E))
    whole = whole + ref._dense_ffn(x, weights["shared.w_in"],
                                   weights["shared.w_out"])
    routing = sigmoid_topk_routing(x, weights["router.weight"],
                                   weights["router.bias"],
                                   cfg.num_experts_per_tok,
                                   cfg.routed_scaling_factor)
    total = gated_ffn(x, weights["shared.w_in"], weights["shared.w_out"])
    given = 0
    for first in range(0, E, 2):
        part, tokens, here = held_experts_ffn(
            x, routing, weights["experts.w_in"][first:first + 2],
            weights["experts.w_out"][first:first + 2], first)
        assert int(tokens.sum()) == int(here.sum())
        given += int(tokens.sum())
        total = total + part
    assert given == 37 * cfg.num_experts_per_tok            # no pair dropped
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def test_page_kinds_share_one_table_and_allocator():
    kinds = (PageKind("latent", 576, (0, 1, 2, 3, 4)), PageKind("index", 128, (0, 4)))
    cache = PagedKVCache(kinds=kinds, num_pages=9, block_size=16, max_slots=2,
                         max_blocks_per_slot=4, dtype=jnp.bfloat16)
    # a row wider than a lane tile is stored in whole tiles
    assert cache.pools["latent"].shape == (5, 9, 1, 16, 640)
    assert cache.pools["index"].shape == (2, 9, 1, 16, 128)
    assert [p.shape for p in cache.pool_args()] == [(5, 9, 1, 16, 640),
                                                    (2, 9, 1, 16, 128)]
    assert cache.kv_bytes_per_token() == 2 * (5 * 640 + 2 * 128)
    assert cache.alloc_slot(0, 40) and cache.allocator.pages_in_use == 3
    assert (np.asarray(cache.table_array())[0, :3] > 0).all()
    cache.update(*(p + 1 for p in cache.pool_args()))
    assert float(cache.pools["index"][0, 0, 0, 0, 0]) == 1.0
    # the K/V pair of a plain decoder is the same cache, declared
    kv = PagedKVCache(2, 4, 16, num_pages=5, block_size=4, max_slots=1,
                      max_blocks_per_slot=4)
    assert [k.name for k in kv.kinds] == ["k", "v"]
    assert kv.k.shape == kv.v.shape == (2, 5, 1, 4, 64)
    assert gpt_tiny().page_kinds() == kv.kinds


def test_gpt_serving_is_unchanged_through_the_pools_door():
    """GPT declares `k` and `v`; its logits through the engine's
    PagedPools door are, bit for bit, those of the K/V view the stack
    always took, and its programs keep their names."""
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    eng = ServingEngine(model, ServingConfig(
        max_batch_slots=2, block_size=4, max_context_len=32,
        prefill_buckets=(8,), batch_buckets=(1,)))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, 8)), jnp.int32)
    table = jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    logits, k, v = eng._fwd(eng.params, ids, eng.cache.k, eng.cache.v, table, pos)
    from paddle_tpu.core.tensor import Tensor, no_grad
    with no_grad():
        direct, view = model(Tensor(ids), caches=PagedCacheView(
            Tensor(eng.cache.k), Tensor(eng.cache.v), Tensor(table)),
            cache_pos=Tensor(pos))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(direct._data))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(view.k._data))
    assert "HloModule jit_serve_decode" in eng._get_decode().compiled.as_text()
    assert eng._get_prefill(1, 8).compiled.as_text().startswith(
        "HloModule jit_serve_prefill_1x8")


def test_counters_and_prefill_span_attributes():
    cfg, model, eng = build()
    t0 = __import__("time").perf_counter()
    prompt = np.random.default_rng(0).integers(0, 256, (21,)).astype(np.int32)
    eng.generate([prompt], max_new_tokens=5)
    counted = eng._stats["model_counters"]
    # decode steps at contexts 22..25 (the first token came from prefill)
    assert counted["serve_dsa_available_total"] == sum(range(22, 26))
    assert counted["serve_dsa_selected_total"] == 4 * cfg.index_topk
    routed = sum(v for k, v in counted.items() if "routed_tokens" in k)
    pairs = 4 * 4 * cfg.num_experts_per_tok          # steps x layers x top-k
    assert routed + counted["serve_moe_skipped_pairs_total"] == pairs
    spans = [r for r in trace.spans(since=t0) if r[0] == "serve.prefill"]
    assert [(r[6]["chunk"], r[6]["ctx"]) for r in spans] == [
        ((8,), (0,)), ((8,), (8,)), ((5,), (16,))]


@pytest.mark.parametrize("budget", [8, 16])
def test_a_prefill_token_budget_bounds_a_step_and_changes_no_token(budget):
    """Under `ServingConfig.prefill_token_budget` a step prefills the
    oldest admissions' chunks that fit, the rest wait; every request
    still returns the tokens it returns without a budget."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (21, 30, 11, 17)]

    def serve(budget):
        paddle.seed(3)
        model = glm.GlmMoeDsaForCausalLM(glm.glm_moe_dsa_tiny())
        with flag_scope("serve_prefill_chunk", CHUNK):
            eng = ServingEngine(model, ServingConfig(
                max_batch_slots=4, block_size=PAGE, max_context_len=64,
                prefill_buckets=(CHUNK,), batch_buckets=(1,),
                prefill_token_budget=budget))
        t0 = __import__("time").perf_counter()
        out = eng.generate(prompts, max_new_tokens=4)
        steps = [r[6]["n_groups"] for r in trace.spans(since=t0)
                 if r[0] == "serve.step"]
        return [np.asarray(o) for o in out], steps

    free, free_steps = serve(0)
    held, steps = serve(budget)
    assert max(free_steps) == len(prompts)          # a chunk for every slot
    assert max(steps) == budget // CHUNK
    for a, b in zip(free, held):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("who", ["system", "control"])
def test_the_cells_checks_pass_the_system_and_refuse_the_control(who):
    """The serving cell's comparison (harness/glm_serve_runner.py) at a
    small size, under the cell's own limits: the system's probe — two
    slots live at different lengths on interleaved pages — passes every
    check; the reference computed in bfloat16 throughout is refused by
    both checks that hold the float32 scores."""
    import json
    import sys
    sys.path.insert(0, ROOT)
    try:
        from benchmark.harness import glm_serve_runner as runner
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(
            ROOT, "benchmark/workloads/glm52_ep16.serve.closed32_ctx8k.json")) as f:
        tol = json.load(f)["correct"]
    cfg, model, eng = build()
    ids = np.random.default_rng(4).integers(0, 256, (27,)).astype(np.int32)
    got = runner.probe_system(eng, model, ids, [24, 16], 3)
    assert got["rows"] == [7, 15, 23, 24, 25, 26]
    assert got["probe_rows"] == [7, 15, 23, 24, 16, 25, 17, 26, 18]
    if who == "control":
        got = runner.control_system(ref, eng.params, ids, sizes_of(cfg),
                                    got["rows"], jnp.bfloat16)
    verdict = {}
    runner.judge_probe(lambda name, ok, detail: verdict.update({name: ok}),
                       tol, got, ref, eng.params, ids, sizes_of(cfg))
    refused = {name for name, ok in verdict.items() if not ok}
    assert len(verdict) == 5
    if who == "system":
        assert not refused
    else:
        assert set(runner._SCORE_CHECKS) <= refused


def test_the_cells_arithmetic_against_hand_numbers():
    """benchmark/models/glm_moe_dsa.py at the published widths: the
    chip's share is 3.88 B parameters; a token at a context of 10,000
    needs ~3.7 GFLOP; a cached position holds 6,272 B, stored as 6,912."""
    import json
    fam = _load("benchmark/models/glm_moe_dsa.py", "fam_glm_moe_dsa")
    with open(os.path.join(ROOT, "benchmark/configs/glm52_ep16.json")) as f:
        sz = fam.sizes(json.load(f))
    assert sz["mlp_layer_types"] == ("dense",) + ("sparse",) * 4
    assert sz["indexer_types"] == ("full", "shared", "shared", "shared", "full")
    # by hand: 5 x 165.0 M attention + 2 x 9.4 M indexer + 226.5 M dense FFN
    # + 4 x (1.6 M router + 37.7 M shared + 0.5 x 37.7 M held) + 119.5 M head
    assert abs(fam.matmul_params_per_token(sz, True) / 1e9 - 1.423) < 0.001
    # + 5 layers x 2,048 selected x 65,536 + 2 x 10,000 scored x 8,192
    assert abs(fam.flops_per_token(sz, 10000) / 1e9 - 3.68) < 0.01
    assert fam.prefill_flops(sz, 0, 1) == fam.flops_per_token(sz, 1)
    read = fam.decode_read_bytes(sz, 2048, 10000, "bfloat16")
    assert read == {"latent": 5 * 2048 * 1152, "index": 2 * 10000 * 256}
    assert sum(fam.decode_read_bytes(sz, 1, 1, "bfloat16").values()) == 6272
    assert fam.kv_bytes_per_token(sz, "bfloat16") == 6912
