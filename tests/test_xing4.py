"""The `xing4_0` family (a four-stream mHC residual path, dense MLA over
the latent cache, every expert held) at a small size on the CPU, each
piece against the plain reference (benchmark/reference/xing4.py) on
seeded weights; the latent form of the paged decode kernel against XLA;
and the pins that hold the families it shares code with to the programs
they traced to before."""
import hashlib
import importlib.util
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.incubate.moe import held_experts_ffn, sigmoid_topk_routing
from paddle_tpu.models import xing4
from paddle_tpu.monitor import trace
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_cache import blocks_needed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/xing4.py", "ref_xing4")

CHUNK, PAGE = 8, 4


def sizes_of(cfg):
    """What the reference needs beside the weights."""
    sz = {k: getattr(cfg, k) for k in (
        "num_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "num_experts_per_tok", "routed_scaling_factor", "experts_held",
        "mlp_layer_types", "rope_theta", "rms_norm_eps", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp")}
    sz["rope"] = dict(factor=cfg.rope_factor,
                      original_max=cfg.rope_original_max_position,
                      beta_fast=cfg.rope_beta_fast,
                      beta_slow=cfg.rope_beta_slow,
                      mscale_all_dim=cfg.rope_mscale_all_dim)
    return sz


def build(seed=3, slots=2, **kw):
    """Weights of N(0, 0.2), ten times the published range: at these
    widths a sublayer's output is then as large as the stream it is
    added to (as it is at the published widths with 0.02), so the
    streams part and the mappings matter."""
    paddle.seed(seed)
    cfg = xing4.xing4_tiny(**{"initializer_range": 0.2, **kw})
    model = xing4.Xing4ForCausalLM(cfg)
    with flag_scope("serve_prefill_chunk", CHUNK):
        eng = ServingEngine(model, ServingConfig(
            max_batch_slots=slots, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,)))
    return cfg, model, eng


def through_pages(eng, model, ids, plen):
    """(logits, taps) of the last row of every program: the prompt
    through the engine's forward in chunks (the plain path at position
    0, the context path after), then a decode step a token, all in the
    engine's pools; and every row's chosen experts and `H_res`."""
    slots, mb = eng.config.max_batch_slots, eng.cache.max_blocks_per_slot
    need = blocks_needed(len(ids), PAGE)
    table = np.zeros((slots, mb), np.int32)
    table[0, :need] = 1 + np.arange(need)
    table = jnp.asarray(table)
    pools, logits, rows = eng.cache.pool_args(), [], []
    n_sub = 2 * model.cfg.num_layers
    n_moe = sum(t == "sparse" for t in model.cfg.mlp_layer_types)
    routing = [np.zeros((len(ids), model.cfg.num_experts_per_tok), np.int32)
               for _ in range(n_moe)]
    h_res = [np.zeros((len(ids),) + (model.cfg.hc_mult,) * 2, np.float32)
             for _ in range(n_sub)]

    def fwd(tokens, tbl, pos, ctx, at, n_rows):
        nonlocal pools
        model.taps = {}
        out, pools, _ = eng._forward(eng.params, tokens, pools, tbl,
                                     jnp.asarray(pos, jnp.int32), ctx=ctx)
        taps, model.taps = model.taps, None
        for mine, theirs in zip(routing + h_res,
                                taps["router_topk"] + taps["h_res"]):
            mine[at:at + n_rows] = np.asarray(theirs[0])[:n_rows]
        logits.append(np.asarray(out[0, n_rows - 1]))
        rows.append(at + n_rows - 1)

    for at in range(0, plen, CHUNK):
        clen = min(CHUNK, plen - at)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :clen] = ids[at:at + clen]
        fwd(jnp.asarray(toks), table[:1], [at], at > 0, at, clen)
    for t in range(plen, len(ids)):
        toks = np.zeros((slots, 1), np.int32)
        toks[0, 0] = ids[t]
        pos = np.zeros((slots,), np.int32)
        pos[0] = t
        fwd(jnp.asarray(toks), table, pos, False, t, 1)
    return np.stack(logits), rows, routing, h_res


IDS = np.random.default_rng(5).integers(0, 256, (27,)).astype(np.int32)


@pytest.mark.parametrize("kernel", [
    pytest.param(True, marks=pytest.mark.pallas, id="kernel"),
    pytest.param(False, id="gathered-xla")])
def test_prefill_chunks_and_decode_match_the_reference(kernel):
    """A prompt of 21 tokens in chunks of 8 (the plain path, then two
    context chunks, the last one cut), then 6 decode steps — the
    absorbed form over the latent pool, through the interpreted kernel
    or the gathered XLA form — against the reference's ONE non-absorbed
    causal forward: the last row's logits of every program, every row's
    `H_res` of all four sublayers, every row's chosen experts."""
    cfg, model, eng = build()
    try:
        got, rows, routing, h_res = through_pages(eng, model, IDS, 21)
        want = ref.forward(eng.params, IDS, sizes_of(cfg), rows=rows,
                           forced={"routing": routing})
    finally:
        eng.shutdown()
    np.testing.assert_allclose(got, np.asarray(want["logits"]), rtol=2e-4,
                               atol=2e-5)
    for j in want["routing_judged"]:
        assert bool(j["sizes_equal"]) and float(j["min_overlap"]) == 1.0
    assert len(h_res) == 4 == len(want["h_res"])
    for mine, theirs in zip(h_res, want["h_res"]):
        # doubly stochastic to the iteration's reach, and the reference's
        np.testing.assert_allclose(mine.sum(-1), 1.0, atol=1e-4)
        np.testing.assert_allclose(mine.sum(-2), 1.0, atol=1e-4)
        np.testing.assert_allclose(mine, np.asarray(theirs), atol=1e-4)
        # the initializer's point: the DYNAMIC part decides, so a missing
        # projection could not pass
        swing = mine.max(0) - mine.min(0)
        assert 0.1 < swing.max() <= 1.0 and swing.min() > 0.02, swing


def test_the_controls_are_not_the_model():
    """What the cell's comparison has to refuse, at the small size: with
    `H_res` = identity the logits move by far more than any rounding,
    and in bfloat16 the mappings move by a rounding's worth."""
    cfg, model, eng = build()
    eng.shutdown()
    sz, rows = sizes_of(cfg), [20, 26]
    want = ref.forward(eng.params, IDS, sz, rows=rows)
    ident = ref.forward(eng.params, IDS, sz, rows=rows, h_res="identity")
    err = np.abs(np.asarray(ident["logits"] - want["logits"])).max() \
        / np.abs(np.asarray(want["logits"])).max()
    assert err > 0.02, err        # float32 rounding reads 2e-4 here
    low = ref.forward(eng.params, IDS, sz, rows=rows, dtype=jnp.bfloat16)
    p = low["mhc_probe"][-1]
    hc = "layers.1.ffn_hc."
    anew = ref.mhc_mappings_of(
        p["x"], eng.params[hc + "phi"], eng.params[hc + "alpha"],
        eng.params[hc + "b"], n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters,
        eps=cfg.hc_eps, clamp=cfg.mhc_h_res_clamp)[2]
    off = float(jnp.max(jnp.abs(p["h_res"] - anew)))
    assert 1e-4 < off < 0.1, off


def test_one_stream_with_unit_mappings_is_a_plain_residual_model():
    """n = 1 with H_pre = H_post = 1 forced (alpha 0, b_pre 30, b_post
    0; H_res is 1 by Sinkhorn on a 1 x 1 matrix): the model is x' = x +
    F(RMSNorm(x)), what every other family here is."""
    cfg, model, eng = build(hc_mult=1)
    try:
        for name, p in eng.params.items():
            if name.endswith("_hc.alpha"):
                eng.params[name] = jnp.zeros_like(p)
            if name.endswith("_hc.b"):
                eng.params[name] = jnp.asarray([30.0, 0.0, 0.0], p.dtype)
        got, rows, routing, h_res = through_pages(eng, model, IDS, 21)
        want = ref.forward(eng.params, IDS, sizes_of(cfg), rows=rows,
                           forced={"routing": routing}, plain=True)
    finally:
        eng.shutdown()
    assert all(np.allclose(m, 1.0, atol=1e-5) for m in h_res)
    np.testing.assert_allclose(got, np.asarray(want["logits"]), rtol=2e-4,
                               atol=2e-5)


def test_all_experts_held_is_the_sum_of_four_shares():
    """The guide's share test with this family's deployment: what ONE
    chip computes with all 8 experts held equals the sum of what four
    chips of 2 would, and the reference's uncut routed part."""
    cfg = xing4.xing4_tiny()
    rng = np.random.default_rng(2)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
    x = n(13, D)
    weights = {"router.weight": n(D, E), "router.bias": n(E) * 0.1,
               "experts.w_in": n(E, D, 2 * F), "experts.w_out": n(E, F, D)}
    routing = sigmoid_topk_routing(
        x, weights["router.weight"], weights["router.bias"],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    whole, tokens, here = held_experts_ffn(
        x, routing, weights["experts.w_in"], weights["experts.w_out"], 0)
    assert bool(jnp.all(here)) and int(tokens.sum()) == 13 * 2
    parts = sum(held_experts_ffn(
        x, routing, weights["experts.w_in"][f:f + 2],
        weights["experts.w_out"][f:f + 2], f)[0] for f in range(0, E, 2))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(parts),
                               rtol=1e-5, atol=1e-6)
    uncut, _, _ = ref.routed_part(x, weights, "", sizes_of(cfg),
                                  experts=(0, E))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)


def test_yarn_frequencies_against_hand_values():
    """The published rotary: 32 pairs over theta 10,000, factor 64 over
    4,096 original positions, beta 32 / 1. Pair i turns 4096 f_i / 2 pi
    times over the original context: more than 32 up to pair 10
    (10.47 -> floor), fewer than 1 from pair 23 on (22.51 -> ceil)."""
    want = {0: 1.0, 10: 10 ** -1.25, 23: 10 ** -2.875 / 64,
            31: 10 ** -3.875 / 64}
    ramp = (16 - 10) / 13
    want[16] = 10 ** -2.0 * (ramp / 64 + 1 - ramp)
    for freq in (xing4.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0),
                 ref.yarn_frequencies(64, 10000.0, 64.0, 4096, 32.0, 1.0)):
        assert freq.shape == (32,) and freq.dtype == jnp.float32
        for i, f in want.items():
            assert float(freq[i]) == pytest.approx(f, rel=1e-5), i
    # factor 1: the plain rotary embedding's
    np.testing.assert_allclose(
        np.asarray(xing4.yarn_inv_freq(64, 10000.0, 1.0, 4096, 32.0, 1.0)),
        10000.0 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)
    assert xing4.Xing4Config().attn_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)


def test_one_latent_page_kind_in_every_layer():
    cfg = xing4.Xing4Config(mlp_layer_types=("dense",) + ("sparse",) * 5)
    (kind,) = cfg.page_kinds()
    assert (kind.name, kind.width, kind.layers, kind.heads, kind.lifetime) \
        == ("latent", 576, (0, 1, 2, 3, 4, 5), 1, "slot")
    assert kind.stored_width() == 640
    _, _, eng = build()
    try:
        (pool,) = eng.cache.pool_args()
        assert pool.shape[0] == 2 and pool.shape[2:] == (1, PAGE, 20)
        assert not eng.cache.windows
    finally:
        eng.shutdown()
    # a sublayer's mHC parameters at the published widths: 344,091
    n, C = 4, 3584
    assert n * C * (2 * n + n * n) + 3 + (2 * n + n * n) == 344_091


# -- the latent form of the paged kernel ------------------------------------------

def _latent_state(rng, dtype, slots=3, H=4, r=16, dr=4, width=20, bs=4, mb=6):
    """A pool of two layers' pages, slots at different positions on
    interleaved pages, absorbed queries padded to the pool's width."""
    n_pages = slots * mb + 1
    pool = jnp.asarray(rng.standard_normal((2 * n_pages, 1, bs, width))
                       .astype(np.float32)).astype(dtype)
    pool = pool.at[..., r + dr:].set(0)
    tbl = jnp.asarray(1 + rng.permutation(slots * mb).reshape(slots, mb)
                      .astype(np.int32))
    pos = jnp.asarray([bs * mb - 1, 5, 0][:slots], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, H, width))
                    .astype(np.float32)).astype(dtype)
    q = q.at[..., r + dr:].set(0)
    return q, pool, tbl, pos, n_pages


def _latent_ref(q, pool, tbl, pos, scale, r):
    """The gathered XLA form in float32."""
    rows = pool[tbl].astype(jnp.float32)             # [B, mb, 1, bs, W]
    rows = rows.reshape(rows.shape[0], -1, rows.shape[-1])
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows,
                   precision="highest") * scale
    seen = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhk,bkr->bhr", p, rows[..., :r], precision="highest")


@pytest.mark.pallas
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["f32", "bf16"])
def test_paged_mla_decode_matches_the_gathered_form(dtype, tol):
    from paddle_tpu.ops.pallas.paged_decode import paged_mla_decode
    q, pool, tbl, pos, n_pages = _latent_state(np.random.default_rng(0), dtype)
    got = jax.jit(lambda *a: paged_mla_decode(
        *a, scale=0.25, value_width=16))(q, pool, tbl + n_pages, pos)
    assert got.shape == (3, 4, 16) and got.dtype == dtype
    want = _latent_ref(q, pool, tbl + n_pages, pos, 0.25, 16)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.pallas
def test_paged_mla_decode_bf16_keeps_f32_probabilities():
    """As `test_paged_decode_bf16_keeps_f32_probabilities`: on the same
    bf16-representable values the bf16 path (one-pass products, the
    float32 probabilities in three bf16 parts) gives what the float32
    path gives, bit for bit once that is rounded to the bf16 output."""
    from paddle_tpu.ops.pallas.paged_decode import paged_mla_decode
    q, pool, tbl, pos, _ = _latent_state(np.random.default_rng(1),
                                         jnp.bfloat16)
    got = paged_mla_decode(q, pool, tbl, pos, scale=0.25, value_width=16)
    f32 = paged_mla_decode(q.astype(jnp.float32), pool.astype(jnp.float32),
                           tbl, pos, scale=0.25, value_width=16)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(f32.astype(jnp.bfloat16).astype(jnp.float32)))


# -- what the engine counts and names ----------------------------------------------

def test_decode_steps_count_latent_reads_and_no_skipped_pair():
    cfg, model, eng = build()
    try:
        t0 = time.perf_counter()
        eng.generate([IDS[:21].tolist()], max_new_tokens=6)
        counters = eng._stats["model_counters"]
        spans = trace.spans(since=t0, name="serve.decode")
    finally:
        eng.shutdown()
    # five decode steps at positions 21 .. 25 read pos + 1 positions each
    assert counters["serve_attn_read_positions_total{lifetime=slot}"] \
        == sum(range(22, 27))
    assert [r[6]["read_latent"] for r in spans] == list(range(22, 27))
    assert counters["serve_moe_skipped_pairs_total"] == 0
    given = sum(v for k, v in counters.items()
                if k.startswith("serve_moe_routed_tokens_total"))
    assert given == 5 * cfg.num_experts_per_tok      # one expert layer


def test_expert_products_resolve_to_their_users_block():
    """XLA:TPU turns `ragged_dot` into a kernel call of its own making
    (`op_name="ragged-dot-none"`: no path, no block). The scope index
    gives such an instruction the block of the instructions that read
    it, so the held experts' products count under `moe`."""
    from paddle_tpu.jit.aot import parse_scopes
    hlo = "\n".join([
        "HloModule jit_serve_decode, entry_computation_layout={()->()}",
        '  %fusion.3 = bf16[256,3584] fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/moe/gather"}',
        '  %ragged-dot-none.1 = f32[256,2048] custom-call(%g.2, %fusion.3, '
        '%w_in.1), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  %fusion.4 = bf16[256,1024] fusion(%ragged-dot-none.1), kind=kLoop, '
        'metadata={op_name="jit(f)/moe/convert_element_type"}',
        '  %ragged-dot-none = f32[256,3584] custom-call(%g.2, %fusion.4, '
        '%w_out.1), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  %convert.8 = bf16[256,3584] convert(%ragged-dot-none), '
        'metadata={op_name="jit(f)/moe/convert_element_type"}',
        '  %lonely = f32[4] custom-call(%p), '
        'metadata={op_name="ragged-dot-none"}',
        '  %other.1 = f32[4] add(%lonely, %p), '
        'metadata={op_name="jit(f)/attn/add"}',
        '  %other.2 = f32[4] add(%lonely, %p), '
        'metadata={op_name="jit(f)/ffn/add"}'])
    module, table = parse_scopes(hlo)
    assert module == "jit_serve_decode"
    assert table["ragged-dot-none.1"] == ("moe", "fwd")
    assert table["ragged-dot-none"] == ("moe", "fwd")
    assert "lonely" not in table                  # its readers disagree


# -- the families that share code trace to what they were --------------------------

#: sha256 of `str(jaxpr)` of the tiny engines' three serving programs
#: (chunk 8, page 4, two slots, float32), taken from the commit BEFORE
#: the MLA pieces and the expert layer became functions this family
#: shares and the paged kernel gained its latent form (b25b6940, PR 34):
#: a refactor, so both families trace to what they were, equation for
#: equation. A change of jax's printer would move the hashes with no
#: change here: take them anew from that commit then.
_PR34_JAXPR = {
    "glm-prefill": "0d09e7abee2e9c398a630ad1380feec6818ff27e76df8e6b79f4314df9ddcc27",
    "glm-prefill_ctx": "8724b9168172a66e333bb33979ff8032362fa3af374acf8f05ca9f9eaab60118",
    "glm-decode": "15458d039b0e1eb85fad54fcb69277095dc1d7df4071caea0bfa308ae03b0437",
    "cohere-prefill": "dadc5fbed6cba012dc69a03f92087e3000eca152f154b4b5feec49fe5927ae9c",
    "cohere-prefill_ctx": "0b30e3aa7094a86714023d0049c19087e04ac8a4d4457b5479890cf7dddb671a",
    "cohere-decode": "5198bedf17f1fae10a82721658468fe0edf0d4e3174deeea45b9dbc9e1efdb24",
    "window-group4-bfloat16": "af7e8113b511407dba5c152de88e0c1e7a4bb6d019fe1646fe44067dc08175c9",
    "window-group4-float32": "9fbb2d87ba7b6c365a038a3a99f93ab10ff9fb84eeab971a915fbb9777abfefd",
    "group16-bfloat16": "904049245766077319d3897059fe63094510675d099a86935a0bc8ba304108b2",
    "group16-float32": "05bfcdee91a437f3f5518e4d013b1cdc73e8200a3049564e24efda08c85442d2"}


def _sha(text) -> str:
    return hashlib.sha256(str(text).encode()).hexdigest()


@pytest.mark.pallas
@pytest.mark.parametrize("family", ["glm", "cohere"])
def test_the_sharing_families_programs_trace_as_before(family):
    if family == "glm":
        from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaForCausalLM,
                                                   glm_moe_dsa_tiny)
        make = lambda: GlmMoeDsaForCausalLM(glm_moe_dsa_tiny())
    else:
        from paddle_tpu.models.cohere2_moe import (Cohere2MoeForCausalLM,
                                                   cohere2_moe_tiny)
        make = lambda: Cohere2MoeForCausalLM(cohere2_moe_tiny())
    paddle.seed(3)
    with flag_scope("serve_prefill_chunk", CHUNK):
        eng = ServingEngine(make(), ServingConfig(
            max_batch_slots=2, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,)))
    try:
        for kind, (prog, args) in (
                ("prefill", eng._prefill_program(1, CHUNK)),
                ("prefill_ctx", eng._prefill_ctx_program(1, CHUNK)),
                ("decode", eng._decode_program())):
            assert _sha(prog._jitted.trace(*args).jaxpr) \
                == _PR34_JAXPR[f"{family}-{kind}"], kind
    finally:
        eng.shutdown()


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_grouped_and_windowed_kernel_forms_trace_as_before(dtype):
    """`kv_group` 4 under a window and `kv_group` 16 (Command A+'s two
    kinds of layer): the kernel they traced to before the latent form."""
    from paddle_tpu.ops.pallas.paged_decode import paged_decode_attention
    pool = jnp.zeros((40, 1, 16, 256), dtype)
    tbl, pos = jnp.zeros((4, 12), jnp.int32), jnp.zeros((4,), jnp.int32)
    name = jnp.dtype(dtype).name
    text = jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a[:5], scale=0.125, first=a[5]))(
        jnp.zeros((4, 16, 64), dtype), pool, pool, tbl, pos, pos)
    assert _sha(text) == _PR34_JAXPR["window-group4-" + name]
    text = jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, scale=0.125))(jnp.zeros((4, 32, 128), dtype), pool, pool, tbl,
                          pos)
    assert _sha(text) == _PR34_JAXPR["group16-" + name]
