"""Serving runtime (paddle_tpu.serving, ISSUE 6): paged KV decode,
continuous batching, AOT serving signatures, load generator, metrics."""

import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.core.tensor import no_grad
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.monitor import scoped_registry
from paddle_tpu.serving import (BlockAllocator, BucketTable, LoadSpec,
                                Request, SamplingParams, ServingConfig,
                                ServingEngine, StreamingDetokenizer,
                                build_requests)

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    return GPTForPretraining(gpt_tiny())


def _engine(model, **kw):
    cfg = dict(max_batch_slots=3, block_size=4, max_context_len=64,
               prefill_buckets=(8, 16), batch_buckets=(1, 2))
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _golden(model, prompt, n):
    """Re-derive every generated token by full uncached forwards."""
    seq = np.asarray(prompt, np.int32)
    for _ in range(n):
        with no_grad():
            lg = model(paddle.to_tensor(seq[None, :])).numpy()
        seq = np.concatenate([seq, [np.int32(lg[0, -1].argmax())]])
    return seq


# ---------------------------------------------------------------------------
# host-side building blocks
# ---------------------------------------------------------------------------


def test_block_allocator():
    a = BlockAllocator(num_pages=5)            # page 0 reserved
    assert a.free_pages == 4 and a.pages_in_use == 0
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.alloc(2) is None                  # all-or-nothing
    assert a.pages_in_use == 3
    a.free(got[:2])
    assert a.free_pages == 3
    with pytest.raises(ValueError):
        a.free([0])                            # scratch page never freed


def test_bucket_table():
    t = BucketTable((8, 16, 32), (1, 2, 4))
    assert t.len_bucket(3) == 8
    assert t.len_bucket(16) == 16
    assert t.len_bucket(17) == 32
    with pytest.raises(ValueError):
        t.len_bucket(33)
    assert t.batch_bucket(1) == 1
    assert t.batch_bucket(3) == 4
    assert t.batch_bucket(9) == 4              # clamps to the largest
    assert len(t.signatures()) == 9


def test_request_validation(tiny_model):
    with pytest.raises(ValueError):
        Request([], max_new_tokens=4)
    with pytest.raises(ValueError):
        Request([1, 2], max_new_tokens=0)
    eng = _engine(tiny_model)
    with pytest.raises(ValueError):            # exceeds slot capacity
        eng.submit(Request(np.arange(60), max_new_tokens=10))
    # a request that can never hold its pages even alone must be
    # rejected at submit, not spin admission forever (livelock guard)
    small = _engine(tiny_model, num_pages=4, max_context_len=40,
                    prefill_buckets=(40,))
    with pytest.raises(ValueError, match="KV pages"):
        small.submit(Request(np.arange(2, 32), max_new_tokens=8))
    # the admission limit is the CONFIGURED window, not the cache's
    # block-rounded capacity (block 4 rounds 30 up to 32 physically)
    odd = _engine(tiny_model, block_size=4, max_context_len=30,
                  prefill_buckets=(30,))
    with pytest.raises(ValueError, match="context"):
        odd.submit(Request(np.arange(2, 28), max_new_tokens=6))  # 32 > 30


def test_serving_config_not_mutated_across_engines(tiny_model):
    cfg = ServingConfig(max_batch_slots=2, block_size=4,
                        max_context_len=512)
    e1 = ServingEngine(tiny_model, cfg)
    # gpt_tiny's max_position_embeddings=128 clamps the ENGINE's copy,
    # never the caller's config object
    assert e1.config.max_context_len == 128
    assert cfg.max_context_len == 512
    assert cfg.prefill_buckets is None and cfg.num_pages is None


def test_sampling_greedy_matches_argmax():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import sample_tokens
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    toks = sample_tokens(logits, jax.random.key(0),
                         jnp.zeros((4,), jnp.float32),
                         jnp.zeros((4,), jnp.int32),
                         jnp.ones((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(logits).argmax(-1))
    # top_k=1 is greedy regardless of temperature
    toks1 = sample_tokens(logits, jax.random.key(1),
                          jnp.full((4,), 1.3, jnp.float32),
                          jnp.ones((4,), jnp.int32),
                          jnp.ones((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(toks1),
                                  np.asarray(logits).argmax(-1))


# ---------------------------------------------------------------------------
# decode parity (acceptance: token-exact vs the full-context forward)
# ---------------------------------------------------------------------------


#: every way a serving program reads and writes the pool: plain decode,
#: context prefill (chunked), speculative verify, a LoRA engine on the
#: zero adapter, int8 pools — flags are read at engine construction
FEATURES = {
    "plain": ({}, {}),
    "chunked-prefill": ({"serve_prefill_chunk": 4}, {}),
    "prefix-cache": ({"serve_prefix_cache": True}, {}),
    "spec-verify": ({"serve_spec_k": 2}, {}),
    "lora": ({}, {"lora_adapters": 2, "lora_rank": 4}),
    "int8": ({"serve_kv_quant": "int8"}, {}),
    "bf16-cache": ({}, {"cache_dtype": "bfloat16"}),
}


def _feature_engine(model, feature, **kw):
    import contextlib
    flags, cfg = FEATURES[feature]
    with contextlib.ExitStack() as st:
        for k, v in flags.items():
            st.enter_context(flag_scope(k, v))
        return _engine(model, **cfg, **kw)


@pytest.mark.parametrize("feature", [f for f in FEATURES
                                     if f != "bf16-cache"])
def test_paged_decode_token_exact_scan_layout(tiny_model, feature):
    """prefill+decode split under scan == full forward, several prompt/
    generation lengths, slots finishing early — through every serving
    program that addresses the pool (FEATURES)."""
    eng = _feature_engine(tiny_model, feature)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, 250, (n,)).astype(np.int32)
               for n in (3, 7, 14)]
    if feature == "spec-verify":        # something for the drafter to eat
        prompts.append(np.array([3, 4, 5, 3, 4, 5, 3, 4], np.int32))
    if feature == "prefix-cache":       # a donated prefix, then its hit
        eng.generate([np.concatenate([prompts[2], [7, 8]])],
                     max_new_tokens=2)
    outs = eng.generate(prompts, max_new_tokens=6)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _golden(tiny_model, p, 6))
    if feature == "spec-verify":
        assert eng._stats["verify_dispatches"] > 0
    if feature == "chunked-prefill":
        assert eng._stats["prefill_chunks"] > 0
    if feature == "prefix-cache":
        assert eng.prefix_cache.stats["hits"] > 0


@pytest.mark.parametrize("feature", list(FEATURES))
def test_paged_decode_loop_layout_matches_scan(tiny_model, feature):
    from paddle_tpu.nn import scan as nn_scan
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, 250, (n,)).astype(np.int32)
               for n in (5, 11)]
    scan_out = _feature_engine(tiny_model, feature).generate(
        prompts, max_new_tokens=5)
    nn_scan.reset_scan_stats()
    with flag_scope("scan_decode", False), warnings.catch_warnings(
            record=True) as w:
        warnings.simplefilter("always")
        loop_out = _feature_engine(tiny_model, feature).generate(
            prompts, max_new_tokens=5)
    for a, b in zip(scan_out, loop_out):
        np.testing.assert_array_equal(a, b)
    # the kill switch is a RECORDED degradation, not a silent one
    assert nn_scan.SCAN_STATS["fallbacks"] >= 1
    msgs = [str(x.message) for x in w
            if "scan-over-layers fell back" in str(x.message)]
    assert len(msgs) == 1              # one-time warning, not per step


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "loop"])
@pytest.mark.parametrize("ctx", [False, True], ids=["prefill", "context"])
def test_layer_writes_stay_in_its_own_pages(tiny_model, scan, ctx):
    """The pool is one array of L*P pages and layer ``l`` owns pages
    ``l*P .. l*P+P-1`` of it: a forward writes each layer's rows — the
    padded tail's and an all-scratch row's scratch writes included —
    into the pages its table names and into its OWN scratch page
    ``l*P``, nowhere else, in both layouts."""
    import jax.numpy as jnp
    with flag_scope("scan_decode", scan), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)   # loop fallback
        eng = _engine(tiny_model)
        L, P = eng.cache.k.shape[:2]
        table = np.zeros((2, eng.cache.max_blocks_per_slot), np.int32)
        table[0, :3] = [5, 2, 9]            # row 1: all scratch (padded)
        ids = np.random.default_rng(3).integers(2, 250, (2, 8))
        pos = np.array([3, 0], np.int32)    # row 0 writes positions 3..10
        _, pools, _ = eng._forward(
            eng.params, jnp.asarray(ids, jnp.int32), eng.cache.pool_args(),
            jnp.asarray(table), jnp.asarray(pos), ctx=ctx)
    for pool in pools:
        pool = np.asarray(pool)
        assert pool.shape == eng.cache.k.shape
        written = np.abs(pool).reshape(L, P, -1).max(-1) > 0
        for layer in range(L):              # page 0 = the layer's scratch
            assert set(np.flatnonzero(written[layer])) == {0, 5, 2, 9}
        rows = np.abs(pool[:, 5, 0]).max(-1) > 0          # [L, bs]
        assert (rows == [False, False, False, True]).all()   # pos 3 only


def test_harness_probe_pool_runs_through_fwd(tiny_model):
    """benchmark/harness/serve_runner.py builds a small pool by
    repeating the engine pool's trailing dims after ``(L, n)`` and runs
    prefill + one decode step through ``eng._fwd`` with page 0 as
    scratch: any such pool is a valid pool, whatever its page count."""
    import jax
    import jax.numpy as jnp
    eng = _engine(tiny_model)
    L, _, a, b, c = eng.cache.k.shape
    need = 3
    pool = jnp.zeros((L, need + 1, a, b, c), eng.cache.k.dtype)
    prompt = np.random.default_rng(5).integers(2, 250, (7,)).astype(np.int32)
    slots, mb = eng.config.max_batch_slots, eng.cache.max_blocks_per_slot
    table = np.zeros((slots, mb), np.int32)
    table[0, :need] = 1 + np.arange(need)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :7] = prompt
    toks = np.zeros((slots,), np.int32)
    toks[0] = 11
    pos = np.zeros((slots,), np.int32)
    pos[0] = 7

    @jax.jit
    def replay(params, k, v):
        _, k, v = eng._fwd(params, jnp.asarray(ids), k, v,
                           jnp.asarray(table[:1]), jnp.zeros((1,), jnp.int32))
        logits, _, _ = eng._fwd(params, jnp.asarray(toks)[:, None], k, v,
                                jnp.asarray(table), jnp.asarray(pos))
        return logits[0, -1]

    got = np.asarray(replay(eng.params, pool, pool))
    with no_grad():
        ref = tiny_model(paddle.to_tensor(
            np.concatenate([prompt, [11]])[None].astype(np.int32))).numpy()
    np.testing.assert_allclose(got, ref[0, -1], rtol=1e-4, atol=1e-5)


def test_mixed_finish_early_eos(tiny_model):
    rng = np.random.default_rng(3)
    p0 = rng.integers(2, 250, (6,)).astype(np.int32)
    p1 = rng.integers(2, 250, (9,)).astype(np.int32)
    eos = int(_golden(tiny_model, p0, 1)[-1])  # req 0's first token
    eng = _engine(tiny_model)
    st0 = eng.submit(Request(p0, max_new_tokens=8, eos_token_id=eos))
    st1 = eng.submit(Request(p1, max_new_tokens=8))
    eng.run()
    assert st0.generated == [eos]              # stopped at eos, token kept
    assert len(st1.generated) == 8
    np.testing.assert_array_equal(
        np.concatenate([p1, st1.generated]), _golden(tiny_model, p1, 8))
    assert eng.cache.allocator.pages_in_use == 0


# ---------------------------------------------------------------------------
# continuous batching (acceptance: >= 2 requests share one decode dispatch,
# streams stay correct, compile count bounded by the bucket table)
# ---------------------------------------------------------------------------


def test_continuous_batching_shares_decode_dispatch(tiny_model):
    eng = _engine(tiny_model)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(2, 250, (6,)).astype(np.int32)
               for _ in range(3)]
    streams = {i: [] for i in range(3)}
    states = []
    for i, p in enumerate(prompts):
        states.append(eng.submit(Request(
            p, max_new_tokens=5,
            on_token=lambda req, tok, txt, i=i: streams[i].append(tok))))
    eng.run()
    s = eng.stats()
    # 3 requests x 5 tokens = 15 tokens out of 3 (prefill-sampled) + 4
    # decode dispatches: batching demonstrably shared the decode program
    assert s["decode_batch_max"] >= 2
    assert s["decode_dispatches"] < s["tokens_generated"]
    for i, (p, st) in enumerate(zip(prompts, states)):
        assert streams[i] == st.generated
        np.testing.assert_array_equal(
            np.concatenate([p, st.generated]),
            _golden(tiny_model, p, 5))


def test_compile_count_bounded_by_bucket_table(tiny_model):
    from paddle_tpu.utils import CompileCounter
    eng = _engine(tiny_model)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 250, (n,)).astype(np.int32)
               for n in (4, 12)]
    eng.generate(prompts, max_new_tokens=3)
    s1 = eng.stats()
    # every program is a bucket-table signature (+ the one decode)
    assert s1["resident_programs"] <= len(eng.buckets.signatures()) + 1
    compiles_before = s1["program_compiles"]
    with CompileCounter() as c:
        eng.generate([rng.integers(2, 250, (n,)).astype(np.int32)
                      for n in (5, 10)], max_new_tokens=3)
    # same buckets -> ZERO new serving programs and zero re-traces
    assert eng.stats()["program_compiles"] == compiles_before
    assert c.jaxpr_traces == 0
    assert c.backend_compiles == 0


def test_slot_turnover_more_requests_than_slots(tiny_model):
    eng = _engine(tiny_model, max_batch_slots=2)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, 250, (5,)).astype(np.int32)
               for _ in range(5)]
    outs = eng.generate(prompts, max_new_tokens=4)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _golden(tiny_model, p, 4))
    s = eng.stats()
    assert s["completed"] == 5
    assert eng.cache.allocator.pages_in_use == 0
    assert eng.scheduler.queue_depth == 0


def test_padded_prefill_rows_never_touch_live_slots(tiny_model):
    """A prefill group smaller than its batch bucket carries padded rows;
    their garbage K/V must land on the scratch page, not in an active
    slot's pages (regression: padded rows once reused slot 0's block
    table)."""
    eng = _engine(tiny_model, max_batch_slots=4, batch_buckets=(1, 4))
    rng = np.random.default_rng(14)
    p0 = rng.integers(2, 250, (6,)).astype(np.int32)
    st0 = eng.submit(Request(p0, max_new_tokens=8))
    eng.step()                      # slot 0 admitted + first decode
    assert len(st0.generated) >= 1
    # 3 more arrive -> one prefill group of 3 padded up to batch bucket 4
    others = [rng.integers(2, 250, (6,)).astype(np.int32)
              for _ in range(3)]
    sts = [eng.submit(Request(p, max_new_tokens=4)) for p in others]
    eng.run()
    np.testing.assert_array_equal(
        np.concatenate([p0, st0.generated]), _golden(tiny_model, p0, 8))
    for p, st in zip(others, sts):
        np.testing.assert_array_equal(
            np.concatenate([p, st.generated]), _golden(tiny_model, p, 4))


def test_preemption_recompute_keeps_greedy_streams_exact(tiny_model):
    # pool of 9 usable pages, two requests needing 6 blocks each at the
    # end -> the newest-admitted must be preempted and recomputed
    eng = _engine(tiny_model, max_batch_slots=2, block_size=4,
                  max_context_len=24, num_pages=10,
                  prefill_buckets=(16, 24))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, 250, (10,)).astype(np.int32)
               for _ in range(2)]
    outs = eng.generate(prompts, max_new_tokens=12)
    assert eng.stats()["preemptions"] >= 1
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _golden(tiny_model, p, 12))
    assert eng.cache.allocator.pages_in_use == 0


def test_mixed_sampling_one_dispatch(tiny_model):
    """Greedy and sampled requests share the decode program (per-slot
    sampling params are arguments, not signatures)."""
    eng = _engine(tiny_model)
    rng = np.random.default_rng(8)
    p0 = rng.integers(2, 250, (6,)).astype(np.int32)
    p1 = rng.integers(2, 250, (6,)).astype(np.int32)
    st0 = eng.submit(Request(p0, max_new_tokens=5))           # greedy
    st1 = eng.submit(Request(p1, max_new_tokens=5,
                             sampling=SamplingParams(temperature=0.9,
                                                     top_k=20)))
    eng.run()
    np.testing.assert_array_equal(
        np.concatenate([p0, st0.generated]), _golden(tiny_model, p0, 5))
    assert all(0 <= t < 256 for t in st1.generated)
    assert eng.stats()["resident_programs"] == \
        len({("prefill", 2, 8), ("decode",)})  # one prefill + one decode


def test_sampling_reproducible_across_engines(tiny_model):
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, 250, (6,)).astype(np.int32)]
    sp = SamplingParams(temperature=0.8, top_k=12)
    a = _engine(tiny_model, seed=7).generate(prompts, max_new_tokens=6,
                                             sampling=sp)
    b = _engine(tiny_model, seed=7).generate(prompts, max_new_tokens=6,
                                             sampling=sp)
    c = _engine(tiny_model, seed=8).generate(prompts, max_new_tokens=6,
                                             sampling=sp)
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0])


# ---------------------------------------------------------------------------
# streaming, metrics, load generator, tooling
# ---------------------------------------------------------------------------


def test_streaming_detokenization(tiny_model):
    vocab = [f"w{i}" if i % 3 else f"##p{i}" for i in range(256)]
    detok = StreamingDetokenizer(vocab)
    eng = _engine(tiny_model)
    eng.config.detokenizer = detok
    rng = np.random.default_rng(10)
    p = rng.integers(2, 250, (5,)).astype(np.int32)
    pieces = []
    st = eng.submit(Request(p, max_new_tokens=4,
                            on_token=lambda r, t, txt: pieces.append(txt)))
    eng.run()
    assert len(pieces) == 4
    assert "".join(pieces) == detok.decode(st.generated)
    # wordpiece join: '##'-pieces glue, others get a space separator
    assert detok.decode([4, 6]) == "w4" + "p6"
    assert detok.decode([4, 5]) == "w4 w5"


def test_metrics_flow_through_registry(tiny_model):
    with scoped_registry() as reg:
        eng = _engine(tiny_model)
        rng = np.random.default_rng(11)
        eng.generate([rng.integers(2, 250, (6,)).astype(np.int32)
                      for _ in range(2)], max_new_tokens=4)
        assert reg.get("serve_ttft_seconds").count() == 2
        assert reg.get("serve_tpot_seconds").count() == 2
        assert reg.get("serve_e2e_seconds").count() == 2
        assert reg.get("serve_decode_step_seconds").count() >= 3
        assert reg.get("serve_requests_total").value(
            event="completed") == 2
        assert reg.get("serve_queue_depth").value() == 0
        assert reg.get("serve_active_slots").value() == 0
        assert reg.get("serve_kv_pages_in_use").value() == 0
        assert reg.get("serve_tokens_generated_total").value() == 8
    summary = eng.metrics_summary()
    assert summary["requests_completed"] == 2
    assert summary["tokens_generated"] == 8
    assert summary["tokens_per_sec"] and summary["tokens_per_sec"] > 0
    assert summary["decode_step_p99_s"] >= summary["decode_step_p50_s"]


def test_decode_live_page_counters(tiny_model):
    """``serve_decode_live_pages_total / serve_decode_table_pages_total``
    is the share of the decode steps' block-table rows that was live,
    counted on the host from the positions the step was built with. A
    request of prompt length n decodes tokens 1..3 at positions n, n+1,
    n+2 (prefill gave token 0), each owning ``pos // 4 + 1`` of its row's
    16 entries — whichever step each slot's decode ran in."""
    lens, new, bs, width = (3, 7, 14), 4, 4, 64 // 4
    with scoped_registry() as reg:
        eng = _engine(tiny_model)
        rng = np.random.default_rng(12)
        eng.generate([rng.integers(2, 250, (n,)).astype(np.int32)
                      for n in lens], max_new_tokens=new)
        live = sum((n + t) // bs + 1 for n in lens for t in range(new - 1))
        table = len(lens) * (new - 1) * width
        assert eng._stats["decode_slot_steps"] == len(lens) * (new - 1)
        assert reg.get("serve_decode_live_pages_total").value() == live
        assert reg.get("serve_decode_table_pages_total").value() == table
    summary = eng.metrics_summary()
    assert (summary["serve_decode_live_pages_total"]
            / summary["serve_decode_table_pages_total"]) == live / table
    assert live / table == pytest.approx(26 / 144)


def test_loadgen_deterministic_and_open_loop():
    spec = LoadSpec(num_requests=5, rate_rps=100.0,
                    prompt_len_range=(4, 8), max_new_range=(2, 4),
                    vocab_size=256, seed=3)
    a = build_requests(spec)
    b = build_requests(spec)
    assert [t for t, _ in a] == [t for t, _ in b]
    assert a[0][0] == 0.0
    for (_, ra), (_, rb) in zip(a, b):
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
        assert ra.max_new_tokens == rb.max_new_tokens
    assert all(x <= y for x, y in zip([t for t, _ in a],
                                      [t for t, _ in a][1:]))


def test_run_open_loop_summary(tiny_model):
    from paddle_tpu.serving import run_open_loop
    eng = _engine(tiny_model)
    spec = LoadSpec(num_requests=4, rate_rps=1000.0,
                    prompt_len_range=(4, 10), max_new_range=(2, 4),
                    vocab_size=256, seed=4)
    summary = run_open_loop(eng, spec)
    assert summary["requests_completed"] == 4
    assert summary["num_requests"] == 4
    assert summary["tokens_per_sec"] > 0
    assert summary["offered_rate_rps"] == pytest.approx(1000.0)


def test_monitor_report_serve_section(tiny_model, tmp_path):
    import importlib.util
    import os
    import sys
    with scoped_registry() as reg:
        eng = _engine(tiny_model)
        rng = np.random.default_rng(12)
        eng.generate([rng.integers(2, 250, (6,)).astype(np.int32)],
                     max_new_tokens=3)
        path = str(tmp_path / "serve.jsonl")
        reg.dump_jsonl(path)
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec = importlib.util.spec_from_file_location(
        "monitor_report", os.path.join(tools, "monitor_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from paddle_tpu.monitor import load_jsonl
    out = mod.render(load_jsonl(path), serve=True)
    assert "Serving latency" in out
    assert "ttft_seconds" in out
    assert "Decode batching" in out
    assert "serve_queue_depth" in out


# ---------------------------------------------------------------------------
# scan-fallback telemetry (ISSUE 6 satellite)
# ---------------------------------------------------------------------------


def test_legacy_static_cache_decode_records_fallback(tiny_model):
    from paddle_tpu.nn import scan as nn_scan
    nn_scan.reset_scan_stats()
    with scoped_registry() as reg, flag_scope("monitor", True), \
            warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        prompt = np.full((1, 4), 7, np.int32)
        tiny_model.generate(prompt, max_new_tokens=3,
                            decode_strategy="greedy_search")
        ctr = reg.get("scan_fallback_total")
        assert ctr is not None
        assert ctr.value(reason="legacy_static_cache", stack="gpt") >= 1
    assert nn_scan.SCAN_STATS["fallbacks"] >= 1
    msgs = [x for x in w
            if "scan-over-layers fell back" in str(x.message)]
    assert len(msgs) == 1              # once, not once per decode step


def test_serving_reset_clears_engines(tiny_model):
    import paddle_tpu.serving as serving
    from paddle_tpu.serving.engine import _LIVE_ENGINES
    eng = _engine(tiny_model)
    assert eng in _LIVE_ENGINES
    serving.reset()
    assert len(_LIVE_ENGINES) == 0
    assert Request([1, 2]).request_id == 0   # id counter restarted


def test_create_serving_engine_from_inference_config(tiny_model):
    from paddle_tpu import inference
    import jax.numpy as jnp
    cfg = inference.Config.from_layer(tiny_model, input_spec=[])
    cfg.enable_tpu_bf16()
    eng = inference.create_serving_engine(
        cfg, ServingConfig(max_batch_slots=2, block_size=4,
                           max_context_len=32, prefill_buckets=(8,),
                           batch_buckets=(1,)))
    assert all(v.dtype == jnp.bfloat16 for v in eng.params.values()
               if jnp.issubdtype(v.dtype, jnp.floating))
    rng = np.random.default_rng(13)
    out = eng.generate([rng.integers(2, 250, (5,)).astype(np.int32)],
                       max_new_tokens=3)
    assert out[0].shape == (8,)
