"""Multi-tenant serving (ISSUE 17): the batched-LoRA bgmv kernel,
int8-quantized paged KV, adapter hot-swap lifecycle and per-tenant
quota — each behind its own kill switch with the flags-off path as the
bit-compatible / token-exact oracle, plus the composed fuzz drill
(quant + radix donation + COW + speculative rollback + drain/resume)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.core.tensor import no_grad
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.ops import pallas as pallas_ops
from paddle_tpu.serving import (LoadSpec, Request, SamplingParams,
                                ServingConfig, ServingEngine,
                                build_requests, load_drain_snapshot,
                                requests_from_snapshot)
from paddle_tpu.serving.kv_cache import (PagedKVCache, dequant_pages,
                                         gather_pages, gather_pages_quant,
                                         write_pages, write_pages_quant)
from paddle_tpu.serving.lora import LoRAManager, save_adapter_checkpoint

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
    seq = np.asarray(prompt, np.int32)
    for _ in range(n):
        with no_grad():
            lg = model(paddle.to_tensor(seq[None, :])).numpy()
        seq = np.concatenate([seq, [np.int32(lg[0, -1].argmax())]])
    return seq


def _adapter(rng, rank=4, scale=0.5, L=2, E=64, O=192):
    """gpt_tiny-shaped (a, b) weights; scale 0.5 is large enough to
    flip greedy argmaxes (pinned below), tiny enough to stay finite."""
    return (rng.standard_normal((L, rank, E)).astype(np.float32) * scale,
            rng.standard_normal((L, rank, O)).astype(np.float32) * scale)


# ---------------------------------------------------------------------------
# bgmv kernel: oracle math, parity, zero row, kill switch
# ---------------------------------------------------------------------------


def _bgmv_ref(x, a, b, ids):
    out = np.zeros((x.shape[0], x.shape[1], b.shape[2]), np.float32)
    for i, ad in enumerate(ids):
        out[i] = (x[i].astype(np.float64) @ a[ad].T.astype(np.float64)
                  @ b[ad].astype(np.float64)).astype(np.float32)
    return out


def _bgmv_inputs(rng, B=4, S=2, E=32, r=4, O=24, A=3):
    x = rng.standard_normal((B, S, E)).astype(np.float32)
    a = rng.standard_normal((A, r, E)).astype(np.float32)
    b = rng.standard_normal((A, r, O)).astype(np.float32)
    a[0] = b[0] = 0.0                   # the reserved zero adapter
    ids = rng.integers(0, A, (B,)).astype(np.int32)
    return x, a, b, ids


def test_bgmv_xla_oracle_matches_per_row_math():
    from paddle_tpu.ops.pallas.bgmv import bgmv_xla
    rng = np.random.default_rng(0)
    x, a, b, ids = _bgmv_inputs(rng)
    got = np.asarray(bgmv_xla(jnp.asarray(x), jnp.asarray(a),
                              jnp.asarray(b), jnp.asarray(ids)))
    np.testing.assert_allclose(got, _bgmv_ref(x, a, b, ids),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.pallas
def test_bgmv_kernel_parity_with_oracle():
    from paddle_tpu.ops.pallas.bgmv import bgmv, bgmv_xla
    rng = np.random.default_rng(1)
    for B, S, E, r, O, A in ((4, 1, 32, 4, 24, 5), (3, 2, 64, 8, 48, 2)):
        x, a, b, ids = _bgmv_inputs(rng, B, S, E, r, O, A)
        args = tuple(jnp.asarray(t) for t in (x, a, b, ids))
        np.testing.assert_allclose(
            np.asarray(bgmv(*args)), np.asarray(bgmv_xla(*args)),
            rtol=1e-5, atol=1e-5)


@pytest.mark.pallas
def test_bgmv_zero_row_delta_is_exactly_zero():
    """Row 0 is the reserved zero adapter: base-model slots in a mixed
    batch contribute a delta of exactly 0.0, both paths."""
    from paddle_tpu.ops.pallas.bgmv import bgmv, bgmv_xla
    rng = np.random.default_rng(2)
    x, a, b, _ = _bgmv_inputs(rng)
    ids = jnp.zeros((x.shape[0],), jnp.int32)
    for fn in (bgmv, bgmv_xla):
        out = np.asarray(fn(jnp.asarray(x), jnp.asarray(a),
                            jnp.asarray(b), ids))
        assert (out == 0.0).all()


def test_bgmv_kill_switch_counted():
    with flag_scope("pallas_interpret", True), \
            flag_scope("pallas_bgmv", False):
        assert not pallas_ops.kernel_enabled("bgmv")
    assert ("bgmv", "flag_off") in pallas_ops.PALLAS_STATS
    # CPU backend without the interpreter (the tier-1 default): fallback
    assert not pallas_ops.kernel_enabled("bgmv")
    assert ("bgmv", "cpu_backend") in pallas_ops.PALLAS_STATS


# ---------------------------------------------------------------------------
# int8 paged-KV quantization primitives
# ---------------------------------------------------------------------------


def _quant_state(rng, B=2, n=(7, 3), P=8, bs=4, H=2, D=8):
    MB = 4
    tbl = np.zeros((B, MB), np.int32)
    tbl[0, :2] = [1, 2]
    tbl[1, :1] = [3]
    new = [rng.standard_normal((1, n[b], H, D)).astype(np.float32) * 3
           for b in range(B)]
    return tbl, new, P, bs, H, D


def _quant_pools(P, bs, H, D, G):
    """Empty lane-dense int8 pool + its scale pool, ``G`` head groups."""
    return (jnp.zeros((P, G, bs, (H // G) * D), jnp.int8),
            jnp.zeros((P, G, bs, H // G), jnp.float32))


@pytest.mark.parametrize("G", [1, 2], ids=["one-group", "two-groups"])
def test_write_pages_quant_round_trip_error_bound(G):
    """Per-(position, head) absmax int8: dequantized values sit within
    half a quantization step (absmax/127/2 per position+head row)."""
    rng = np.random.default_rng(3)
    tbl, new, P, bs, H, D = _quant_state(rng)
    pages, scales = _quant_pools(P, bs, H, D, G)
    for b in range(2):
        pages, scales = write_pages_quant(
            pages, scales, jnp.asarray(new[b]),
            jnp.asarray(tbl[b:b + 1]), jnp.zeros((1,), jnp.int32))
    # [P, G, bs, (H/G)*D] -> [P, bs, H, D]
    deq = np.asarray(dequant_pages(pages, scales)).reshape(
        P, G, bs, H // G, D).transpose(0, 2, 1, 3, 4).reshape(P, bs, H, D)
    for b, blocks in ((0, [1, 2]), (1, [3])):
        x = new[b][0]                                   # [n, H, D]
        nb = len(blocks)
        got = np.concatenate([deq[p] for p in blocks])[:x.shape[0]]
        step = np.abs(x).max(axis=-1, keepdims=True) / 127.0
        assert (np.abs(got - x) <= step * 0.5 + 1e-7).all()
        assert nb * bs >= x.shape[0]


@pytest.mark.parametrize("G", [1, 2], ids=["one-group", "two-groups"])
def test_gather_pages_quant_matches_dequant_then_gather(G):
    rng = np.random.default_rng(4)
    tbl, new, P, bs, H, D = _quant_state(rng)
    pages, scales = _quant_pools(P, bs, H, D, G)
    for b in range(2):
        pages, scales = write_pages_quant(
            pages, scales, jnp.asarray(new[b]),
            jnp.asarray(tbl[b:b + 1]), jnp.zeros((1,), jnp.int32))
    got = np.asarray(gather_pages_quant(pages, scales, jnp.asarray(tbl), D))
    ref = np.asarray(gather_pages(dequant_pages(pages, scales),
                                  jnp.asarray(tbl), D))
    np.testing.assert_array_equal(got, ref)
    assert got.shape == (2, 4 * bs, H, D)


def test_quant_cache_pools_and_footprint_accounting():
    """FLAGS_serve_kv_quant=int8 at construction: pools become
    (int8 pages, f32 scales) tuples and kv_bytes_per_token() accounts
    pages + scales; flags off: plain full-precision arrays."""
    mk = lambda: PagedKVCache(2, 4, 16, num_pages=6, block_size=4,
                              max_slots=2, max_blocks_per_slot=4)
    with flag_scope("serve_kv_quant", "int8"):
        qc = mk()
    assert qc.quant == "int8"
    assert isinstance(qc.k, tuple) and qc.k[0].dtype == jnp.int8
    assert qc.k[1].dtype == jnp.float32
    # 2 (k+v) * L * (H*D int8 + H f32 scales)
    assert qc.kv_bytes_per_token() == 2 * 2 * (4 * 16 + 4 * 4)
    fc = mk()
    assert fc.quant == "" and not isinstance(fc.k, tuple)
    assert fc.kv_bytes_per_token() == 2 * 2 * 4 * 16 * fc.k.dtype.itemsize
    assert qc.kv_bytes_per_token() < 0.4 * fc.kv_bytes_per_token()
    with flag_scope("serve_kv_quant", "fp4"), \
            pytest.raises(ValueError, match="serve_kv_quant"):
        mk()


# ---------------------------------------------------------------------------
# LoRAManager lifecycle
# ---------------------------------------------------------------------------


def test_lora_manager_load_unload_refcount():
    rng = np.random.default_rng(5)
    mgr = LoRAManager(2, 64, 192, max_adapters=2, rank=4)
    r1 = mgr.load_adapter("t0/a", weights=_adapter(rng))
    r2 = mgr.load_adapter("t1/b", weights=_adapter(rng))
    assert (r1, r2) == (1, 2) and mgr.num_loaded == 2
    assert mgr.load_adapter("t0/a", weights=_adapter(rng)) == r1  # no-op
    assert mgr.swaps == 2
    # pool full
    with pytest.raises(RuntimeError, match="pool full"):
        mgr.load_adapter("t2/c", weights=_adapter(rng))
    # held adapters refuse to unload
    assert mgr.acquire("t0/a") == r1
    with pytest.raises(RuntimeError, match="referenced"):
        mgr.unload_adapter("t0/a")
    mgr.release("t0/a")
    mgr.unload_adapter("t0/a")
    assert mgr.row("t0/a") is None
    # the freed row is zeroed: a stale id selects the zero delta
    assert float(jnp.abs(mgr.a[:, r1]).max()) == 0.0
    assert float(jnp.abs(mgr.b[:, r1]).max()) == 0.0
    assert mgr.load_adapter("t2/c", weights=_adapter(rng)) == r1  # reused
    with pytest.raises(RuntimeError, match="without a live reference"):
        mgr.release("t1/b")
    # rows_for maps None -> the zero adapter
    rows = np.asarray(mgr.rows_for([None, "t1/b", "t2/c"]))
    np.testing.assert_array_equal(rows, [0, r2, r1])


def test_lora_manager_rejects_bad_shapes_and_sources():
    rng = np.random.default_rng(6)
    mgr = LoRAManager(2, 64, 192, max_adapters=1, rank=4)
    a, b = _adapter(rng)
    with pytest.raises(ValueError, match="this manager serves"):
        mgr.load_adapter("bad", weights=(a[:, :2], b))
    with pytest.raises(ValueError, match="exactly one"):
        mgr.load_adapter("bad", weights=(a, b), path="/nope")
    assert mgr.num_loaded == 0          # nothing partially loaded


def test_lora_checkpoint_round_trip_and_atomic_fail(tmp_path):
    rng = np.random.default_rng(7)
    a, b = _adapter(rng)
    path = str(tmp_path / "adapter")
    save_adapter_checkpoint(path, a, b)
    mgr = LoRAManager(2, 64, 192, max_adapters=1, rank=4)
    row = mgr.load_adapter("ck", path=path)
    np.testing.assert_allclose(np.asarray(mgr.a[:, row]), a, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mgr.b[:, row]), b, rtol=1e-6)
    # a torn checkpoint fails manifest verification BEFORE the pools
    # mutate (the ckpt.write.torn failure mode: a data file lost its
    # tail after its size was recorded)
    mgr.unload_adapter("ck")
    import os
    from paddle_tpu.distributed.checkpoint import read_manifest
    files = read_manifest(path)["files"]
    victim = max(files, key=lambda r: files[r]["size"])
    with open(os.path.join(path, victim), "r+b") as f:
        f.truncate(files[victim]["size"] // 2)
    with pytest.raises(ValueError, match="verification"):
        mgr.load_adapter("ck", path=path)
    assert mgr.num_loaded == 0
    assert float(jnp.abs(mgr.a).max()) == 0.0


# ---------------------------------------------------------------------------
# engine: kv-quant parity, LoRA identity/effect, flags-off pins
# ---------------------------------------------------------------------------


def _prompts(rng, k=3):
    return [rng.integers(2, 250, (int(n),)).tolist()
            for n in rng.integers(5, 14, (k,))]


def test_kv_quant_greedy_token_parity(tiny_model):
    """Greedy decode under FLAGS_serve_kv_quant=int8 is token-identical
    to the full-precision oracle on the bench-sized workload (the
    documented acceptance bound: token parity, not bitwise logits)."""
    prompts = _prompts(np.random.default_rng(8))
    off = _engine(tiny_model)
    ref = [o.tolist() for o in off.generate(prompts, max_new_tokens=8)]
    off.shutdown()
    assert ref[0][-8:] == _golden(tiny_model, prompts[0], 8)[-8:].tolist()
    with flag_scope("serve_kv_quant", "int8"):
        q = _engine(tiny_model)
    got = [o.tolist() for o in q.generate(prompts, max_new_tokens=8)]
    q.shutdown()
    assert got == ref


def test_flags_off_engine_is_bit_identical_pre_pr(tiny_model):
    """Defaults = pre-ISSUE-17 engine: plain ndarray pools, no LoRA
    manager, empty lora program signature, and greedy outputs equal the
    step-by-step golden."""
    eng = _engine(tiny_model)
    assert eng.cache.quant == "" and not isinstance(eng.cache.k, tuple)
    assert eng.lora is None
    assert eng._lora_sig(3) == () and eng._lora_args([None] * 3) == ()
    prompts = _prompts(np.random.default_rng(9), k=2)
    outs = eng.generate(prompts, max_new_tokens=6)
    eng.shutdown()
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, _golden(tiny_model, p, 6))


def test_lora_zero_adapter_and_quant_compose_to_identity(tiny_model):
    """Base (adapter-less) requests on a LoRA+quant engine ride the
    zero adapter: outputs match the plain engine token for token."""
    prompts = _prompts(np.random.default_rng(10))
    plain = _engine(tiny_model)
    ref = [o.tolist() for o in plain.generate(prompts, max_new_tokens=8)]
    plain.shutdown()
    with flag_scope("serve_kv_quant", "int8"):
        eng = _engine(tiny_model, lora_adapters=2, lora_rank=4)
    eng.lora.load_adapter("t0/a", weights=_adapter(
        np.random.default_rng(11)))
    got = [o.tolist() for o in eng.generate(prompts, max_new_tokens=8)]
    eng.shutdown()
    assert got == ref


def test_adapter_requests_change_outputs_and_release_refs(tiny_model):
    rng = np.random.default_rng(12)
    prompts = _prompts(rng)
    eng = _engine(tiny_model, lora_adapters=2, lora_rank=4)
    eng.lora.load_adapter("t0/a", weights=_adapter(rng))
    base = [eng.submit(Request(p, max_new_tokens=6)) for p in prompts]
    tuned = [eng.submit(Request(p, max_new_tokens=6, adapter="t0/a"))
             for p in prompts]
    eng.run()
    assert all(st.outcome == "completed" for st in base + tuned)
    b = [st.generated for st in base]
    t = [st.generated for st in tuned]
    assert b != t                       # the adapter really decodes
    for p, st in zip(prompts, base):    # base slots: exact zero delta
        np.testing.assert_array_equal(
            np.asarray(st.generated), _golden(tiny_model, p, 6)[len(p):])
    # every slot reference was released at termination -> unload works
    assert eng.lora.refcount("t0/a") == 0
    eng.lora.unload_adapter("t0/a")
    eng.shutdown()


def test_unknown_adapter_rejected_at_submit(tiny_model):
    eng = _engine(tiny_model, lora_adapters=1)
    with pytest.raises(ValueError, match="not loaded"):
        eng.submit(Request([1, 2, 3], adapter="nope"))
    plain = _engine(tiny_model)
    with pytest.raises(ValueError, match="no LoRA manager"):
        plain.submit(Request([1, 2, 3], adapter="any"))
    eng.shutdown()
    plain.shutdown()


def test_adapter_unloaded_between_submit_and_admission_fails_loudly(
        tiny_model):
    eng = _engine(tiny_model, lora_adapters=1, lora_rank=4)
    eng.lora.load_adapter("t0/a", weights=_adapter(
        np.random.default_rng(13)))
    st = eng.submit(Request([5, 6, 7], max_new_tokens=4, adapter="t0/a"))
    eng.lora.unload_adapter("t0/a")     # not yet admitted: refcount 0
    eng.run()
    assert st.outcome == "failed"
    eng.shutdown()


# ---------------------------------------------------------------------------
# per-tenant quota
# ---------------------------------------------------------------------------


def test_tenant_quota_caps_slots_without_starving_others(tiny_model):
    eng = _engine(tiny_model, tenant_quota=1)
    sched = eng.scheduler
    a = [eng.submit(Request([2 + i, 3, 4], max_new_tokens=6, tenant="a"))
         for i in range(3)]
    b = eng.submit(Request([9, 10, 11], max_new_tokens=6, tenant="b"))
    eng.step()
    active = [st.request.tenant for _, st in sched.active()]
    # tenant a holds exactly 1 of its 3; b admitted PAST the blocked a's
    assert active.count("a") == 1 and active.count("b") == 1
    assert sched.tenant_deferrals.get("a", 0) > 0
    assert "b" not in sched.tenant_deferrals
    eng.run()
    assert all(st.outcome == "completed" for st in a + [b])
    assert sched.stats["quota_deferred"] == sum(
        sched.tenant_deferrals.values())
    eng.shutdown()


def test_untenanted_requests_never_quota_limited(tiny_model):
    eng = _engine(tiny_model, tenant_quota=1)
    sts = [eng.submit(Request([3 + i, 4, 5], max_new_tokens=4))
           for i in range(3)]
    eng.step()
    assert len(eng.scheduler.active()) == 3
    assert eng.scheduler.tenant_deferrals == {}
    eng.run()
    assert all(st.outcome == "completed" for st in sts)
    eng.shutdown()


# ---------------------------------------------------------------------------
# loadgen: adapter_pool rides a side RNG
# ---------------------------------------------------------------------------


def test_loadgen_adapter_pool_pin_and_side_rng():
    base = LoadSpec(num_requests=24, rate_rps=50.0, prompt_len_range=(4, 8),
                    seed=5, shared_prefix_len=8, prefix_pool_size=2,
                    tenants=3)
    import dataclasses
    armed = dataclasses.replace(base, adapter_pool=2)
    off = build_requests(base)
    on = build_requests(armed)
    # arming adapters perturbs NOTHING the default spec draws
    assert [t for t, _ in off] == [t for t, _ in on]
    for (_, r0), (_, r1) in zip(off, on):
        np.testing.assert_array_equal(r0.prompt, r1.prompt)
        assert r0.max_new_tokens == r1.max_new_tokens
        assert r0.tenant is None and r0.adapter is None   # pinned off
        assert r1.tenant is not None
        t = int(r1.tenant[len("tenant"):])
        assert r1.adapter in {f"tenant{t}/adapter{k}" for k in range(2)}
    # deterministic per seed
    again = build_requests(dataclasses.replace(base, adapter_pool=2))
    assert [r.adapter for _, r in on] == [r.adapter for _, r in again]
    with pytest.raises(ValueError, match="tenants"):
        build_requests(LoadSpec(adapter_pool=2))


# ---------------------------------------------------------------------------
# monitor_report: the per-tenant table claims its series
# ---------------------------------------------------------------------------


def test_monitor_report_renders_tenant_table(tiny_model, tmp_path):
    import json
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "tools"))
    import monitor_report
    from paddle_tpu.monitor import scoped_registry
    with scoped_registry() as reg, flag_scope("monitor", True):
        with flag_scope("serve_kv_quant", "int8"):
            eng = _engine(tiny_model, lora_adapters=2, lora_rank=4,
                          tenant_quota=1)
        eng.lora.load_adapter("t0/a", weights=_adapter(
            np.random.default_rng(14)))
        sts = [eng.submit(Request([7 + i, 8, 9], max_new_tokens=4,
                                  tenant="acme", adapter="t0/a"))
               for i in range(3)]
        eng.run()
        assert all(st.outcome == "completed" for st in sts)
        path = str(tmp_path / "m.jsonl")
        reg.dump_jsonl(path)
        eng.shutdown()
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    out = monitor_report.render(rows, serve=True)
    assert "Tenants" in out and "acme" in out
    assert "Multi-tenant pool (LoRA + quantized KV)" in out
    assert "LoRA adapters loaded" in out
    assert "quantized KV bytes/token" in out
    # claimed by the tenant section, NOT re-rendered by the catch-all
    assert "serve_tenant_requests_total" not in out


# ---------------------------------------------------------------------------
# the composed drill: quant + radix + COW + spec rollback + drain/resume
# ---------------------------------------------------------------------------


def test_kv_quant_composed_paths_token_exact_and_leak_free(
        tiny_model, tmp_path):
    """Seeded drill over the FULL composed surface: int8 KV + radix
    donation/COW + chunked prefill + speculative rollback
    (truncate_slot on quantized pages) + a constrained pool (forced
    eviction) + a mid-run drain/resume. Greedy outputs stay
    token-identical to the flags-off step-by-step golden and the page
    pool drains to zero — quantized pages move through every path
    unchanged."""
    rng = np.random.default_rng(42)
    prefixes = [rng.integers(2, 250, (8,)).tolist() for _ in range(2)]
    prompts = [prefixes[int(rng.integers(0, 2))]
               + rng.integers(2, 250, (int(rng.integers(2, 7)),)).tolist()
               for _ in range(6)]
    goldens = [_golden(tiny_model, p, 5) for p in prompts]

    def build():
        with flag_scope("serve_kv_quant", "int8"), \
                flag_scope("serve_prefix_cache", True), \
                flag_scope("serve_prefill_chunk", 4), \
                flag_scope("serve_spec_k", 2):
            return _engine(tiny_model, num_pages=24,
                           prefill_buckets=(4, 8, 16))
    eng = build()
    states = [eng.submit(Request(p, max_new_tokens=5)) for p in prompts]
    for _ in range(3):                  # partway in, then SIGTERM
        eng.step()
    report = eng.drain(snapshot_dir=str(tmp_path / "d"), budget_s=0.0)
    assert report.snapshotted > 0
    eng.shutdown()

    done = {tuple(st.request.prompt.tolist()): st.generated
            for st in states if st.outcome == "completed"}
    _, specs = load_drain_snapshot(str(tmp_path / "d"))
    eng2 = build()                      # successor, same composed flags
    resumed = [eng2.submit(r) for r in requests_from_snapshot(specs)]
    eng2.run()
    full = dict(done)
    for st in resumed:
        assert st.outcome == "completed"
        # the resumed effective prompt = original prompt + committed
        # tokens; stitch back to the original request
        seq = st.request.prompt.tolist() + list(st.generated)
        for p in prompts:
            if seq[:len(p)] == list(p):
                full.setdefault(tuple(p), seq[len(p):])
    for p, g in zip(prompts, goldens):
        assert full[tuple(p)] == g[len(p):].tolist(), p
    # zero page leaks: evicting the radix tree returns every page
    eng2.cache.prefix_cache.evict_for(10_000)
    assert eng2.cache.allocator.pages_in_use == 0
    eng2.shutdown()
