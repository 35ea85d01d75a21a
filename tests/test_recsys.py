"""Giant-embedding recsys subsystem tests (ISSUE 12; docs/RECSYS.md).

Coverage map:
- ShardedEmbeddingTable: dedup-vs-naive parity (fwd + sparse grads,
  bitwise), manual shard_map path vs SparseTable reference on a ps-only
  mesh, counted fallbacks (kill switch + incapable mesh), cross-mesh
  checkpoint restore;
- the three-table pull/push parity fuzz (SparseTable vs SSDSparseTable
  vs ShardedEmbeddingTable on one id stream — the satellite pin);
- TieredEmbeddingTable: admission/eviction/promotion mechanics, hot-set
  device fast path, parity vs the untiered table, state_dict residency
  round-trip;
- DLRM + criteo-synthetic: loss decreases, tables stay out of the
  dense parameter set;
- RecsysEngine: deadlines, bounded-queue policies, overload detector
  hysteresis, outcome counters, batched-lookup dedup across requests;
- save/restore through the atomic checkpoint manifest incl. the
  chaos ``ckpt.write.torn`` fallback drill;
- monitor_report --recsys render + per-table HBM census.
"""

import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import recsys
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.distributed.ps import SparseTable, SSDSparseTable
from paddle_tpu.distributed.spmd import make_mesh
from paddle_tpu.models.dlrm import DLRM, DLRMConfig, dlrm_tiny
from paddle_tpu.monitor import scoped_registry
from paddle_tpu.recsys import (CriteoSynthetic, RECSYS_STATS,
                               RecsysEngine, RecsysRequest,
                               RecsysServingConfig,
                               ShardedEmbeddingTable,
                               TieredEmbeddingTable, load_tables,
                               save_tables)
from paddle_tpu.serving.resilience import ServerOverloaded
from paddle_tpu.testing import chaos

pytestmark = pytest.mark.recsys

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


# ---------------------------------------------------------------------------
# ShardedEmbeddingTable
# ---------------------------------------------------------------------------

def test_dedup_lookup_parity_vs_naive_per_id_gather():
    """The dedup lookup (sort-unique -> one gather -> inverse permute)
    must be BIT-identical to the naive per-id gather, forward and
    through the sparse adagrad update — the kill switch is a parity
    oracle, not an approximation."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 50, size=300)              # heavy duplication
    grads = rng.normal(size=(300, 8)).astype(np.float32)
    out = {}
    for dedup in (True, False):
        with flag_scope("recsys_dedup", dedup):
            t = ShardedEmbeddingTable(50, 8, lr=0.1, seed=4)
            rows = t.pull(ids)
            t.push(ids, grads)
            out[dedup] = (rows, t.state_dict())
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_array_equal(out[True][1]["data"],
                                  out[False][1]["data"])
    np.testing.assert_array_equal(out[True][1]["g2"],
                                  out[False][1]["g2"])
    # and the dedup path really fetched fewer rows
    with flag_scope("recsys_dedup", True):
        t = ShardedEmbeddingTable(50, 8, seed=4)
        t.pull(ids)
        assert t.rows_fetched < ids.size
        assert t.dedup_ratio > 2.0


@pytest.mark.multichip
def test_sharded_manual_path_parity_vs_sparse_table():
    """ps-only mesh: the explicit shard_map gather+psum program runs
    (no fallback) and matches the host SparseTable row-for-row through
    pulls and adagrad pushes."""
    mesh = make_mesh({"ps": 8})
    dist_env.set_mesh(mesh)
    sh = ShardedEmbeddingTable(100, 16, lr=0.1, seed=7)
    assert sh.num_shards == 8
    ref = SparseTable(100, 16, optimizer="adagrad", lr=0.1, seed=7)
    ref.load_state_dict({"data": sh.state_dict()["data"],
                         "g2": np.zeros(100, np.float32)})
    rng = np.random.default_rng(0)
    # pre-update rows are BIT-equal (one gather, no arithmetic)
    np.testing.assert_array_equal(sh.pull(np.arange(100)),
                                  ref.pull(np.arange(100)))
    for step in range(4):
        ids = rng.integers(0, 100, size=40)
        # post-update rows: XLA's row update vs numpy's is 1-ULP
        np.testing.assert_allclose(sh.pull(ids), ref.pull(ids),
                                   rtol=1e-6, atol=1e-7)
        g = rng.normal(size=(40, 16)).astype(np.float32)
        sh.push(ids, g)
        ref.push(ids, g)
    np.testing.assert_allclose(sh.state_dict()["data"], ref.data,
                               atol=5e-7)
    assert RECSYS_STATS["manual_lookups"] >= 4
    assert RECSYS_STATS["manual_updates"] >= 4
    assert RECSYS_STATS["fallbacks"] == 0


@pytest.mark.multichip
def test_sharded_kill_switch_auto_path_counted_and_equal():
    """FLAGS_recsys_sharded_lookup off on a ps mesh: the GSPMD auto
    path serves (counted flag_off fallback) and matches the manual
    program bit-for-bit."""
    mesh = make_mesh({"ps": 8})
    dist_env.set_mesh(mesh)
    ids = np.array([0, 9, 9, 42, 63, 63, 63, 7])
    g = np.random.default_rng(1).normal(size=(8, 8)).astype(np.float32)
    sh_m = ShardedEmbeddingTable(64, 8, lr=0.1, seed=2)
    rows_m = sh_m.pull(ids)
    sh_m.push(ids, g)
    with flag_scope("recsys_sharded_lookup", False), \
            pytest.warns(RuntimeWarning, match="GSPMD auto path"):
        sh_a = ShardedEmbeddingTable(64, 8, lr=0.1, seed=2)
        rows_a = sh_a.pull(ids)
        sh_a.push(ids, g)
    np.testing.assert_array_equal(rows_m, rows_a)
    np.testing.assert_allclose(sh_m.state_dict()["data"],
                               sh_a.state_dict()["data"], atol=5e-7)
    assert RECSYS_STATS["fallbacks"] >= 2          # pull + push
    assert RECSYS_STATS["auto_lookups"] >= 1


@pytest.mark.multichip
def test_sharded_fallback_counted_on_mixed_mesh(recwarn):
    """A mesh with another nontrivial axis cannot compile the manual
    program on XLA:CPU — the auto path serves with a counted
    backend_mesh fallback, results still correct vs the reference."""
    dist_env.set_mesh(make_mesh({"dp": 2, "ps": 4}))
    sh = ShardedEmbeddingTable(40, 4, lr=0.2, seed=9)
    assert sh.num_shards == 4
    ref = SparseTable(40, 4, optimizer="adagrad", lr=0.2, seed=9,
                      num_shards=1)
    ref.load_state_dict({"data": sh.state_dict()["data"],
                         "g2": np.zeros(40, np.float32)})
    ids = np.array([1, 1, 2, 39])
    np.testing.assert_allclose(sh.pull(ids), ref.pull(ids), atol=0)
    assert RECSYS_STATS["fallbacks"] >= 1
    assert RECSYS_STATS["manual_lookups"] == 0
    assert any("GSPMD auto path" in str(w.message) for w in recwarn.list)


@pytest.mark.multichip
def test_sharded_checkpoint_restores_across_mesh_layouts(tmp_path):
    """state_dict is global-row-order: a snapshot written on ps=8
    restores bit-exactly onto a mesh-less single-shard table."""
    dist_env.set_mesh(make_mesh({"ps": 8}))
    sh = ShardedEmbeddingTable(30, 4, lr=0.1, seed=1)
    sh.push([3, 3, 17], np.ones((3, 4), np.float32))
    expect = sh.state_dict()
    save_tables(str(tmp_path), {"emb": sh})
    dist_env.set_mesh(None)
    fresh = ShardedEmbeddingTable(30, 4, lr=0.1, seed=99)
    assert load_tables(str(tmp_path), {"emb": fresh}) is not None
    np.testing.assert_array_equal(fresh.state_dict()["data"],
                                  expect["data"])
    np.testing.assert_array_equal(fresh.state_dict()["g2"],
                                  expect["g2"])


def test_pull_push_parity_fuzz_three_tables(tmp_path):
    """The satellite pin: SparseTable, SSDSparseTable (cache small
    enough to spill) and ShardedEmbeddingTable driven by ONE seeded id
    stream stay row-equal through mixed pulls and pushes."""
    V, D = 64, 8
    rng = np.random.default_rng(42)
    base = rng.uniform(-0.3, 0.3, (V, D)).astype(np.float32)
    sp = SparseTable(V, D, optimizer="adagrad", lr=0.1)
    sp.load_state_dict({"data": base.copy(),
                        "g2": np.zeros(V, np.float32)})
    ssd = SSDSparseTable(V, D, cache_rows=16, optimizer="adagrad",
                         lr=0.1, path=str(tmp_path / "fuzz.log"))
    ssd.load_state_dict({"row_ids": np.arange(V), "data": base.copy(),
                         "g2": np.zeros(V, np.float32)})
    sh = ShardedEmbeddingTable(V, D, optimizer="adagrad", lr=0.1)
    sh.load_state_dict({"data": base.copy(),
                        "g2": np.zeros(V, np.float32)})
    for step in range(25):
        n = int(rng.integers(1, 48))
        ids = rng.integers(0, V, size=n)
        if step % 3 == 2:
            r_sp = sp.pull(ids)
            np.testing.assert_allclose(ssd.pull(ids), r_sp,
                                       rtol=1e-5, atol=2e-6)
            np.testing.assert_allclose(sh.pull(ids), r_sp,
                                       rtol=1e-5, atol=2e-6)
        else:
            g = rng.normal(size=(n, D)).astype(np.float32)
            sp.push(ids, g)
            ssd.push(ids, g)
            sh.push(ids, g)
    assert ssd.evict_count > 0                    # the spill really ran
    np.testing.assert_allclose(
        sh.state_dict()["data"], sp.data, rtol=1e-5, atol=2e-6)
    full = ssd.pull(np.arange(V))
    np.testing.assert_allclose(full, sp.data, rtol=1e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# TieredEmbeddingTable
# ---------------------------------------------------------------------------

def test_tiered_admission_eviction_promotion_counters():
    t = TieredEmbeddingTable(1000, 8, hot_rows=4, admit_after=2,
                             lr=0.1, seed=1, name="tiers")
    recsys.register_table("tiers", t)
    t.pull(np.arange(10))                  # freq 1: nothing admitted
    assert t.stats["promotions"] == 0 and t.resident_hot_rows == 0
    t.pull(np.arange(10))                  # freq 2: admit, 4-slot LRU
    assert t.stats["promotions"] == 10
    # CLEAN rows (never pushed while hot) evict without a write-back:
    # the backing copy is still current, so evictions > demotions
    assert t.stats["evictions"] == 6 and t.stats["demotions"] == 0
    assert t.resident_hot_rows == 4
    # evicted rows still serve correctly from the backing copy
    out = t.pull(np.arange(10))
    assert out.shape == (10, 8)
    rates = t.hit_rates()
    assert abs(sum(rates.values()) - 100.0) < 1e-6
    assert rates["hbm"] > 0


def test_tiered_dirty_rows_demote_clean_rows_do_not():
    """Only a row UPDATED while hot pays the demotion write-back; a
    clean row's eviction is free (its backing copy is current) — and
    the dirty row's updated value survives the round trip."""
    t = TieredEmbeddingTable(1000, 4, hot_rows=2, admit_after=1,
                             optimizer="sgd", lr=1.0, name="dirty")
    base = t.pull([1, 2])                  # promote 1, 2 (clean)
    t.push([1], np.ones((1, 4), np.float32))     # 1 is now dirty
    want1 = t.pull([1]).copy()
    assert t.stats["demotions"] == 0
    t.pull([3, 4])                         # evict 1 AND 2
    assert t.stats["evictions"] == 2
    assert t.stats["demotions"] == 1       # only the dirty row wrote
    np.testing.assert_allclose(t.pull([1]), want1, rtol=1e-6)
    np.testing.assert_allclose(t.pull([2]), base[1:2], rtol=1e-6)


def test_tiered_parity_vs_untiered_sparse_table():
    """Hot rows update on device with the same adagrad math the host
    table applies — tiering must not change a single row's trajectory."""
    V, D = 200, 8
    rng = np.random.default_rng(5)
    base = rng.uniform(-0.2, 0.2, (V, D)).astype(np.float32)
    ref = SparseTable(V, D, optimizer="adagrad", lr=0.1)
    ref.load_state_dict({"data": base.copy(),
                         "g2": np.zeros(V, np.float32)})
    backing = SparseTable(V, D, optimizer="adagrad", lr=0.1)
    backing.load_state_dict({"data": base.copy(),
                             "g2": np.zeros(V, np.float32)})
    t = TieredEmbeddingTable(V, D, hot_rows=8, backing=backing,
                             admit_after=1, lr=0.1, name="par")
    for step in range(12):
        ids = rng.integers(0, V, size=24)
        np.testing.assert_allclose(t.pull(ids), ref.pull(ids),
                                   rtol=1e-5, atol=2e-6)
        g = rng.normal(size=(24, D)).astype(np.float32)
        t.push(ids, g)
        ref.push(ids, g)
    assert t.stats["promotions"] > 0 and t.stats["demotions"] > 0
    np.testing.assert_allclose(t.pull(np.arange(V)),
                               ref.pull(np.arange(V)),
                               rtol=1e-5, atol=3e-6)


def test_tiered_hot_set_serves_from_device():
    """Once the working set is resident, lookup() touches no backing
    tier: pure device gathers (the 'hot set at device speed' claim)."""
    t = TieredEmbeddingTable(100, 4, hot_rows=16, admit_after=1,
                             name="dev")
    ids = np.array([1, 2, 3, 4])
    t.pull(ids)                            # admit-on-first-touch
    before_pulls = t.backing.pull_count
    before_hbm = t.stats["hbm_hits"]
    rows = t.lookup(np.array([1, 2, 3, 4, 4, 1]))
    assert rows.shape == (6, 4)
    assert t.backing.pull_count == before_pulls     # no host fetch
    assert t.stats["hbm_hits"] > before_hbm


def test_tiered_state_dict_roundtrip_preserves_residency(tmp_path):
    """Round trip over a churned table. The restoring table shares the
    SEED (the SSDSparseTable contract: rows never UPDATED re-derive
    from the deterministic initializer rather than being materialized
    — and with clean evictions skipping the write-back, touched-but-
    never-pushed rows stay in that class)."""
    t = TieredEmbeddingTable(300, 4, hot_rows=8, admit_after=1,
                             lr=0.1, seed=3, name="rt")
    rng = np.random.default_rng(0)
    for _ in range(6):
        ids = rng.integers(0, 300, size=16)
        t.pull(ids)
        t.push(ids, rng.normal(size=(16, 4)).astype(np.float32))
    want = t.pull(np.arange(0, 300, 7))
    hot_before = t.resident_hot_rows
    state = t.state_dict()
    t2 = TieredEmbeddingTable(300, 4, hot_rows=8, admit_after=1,
                              lr=0.1, seed=3, name="rt2")
    t2.load_state_dict(state)
    assert t2.resident_hot_rows == hot_before
    np.testing.assert_allclose(t2.pull(np.arange(0, 300, 7)), want,
                               rtol=1e-5, atol=2e-6)


def test_tiered_ssd_ladder_spills_to_disk(tmp_path):
    """Default backing = SSDSparseTable: a working set larger than the
    host cache spills rows to the log and reads them back — all three
    tier counters move."""
    t = TieredEmbeddingTable(5000, 4, hot_rows=8, host_rows=32,
                             admit_after=2, name="ladder")
    rng = np.random.default_rng(2)
    for i in range(8):
        ids = np.concatenate([np.arange(6),               # hot head
                              rng.integers(0, 5000, size=60)])
        t.pull(ids)
        t.push(ids, rng.normal(size=(ids.size, 4)).astype(np.float32))
    s = t.stats
    assert s["hbm_hits"] > 0
    assert s["ssd_reads"] + s["lazy_inits"] > 0
    assert t.backing.evict_count > 0          # host -> ssd spills
    assert s["promotions"] > 0
    rows = t.tier_rows()
    assert rows["hbm"] > 0 and rows["host"] > 0 and rows["ssd"] > 0


# ---------------------------------------------------------------------------
# DLRM + criteo-synthetic
# ---------------------------------------------------------------------------

def test_criteo_synthetic_power_law_and_determinism():
    gen = CriteoSynthetic(num_dense=4, num_sparse=4, vocab_sizes=1000,
                          alpha=1.1, batch_size=512, seed=7)
    d1, i1, l1 = gen.batch(3)
    d2, i2, l2 = gen.batch(3)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(l1, l2)
    assert d1.shape == (512, 4) and i1.shape == (512, 4)
    assert set(np.unique(l1)) <= {0.0, 1.0}
    # power law: the top-10 ids take far more than their uniform share
    head_share = (i1 < 10).mean()
    assert head_share > 0.15, head_share          # uniform would be 1%


def test_dlrm_trains_and_tables_stay_sparse():
    paddle.seed(11)
    cfg = dlrm_tiny()
    model = DLRM(cfg, seed=0)
    gen = CriteoSynthetic(num_dense=cfg.num_dense,
                          num_sparse=cfg.num_sparse, vocab_sizes=512,
                          batch_size=64, seed=0)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    losses = []
    for i in range(25):
        dense, ids, labels = gen.batch(i)
        loss = model.loss(dense, ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, (losses[0], losses[-1])
    # embedding tables are NOT dense Parameters: the PS discipline
    assert all("table" not in k for k, _ in model.named_parameters())
    assert all(t.push_count >= 25 for t in model.tables)
    assert model.last_timings["lookup_s"] > 0


def test_dlrm_through_tiered_and_sharded_tables():
    """The model composes with every table kind, including one shared
    table across slots."""
    paddle.seed(12)
    cfg = dlrm_tiny(num_sparse=3, vocab_sizes=256)
    shared = [ShardedEmbeddingTable(256, cfg.embedding_dim, lr=0.05)]
    m1 = DLRM(cfg, tables=shared)
    tiered = [TieredEmbeddingTable(256, cfg.embedding_dim, hot_rows=16,
                                   admit_after=1, name=f"s{f}")
              for f in range(3)]
    m2 = DLRM(cfg, tables=tiered)
    gen = CriteoSynthetic(num_dense=cfg.num_dense, num_sparse=3,
                          vocab_sizes=256, batch_size=32, seed=1)
    dense, ids, labels = gen.batch(0)
    for m in (m1, m2):
        loss = m.loss(dense, ids, labels)
        loss.backward()
        assert np.isfinite(float(loss))
    assert shared[0].push_count == 3          # one push per slot
    assert any(t.stats["promotions"] > 0 for t in tiered) or \
        all(t.backing.pull_count > 0 for t in tiered)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _mk_model(num_sparse=3, vocab=256):
    cfg = dlrm_tiny(num_sparse=num_sparse, vocab_sizes=vocab)
    tables = [TieredEmbeddingTable(vocab, cfg.embedding_dim,
                                   hot_rows=32, admit_after=1,
                                   name=f"srv{f}")
              for f in range(num_sparse)]
    return DLRM(cfg, tables=tables), cfg


def _req(rng, cfg, K=5, vocab=256, **kw):
    return RecsysRequest(
        rng.normal(size=cfg.num_dense).astype(np.float32),
        rng.integers(0, vocab, size=(K, cfg.num_sparse)).astype(np.int64),
        **kw)


def test_serving_completes_and_ranks():
    model, cfg = _mk_model()
    eng = RecsysEngine(model, RecsysServingConfig(max_batch=4))
    rng = np.random.default_rng(0)
    with scoped_registry() as reg:
        states = [eng.submit(_req(rng, cfg, K=6)) for _ in range(5)]
        eng.run()
        assert all(st.outcome == "completed" for st in states)
        res = states[0].result
        assert res.scores.shape == (6,)
        # order really sorts by score, best first
        assert (np.diff(res.scores[res.order]) <= 1e-7).all()
        c = reg.get("recsys_requests_total")
        assert c.value(event="completed") == 5
        assert reg.get("recsys_lookup_seconds").count() > 0
        assert reg.get("recsys_e2e_seconds").count() == 5
    s = eng.metrics_summary()
    assert s["requests_completed"] == 5
    assert s["candidates_per_sec"] > 0


def test_serving_deadline_expires_before_lookup():
    model, cfg = _mk_model()
    eng = RecsysEngine(model, RecsysServingConfig())
    rng = np.random.default_rng(1)
    pulls_before = sum(t.pull_count for t in model.tables)
    with scoped_registry() as reg:
        st = eng.submit(_req(rng, cfg, deadline_s=-0.001))
        ok = eng.submit(_req(rng, cfg, deadline_s=60.0))
        eng.run()
        assert st.outcome == "expired" and st.result is None
        assert ok.outcome == "completed"
        # a blown deadline spent NO table bandwidth: exactly one pull
        # per table for the one live request's forward
        assert sum(t.pull_count for t in model.tables) - pulls_before \
            == len(model.tables)
        assert reg.get("recsys_requests_total").value(
            event="expired") == 1
        assert reg.get("recsys_deadline_slack_seconds").count() == 1


def test_serving_queue_policies():
    model, cfg = _mk_model()
    rng = np.random.default_rng(2)
    # reject-new: the newcomer bounces with a typed refusal
    eng = RecsysEngine(model, RecsysServingConfig(max_queue=2))
    eng.submit(_req(rng, cfg))
    eng.submit(_req(rng, cfg))
    with pytest.raises(ServerOverloaded) as e:
        eng.submit(_req(rng, cfg))
    assert e.value.reason == "queue_full"
    assert eng.stats["rejected"] == 1
    # drop-oldest: the oldest queued request is shed, newcomer admitted
    eng2 = RecsysEngine(model, RecsysServingConfig(
        max_queue=2, queue_policy="drop-oldest"))
    first = eng2.submit(_req(rng, cfg))
    eng2.submit(_req(rng, cfg))
    eng2.submit(_req(rng, cfg))
    assert first.outcome == "shed"
    assert eng2.stats["shed"] == 1 and eng2.queue_depth == 2


def test_serving_overload_detector_hysteresis():
    model, cfg = _mk_model()
    now = [0.0]
    eng = RecsysEngine(model, RecsysServingConfig(
        max_batch=1, overload_threshold_s=1.0, overload_alpha=1.0,
        overload_exit_frac=0.5), clock=lambda: now[0])
    rng = np.random.default_rng(3)
    eng.submit(_req(rng, cfg))
    eng.submit(_req(rng, cfg))
    now[0] = 5.0                      # head-of-queue delay 5s >> 1s
    eng.step()                        # observes, trips
    assert eng._overload.overloaded
    with pytest.raises(ServerOverloaded) as e:
        eng.submit(_req(rng, cfg))
    assert e.value.reason == "overload"
    eng.run()                         # drain the queue
    # idle engine: the submit-time zero-delay sample recovers it
    st = eng.submit(_req(rng, cfg))
    assert not eng._overload.overloaded
    eng.run()
    assert st.outcome == "completed"


def test_serving_batches_dedup_across_requests():
    """One engine step ranks many requests in ONE forward, so the
    table-level dedup window spans requests: shared hot ids cost one
    row fetch for the whole batch."""
    cfg = dlrm_tiny(num_sparse=2, vocab_sizes=128)
    tab = ShardedEmbeddingTable(128, cfg.embedding_dim)
    model = DLRM(cfg, tables=[tab])
    eng = RecsysEngine(model, RecsysServingConfig(max_batch=8))
    rng = np.random.default_rng(4)
    same = np.zeros((4, 2), np.int64)         # every candidate id 0
    for _ in range(6):
        eng.submit(RecsysRequest(
            rng.normal(size=cfg.num_dense).astype(np.float32),
            same.copy()))
    eng.step()
    # 6 requests x 4 candidates x 2 slots = 48 ids, 1 unique row; the
    # shared table sees 2 lookups (one per slot) of 24 ids each
    assert tab.ids_seen == 48
    assert tab.rows_fetched == 2
    assert eng.stats["completed"] == 6


def test_serving_fault_isolation_poisoned_request_fails_alone():
    """A request whose candidates make the model raise (out-of-range
    ids against a range-validating table) must land outcome 'failed'
    while its batch-mates complete — every submitted request gets a
    terminal outcome (the PR 8 accounting discipline)."""
    cfg = dlrm_tiny(num_sparse=2, vocab_sizes=64)
    model = DLRM(cfg, tables=[ShardedEmbeddingTable(64, cfg.embedding_dim)])
    eng = RecsysEngine(model, RecsysServingConfig(max_batch=8))
    rng = np.random.default_rng(7)
    good = [eng.submit(_req(rng, cfg, K=3, vocab=64)) for _ in range(3)]
    bad_ids = np.array([[1, 64], [2, 3], [4, 5]], np.int64)  # 64 = OOR
    bad = eng.submit(RecsysRequest(
        rng.normal(size=cfg.num_dense).astype(np.float32), bad_ids))
    with scoped_registry() as reg:
        eng.run()
        assert reg.get("recsys_requests_total").value(
            event="failed") == 1
    assert bad.outcome == "failed" and "64" in bad.failure
    assert all(st.outcome == "completed" for st in good)
    assert eng.stats["failed"] == 1
    assert eng.metrics_summary()["requests_failed"] == 1


def test_sharded_push_rejects_out_of_range_ids():
    """push validates like pull: the manual program clips local
    indices for its pad rows, so an out-of-range id would silently
    update the wrong row — it must raise instead."""
    t = ShardedEmbeddingTable(32, 4)
    with pytest.raises(ValueError, match="outside"):
        t.push([32], np.ones((1, 4), np.float32))
    with pytest.raises(ValueError, match="outside"):
        t.pull([-1])


# ---------------------------------------------------------------------------
# checkpoint manifest + chaos
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_table_snapshot_torn_commit_falls_back(tmp_path):
    """A torn write racing the commit (chaos ckpt.write.torn) must not
    pass for a snapshot: load_tables falls back to the previous valid
    one — the PR 5 reader discipline on table state."""
    t = TieredEmbeddingTable(400, 8, hot_rows=16, admit_after=1,
                             lr=0.1, seed=0, name="ck")
    ids = np.arange(12)
    t.pull(ids)
    t.push(ids, np.ones((12, 8), np.float32))
    good = t.pull(ids).copy()
    save_tables(str(tmp_path), {"ck": t})
    t.push(ids, np.ones((12, 8), np.float32))
    with chaos.chaos_scope("ckpt.write.torn@1"):
        save_tables(str(tmp_path), {"ck": t})
    t.push(ids, np.ones((12, 8), np.float32))
    fresh = TieredEmbeddingTable(400, 8, hot_rows=16, admit_after=1,
                                 lr=0.1, seed=9, name="ck2")
    path = load_tables(str(tmp_path), {"ck": fresh})
    assert path is not None and path.endswith("tables_1")
    np.testing.assert_allclose(fresh.pull(ids), good, rtol=1e-5,
                               atol=2e-6)


def test_load_tables_empty_root_is_noop(tmp_path):
    t = ShardedEmbeddingTable(10, 4, seed=0)
    before = t.state_dict()["data"].copy()
    assert load_tables(str(tmp_path / "nothing"), {"t": t}) is None
    np.testing.assert_array_equal(t.state_dict()["data"], before)


# ---------------------------------------------------------------------------
# telemetry / tools
# ---------------------------------------------------------------------------

def test_tier_metrics_publish_and_report_render(tmp_path):
    sys.path.insert(0, TOOLS)
    try:
        import monitor_report
    finally:
        sys.path.remove(TOOLS)
    t = TieredEmbeddingTable(500, 8, hot_rows=4, admit_after=1,
                             name="rpt")
    recsys.register_table("rpt", t)
    rng = np.random.default_rng(0)
    for _ in range(4):
        t.pull(rng.integers(0, 500, size=32))
    with scoped_registry() as reg:
        t.publish_tier_metrics()
        recsys.publish_table_hbm()
        assert reg.get("recsys_table_rows") is not None
        assert reg.get("recsys_tier_hits_total") is not None
        hbm = reg.get("recsys_table_hbm_bytes")
        assert hbm.value(table="rpt") == t.hbm_bytes() > 0
        path = str(tmp_path / "m.jsonl")
        reg.dump_jsonl(path)
    from paddle_tpu.monitor import load_jsonl
    out = monitor_report.render(load_jsonl(path), recsys=True)
    assert "Recsys embedding tiers" in out
    assert "rpt" in out
    # counters are delta-published: a second publish with no new
    # traffic must not double-count
    with scoped_registry() as reg:
        t.publish_tier_metrics()
        t.publish_tier_metrics()
        c = reg.get("recsys_tier_promotions_total")
        assert c is None or c.value(table="rpt") == 0


def test_publish_table_hbm_skips_dead_arrays():
    t = TieredEmbeddingTable(100, 8, hot_rows=4, name="dead")
    recsys.register_table("dead", t)
    t._hot = None                 # drop the device buffer
    t._hot_g2 = None
    with scoped_registry():
        out = recsys.publish_table_hbm()
    assert out["dead"] == 0


def test_recsys_reset_closes_registered_tables(tmp_path):
    t = TieredEmbeddingTable(100, 4, hot_rows=4, name="closing")
    path = t.backing.path
    recsys.register_table("closing", t)
    assert os.path.exists(path)
    recsys.reset()
    assert not os.path.exists(path)       # owned tmp SSD log removed
    assert recsys.tables() == {}
