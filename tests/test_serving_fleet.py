"""Fleet-scale serving (ISSUE 16): TP-sharded decode under a tensor-
parallel mesh, the prefix-affine FleetRouter over N engine replicas,
and chaos-proof migration — replica death and graceful drain both
resume in-flight requests token-exact on survivors, with availability
accounted (nothing dropped, nothing double-counted)."""

import contextlib
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.core.tensor import no_grad
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.serving import (FleetRouter, LoadSpec, Request,
                                RouterConfig, SamplingParams,
                                ServingConfig, ServingEngine,
                                run_fleet_open_loop)

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


def _fleet(model, n=2, router_kw=None, flags=(), **kw):
    """N replicas behind a router; flags entering scope at engine
    construction (kill switches are read once at init)."""
    with contextlib.ExitStack() as stack:
        for name, val in flags:
            stack.enter_context(flag_scope(name, val))
        reps = {f"r{i}": _engine(model, **kw) for i in range(n)}
        return FleetRouter(reps, RouterConfig(**(router_kw or {})))


def _golden(model, prompt, n):
    seq = np.asarray(prompt, np.int32)
    for _ in range(n):
        with no_grad():
            lg = model(paddle.to_tensor(seq[None, :])).numpy()
        seq = np.concatenate([seq, [np.int32(lg[0, -1].argmax())]])
    return seq


REP_PROMPT = [3, 4, 5, 3, 4, 5, 3, 4]
PROMPTS = [REP_PROMPT, [7, 8, 9, 7, 8, 9, 7, 8], [1, 2, 1, 2, 1, 2]]


# ---------------------------------------------------------------------------
# TP-sharded decode (tentpole a)
# ---------------------------------------------------------------------------


def test_tp_mesh_decode_token_identical(tiny_model):
    """Serving under a 1x2 tensor-parallel mesh: params sharded by the
    hybrid-parallel specs, paged KV sharded over heads, collectives
    inside the compiled programs — outputs token-identical to the
    unsharded engine."""
    import jax
    from paddle_tpu.distributed.spmd import make_mesh

    base = _engine(tiny_model)
    want = [o.tolist() for o in base.generate(PROMPTS, max_new_tokens=6)]
    base.shutdown()

    mesh = make_mesh({"mp": 2}, jax.devices()[:2])
    eng = _engine(tiny_model, mesh=mesh)
    got = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=6)]
    assert got == want
    # the paged KV pool [L, P, G, bs, (H/G)*D] is physically sharded
    # over its head-group axis, one group a chip (trailing Nones may be
    # normalized away by XLA output shardings)
    for arr in (eng.cache.k, eng.cache.v):
        assert arr.shape[2] == 2
        spec = tuple(arr.sharding.spec)
        assert spec[2] == "mp"
        assert all(ax is None for i, ax in enumerate(spec) if i != 2)
    eng.shutdown()


def test_tp_mesh_spec_decode_token_identical(tiny_model):
    """Speculative verify dispatches compile and stay token-exact under
    the mesh too (greedy oracle pin)."""
    import jax
    from paddle_tpu.distributed.spmd import make_mesh

    mesh = make_mesh({"mp": 2}, jax.devices()[:2])
    with flag_scope("serve_spec_k", 3):
        eng = _engine(tiny_model, mesh=mesh)
    out = eng.generate([REP_PROMPT], max_new_tokens=8)[0]
    assert np.array_equal(out, _golden(tiny_model, REP_PROMPT, 8))
    assert eng._stats["spec_proposed"] > 0
    eng.shutdown()


def test_tp_mesh_rejects_indivisible_heads(tiny_model):
    """gpt_tiny has 4 heads; an mp=3 mesh cannot shard them evenly and
    the engine must say so at init, not NaN at serve time."""
    import jax
    from paddle_tpu.distributed.spmd import make_mesh

    mesh = make_mesh({"mp": 3}, jax.devices()[:3])
    with pytest.raises(ValueError, match="num_heads"):
        _engine(tiny_model, mesh=mesh)


# ---------------------------------------------------------------------------
# prefix-affine routing (tentpole b)
# ---------------------------------------------------------------------------


def test_affinity_same_prefix_same_replica(tiny_model):
    """Requests sharing an affinity key (first block of prompt tokens)
    land on ONE replica — that replica's radix tree owns the family."""
    router = _fleet(tiny_model, n=3,
                    router_kw=dict(saturation_queue_depth=999),
                    flags=(("serve_prefix_cache", True),))
    pre = [11, 12, 13, 14]                       # one block (block_size 4)
    recs = [router.submit(Request(pre + [20 + i], max_new_tokens=3))
            for i in range(5)]
    assert len({r.replica for r in recs}) == 1
    # distinct keys spread: 8 different families should not all pile
    # onto a single replica of three
    others = [router.submit(Request([40 + 5 * i] * 4, max_new_tokens=2))
              for i in range(8)]
    assert len({r.replica for r in others}) >= 2
    router.run()
    assert all(r.outcome == "completed" for r in recs + others)
    assert router.summary()["routed_affine"] == 13
    router.shutdown()


def test_p2c_fallback_when_saturated(tiny_model):
    """With every replica reporting saturation the router falls back to
    power-of-two-choices over ready replicas instead of queueing the
    world on the affinity owner."""
    router = _fleet(tiny_model, n=2,
                    router_kw=dict(saturation_queue_depth=0))
    recs = [router.submit(Request(REP_PROMPT, max_new_tokens=2))
            for _ in range(8)]
    s = router.summary()
    assert s["routed_balanced"] == 8 and s["routed_affine"] == 0
    assert len({r.replica for r in recs}) == 2   # spread, not piled
    router.run()
    assert all(r.outcome == "completed" for r in recs)
    router.shutdown()


def test_unready_replica_gets_no_traffic(tiny_model):
    """Ring walk skips not-ready owners: after one replica dies and one
    drains, every key spills to the survivor and the fleet still
    serves."""
    router = _fleet(tiny_model, n=3)
    router.kill_replica("r0")
    router.drain_replica("r1")
    recs = [router.submit(Request([50 + 3 * i] * 4, max_new_tokens=2))
            for i in range(6)]
    assert {r.replica for r in recs} == {"r2"}
    router.run()
    assert all(r.outcome == "completed" for r in recs)
    router.shutdown()


def test_fleet_prefix_hit_parity_with_single_engine(tiny_model):
    """The acceptance criterion: prefix-affine placement keeps the
    FLEET's radix hit rate within 5 points of one engine serving the
    same tenanted workload (naive round-robin would shred it)."""
    spec = LoadSpec(num_requests=24, rate_rps=1e6,
                    prompt_len_range=(4, 10), max_new_range=(3, 6),
                    vocab_size=256, seed=5, sampling=SamplingParams(),
                    shared_prefix_len=8, prefix_pool_size=2,
                    prefix_zipf=1.2, tenants=4)
    hits = {}
    for n in (1, 2):
        router = _fleet(tiny_model, n=n,
                        router_kw=dict(saturation_queue_depth=999),
                        flags=(("serve_prefix_cache", True),))
        summary = run_fleet_open_loop(router, spec)
        hits[n] = summary["fleet_prefix_hit_pct"]
        assert summary["requests_completed"] == 24
        router.shutdown()
    assert hits[1] > 0
    assert abs(hits[2] - hits[1]) <= 5.0


# ---------------------------------------------------------------------------
# chaos-proof migration (tentpole c)
# ---------------------------------------------------------------------------


def test_kill_replica_mid_decode_token_exact(tiny_model):
    """The chaos drill: a replica dies mid-decode with streamed tokens
    outstanding; the router re-homes its in-flight requests from its
    own journal and every stream finishes token-exact vs the
    single-engine oracle — no dropped ids, no duplicates, availability
    100%."""
    oracle = [_golden(tiny_model, p, 8).tolist() for p in PROMPTS]
    router = _fleet(tiny_model, n=2)
    recs = [router.submit(Request(p, max_new_tokens=8)) for p in PROMPTS]
    for _ in range(3):                           # stream a few tokens
        router.step_all()
    victim = next(r.replica for r in recs if not r.done)
    streamed = {r.request_id: list(r.tokens) for r in recs}
    moved = router.kill_replica(victim)
    assert moved >= 1
    router.run()
    assert [r.prompt + r.tokens for r in recs] == oracle
    # journaled prefixes survived verbatim (mid-stream continuation,
    # not a restart of the visible stream)
    for r in recs:
        assert r.tokens[:len(streamed[r.request_id])] \
            == streamed[r.request_id]
    s = router.summary()
    assert s["migrated_death"] == moved
    assert s["duplicate_request_ids"] == 0
    assert s["requests_offered"] == len(PROMPTS)
    assert s["requests_completed"] == len(PROMPTS)
    assert s["availability_pct"] == 100.0
    router.shutdown()


def test_kill_replica_mid_chunk_prefill_token_exact(tiny_model):
    """Death strikes BETWEEN prefill chunks (no token streamed yet):
    the survivor re-prefills from the original prompt and the output is
    still token-exact."""
    router = _fleet(tiny_model, n=2,
                    flags=(("serve_prefill_chunk", 4),))
    prompt = list(range(2, 14))                  # 12 tokens -> 3 chunks
    rec = router.submit(Request(prompt, max_new_tokens=6))
    router.step_all()                            # first chunk only
    victim = router.replicas[rec.replica]
    assert victim.engine._stats["prefill_chunks"] >= 1
    assert not rec.done and rec.tokens == []
    router.kill_replica(rec.replica)
    router.run()
    assert rec.outcome == "completed"
    assert rec.prompt + rec.tokens \
        == _golden(tiny_model, prompt, 6).tolist()
    assert router.summary()["migrated_death"] == 1
    router.shutdown()


def test_drain_replica_snapshots_and_migrates(tiny_model, tmp_path):
    """Graceful hand-off: drain with a zero budget snapshots the
    in-flight request (mid-stream position and trace identity
    included); the router restores it on the survivor token-exact and
    the trace_id survives the hop."""
    with flag_scope("trace", True):
        router = _fleet(tiny_model, n=2,
                        router_kw=dict(drain_dir=str(tmp_path)))
        rec = router.submit(Request(REP_PROMPT, max_new_tokens=8))
        for _ in range(3):
            router.step_all()
        assert 0 < len(rec.tokens) < 8
        tid = rec.trace_id
        assert tid is not None
        report = router.drain_replica(rec.replica, budget_s=0.0)
        assert report["snapshotted"] == 1 and report["migrated"] == 1
        router.run()
    assert rec.outcome == "completed"
    assert rec.prompt + rec.tokens \
        == _golden(tiny_model, REP_PROMPT, 8).tolist()
    assert rec.trace_id == tid and rec.hops == 1
    s = router.summary()
    assert s["migrated_drain"] == 1 and s["availability_pct"] == 100.0
    router.shutdown()


def test_threaded_fleet_serves_and_survives_stop(tiny_model):
    """Threaded driving mode: one serve loop per replica; submissions
    complete without the caller stepping, and stop() is clean."""
    router = _fleet(tiny_model, n=2)
    router.start()
    try:
        recs = [router.submit(Request(p, max_new_tokens=4))
                for p in PROMPTS]
        deadline = time.monotonic() + 60.0
        while not all(r.done for r in recs):
            if time.monotonic() > deadline:
                pytest.fail("threaded fleet did not drain in 60s")
            time.sleep(0.01)
            router._sweep()
    finally:
        router.stop()
    assert all(r.outcome == "completed" for r in recs)
    assert not any(rep.last_error for rep in router.replicas.values())
    router.shutdown()


# ---------------------------------------------------------------------------
# construction contracts + telemetry
# ---------------------------------------------------------------------------


def test_router_rejects_mismatched_block_sizes(tiny_model):
    a = _engine(tiny_model)
    b = _engine(tiny_model, block_size=8)
    with pytest.raises(ValueError, match="block_size"):
        FleetRouter({"a": a, "b": b})
    a.shutdown()
    b.shutdown()


def _merged_docs():
    from paddle_tpu.monitor import trace as trace_mod
    from paddle_tpu.monitor.fleet import merge_fleet_traces
    return merge_fleet_traces(
        trace_mod.get_tracer().snapshot(include_live=True))


def test_router_trace_parents_replica_tree(tiny_model):
    """ISSUE 18: one routed request produces ONE merged span tree — the
    router's fleet.request root with its route span, and the replica's
    serve.request tree parented UNDER the route decision (the Dapper
    join the Request trace context carries)."""
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        router = _fleet(tiny_model, n=2)
        rec = router.submit(Request(REP_PROMPT, max_new_tokens=4))
        router.run()
        router.shutdown()
        docs = _merged_docs()
    doc = next(d for d in docs if d["trace_id"] == rec.trace_id)
    assert doc["name"] == "fleet.request"
    assert doc["merged_from"] == 2 and doc["finished"]
    assert doc["processes"][0] == "router"
    spans = {s["span_id"]: s for s in doc["spans"]}
    route = next(s for s in doc["spans"] if s["name"] == "route")
    serve = next(s for s in doc["spans"]
                 if s["name"] == "serve.request")
    assert serve["parent_id"] == route["span_id"]
    assert serve["process"] == rec.replica
    assert route["attrs"]["replica"] == rec.replica
    assert "affinity_key" in route["attrs"]
    root = spans[route["parent_id"]]
    assert root["name"] == "fleet.request"
    assert root["attrs"]["outcome"] == "completed"
    assert root["attrs"]["hops"] == 0


def test_drain_trace_parent_follows_migrate_hop(tiny_model, tmp_path):
    """Drain keeps ONE trace across the hop: the router opens a migrate
    span, the propagated parent token moves to it, and the resumed
    serve.request tree on the survivor parents under the hop."""
    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        router = _fleet(tiny_model, n=2,
                        router_kw=dict(drain_dir=str(tmp_path)))
        rec = router.submit(Request(REP_PROMPT, max_new_tokens=8))
        for _ in range(3):
            router.step_all()
        first_parent = rec.trace_parent
        assert first_parent is not None
        router.drain_replica(rec.replica, budget_s=0.0)
        assert rec.trace_parent != first_parent   # re-parented at hop
        router.run()
        router.shutdown()
        docs = _merged_docs()
    doc = next(d for d in docs if d["trace_id"] == rec.trace_id)
    assert doc["merged_from"] == 3            # router + both replicas
    hop = next(s for s in doc["spans"] if s["name"] == "migrate")
    assert hop["attrs"]["reason"] == "drain"
    serves = [s for s in doc["spans"] if s["name"] == "serve.request"]
    assert len(serves) == 2
    assert hop["span_id"] in {s["parent_id"] for s in serves}


def test_kill_replica_merged_trace_shows_hops(tiny_model):
    """Replica death still reads as ONE distributed trace: a migrate
    span with reason=death under the router root, the survivor's
    serve.request under the hop, and the Perfetto rendering carries one
    process track per participant."""
    from paddle_tpu.monitor import trace as trace_mod

    with flag_scope("trace", True), flag_scope("trace_sample", 1.0):
        router = _fleet(tiny_model, n=2)
        recs = [router.submit(Request(p, max_new_tokens=8))
                for p in PROMPTS]
        for _ in range(3):
            router.step_all()
        victim = next(r.replica for r in recs if not r.done)
        moved = [r for r in recs
                 if not r.done and r.replica == victim]
        router.kill_replica(victim)
        router.run()
        router.shutdown()
        docs = _merged_docs()
        perf = trace_mod.perfetto_doc(docs,
                                      include_host_timeline=False)
    rec = moved[0]
    assert rec.outcome == "completed" and rec.hops == 1
    doc = next(d for d in docs if d["trace_id"] == rec.trace_id)
    hop = next(s for s in doc["spans"] if s["name"] == "migrate")
    assert hop["attrs"]["reason"] == "death"
    serves = [s for s in doc["spans"] if s["name"] == "serve.request"]
    assert hop["span_id"] in {s["parent_id"] for s in serves}
    assert {s.get("process") for s in doc["spans"]} \
        == {"router", victim, rec.replica}
    tracks = {e["args"]["name"] for e in perf["traceEvents"]
              if e.get("name") == "process_name"}
    assert {"paddle_tpu.trace:router",
            f"paddle_tpu.trace:{victim}",
            f"paddle_tpu.trace:{rec.replica}"} <= tracks


def test_fleet_observability_drill(tiny_model, tmp_path):
    """The ISSUE 18 acceptance drill: tenanted traffic over a
    2-replica fleet with a mid-flight replica kill and a deadline
    blowout, a FleetFederator over the shared registry — the federated
    page is lint-clean and sums to the source, the availability burn
    fires exactly ONE rate-limited incident bundle, and the bundle
    carries the merged fleet trace."""
    import json
    import os

    from paddle_tpu.monitor import scoped_registry
    from paddle_tpu.monitor.fleet import (FederatorConfig,
                                          FleetFederator,
                                          local_registry_target)
    from paddle_tpu.monitor.metrics import lint_exposition

    clk = [1000.0]
    with scoped_registry() as reg, flag_scope("trace", True), \
            flag_scope("trace_sample", 1.0):
        router = _fleet(tiny_model, n=2)
        fed = FleetFederator(
            [local_registry_target("local")],
            FederatorConfig(
                slo_availability=0.9, slo_windows=(60.0, 600.0),
                alert_pairs=((600.0, 60.0, 1.0),),
                incident_dir=str(tmp_path),
                incident_min_interval_s=300.0),
            router=router, clock=lambda: clk[0])
        recs = [router.submit(Request(p, max_new_tokens=6,
                                      tenant=f"t{i % 2}"))
                for i, p in enumerate(PROMPTS)]
        for _ in range(3):
            router.step_all()
        victim = next(r.replica for r in recs if not r.done)
        router.kill_replica(victim)
        # one request past its deadline spends availability budget
        # (expired is a BAD event in the federator's SLO vocabulary)
        doomed = router.submit(Request(REP_PROMPT, max_new_tokens=4,
                                       deadline_s=1e-6))
        time.sleep(0.01)
        router.run()
        assert doomed.outcome == "expired"
        assert all(r.outcome == "completed" for r in recs)

        s1 = fed.scrape_once()
        assert s1["targets_scraped"] == 1
        assert s1["alerts"] and s1["incident"] is not None
        clk[0] += 10.0
        s2 = fed.scrape_once()
        assert s2["incident"] is None        # inside the rate floor

        page = fed.registry.to_prometheus()
        assert lint_exposition(page) == []
        # federated serve_requests_total == the source registry, and
        # every federated serving sample carries the host label
        src = {lb["event"]: float(v) for lb, v in
               reg.snapshot()["serve_requests_total"]["samples"]}
        fed_by_event = {}
        for lb, v in fed.registry.get(
                "serve_requests_total").samples():
            assert lb["host"] == "local"
            fed_by_event[lb["event"]] = \
                fed_by_event.get(lb["event"], 0.0) + float(v)
        assert fed_by_event == src
        # tenant rollup crossed the federation boundary
        tenants = fed._fleet_status()["tenants"]
        assert set(tenants) >= {"t0", "t1"}
        router.shutdown()

    bundles = [d for d in os.listdir(tmp_path)
               if d.startswith("incident_")]
    assert len(bundles) == 1 and bundles[0].endswith("slo_burn")
    bundle = os.path.join(tmp_path, bundles[0])
    files = set(os.listdir(bundle))
    assert {"incident.json", "statusz.json", "metrics.prom",
            "flight.json", "trace_perfetto.json"} <= files
    with open(os.path.join(bundle, "incident.json")) as f:
        inc = json.load(f)
    assert inc["trigger"] == "slo_burn" and inc["alerts"]
    with open(os.path.join(bundle, "trace_perfetto.json")) as f:
        perf = json.load(f)
    tracks = {e["args"]["name"] for e in perf["traceEvents"]
              if e.get("name") == "process_name"}
    assert "paddle_tpu.trace:router" in tracks


def test_fleet_observability_off_by_default(tiny_model):
    """Zero-overhead pin: with FLAGS_fleet_monitor_* at defaults the
    router fast path allocates no federator, no scrape thread and no
    spans."""
    import threading

    from paddle_tpu.monitor import trace as trace_mod
    from paddle_tpu.monitor.fleet import (SCRAPE_THREAD_PREFIX,
                                          get_federator)

    router = _fleet(tiny_model, n=2)
    router.generate([REP_PROMPT], max_new_tokens=3)
    router.shutdown()
    assert get_federator() is None
    assert not [t for t in threading.enumerate()
                if t.name.startswith(SCRAPE_THREAD_PREFIX)]
    assert trace_mod.TRACE_STATS["spans_allocated"] == 0


def test_fleet_gauges_published(tiny_model):
    """summary() publishes the per-replica gauges the --fleet report
    renders: queue depth, prefix hit%, shed, and fleet size by state."""
    from paddle_tpu.monitor import scoped_registry

    with scoped_registry() as reg:
        router = _fleet(tiny_model, n=2,
                        flags=(("serve_prefix_cache", True),))
        router.generate([REP_PROMPT], max_new_tokens=3)
        router.kill_replica("r1")
        router.summary()
        snap = reg.snapshot()
        router.shutdown()
    states = {tuple(sorted(lb.items())): v for lb, v in
              snap["serve_router_replicas"]["samples"]}
    assert states[(("state", "alive"),)] == 1
    assert states[(("state", "ready"),)] == 1
    assert any(lb.get("replica") == "r0" for lb, _ in
               snap["serve_router_replica_queue_depth"]["samples"])
