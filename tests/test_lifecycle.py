"""Zero-downtime model lifecycle (ISSUE 20): live weight hot-swap with
per-slot weight epochs, shadow/A-B traffic splitting, and the
SLO-guarded promote-or-rollback controller — plus the flags-off
byte-identity pins, the chaos drills for torn/corrupt/dying pushes, and
the tooling surface (monitor_report --lifecycle)."""

import gc
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.distributed import checkpoint as dckpt
from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
from paddle_tpu.monitor import scoped_registry
from paddle_tpu.serving import (FleetRouter, LifecycleConfig,
                                LifecycleController, LoadSpec, Request,
                                RouterConfig, SamplingParams,
                                ServingConfig, ServingEngine,
                                TrafficSplit, WeightSwapError,
                                assign_arm, build_requests,
                                should_shadow)
from paddle_tpu.testing import chaos

pytestmark = pytest.mark.serve

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))


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
    import contextlib
    with contextlib.ExitStack() as stack:
        for name, val in flags:
            stack.enter_context(flag_scope(name, val))
        reps = {f"r{i}": _engine(model, **kw) for i in range(n)}
        return FleetRouter(reps, RouterConfig(**(router_kw or {})))


def _save_manifest(engine, path, perturb=0.0):
    """The engine's live tree (optionally perturbed) as a committed
    manifest checkpoint — the shape every push must arrive in."""
    import jax.numpy as jnp
    state = {}
    for name, arr in engine.params.items():
        a = jnp.asarray(arr)
        if perturb and jnp.issubdtype(a.dtype, jnp.inexact):
            a = a + jnp.asarray(perturb, a.dtype)
        state[name] = a
    dckpt.save(state, str(path), asynchronous=False)
    return str(path)


PROMPTS = [[3, 4, 5, 3, 4, 5, 3, 4], [7, 8, 9, 7, 8, 9, 7, 8],
           [1, 2, 1, 2, 1, 2]]


# ---------------------------------------------------------------------------
# swap_weights: flag gate, refusal paths, identity cutover
# ---------------------------------------------------------------------------


def test_swap_flag_off_raises(tiny_model, tmp_path):
    eng = _engine(tiny_model)
    with pytest.raises(RuntimeError, match="serve_hot_swap"):
        eng.swap_weights(str(tmp_path))
    with pytest.raises(RuntimeError, match="serve_hot_swap"):
        eng.rollback_weights()
    assert "weights" not in eng._admin_status()
    eng.shutdown()


def test_identity_swap_token_exact_and_rollback_chain(tiny_model,
                                                      tmp_path):
    """An identity push (the live tree re-saved) must be a perfect
    no-op for greedy output; rollback re-stages the retained tree and
    commit drops the anchor for good."""
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    want = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=6)]
    push = _save_manifest(eng, tmp_path / "push")
    info = eng.swap_weights(push)
    # idle engine: between steps IS an iteration boundary — immediate
    assert info["mode"] == "staged" and not info["pending"]
    assert eng.metrics_summary()["weights_epoch"] == 1
    got = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=6)]
    assert got == want
    # rollback is a cutover back to the retained tree (epoch 2), and
    # commit afterwards drops the anchor: a second rollback refuses
    eng.rollback_weights()
    assert eng.metrics_summary()["weights_epoch"] == 2
    got = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=6)]
    assert got == want
    eng.commit_swap()
    with pytest.raises(WeightSwapError, match="no previous"):
        eng.rollback_weights()
    w = eng._admin_status()["weights"]
    assert w["epoch"] == 2 and w["live_manifest"] is None
    assert w["swaps"]["cutover"] == 2 and w["swaps"]["rolled_back"] == 1
    eng.shutdown()


def test_swap_refuses_torn_manifest_chaos(tiny_model, tmp_path):
    """Chaos site serve.swap.torn_manifest: the push reads as torn and
    MUST refuse with zero side effects — old weights keep serving."""
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    want = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=4)]
    push = _save_manifest(eng, tmp_path / "push")
    with chaos.chaos_scope("serve.swap.torn_manifest@1"):
        with pytest.raises(WeightSwapError, match="torn"):
            eng.swap_weights(push)
        assert chaos.fired()
    assert eng.metrics_summary()["weights_epoch"] == 0
    assert eng.metrics_summary()["weight_swaps_refused"] == 1
    got = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=4)]
    assert got == want
    eng.shutdown()


def test_swap_refuses_missing_and_mismatched(tiny_model, tmp_path):
    """Real refusals, no chaos: a manifest that does not exist, and a
    committed one whose tree does not match the live params."""
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    with pytest.raises(WeightSwapError):
        eng.swap_weights(str(tmp_path / "nope"))
    # right key set, wrong shape on one leaf
    import jax.numpy as jnp
    state = {k: jnp.asarray(v) for k, v in eng.params.items()}
    first = next(iter(state))
    state[first] = jnp.zeros((3, 3), state[first].dtype)
    dckpt.save(state, str(tmp_path / "badshape"), asynchronous=False)
    with pytest.raises(WeightSwapError, match="shape"):
        eng.swap_weights(str(tmp_path / "badshape"))
    # missing + extra keys
    state = {k: jnp.asarray(v) for k, v in eng.params.items()}
    state.pop(first)
    state["not_a_param"] = jnp.zeros((2,), "float32")
    dckpt.save(state, str(tmp_path / "badkeys"), asynchronous=False)
    with pytest.raises(WeightSwapError, match="missing"):
        eng.swap_weights(str(tmp_path / "badkeys"))
    assert eng.metrics_summary()["weight_swaps_refused"] == 3
    assert eng.metrics_summary()["weights_epoch"] == 0
    eng.shutdown()


def test_flags_off_and_armed_unused_byte_identical(tiny_model):
    """The tentpole's no-op contract: a hot-swap-armed engine that
    never swaps runs the SAME dispatches and tokens as a flags-off
    engine, and a flags-off run emits none of the lifecycle series."""
    with scoped_registry() as reg:
        base = _engine(tiny_model)
        want = [o.tolist() for o in base.generate(PROMPTS,
                                                  max_new_tokens=6)]
        base_sum = base.metrics_summary()
        base.shutdown()
        assert "serve_swaps_total" not in reg.snapshot()
        assert "serve_weights_epoch" not in reg.snapshot()
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    got = [o.tolist() for o in eng.generate(PROMPTS, max_new_tokens=6)]
    armed_sum = eng.metrics_summary()
    eng.shutdown()
    assert got == want
    assert armed_sum["decode_dispatches"] == \
        base_sum["decode_dispatches"]
    assert armed_sum["prefill_chunks"] == base_sum["prefill_chunks"]


# ---------------------------------------------------------------------------
# cross-epoch invariants: the 200-request mid-swap drill
# ---------------------------------------------------------------------------


def test_mid_swap_cross_epoch_drill_200_requests(tiny_model, tmp_path):
    """200 open-loop requests with a REAL weight change pushed mid-run:
    every request in flight (or already done) at the cutover is greedy
    token-identical to a no-swap oracle — slots finish decoding on the
    weights that wrote their KV — and the terminal accounting identity
    closes exactly (submitted == completed + expired + shed +
    cancelled + failed + drained). The retired tree is released once
    its last slot terminates."""
    spec = LoadSpec(num_requests=200, rate_rps=600.0,
                    prompt_len_range=(4, 10), max_new_range=(3, 6),
                    vocab_size=tiny_model.cfg.vocab_size, seed=5,
                    sampling=SamplingParams())

    def drive(engine, swap_at=None, push=None):
        schedule = build_requests(spec)
        tokens = {}
        for idx, (_, req) in enumerate(schedule):
            def cb(r, tok, text, idx=idx):
                tokens.setdefault(idx, []).append(int(tok))
            req.on_token = cb
        done_by_swap = None
        t0 = time.perf_counter()
        i = 0
        states = []
        while i < len(schedule) or engine.scheduler.has_work:
            now = time.perf_counter() - t0
            while i < len(schedule) and schedule[i][0] <= now:
                states.append((i, engine.submit(schedule[i][1])))
                i += 1
            if swap_at is not None and i >= swap_at:
                # pre-swap cohort: everything terminal or resident NOW
                # (the cutover stamps every resident slot, stamped or
                # not, with the old epoch)
                done_by_swap = (
                    {idx for idx, st in states if st.outcome is not None}
                    | {idx for idx, st in states
                       for _, a in engine.scheduler.active()
                       if a is st})
                engine.swap_weights(push)
                swap_at = None
            if engine.scheduler.has_work:
                engine.step()
        return tokens, done_by_swap, engine.metrics_summary()

    oracle = _engine(tiny_model, max_batch_slots=4,
                     batch_buckets=(1, 2, 4))
    want, _, _ = drive(oracle)
    oracle.shutdown()

    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model, max_batch_slots=4,
                      batch_buckets=(1, 2, 4))
    push = _save_manifest(eng, tmp_path / "push", perturb=0.05)
    got, preswap, summary = drive(eng, swap_at=100, push=push)
    assert preswap, "drill never caught requests in flight at cutover"
    for idx in sorted(preswap):
        assert got[idx] == want[idx], \
            f"pre-swap request {idx} diverged from the no-swap oracle"
    # terminal accounting identity — nothing lost, nothing double
    assert summary["requests_submitted"] == 200
    assert summary["requests_submitted"] == (
        summary["requests_completed"] + summary["requests_expired"]
        + summary["requests_shed"] + summary["requests_cancelled"]
        + summary["requests_failed"] + summary["requests_drained"])
    assert summary["weights_epoch"] == 1
    # the old tree was retired and then released with its last slot,
    # and prefix-cache donation (detached through the transition) is
    # live again once the last old-epoch slot leaves
    assert eng._retired == {}
    if eng.prefix_cache is not None:
        assert eng.cache.prefix_cache is not None
    eng.shutdown()


def test_three_live_swaps_under_mmpp_fleet_load(tiny_model, tmp_path):
    """The acceptance drill: 3 consecutive identity swaps across a
    2-replica fleet under bursty mmpp arrivals — availability >= 99.9%
    with zero lost and zero duplicated requests, and every replica
    lands on epoch 3."""
    from paddle_tpu.serving.resilience import ServerOverloaded
    spec = LoadSpec(num_requests=36, rate_rps=300.0,
                    prompt_len_range=(4, 10), max_new_range=(3, 6),
                    vocab_size=tiny_model.cfg.vocab_size, seed=9,
                    sampling=SamplingParams(), arrival="mmpp",
                    burstiness=3.0, mmpp_switch=0.2)
    router = _fleet(tiny_model, n=2,
                    router_kw={"saturation_queue_depth": 12},
                    flags=(("serve_hot_swap", True),))
    push = _save_manifest(router.replicas["r0"].engine,
                          tmp_path / "push")
    schedule = build_requests(spec)
    quarters = [len(schedule) // 4, len(schedule) // 2,
                3 * len(schedule) // 4]
    swaps = 0
    t0 = time.perf_counter()
    i = 0
    while i < len(schedule) or any(
            r.alive and r.engine.scheduler.has_work
            for r in router.replicas.values()):
        now = time.perf_counter() - t0
        while i < len(schedule) and schedule[i][0] <= now:
            try:
                router.submit(schedule[i][1])
            except ServerOverloaded:
                pass
            i += 1
        if swaps < len(quarters) and i >= quarters[swaps]:
            for rep in router.replicas.values():
                info = rep.engine.swap_weights(push)
                if not info.get("pending"):
                    rep.engine.commit_swap()
            swaps += 1
        router.step_all()
    summary = router.summary()
    epochs = {n: r.engine.metrics_summary()["weights_epoch"]
              for n, r in router.replicas.items()}
    router.shutdown()
    assert swaps == 3 and epochs == {"r0": 3, "r1": 3}
    assert summary["availability_pct"] >= 99.9
    assert summary["duplicate_request_ids"] == 0
    assert summary["requests_in_flight"] == 0
    lost = (summary["requests_offered"] - summary["requests_completed"]
            - summary["requests_failed"] - summary["requests_rejected"])
    assert lost == 0


def test_drain_fallback_swap_resubmits_continuations(tiny_model,
                                                     tmp_path):
    """mode="drain": in-flight slots snapshot, release, cut over, and
    resubmit on the new weights — streamed tokens stand, callbacks
    survive the hop, and the drained/resubmitted accounting closes."""
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    push = _save_manifest(eng, tmp_path / "push")
    # the continuation is a NEW request carrying the ORIGINAL callback
    # object — a per-client closure sees the stream stay contiguous
    # across the hop even though the request id changes
    streams = []

    def _client():
        lst = []
        streams.append(lst)
        return lambda req, tok, text: lst.append(int(tok))

    sts = [eng.submit(Request(p, max_new_tokens=8,
                              on_token=_client()))
           for p in PROMPTS[:2]]
    eng.step()                               # prefill: slots resident
    pre_lens = [len(s) for s in streams]
    info = eng.swap_weights(push, mode="drain")
    assert info["mode"] == "drain"
    assert info["resubmitted"] == 2
    assert eng.metrics_summary()["weights_epoch"] == 1
    eng.run()
    stats = eng.scheduler.stats
    assert stats["drained"] == 2
    # 2 originals + 2 continuations, all accounted
    assert stats["submitted"] == 4
    assert stats["submitted"] == (
        stats["completed"] + stats["expired"] + stats["shed"]
        + stats["cancelled"] + stats["failed"] + stats["drained"])
    for s, pre in zip(streams, pre_lens):
        # each client stream kept growing after the hop, to full budget
        assert len(s) == 8 >= pre
    assert eng._swap_stats["drain_swaps"] == 1
    eng.shutdown()
    del sts


def test_auto_mode_headroom_preflight(tiny_model, tmp_path,
                                      monkeypatch):
    """mode="auto" stages when the device reports headroom (or reports
    nothing — the CPU backend) and falls back to drain when the
    candidate would not fit beside the live + retired trees."""
    from paddle_tpu.monitor import memory as _memory
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    push = _save_manifest(eng, tmp_path / "push")
    assert eng.swap_weights(push)["mode"] == "staged"   # CPU: no stats
    monkeypatch.setattr(
        _memory, "device_memory_stats",
        lambda device=None: {"bytes_limit": 100,
                             "bytes_in_use": 99})
    assert eng.swap_weights(push)["mode"] == "drain"
    eng.shutdown()


def test_shutdown_unstages_pending_candidate_no_leak(tiny_model,
                                                     tmp_path):
    """A candidate staged behind a busy engine must not outlive
    shutdown(): the staged tree's bytes leave the live-buffer census
    once the engine is torn down (the half-loaded-push leak pin)."""
    import jax.numpy as jnp
    from paddle_tpu.monitor.memory import live_bytes
    with flag_scope("serve_hot_swap", True):
        eng = _engine(tiny_model)
    push = _save_manifest(eng, tmp_path / "push")
    tree_bytes = sum(
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for a in eng.params.values())
    eng.submit(Request(PROMPTS[0], max_new_tokens=32))
    eng.step()                               # resident slot: busy
    gc.collect()
    before = live_bytes()
    info = eng.swap_weights(push)
    assert info["pending"], "engine was not busy — staging not pending"
    gc.collect()
    staged = live_bytes()
    assert staged >= before + 0.9 * tree_bytes
    eng.shutdown()
    del eng, info
    gc.collect()
    after = live_bytes()
    # the staged tree (at least) was released; shutdown also frees the
    # KV pools, so the census drops by MORE than the candidate's bytes
    assert after <= staged - 0.9 * tree_bytes


# ---------------------------------------------------------------------------
# shadow/A-B traffic splitting
# ---------------------------------------------------------------------------


def test_split_hash_deterministic_and_loadgen_agrees(tiny_model):
    """assign_arm/should_shadow are pure hashes — stable across calls
    and processes — and LoadSpec tagging stamps the SAME assignment the
    router would make, without perturbing the default draws."""
    arms = [assign_arm(i, seed=7, candidate_frac=0.3)
            for i in range(200)]
    assert arms == [assign_arm(i, seed=7, candidate_frac=0.3)
                    for i in range(200)]
    frac = arms.count("candidate") / 200.0
    assert 0.15 < frac < 0.45
    assert assign_arm(5, seed=7, candidate_frac=0.0) == "baseline"
    assert not should_shadow(5, seed=7, shadow_frac=0.0)
    # loadgen: defaults are byte-identical, tags match the hashes
    base = LoadSpec(num_requests=12, rate_rps=50.0, seed=3,
                    vocab_size=64, sampling=SamplingParams())
    import dataclasses
    tagged = dataclasses.replace(base, ab_split=0.3, shadow_frac=0.5,
                                 split_seed=7)
    a = build_requests(base)
    from paddle_tpu.serving import scheduler as _sched
    _sched._reset_request_ids()
    b = build_requests(tagged)
    assert [(t, list(map(int, r.prompt)), r.max_new_tokens)
            for t, r in a] == \
        [(t, list(map(int, r.prompt)), r.max_new_tokens)
         for t, r in b]
    assert all(not hasattr(r, "lifecycle_arm") for _, r in a)
    for _, r in b:
        assert r.lifecycle_arm == assign_arm(int(r.request_id), 7, 0.3)
        assert r.lifecycle_shadow == should_shadow(
            int(r.request_id), 7, 0.5)


def test_traffic_split_flag_off_raises(tiny_model):
    router = _fleet(tiny_model, n=2)
    with pytest.raises(RuntimeError, match="serve_traffic_split"):
        router.set_traffic_split(TrafficSplit(candidate="r1"))
    router.shutdown()
    with pytest.raises(ValueError):
        TrafficSplit(candidate="r1", ab_frac=1.5)


def test_shadow_mirror_measures_but_never_serves(tiny_model, tmp_path):
    """shadow_frac=1.0 over a perturbed candidate: every baseline
    completion mirrors to the candidate, divergence is counted, the
    per-arm series exist — and shadows never touch client callbacks or
    the availability books."""
    with scoped_registry() as reg:
        router = _fleet(tiny_model, n=2,
                        flags=(("serve_hot_swap", True),
                               ("serve_traffic_split", True)))
        push = _save_manifest(router.replicas["r1"].engine,
                              tmp_path / "cand", perturb=0.05)
        router.replicas["r1"].engine.swap_weights(push)
        router.set_traffic_split(TrafficSplit(
            candidate="r1", shadow_frac=1.0, seed=7))
        tokens = []
        recs = [router.submit(Request(
            p, max_new_tokens=6,
            on_token=lambda r, t, x: tokens.append(int(t))))
            for p in PROMPTS]
        router.run()
        summary = router.summary()
        router.shutdown()
        snap = reg.snapshot()
    assert all(r.outcome == "completed" for r in recs)
    assert summary["shadow_mirrored"] == 3
    assert summary["arm_requests"].get("shadow") == 3
    # shadows are invisible to clients and to availability
    assert len(tokens) == sum(len(r.tokens) for r in recs)
    assert summary["requests_offered"] == 3
    assert summary["availability_pct"] == 100.0
    # perturbed weights on greedy mirrors: divergence counted
    assert summary["shadow_divergence"] >= 1
    assert "serve_shadow_divergence_total" in snap
    arm_events = {tuple(sorted(lb.items())) for lb, _ in
                  snap["serve_arm_requests_total"]["samples"]}
    assert (("arm", "baseline"), ("event", "completed")) in arm_events
    assert (("arm", "shadow"), ("event", "completed")) in arm_events
    assert "serve_arm_e2e_seconds" in snap


def test_ab_split_routes_and_matches_loadgen_tags(tiny_model,
                                                  tmp_path):
    """A/B arms route deterministically: candidate-arm requests land on
    the candidate replica, baseline never does, and the router's arm
    assignment agrees with LoadSpec tagging request-by-request."""
    router = _fleet(tiny_model, n=2,
                    flags=(("serve_hot_swap", True),
                           ("serve_traffic_split", True)))
    router.set_traffic_split(TrafficSplit(candidate="r1", ab_frac=0.4,
                                          seed=11))
    spec = LoadSpec(num_requests=16, rate_rps=100.0,
                    prompt_len_range=(4, 10), max_new_range=(2, 4),
                    vocab_size=tiny_model.cfg.vocab_size, seed=2,
                    sampling=SamplingParams(), ab_split=0.4,
                    split_seed=11)
    schedule = build_requests(spec)
    tags = {int(r.request_id): r.lifecycle_arm for _, r in schedule}
    recs = [router.submit(req) for _, req in schedule]
    router.run()
    summary = router.summary()
    router.shutdown()
    assert {r.outcome for r in recs} == {"completed"}
    arms = {r.request_id: r.arm for r in recs}
    assert arms == tags
    assert "candidate" in arms.values() and "baseline" in arms.values()
    for r in recs:
        if r.arm == "candidate":
            assert r.replica == "r1"
        else:
            assert r.replica != "r1"
    assert summary["traffic_split"]["candidate"] == "r1"


# ---------------------------------------------------------------------------
# the SLO-guarded promotion controller
# ---------------------------------------------------------------------------


def test_lifecycle_flag_off_raises(tiny_model):
    router = _fleet(tiny_model, n=2)
    with pytest.raises(RuntimeError, match="serve_lifecycle"):
        LifecycleController(router)
    router.shutdown()


def _controller(router, **cfg):
    with flag_scope("serve_lifecycle", True):
        return LifecycleController(router, LifecycleConfig(**cfg))


def _drive(router, n, max_new=4, seed=4):
    spec = LoadSpec(num_requests=n, rate_rps=400.0,
                    prompt_len_range=(4, 10),
                    max_new_range=(2, max_new),
                    vocab_size=router.replicas["r0"].engine.model
                    .cfg.vocab_size if hasattr(
                        router.replicas["r0"].engine, "model")
                    else 128,
                    seed=seed, sampling=SamplingParams())
    recs = [router.submit(req) for _, req in build_requests(spec)]
    router.run()
    return recs


def test_lifecycle_promotes_good_push_rolling(tiny_model, tmp_path):
    """A healthy identity push bakes on shadow traffic and promotes:
    the split clears, the remaining replicas roll one at a time, every
    engine lands on the new epoch with its anchor committed."""
    router = _fleet(tiny_model, n=2,
                    flags=(("serve_hot_swap", True),
                           ("serve_traffic_split", True)))
    push = _save_manifest(router.replicas["r0"].engine,
                          tmp_path / "push")
    ctrl = _controller(router, bake_window_s=0.0, min_requests=3)
    out = ctrl.begin(push, candidate="r1",
                     split=TrafficSplit(candidate="r1", ab_frac=0.3,
                                        shadow_frac=1.0, seed=7))
    assert out["state"] == "baking" and out["epoch"] == 1
    recs = _drive(router, 12)
    assert all(r.outcome == "completed" for r in recs)
    # router.step_all ticks maybe_decide — with a zero bake window the
    # promotion usually lands during the drive itself
    if ctrl.state != "promoted":
        assert ctrl.maybe_decide() == "promoted"
    assert ctrl.state == "promoted"
    summary = ctrl.summary()
    assert summary["decision"]["rolled"] == ["r0"]
    epochs = {n: r.engine.metrics_summary()["weights_epoch"]
              for n, r in router.replicas.items()}
    assert epochs == {"r0": 1, "r1": 1}
    assert router.summary()["traffic_split"] is None
    # the CANDIDATE's anchor commits at promote (its bake passed); the
    # rolled replica keeps its rollback anchor when the rolling swap
    # landed behind in-flight slots — that one is the operator's call
    with pytest.raises(WeightSwapError):
        router.replicas["r1"].engine.rollback_weights()
    states = [e["to"] for e in ctrl.timeline]
    assert states == ["serving", "staging", "baking", "promoted"]
    router.shutdown()


def test_lifecycle_bad_push_auto_rollback_incident(tiny_model,
                                                   tmp_path):
    """The bad-push drill: chaos plants NaNs into the candidate tree
    AFTER validation; shadow traffic fails on the candidate, the
    nonfinite trigger rolls back within the bake window, baseline
    output is bit-identical throughout, and the forensics land — an
    incident bundle (incident.json + flight.json) and flight events."""
    from paddle_tpu.monitor.flight_recorder import get_flight_recorder
    inc_dir = str(tmp_path / "incidents")
    with flag_scope("flight_recorder", True), \
            flag_scope("flight_recorder_dir", str(tmp_path)):
        router = _fleet(tiny_model, n=2,
                        flags=(("serve_hot_swap", True),
                               ("serve_traffic_split", True)))
        base_eng = router.replicas["r0"].engine
        want = [o.tolist() for o in base_eng.generate(
            PROMPTS, max_new_tokens=4)]
        push = _save_manifest(base_eng, tmp_path / "push")
        ctrl = _controller(router, bake_window_s=30.0, min_requests=3,
                           incident_dir=inc_dir)
        with chaos.chaos_scope("serve.swap.bad_weights@1"):
            out = ctrl.begin(push, candidate="r1")
        assert out["state"] == "baking"
        recs = _drive(router, 10)
        assert ctrl.state == "rolled-back"
        assert ctrl.summary()["decision"]["trigger"] == "nonfinite"
        # baseline traffic never touched the bad weights
        assert all(r.outcome == "completed" for r in recs)
        assert router.summary()["availability_pct"] == 100.0
        got = [o.tolist() for o in base_eng.generate(
            PROMPTS, max_new_tokens=4)]
        assert got == want
        # the candidate rolled back to the pre-push tree: bit-identical
        # to the baseline replica again
        got_c = [o.tolist() for o in
                 router.replicas["r1"].engine.generate(
                     PROMPTS, max_new_tokens=4)]
        assert got_c == want
        events = [e["event"] for e in
                  get_flight_recorder().events]
        router.shutdown()
    assert "lifecycle_rollback" in events
    assert "weights_cutover" in events
    bundles = os.listdir(inc_dir)
    assert len(bundles) == 1 and bundles[0].endswith("nonfinite")
    bdir = os.path.join(inc_dir, bundles[0])
    assert {"incident.json", "flight.json"} <= set(os.listdir(bdir))
    with open(os.path.join(bdir, "incident.json")) as f:
        inc = json.load(f)
    assert inc["decision"] == "rolled-back"
    assert inc["trigger"] == "nonfinite"
    assert inc["arms"]["shadow"]["outcomes"].get("failed", 0) >= 1


def test_lifecycle_refused_push_aborts_to_serving(tiny_model,
                                                  tmp_path):
    router = _fleet(tiny_model, n=2,
                    flags=(("serve_hot_swap", True),
                           ("serve_traffic_split", True)))
    ctrl = _controller(router)
    out = ctrl.begin(str(tmp_path / "nope"), candidate="r1")
    assert out["aborted"] == "refused" and ctrl.state == "serving"
    assert router.summary()["traffic_split"] is None
    # the fleet still serves
    recs = _drive(router, 4)
    assert all(r.outcome == "completed" for r in recs)
    router.shutdown()


def test_chaos_replica_die_mid_swap_aborts(tiny_model, tmp_path):
    """Chaos site serve.swap.replica_die_mid_swap: the candidate dies
    with the swap staged — the push aborts to serving, the dead
    replica's work migrates, and the baseline keeps serving."""
    router = _fleet(tiny_model, n=2,
                    flags=(("serve_hot_swap", True),
                           ("serve_traffic_split", True)))
    push = _save_manifest(router.replicas["r0"].engine,
                          tmp_path / "push")
    ctrl = _controller(router)
    with chaos.chaos_scope("serve.swap.replica_die_mid_swap@1"):
        out = ctrl.begin(push, candidate="r1")
        assert chaos.fired()
    assert out["aborted"] == "replica_died"
    assert ctrl.state == "serving"
    assert not router.replicas["r1"].alive
    recs = _drive(router, 4)
    assert all(r.outcome == "completed" for r in recs)
    assert all(r.replica == "r0" for r in recs)
    router.shutdown()


# ---------------------------------------------------------------------------
# tooling: monitor_report --lifecycle
# ---------------------------------------------------------------------------


def test_monitor_report_lifecycle_renders(tiny_model, tmp_path):
    """--lifecycle renders the push state, swap counters, per-arm
    tables and the state/epoch timeline from a real registry dump."""
    import monitor_report
    with scoped_registry() as reg:
        router = _fleet(tiny_model, n=2,
                        flags=(("serve_hot_swap", True),
                               ("serve_traffic_split", True)))
        push = _save_manifest(router.replicas["r0"].engine,
                              tmp_path / "push")
        ctrl = _controller(router, bake_window_s=0.0, min_requests=2)
        ctrl.begin(push, candidate="r1")
        recs = _drive(router, 6)
        assert all(r.outcome == "completed" for r in recs)
        if ctrl.state != "promoted":
            assert ctrl.maybe_decide() == "promoted"
        path = str(tmp_path / "m.jsonl")
        reg.dump_jsonl(path)
        router.shutdown()
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    out = monitor_report.render(rows, lifecycle=True)
    assert "Lifecycle (hot-swap push state)" in out
    assert "promoted" in out
    assert "Weight-swap events" in out and "cutover" in out
    assert "Shadow/A-B arms" in out
    assert "Lifecycle timeline" in out
    # sync pin: the tool's standalone fallback can never drift from
    # the canonical state tuple
    from paddle_tpu.serving.lifecycle import STATES
    assert monitor_report._LIFECYCLE_STATES_FALLBACK == tuple(STATES)
