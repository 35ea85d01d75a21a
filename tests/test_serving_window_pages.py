"""The second page lifetime where it lives (`serving/kv_cache.py`
`WindowPages`, under `PagedKVCache`, the scheduler and the engine): a
window lifetime never frees a page a later query can reach, never keeps
more than `ceil((W + C) / bs) + 1` a slot, and never double-frees — under
a seeded fuzz of admissions, chunks, decode steps, preemptions and frees
over BOTH lifetimes; what assumes that a page lives as long as its slot
is refused by name, and what carries only tokens (drain, migration) runs."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.models import cohere2_moe as cohere
from paddle_tpu.serving import (FleetRouter, Request, RouterConfig,
                                ServingConfig, ServingEngine,
                                load_drain_snapshot, requests_from_snapshot)
from paddle_tpu.serving.kv_cache import (SCRATCH_PAGE, PagedKVCache, PageKind,
                                         blocks_needed)
from paddle_tpu.serving.resilience import ServerOverloaded
from paddle_tpu.serving.scheduler import BucketTable, Scheduler

W, C, BS, MB = 8, 6, 4, 16
KINDS = (PageKind("k", 8, (1,), 1), PageKind("v", 8, (1,), 1),
         PageKind("k_window", 8, (0,), 1, W),
         PageKind("v_window", 8, (0,), 1, W))


def _cache(max_slots=3, num_pages=20):
    return PagedKVCache(kinds=KINDS, num_pages=num_pages, block_size=BS,
                        max_slots=max_slots, max_blocks_per_slot=MB,
                        max_chunk=C)


def test_window_pages_follow_the_position():
    cache = _cache()
    (win,) = cache.windows
    assert win.pages_per_slot == blocks_needed(W + C, BS) + 1 == 5
    assert win.num_pages == 3 * 5 + 1
    assert [p.shape[1] for p in cache.pool_args()] == [20, 20, 16, 16]
    assert cache.alloc_slot(0, 30)
    assert cache.advance(0, 0, 6) == 0 and win.live_blocks(0) == 2
    assert cache.advance(0, 6, 6) == 0 and win.live_blocks(0) == 3
    # a chunk at 12 needs keys from 12 - 7 = 5 on: entry 0 (0..3) goes
    assert cache.advance(0, 12, 6) == 1
    assert win.first_position(0) == 4 and win.live_blocks(0) == 4
    assert win.tables[0, 0] == SCRATCH_PAGE and win.tables[0, 1] != SCRATCH_PAGE
    # a decode step at 29: keys 22..29, entries 5..7
    assert cache.advance(0, 29, 1) == 4
    assert win.first_position(0) == 20 and win.live_blocks(0) == 3
    slot_table, win_table = cache.table_array()
    assert int((np.asarray(slot_table)[0] != 0).sum()) == 8    # 30 positions
    assert int((np.asarray(win_table)[0] != 0).sum()) == 3
    cache.free_slot(0)
    assert win.allocator.pages_in_use == 0
    assert cache.allocator.pages_in_use == 0


def test_a_program_longer_than_max_chunk_is_an_error_not_a_leak():
    cache = _cache(max_slots=1)
    assert cache.alloc_slot(0, 40)
    with pytest.raises(RuntimeError, match="max_chunk"):
        cache.advance(0, 0, 40)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_two_lifetimes_fuzz_no_reachable_page_freed_no_double_free(seed):
    """Admissions, chunks, decode steps, preemptions by recompute and
    frees through the SCHEDULER over both lifetimes. After every
    operation: the window table of an active slot holds a page of its
    own for every position a later query can reach and scratch
    everywhere else, at most `pages_per_slot` of them; no page is in two
    places or twice on a free list, in either lifetime."""
    cache = _cache(max_slots=3, num_pages=14)
    (win,) = cache.windows
    sched = Scheduler(cache, BucketTable((8, 16, 64), (1, 2)), max_queue=32,
                      max_seq_len=MB * BS)
    rng = np.random.default_rng(seed)
    #: slot -> the next position a program writes (all below are written)
    #: and the position the slot's LAST program began at (pages are freed
    #: when a program begins: until the next one does, the cache holds
    #: what that one could reach)
    at, began = {}, {}

    def check():
        for alloc, held in (
                (cache.allocator,
                 [p for lst in cache._slot_pages for p in lst]),
                (win.allocator,
                 [int(p) for row in win.tables for p in row
                  if p != SCRATCH_PAGE])):
            free = list(alloc._free)
            assert len(free) == len(set(free))            # no double free
            assert len(held) == len(set(held))            # no page twice
            assert not set(held) & set(free)
            assert alloc.pages_in_use == len(held)        # no leak
        for slot, st in sched.active():
            p = at.get(slot, 0)
            row = win.tables[slot]
            first = max(0, began.get(slot, 0) - W + 1) // BS
            # every written position a query at >= began can reach
            # (first*BS .. p-1) has a page; nothing before has one
            assert not row[:first].any(), (slot, p, row)
            if p:
                assert row[first:blocks_needed(p, BS)].all(), (slot, p, row)
            assert win.live_blocks(slot) <= win.pages_per_slot
        for slot, st in enumerate(sched.slots):
            if st is None:
                assert not win.tables[slot].any()

    submitted = []
    for it in range(400):
        op = rng.integers(0, 6)
        if op == 0:
            plen = int(rng.integers(1, 30))
            try:
                submitted.append(sched.submit(Request(
                    rng.integers(1, 99, (plen,)),
                    max_new_tokens=int(rng.integers(1, 20)))))
            except ServerOverloaded:
                pass
        elif op == 1:
            for st in sched.plan_admissions():
                at[st.slot] = began[st.slot] = 0
        elif op == 2:                                     # a chunk each
            for slot, st in sched.active():
                if st.prefilling:
                    n = min(C, st.prefill_len - st.prefill_pos)
                    cache.advance(slot, st.prefill_pos, n)
                    began[slot] = st.prefill_pos
                    st.prefill_pos += n
                    at[slot] = st.prefill_pos
                    if not st.prefilling:
                        st.generated.append(1)
        elif op == 3:                                     # a decode step
            for st in sched.ensure_decode_capacity():
                at.pop(st.slot, None)                     # preempted
            for slot, st in list(sched.active()):
                if st.prefilling or not st.generated:
                    continue
                cache.advance(slot, st.seq_len - 1, 1)
                began[slot], at[slot] = st.seq_len - 1, st.seq_len
                st.generated.append(1)
                if st.is_done():
                    sched.finish(st)
        elif op == 4:                                     # preempt newest
            act = [st for _, st in sched.active()]
            if len(act) >= 2 and rng.random() < 0.5:
                sched._preempt(max(act, key=lambda s: s.admitted_t))
        elif op == 5:                                     # fail one
            act = sched.active()
            if act and rng.random() < 0.3:
                sched.fail(act[int(rng.integers(0, len(act)))][1], "fuzz")
        check()
    assert win.freed > 0
    for _, st in list(sched.active()):
        sched.fail(st, "end")
    check()
    assert win.allocator.pages_in_use == cache.allocator.pages_in_use == 0


# -- what assumes that a page lives as long as its slot --------------------------

def _engine(**kw):
    paddle.seed(3)
    model = cohere.Cohere2MoeForCausalLM(cohere.cohere2_moe_tiny())
    with flag_scope("serve_prefill_chunk", 8):
        return model, ServingEngine(model, ServingConfig(
            max_batch_slots=2, block_size=4, max_context_len=64,
            prefill_buckets=(8,), batch_buckets=(1,), **kw))


@pytest.mark.parametrize("flag,value,names", [
    ("serve_prefix_cache", True, "radix prefix cache"),
    ("serve_spec_k", 2, "truncate_slot"),
    ("serve_kv_quant", "int8", "int8 pages"),
])
def test_refused_with_a_window_lifetime_by_name(flag, value, names):
    with flag_scope(flag, value):
        with pytest.raises(ValueError) as ei:
            _engine()
    assert f"FLAGS_{flag} with a window page lifetime" in str(ei.value)
    assert names in str(ei.value)


def test_truncate_slot_refuses_a_window_lifetime():
    cache = _cache()
    assert cache.alloc_slot(0, 12)
    with pytest.raises(NotImplementedError, match="window page lifetime"):
        cache.truncate_slot(0, 4)


def test_a_serving_mesh_is_refused_with_a_window_lifetime():
    import jax
    from paddle_tpu.distributed.spmd import make_mesh
    mesh = make_mesh({"mp": 2}, jax.devices()[:2])
    with pytest.raises(ValueError, match="serving mesh with a window"):
        _engine(mesh=mesh)


def test_a_model_of_slot_kinds_only_has_no_window_state():
    from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
    eng = ServingEngine(GPTForPretraining(gpt_tiny()), ServingConfig(
        max_batch_slots=2, block_size=4, max_context_len=64,
        prefill_buckets=(8, 16), batch_buckets=(1, 2)))
    assert eng.cache.windows == ()
    assert not isinstance(eng.cache.table_array(), tuple)
    assert eng.cache.advance(0, 0, 5) == 0
    eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=3)
    assert "model_counters" not in eng._stats


# -- what carries tokens, not pages: it runs -----------------------------------

PROMPT = list(range(2, 23))                           # 21 tokens: 3 chunks


def _oracle(n=14):
    _, eng = _engine()
    return [int(t) for t in eng.generate([PROMPT], max_new_tokens=n)[0]]


def test_drain_mid_window_and_resume_token_exact(tmp_path):
    """A drain's snapshot holds tokens; the successor prefills them anew
    into window pages of its own."""
    oracle = _oracle()
    _, eng = _engine()
    st = eng.submit(Request(PROMPT, max_new_tokens=14))
    for _ in range(8):                 # past the window: pages were freed
        eng.step()
    assert eng.cache.windows[0].freed > 0 and 0 < len(st.generated) < 14
    report = eng.drain(str(tmp_path), budget_s=0.0)
    assert report.snapshotted == 1
    assert eng.cache.windows[0].allocator.pages_in_use == 0
    _, specs = load_drain_snapshot(str(tmp_path))
    _, eng2 = _engine()
    (st2,) = [eng2.submit(r) for r in requests_from_snapshot(specs)]
    eng2.run()
    assert specs[0]["prompt"] + specs[0]["generated"] \
        + [int(t) for t in st2.generated] == oracle


def test_migration_after_a_replica_dies_token_exact():
    """The router replays its journal of tokens on the survivor."""
    oracle = _oracle()
    router = FleetRouter({f"r{i}": _engine()[1] for i in range(2)},
                         RouterConfig())
    rec = router.submit(Request(PROMPT, max_new_tokens=14))
    for _ in range(8):
        router.step_all()
    assert 0 < len(rec.tokens) < 14
    assert router.kill_replica(rec.replica) == 1
    router.run()
    assert rec.prompt + rec.tokens == oracle
    router.shutdown()
