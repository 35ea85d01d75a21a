"""Continuous-batching scheduler: iteration-level admission into fixed
batch slots.

The Orca (OSDI '22) scheduling model on static XLA shapes: scheduling
decisions happen **between** decode steps, never inside a compiled
program —

- a FIFO request queue feeds ``max_batch_slots`` fixed slots; a request
  is admitted the step a slot AND enough KV pages free up, and its slot
  is released the step it finishes (no waiting for a batch to drain —
  the throughput lever continuous batching exists for);
- admitted requests are **prefilled in bucketed groups**: the prompt
  rounds up to a ``(batch, prefill_len)`` bucket from the
  :class:`BucketTable`, so the number of distinct prefill executables is
  bounded by the table, not by traffic (decode is always the ONE
  full-slot-batch program — admission/eviction just flips the active
  mask and block tables, which are arguments);
- when the page pool runs dry mid-decode, the newest-admitted request is
  **preempted** (vLLM's recompute policy): its pages are freed, its
  prompt + tokens-so-far go back to the FRONT of the queue, and it
  re-prefills later — for greedy decoding the continuation is
  token-identical.

All of this is host-side bookkeeping over ints; device state never
changes shape.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..monitor import trace as _trace
from ..testing import chaos
from .kv_cache import PagedKVCache
from .resilience import ServerOverloaded
from .sampling import SamplingParams

__all__ = ["Request", "RequestState", "BucketTable", "Scheduler",
           "AdmissionGroup", "QUEUE_POLICIES", "TERMINAL_OUTCOMES"]

#: bounded-queue shedding policies (ServingConfig.queue_policy)
QUEUE_POLICIES = ("reject-new", "drop-oldest", "priority")

#: every request ends in exactly one of these (the fuzz test pins the
#: exclusivity); "completed" is the only success
TERMINAL_OUTCOMES = ("completed", "expired", "shed", "cancelled",
                     "failed", "drained")

_request_ids = itertools.count()


def _reset_request_ids() -> None:
    global _request_ids
    _request_ids = itertools.count()


@dataclass
class Request:
    """One generation request.

    ``on_token(request, token_id, text)`` streams every generated token
    the decode step it is produced (``text`` is None unless the engine
    has a detokenizer). ``eos_token_id`` ends the stream early; the eos
    token itself is reported and included in the output.

    ``deadline_s`` is a time-to-live from submission: a queued request
    past its deadline expires before it ever touches a slot; an
    in-flight one is cancelled at the next iteration boundary and its
    pages freed immediately. ``priority`` feeds the ``priority`` queue
    policy (higher = more important; ties stay FIFO). ``stop`` is an
    optional custom stop condition ``stop(generated_ids) -> bool``
    evaluated after every accepted token; a raising (malformed) stop
    condition fails ONLY its own request.

    ``trace_id`` resumes an existing trace identity under
    ``FLAGS_trace`` (drain snapshots carry it so a request's span tree
    continues on the successor engine); None = the tracer mints one.
    ``trace_parent`` / ``trace_process`` / ``trace_sampled`` are the
    rest of the cross-process trace context (ISSUE 18): the
    ``Trace.context_for`` token of the upstream (router) span this
    request's ``serve.request`` tree parents under, the replica label
    the submitter assigned this engine (one Perfetto track per
    process), and the upstream head-sampling decision — Dapper's
    sampled bit, so one coin governs every process's slice of the
    trace. All None for a bare single-engine submit.

    ``tenant`` names the submitting tenant for per-tenant quota +
    metrics (ISSUE 17; None = untenanted, never quota-limited);
    ``adapter`` names a loaded LoRA adapter (serving.lora) the request
    decodes against (None = the base model).
    """

    prompt: Sequence[int]
    max_new_tokens: int = 16
    sampling: SamplingParams = field(default_factory=SamplingParams)
    eos_token_id: Optional[int] = None
    on_token: Optional[Callable] = None
    deadline_s: Optional[float] = None
    priority: int = 0
    stop: Optional[Callable] = None
    trace_id: Optional[str] = None
    trace_parent: Optional[str] = None
    trace_process: Optional[str] = None
    trace_sampled: Optional[bool] = None
    tenant: Optional[str] = None
    adapter: Optional[str] = None
    request_id: int = field(default_factory=lambda: next(_request_ids))

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError("deadline_s must be > 0 (None = no deadline)")


class RequestState:
    """Scheduler-internal lifecycle record for one request."""

    def __init__(self, request: Request, now: float):
        self.request = request
        self.prompt_len = int(request.prompt.size)
        self.generated: List[int] = []
        self.slot: Optional[int] = None
        self.submitted_t = now
        #: when this request last entered the waiting queue: submission,
        #: or its requeue after a preemption (the start of the
        #: ``serve.queued`` span the next admission records)
        self.queued_t = now
        self.admitted_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.finished_t: Optional[float] = None
        self.preemptions = 0
        self.finished = False
        #: exactly one TERMINAL_OUTCOMES value once the request ends
        self.outcome: Optional[str] = None
        #: human-readable reason for outcome == "failed"
        self.failure: Optional[str] = None
        #: absolute wall deadline (scheduler clock domain)
        self.deadline_t: Optional[float] = (
            now + request.deadline_s if request.deadline_s is not None
            else None)
        #: client disconnect latched; honoured at the iteration boundary
        self.cancel_requested = False
        #: custom stop condition returned True (engine-evaluated)
        self.stop_hit = False
        #: chaos serve.request.poison marked this request
        self.poisoned = False
        #: prompt positions whose K/V are already in the slot's pages
        #: (ISSUE 15): admission seeds it with the prefix-cache hit
        #: length; chunked prefill advances it per chunk. Reset on
        #: preemption (the pages are gone).
        self.prefill_pos = 0
        #: weights generation this residency's KV pages were written
        #: with (ISSUE 20): stamped by the engine at the first prefill
        #: chunk, so a slot in flight across a hot swap keeps decoding
        #: on the SAME tree its pages came from. None = not stamped
        #: yet (next prefill uses the engine's live epoch). Reset on
        #: preemption — the pages are gone and the re-prefill writes
        #: fresh ones with the then-live weights.
        self.weights_epoch: Optional[int] = None
        #: effective-prompt length this residency must prefill (set at
        #: admission — effective_prompt() grows as tokens generate, so
        #: the target is stamped, not recomputed)
        self.prefill_len: Optional[int] = None
        #: speculative draft tokens proposed for the NEXT verify
        #: dispatch (uncommitted: never part of ``generated`` until the
        #: verifier accepts them; drain snapshots record them as
        #: in-flight work, restore recomputes them)
        self.draft: List[int] = []
        #: structured-tracing context (monitor/trace.py): the engine
        #: attaches a Trace + open-span handles when FLAGS_trace is on;
        #: the scheduler itself never touches them (same division of
        #: labor as the registry — the engine owns observability)
        self.trace = None
        self.trace_spans: dict = {}

    @property
    def terminal(self) -> bool:
        return self.outcome is not None

    @property
    def seq_len(self) -> int:
        """Positions currently held in the KV cache (prompt + generated
        tokens whose K/V have been written)."""
        return self.prompt_len + len(self.generated)

    def effective_prompt(self) -> np.ndarray:
        """What a (re-)prefill must process: the original prompt plus any
        tokens generated before a preemption."""
        if not self.generated:
            return self.request.prompt
        return np.concatenate([
            self.request.prompt,
            np.asarray(self.generated, np.int32)])

    def remaining_new_tokens(self) -> int:
        return self.request.max_new_tokens - len(self.generated)

    @property
    def prefilling(self) -> bool:
        """Holds a slot but has not finished its (possibly chunked)
        prefill — it takes no decode row yet."""
        return self.slot is not None and self.prefill_len is not None \
            and self.prefill_pos < self.prefill_len

    @property
    def phase(self) -> Optional[str]:
        """Slot phase for /statusz and docs/SERVING.md's state machine:
        ``prefilling`` | ``verifying`` (a speculative draft is staged
        for / aboard a verify dispatch) | ``decoding``; None while not
        resident."""
        if self.slot is None:
            return None
        if self.prefilling:
            return "prefilling"
        return "verifying" if self.draft else "decoding"

    def written_tokens(self) -> np.ndarray:
        """The token ids whose K/V this slot's pages VALIDLY hold right
        now — the prefix-cache donation payload. Mid-prefill that is
        the chunk progress; decoding it is everything but the newest
        generated token (whose K/V the next dispatch writes)."""
        eff = self.effective_prompt()
        if self.prefilling or not self.generated:
            return eff[:self.prefill_pos]
        return eff[:self.seq_len - 1]

    def is_done(self) -> bool:
        if self.stop_hit:
            return True
        if len(self.generated) >= self.request.max_new_tokens:
            return True
        eos = self.request.eos_token_id
        return eos is not None and bool(self.generated) \
            and self.generated[-1] == eos

    def max_total_len(self) -> int:
        return self.prompt_len + self.request.max_new_tokens


class BucketTable:
    """The compile-count budget: every prefill runs at a
    ``(batch_bucket, len_bucket)`` shape from this table, so the set of
    prefill executables is bounded by ``len(batch) * len(lens)``
    regardless of traffic mix. Decode is excluded — it has exactly one
    shape (the full slot batch)."""

    def __init__(self, prefill_lens: Sequence[int],
                 batch_sizes: Sequence[int]):
        if not prefill_lens or not batch_sizes:
            raise ValueError("bucket table needs >= 1 len and batch bucket")
        self.prefill_lens = tuple(sorted(set(int(x) for x in prefill_lens)))
        self.batch_sizes = tuple(sorted(set(int(x) for x in batch_sizes)))

    @property
    def max_prefill_len(self) -> int:
        return self.prefill_lens[-1]

    @property
    def max_batch(self) -> int:
        return self.batch_sizes[-1]

    def len_bucket(self, n: int) -> int:
        for b in self.prefill_lens:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds the largest "
                         f"prefill bucket ({self.max_prefill_len})")

    def batch_bucket(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.max_batch

    def signatures(self) -> List[Tuple[int, int]]:
        return [(b, s) for s in self.prefill_lens for b in self.batch_sizes]


@dataclass
class AdmissionGroup:
    """One bucketed prefill dispatch: ``states`` (already holding slots
    and pages) padded up to ``batch_bucket`` rows at ``len_bucket``
    columns by the engine."""

    len_bucket: int
    batch_bucket: int
    states: List[RequestState]


class Scheduler:
    """FIFO queue + slot/page admission control (host-side only)."""

    def __init__(self, cache: PagedKVCache, buckets: BucketTable,
                 max_queue: int = 1024, clock=time.perf_counter,
                 max_seq_len: Optional[int] = None,
                 policy: str = "reject-new",
                 on_event: Optional[Callable] = None,
                 tenant_quota: Optional[int] = None,
                 lora=None):
        self.cache = cache
        self.buckets = buckets
        #: per-tenant fairness (ISSUE 17): max ACTIVE slots any one
        #: tenant may hold; None disables the check entirely (admission
        #: is byte-identical to the pre-quota FIFO). Untenanted requests
        #: are never limited.
        self.tenant_quota = (int(tenant_quota)
                             if tenant_quota is not None else None)
        #: optional serving.lora.LoRAManager: admission acquires the
        #: request's adapter (slot reference), slot release drops it —
        #: the refcount unload_adapter checks
        self.lora = lora
        # the admission limit is the CONFIGURED context window (position
        # embeddings!), not the cache's block-rounded physical capacity
        # which may be up to block_size-1 positions larger
        self.max_seq_len = int(max_seq_len if max_seq_len is not None
                               else cache.max_context_len)
        self.max_queue = int(max_queue)
        if policy not in QUEUE_POLICIES:
            raise ValueError(f"unknown queue policy {policy!r}; one of "
                             f"{QUEUE_POLICIES}")
        self.policy = policy
        #: ``on_event(outcome, state)`` fires on every terminal
        #: transition — the engine's metrics/flight hook. The scheduler
        #: itself never writes the registry (the zero-overhead pin).
        self.on_event = on_event
        self.clock = clock
        self.waiting: List[RequestState] = []
        self.slots: List[Optional[RequestState]] = \
            [None] * cache.max_slots
        self.stats = {"submitted": 0, "completed": 0, "preemptions": 0,
                      "admitted": 0, "expired": 0, "expired_queued": 0,
                      "shed": 0, "cancelled": 0, "failed": 0,
                      "drained": 0, "quota_deferred": 0}
        #: per-tenant quota-deferral counts (cumulative; the engine
        #: delta-publishes them as a labeled registry counter)
        self.tenant_deferrals: Dict[str, int] = {}
        # deadline sweeps stay O(0) until the first deadline-carrying
        # request ever arrives
        self._saw_deadline = False

    # -- terminal transitions ----------------------------------------------
    def _terminate(self, st: RequestState, outcome: str,
                   reason: Optional[str] = None) -> None:
        """The ONE exit path: frees any held slot/pages, stamps exactly
        one outcome, updates stats and fires ``on_event``."""
        assert st.outcome is None, \
            f"request {st.request.request_id} already {st.outcome}"
        if st.slot is not None:
            self._release_adapter(st)
            # prefix-cache donation (ISSUE 15): the K/V this residency
            # computed seeds future prefix hits — except a FAILED
            # request's (a non-finite forward may have written garbage)
            donate = (st.written_tokens()
                      if outcome != "failed" else None)
            self.cache.free_slot(st.slot, donate_tokens=donate)
            self.slots[st.slot] = None
            st.slot = None
        st.outcome = outcome
        st.failure = reason
        st.finished = outcome == "completed"
        st.finished_t = self.clock()
        self.stats[outcome] += 1
        if self.on_event is not None:
            self.on_event(outcome, st)

    def _shed_victim(self, request: Request) -> Optional[RequestState]:
        """Who leaves the full queue so ``request`` can enter (None =
        nobody; reject the newcomer)."""
        if self.policy == "drop-oldest":
            return self.waiting[0] if self.waiting else None
        if self.policy == "priority":
            # lowest priority first, oldest within the class — and only
            # when the newcomer actually outranks it
            victim = min(self.waiting, default=None,
                         key=lambda s: s.request.priority)
            if victim is not None \
                    and victim.request.priority < request.priority:
                return victim
        return None

    # -- queue --------------------------------------------------------------
    def submit(self, request: Request) -> RequestState:
        if request.prompt.size + request.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({request.prompt.size}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds the per-slot context "
                f"capacity ({self.max_seq_len})")
        # a request that could never hold its pages even ALONE in the pool
        # would stall admission forever (alloc fails with everything free,
        # nothing to preempt) — reject it at submit, not as a livelock
        from .kv_cache import blocks_needed
        alloc = self.cache.allocator
        need = blocks_needed(request.prompt.size + request.max_new_tokens,
                             self.cache.block_size)
        if need > alloc.num_pages - alloc.reserved:
            raise ValueError(
                f"request needs {need} KV pages at full length but the "
                f"pool only holds {alloc.num_pages - alloc.reserved} — "
                "raise ServingConfig.num_pages or shrink the request")
        # the bucket table must be able to re-prefill this request even
        # after a worst-case preemption (prompt + all generated tokens)
        self.buckets.len_bucket(
            request.prompt.size + request.max_new_tokens - 1)
        # queue-full policy runs AFTER validation: an invalid request
        # must never shed a valid waiter on its way to a ValueError.
        # Sweep already-expired waiters first (O(0) without deadlines):
        # a dead request must not hold capacity against a live submit,
        # nor get mis-terminated as "shed" when it in fact expired.
        self.expire_queued()
        if len(self.waiting) >= self.max_queue:
            victim = self._shed_victim(request)
            if victim is None:
                raise ServerOverloaded("queue_full",
                                       queue_depth=len(self.waiting))
            self.waiting.remove(victim)
            self._terminate(victim, "shed")
        st = RequestState(request, self.clock())
        if st.deadline_t is not None:
            self._saw_deadline = True
        if self.policy == "priority":
            # priority lanes: insert behind the last peer of >= priority
            idx = next((i for i, w in enumerate(self.waiting)
                        if w.request.priority < request.priority),
                       len(self.waiting))
            self.waiting.insert(idx, st)
        else:
            self.waiting.append(st)
        self.stats["submitted"] += 1
        return st

    def cancel(self, request_id: int) -> bool:
        """Client disconnect: a queued request is cancelled on the spot;
        an in-flight one is latched and cancelled at the next iteration
        boundary (``sweep_active``), freeing its pages immediately then.
        False when the id is unknown or already terminal."""
        for st in self.waiting:
            if st.request.request_id == request_id:
                self.waiting.remove(st)
                self._terminate(st, "cancelled")
                return True
        for _, st in self.active():
            if st.request.request_id == request_id:
                st.cancel_requested = True
                return True
        return False

    def expire_queued(self) -> List[RequestState]:
        """Drop queued requests past their deadline — BEFORE they ever
        touch a slot (no prefill, no pages, no wasted decode work).
        O(0) until the first deadline-carrying request exists."""
        if not self._saw_deadline or not self.waiting:
            return []
        now = self.clock()
        out = []
        for st in [w for w in self.waiting
                   if w.deadline_t is not None and now >= w.deadline_t]:
            self.waiting.remove(st)
            self._terminate(st, "expired")
            # queued expiries never cost the engine any work — shed-rate
            # accounting treats them like admission drops, unlike an
            # in-flight expiry (admitted, decoded, then ran out of time)
            self.stats["expired_queued"] += 1
            out.append(st)
        return out

    def sweep_active(self) -> List[RequestState]:
        """Iteration-boundary sweep over the slots: honour latched
        cancellations and expire in-flight requests past their deadline,
        freeing their pages immediately."""
        out = []
        for _, st in list(self.active()):
            if st.cancel_requested:
                self._terminate(st, "cancelled")
                out.append(st)
            elif st.deadline_t is not None \
                    and self.clock() >= st.deadline_t:
                self._terminate(st, "expired")
                out.append(st)
        return out

    def honour_queued_cancels(self) -> List[RequestState]:
        """Terminate waiting requests whose in-flight cancel was latched
        before a preemption put them back in the queue. Admission honours
        the latch lazily (:meth:`plan_admissions`); drain calls this
        eagerly so a disconnected client's work is never snapshotted."""
        out = []
        for st in [w for w in self.waiting if w.cancel_requested]:
            self.waiting.remove(st)
            self._terminate(st, "cancelled")
            out.append(st)
        return out

    def fail(self, st: RequestState, reason: str) -> None:
        """Fault isolation: a poisoned request fails ALONE (its slot and
        pages are released; the rest of the batch streams on)."""
        self._terminate(st, "failed", reason=reason)

    def drain_release(self, st: RequestState) -> None:
        """Graceful drain: release the request (queued or in-flight)
        with outcome ``drained`` — its undone work goes to the snapshot,
        nothing is silently lost."""
        if st in self.waiting:
            self.waiting.remove(st)
        self._terminate(st, "drained")

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def oldest_waiting_t(self) -> Optional[float]:
        """``submitted_t`` of the oldest waiter, or None when the queue
        is empty. Under the ``priority`` policy the queue is lane-ordered
        (not FIFO), so the oldest waiter — the one the overload detector
        must see, or starving low-priority requests can age unboundedly
        without ever tripping it — is not necessarily ``waiting[0]``."""
        if not self.waiting:
            return None
        if self.policy == "priority":
            return min(st.submitted_t for st in self.waiting)
        return self.waiting[0].submitted_t

    def active(self) -> List[Tuple[int, RequestState]]:
        return [(i, st) for i, st in enumerate(self.slots)
                if st is not None]

    def state(self) -> dict:
        """Lifecycle snapshot for the admin plane (``/statusz`` and the
        engine's readiness reason bodies): queue depth, per-slot
        residency and the cumulative outcome stats. Called from HTTP
        handler threads at arbitrary times, so it works on one-shot
        ``list()`` copies of the queue/slot lists (atomic under the
        GIL) — the serving loop may mutate them mid-render and the
        snapshot must stay internally consistent, never raise."""
        now = self.clock()
        waiting = list(self.waiting)
        slots = list(self.slots)
        oldest = min((st.submitted_t for st in waiting), default=None)
        return {
            "queue_depth": len(waiting),
            "oldest_waiting_s": (max(0.0, now - oldest)
                                 if oldest is not None else None),
            "active_slots": sum(1 for st in slots if st is not None),
            "max_slots": len(slots),
            "slots": [
                {"slot": slot, "request_id": st.request.request_id,
                 "prompt_len": st.prompt_len,
                 "generated": len(st.generated),
                 "seq_len": st.seq_len,
                 "phase": st.phase,
                 "prefill_pos": st.prefill_pos,
                 "preemptions": st.preemptions}
                for slot, st in enumerate(slots) if st is not None],
            "stats": dict(self.stats),
        }

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or any(
            st is not None for st in self.slots)

    # -- admission ----------------------------------------------------------
    def plan_admissions(self) -> List[RequestState]:
        """Admit as many waiting requests as slots + pages allow, FIFO:
        slot assigned, pages allocated for the effective prompt (with
        any prefix-cache hit mapped COW), ``prefill_pos``/``prefill_len``
        stamped. Returns the newly admitted states in admission order —
        grouping them into bucketed prefill dispatches is the engine's
        job (``ServingEngine._plan_prefill_groups``, ONE grouping path
        that also carries chunked-prefill continuations)."""
        admitted: List[Tuple[int, RequestState]] = []
        free_slots = [i for i, st in enumerate(self.slots) if st is None]
        if self.waiting and free_slots and chaos.active() \
                and chaos.probe("serve.pages.exhaust"):
            return []                  # injected dry pool: admission waits
        # idx scans past quota-blocked requests (per-tenant fairness,
        # ISSUE 17) so one tenant at its cap cannot head-of-line-block
        # every other tenant; without a quota idx never advances and the
        # loop is the pre-quota FIFO exactly
        idx = 0
        while free_slots and idx < len(self.waiting):
            st = self.waiting[idx]
            if st.cancel_requested:
                # a latched in-flight cancel survives preemption back to
                # the queue: honour it here, never waste a prefill on it
                self.waiting.pop(idx)
                self._terminate(st, "cancelled")
                continue
            if self.tenant_quota is not None \
                    and st.request.tenant is not None \
                    and self._tenant_active(st.request.tenant) \
                    >= self.tenant_quota:
                self.stats["quota_deferred"] += 1
                t = st.request.tenant
                self.tenant_deferrals[t] = \
                    self.tenant_deferrals.get(t, 0) + 1
                idx += 1               # skip; later tenants still admit
                continue
            if st.request.adapter and (
                    self.lora is None
                    or self.lora.row(st.request.adapter) is None):
                # the adapter was unloaded (or never loaded) between
                # submit and admission: fail THIS request alone rather
                # than decode it against the zero adapter silently
                self.waiting.pop(idx)
                self._terminate(
                    st, "failed",
                    reason=f"adapter {st.request.adapter!r} not loaded")
                continue
            slot = free_slots[0]
            eff = st.effective_prompt()
            # radix prefix cache (ISSUE 15): map the longest cached
            # page-aligned prefix copy-on-write into the block-table
            # head; the slot prefills only the tail. match() incref'd
            # the hit pages; a failed alloc drops them again inside
            # alloc_slot, so the retry next iteration re-matches.
            n_hit, shared = 0, ()
            if self.cache.prefix_cache is not None:
                n_hit, shared = self.cache.prefix_cache.match(eff)
            if not self.cache.alloc_slot(slot, eff.size,
                                         shared_pages=shared):
                break                      # page pool dry: FIFO blocks
            self.waiting.pop(idx)
            free_slots.pop(0)
            st.slot = slot
            st.admitted_t = self.clock()
            _trace.record("serve.queued", st.queued_t, st.admitted_t,
                          request_id=st.request.request_id,
                          prompt_len=st.prompt_len)
            st.prefill_pos = n_hit
            st.prefill_len = int(eff.size)
            self.slots[slot] = st
            if self.lora is not None and st.request.adapter:
                self.lora.acquire(st.request.adapter)
            admitted.append((slot, st))
            self.stats["admitted"] += 1
        return [st for _, st in admitted]

    # -- decode-time growth / preemption ------------------------------------
    def ensure_decode_capacity(self) -> List[RequestState]:
        """Before a decode step, make sure every active slot has a page
        for the position it is about to write (``seq_len``). On a dry
        pool, preempt newest-admitted requests (recompute policy) until
        the older ones fit. Returns the preempted states (already
        requeued at the queue front)."""
        preempted: List[RequestState] = []
        if len(self.active()) >= 2 and chaos.active() \
                and chaos.probe("serve.pages.exhaust"):
            # injected pool pressure: recompute-preempt the newest
            # admitted request (token-identical continuation for greedy)
            # — the same victim order as the real dry-pool path below;
            # the oldest is excluded so the batch always keeps progress
            oldest = min(self.active(),
                         key=lambda p: p[1].admitted_t)[1]
            victim = self._newest_active(exclude=oldest)
            if victim is not None:
                self._preempt(victim)
                preempted.append(victim)
        # oldest-first: earlier-admitted requests keep their pages
        order = sorted(self.active(), key=lambda p: p[1].admitted_t)
        for slot, st in order:
            if self.slots[slot] is not st:
                continue                       # preempted below, skip
            # this decode step writes position seq_len-1 (the newest
            # generated token's K/V) -> the slot must cover seq_len
            # positions; a staged speculative draft writes its k tokens
            # at the following positions, so the slot must also cover
            # them BEFORE the verify dispatch (a draft's K/V must never
            # spill into the shared scratch page — rows of the verify
            # window read it back)
            while not self.cache.extend_slot(
                    slot, st.seq_len + len(st.draft)):
                victim = self._newest_active(exclude=st)
                if victim is None:
                    raise RuntimeError(
                        "KV page pool too small for a single request: "
                        f"{st.seq_len} tokens need more pages than "
                        "the pool holds — raise num_pages or shrink "
                        "max_new_tokens")
                self._preempt(victim)
                preempted.append(victim)
        return preempted

    def _newest_active(self, exclude: RequestState) \
            -> Optional[RequestState]:
        cands = [st for _, st in self.active() if st is not exclude]
        if not cands:
            return None
        return max(cands, key=lambda s: s.admitted_t)

    def _release_adapter(self, st: RequestState) -> None:
        """Drop the slot's LoRA adapter reference (acquired at
        admission) — called from BOTH slot-release paths
        (:meth:`_terminate`, :meth:`_preempt`), so the reference
        invariant is exactly "held iff resident"."""
        if self.lora is not None and st.request.adapter:
            self.lora.release(st.request.adapter)

    def _tenant_active(self, tenant: str) -> int:
        """Slots currently held by ``tenant`` (the quota currency)."""
        return sum(1 for st in self.slots
                   if st is not None and st.request.tenant == tenant)

    def _preempt(self, st: RequestState, count: bool = True) -> None:
        assert st.slot is not None
        self._release_adapter(st)
        # evicted residencies donate too (vLLM/SGLang recompute policy
        # meets the radix cache): the pages stay warm in the tree, so a
        # re-admission — or any sibling sharing the prefix — hits them
        # instead of re-prefilling; allocation pressure evicts them LRU
        self.cache.free_slot(st.slot,
                             donate_tokens=st.written_tokens())
        self.slots[st.slot] = None
        st.slot = None
        st.admitted_t = None
        st.queued_t = self.clock()
        st.prefill_pos = 0
        st.prefill_len = None
        st.weights_epoch = None
        st.draft = []
        if count:
            st.preemptions += 1
            self.stats["preemptions"] += 1
        if self.policy == "priority":
            # front of its priority class (ahead of equal-priority
            # waiters: it already held a slot once)
            idx = next((i for i, w in enumerate(self.waiting)
                        if w.request.priority <= st.request.priority),
                       len(self.waiting))
            self.waiting.insert(idx, st)
        else:
            self.waiting.insert(0, st)         # reclaims FIFO priority

    def rollback_admission(self, sts: Sequence[RequestState]) -> None:
        """Un-admit freshly admitted states whose prefill never produced
        a token (watchdog trip abandoned the dispatch): back to the
        queue front, pages freed, so a retried ``step()`` re-plans the
        admission and re-prefills instead of decoding slots that have no
        generated token to feed. Reversed so FIFO order survives the
        one-at-a-time front inserts. Not counted as a preemption — the
        page-pressure telemetry must not read watchdog incidents as a
        dry KV pool."""
        for st in reversed(list(sts)):
            if st.slot is not None and self.slots[st.slot] is st:
                self._preempt(st, count=False)

    # -- completion ---------------------------------------------------------
    def finish(self, st: RequestState) -> None:
        assert st.slot is not None
        self._terminate(st, "completed")
