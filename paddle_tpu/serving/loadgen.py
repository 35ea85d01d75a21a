"""Synthetic open-loop load generator for the serving engine.

Open-loop means arrivals follow a FIXED schedule regardless of how fast
the engine drains — the honest way to measure serving latency: a
closed-loop driver (next request only after the previous completes)
hides queueing delay exactly when the system saturates. Everything is
seeded, so a load run replays exactly (the same property the chaos
harness pins for faults).

Arrival processes (``LoadSpec.arrival``):

- ``poisson`` — exponential inter-arrival gaps at ``rate_rps`` (the
  classic memoryless open-loop load);
- ``gamma`` — Gamma-distributed gaps with the SAME mean rate but
  squared coefficient of variation ``burstiness`` (the shape parameter
  is ``1/burstiness``: > 1 clumps arrivals into bursts, < 1 produces
  smoother-than-poisson pacing);
- ``mmpp`` — a 2-state Markov-modulated Poisson process: a hidden state
  flips between a hot rate ``rate*(1+burstiness)`` and a cold rate
  ``rate/(1+burstiness)`` with probability ``mmpp_switch`` per arrival,
  gaps rescaled so the mean rate is still ``rate_rps`` — sustained
  overload episodes followed by idle valleys, the arrival shape that
  actually exercises shedding and the overload detector.

Per-request ``deadline_range`` / ``priority_choices`` sampling makes the
expiry and priority-lane paths reachable from a load run. The extra
draws only happen when the corresponding field is set, so default
specs generate byte-identical traffic to the pre-resilience generator.

:class:`TokenBucket` is client-side rate limiting for loadgen-driven
tests: ``run_open_loop(..., token_bucket=...)`` drops (counts) arrivals
that exceed the bucket instead of submitting them. Server-side shedding
(:class:`~.resilience.ServerOverloaded`) is likewise counted, not
crashed on — an overloaded server answering "no" is the behaviour under
test, not an error in the driver.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from .resilience import DecodeWatchdogError, ServerOverloaded
from .sampling import SamplingParams
from .scheduler import Request

__all__ = ["LoadSpec", "TokenBucket", "build_requests",
           "run_fleet_open_loop", "run_open_loop"]

_ARRIVALS = ("poisson", "gamma", "mmpp")

#: run_open_loop gives up (re-raises) after this many watchdog trips in
#: a row with no successful step between them: a backend that hangs on
#: EVERY retry is down, not slow
MAX_CONSECUTIVE_WATCHDOG_TRIPS = 8


@dataclass
class LoadSpec:
    num_requests: int = 16
    rate_rps: float = 4.0
    prompt_len_range: Tuple[int, int] = (16, 64)
    max_new_range: Tuple[int, int] = (8, 32)
    vocab_size: int = 50304
    seed: int = 0
    sampling: Optional[SamplingParams] = None
    #: arrival process: poisson | gamma | mmpp (see module docstring)
    arrival: str = "poisson"
    #: gamma: squared CV of the gaps; mmpp: hot/cold rate swing. 1.0
    #: with gamma degenerates to poisson.
    burstiness: float = 1.0
    #: mmpp: per-arrival probability of flipping the hidden rate state
    mmpp_switch: float = 0.1
    #: uniform per-request deadline_s sample; None = no deadlines
    deadline_range: Optional[Tuple[float, float]] = None
    #: uniform per-request priority sample; None = all priority 0
    priority_choices: Optional[Tuple[int, ...]] = None
    #: chat-style shared prefixes (ISSUE 15): > 0 = every prompt opens
    #: with one of ``prefix_pool_size`` fixed prefixes of this many
    #: tokens (a "system prompt"), drawn with bounded-zipf reuse so a
    #: hot head of prefixes dominates — the traffic shape the radix
    #: prefix cache exists for.
    #: 0 (default) = no prefixes, byte-identical to pre-ISSUE-15 specs.
    shared_prefix_len: int = 0
    #: number of distinct prefixes in the pool
    prefix_pool_size: int = 8
    #: zipf exponent of prefix reuse (rank==index; higher = hotter head)
    prefix_zipf: float = 1.1
    #: fleet workload (ISSUE 16): > 0 = every request belongs to one of
    #: this many tenants, drawn zipf(``prefix_zipf``) per request, and
    #: each tenant owns its OWN prefix pool (``prefix_pool_size``
    #: prefixes of ``shared_prefix_len`` tokens, per-tenant seeded) —
    #: the traffic shape prefix-affine routing exists for: a tenant's
    #: whole prefix family hashes to one replica, so its radix tree
    #: stays hot there. Requires ``shared_prefix_len > 0``. 0 (default)
    #: = the single shared pool above, byte-identical to pre-fleet
    #: specs.
    tenants: int = 0
    #: multi-tenant LoRA traffic (ISSUE 17): > 0 = every tenanted
    #: request names one of this many per-tenant adapters
    #: ("tenant{t}/adapter{k}", k uniform from a fixed-seed SIDE
    #: generator, so arming adapters perturbs none of the default
    #: draws — arrivals/prompts/lengths replay exactly) and carries its
    #: tenant name, reaching the per-tenant quota + batched-bgmv paths.
    #: Requires ``tenants > 0``. 0 (default) = no adapter/tenant
    #: stamping, byte-identical to pre-LoRA specs.
    adapter_pool: int = 0
    #: model-lifecycle traffic tagging (ISSUE 20): > 0 = stamp each
    #: request with the A/B arm (``lifecycle_arm``) a router running
    #: ``TrafficSplit(ab_frac=ab_split, seed=split_seed)`` would place
    #: it in — the SAME ``lifecycle.assign_arm`` hash of the request
    #: id, no RNG draws, so arming it perturbs nothing about the
    #: default draws (arrivals/prompts/lengths replay exactly; pinned).
    #: 0.0 (default) = no stamping, byte-identical to pre-lifecycle
    #: specs.
    ab_split: float = 0.0
    #: > 0 = stamp ``lifecycle_shadow=True`` on the requests a
    #: ``TrafficSplit(shadow_frac=...)`` router would mirror (same
    #: deterministic ``lifecycle.should_shadow`` hash); 0.0 (default)
    #: = no stamping
    shadow_frac: float = 0.0
    #: seed the tags hash with (matches ``TrafficSplit.seed``)
    split_seed: int = 0


class TokenBucket:
    """Deterministic client-side rate limiter: ``rate`` tokens/s refill
    up to a ``burst`` cap; :meth:`admit` spends one token or answers
    False. Driven by the caller's clock values, so tests replay
    exactly."""

    def __init__(self, rate: float, burst: float):
        if rate <= 0 or burst < 1:
            raise ValueError("token bucket needs rate > 0 and burst >= 1")
        self.rate = float(rate)
        self.capacity = float(burst)
        self.tokens = float(burst)
        self._last: Optional[float] = None

    def admit(self, now: float) -> bool:
        if self._last is not None:
            self.tokens = min(self.capacity,
                              self.tokens + (now - self._last) * self.rate)
        self._last = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


def _arrival_gaps(spec: LoadSpec, rng) -> np.ndarray:
    """Inter-arrival gaps (seconds) for ``num_requests`` arrivals, mean
    rate ``rate_rps`` for every mode."""
    if spec.arrival not in _ARRIVALS:
        raise ValueError(f"unknown arrival mode {spec.arrival!r}; one "
                         f"of {_ARRIVALS}")
    mean = 1.0 / max(spec.rate_rps, 1e-9)
    n = spec.num_requests
    if spec.arrival == "gamma" and spec.burstiness <= 0.0:
        raise ValueError("gamma arrival needs burstiness > 0 "
                         "(= the squared CV of the gaps)")
    if spec.arrival == "poisson" or \
            (spec.arrival == "gamma" and spec.burstiness == 1.0):
        return rng.exponential(mean, n)
    if spec.arrival == "gamma":
        # CV^2 = burstiness: > 1 clumps arrivals, < 1 smooths them
        # (shape > 1, more regular than poisson) — both valid loads
        shape = 1.0 / float(spec.burstiness)
        return rng.gamma(shape, mean / shape, n)
    # mmpp: hidden 2-state rate, switched per arrival
    swing = 1.0 + max(float(spec.burstiness), 0.0)
    rates = (spec.rate_rps * swing, spec.rate_rps / swing)
    state = 0
    gaps = np.empty((n,), np.float64)
    for i in range(n):
        gaps[i] = rng.exponential(1.0 / max(rates[state], 1e-9))
        if rng.random() < spec.mmpp_switch:
            state = 1 - state
    # symmetric switching -> stationary occupancy 1/2 per state, so the
    # raw expected gap is (1/swing + swing)/(2*rate); rescale to keep
    # the promised mean rate exactly (offered_rate_rps stays honest)
    gaps *= 2.0 / (swing + 1.0 / swing)
    return gaps


def build_requests(spec: LoadSpec) -> List[Tuple[float, Request]]:
    """[(arrival_offset_s, Request), ...] sorted by arrival — the chosen
    arrival process, uniform prompt/output lengths, uniform random token
    ids, optional deadline/priority sampling — deterministic per seed."""
    if spec.adapter_pool > 0 and spec.tenants <= 0:
        raise ValueError("adapter_pool needs tenants > 0 (adapters are "
                         "per-tenant)")
    rng = np.random.default_rng(spec.seed)
    # adapter draws come from their own fixed-seed generator so arming
    # adapter_pool leaves every draw from ``rng`` untouched (pinned)
    arng = (np.random.default_rng(spec.seed ^ 0xADA9)
            if spec.adapter_pool > 0 else None)
    arrivals = np.cumsum(_arrival_gaps(spec, rng))
    arrivals[0] = 0.0                       # first request at t=0
    out = []
    lo_p, hi_p = spec.prompt_len_range
    lo_n, hi_n = spec.max_new_range
    prefixes = prefix_cdf = None
    tenant_pools = tenant_cdf = None

    def _zipf_cdf(n: int) -> np.ndarray:
        w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64),
                           float(spec.prefix_zipf))
        return np.cumsum(w / w.sum())

    if spec.shared_prefix_len > 0 and spec.tenants > 0:
        # per-tenant prefix pools (ISSUE 16): tenant t's pool comes from
        # its own fixed-seed side generator, so pools are disjoint and
        # stable per seed, and — like the single-pool path — none of
        # the default draws below are perturbed by building them
        tenant_pools = []
        for t in range(spec.tenants):
            prng = np.random.default_rng(
                spec.seed ^ 0x5A5A ^ (0x1000 * (t + 1)))
            tenant_pools.append(prng.integers(
                0, spec.vocab_size,
                (max(1, spec.prefix_pool_size), spec.shared_prefix_len)
            ).astype(np.int32))
        tenant_cdf = _zipf_cdf(spec.tenants)
        prefix_cdf = _zipf_cdf(tenant_pools[0].shape[0])
    elif spec.shared_prefix_len > 0:
        # the prefix pool and its zipf CDF draw from a fixed-seed side
        # generator, so enabling prefixes perturbs NOTHING about the
        # default draws below (arrivals/lengths/tails replay exactly)
        prng = np.random.default_rng(spec.seed ^ 0x5A5A)
        prefixes = prng.integers(
            0, spec.vocab_size,
            (max(1, spec.prefix_pool_size), spec.shared_prefix_len)
        ).astype(np.int32)
        prefix_cdf = _zipf_cdf(prefixes.shape[0])
    for i in range(spec.num_requests):
        plen = int(rng.integers(lo_p, hi_p + 1))
        prompt = rng.integers(0, spec.vocab_size, (plen,)).astype(np.int32)
        tenant = adapter = None
        if tenant_pools is not None:
            t = int(np.searchsorted(tenant_cdf, rng.random()))
            t = min(t, len(tenant_pools) - 1)
            pool = tenant_pools[t]
            pi = int(np.searchsorted(prefix_cdf, rng.random()))
            prompt = np.concatenate([pool[min(pi, len(pool) - 1)],
                                     prompt])
            if arng is not None:
                tenant = f"tenant{t}"
                adapter = (f"tenant{t}/adapter"
                           f"{int(arng.integers(0, spec.adapter_pool))}")
        elif prefixes is not None:
            pi = int(np.searchsorted(prefix_cdf, rng.random()))
            prompt = np.concatenate([prefixes[min(pi, len(prefix_cdf)
                                                  - 1)], prompt])
        deadline = None
        if spec.deadline_range is not None:
            lo_d, hi_d = spec.deadline_range
            deadline = float(rng.uniform(lo_d, hi_d))
        priority = 0
        if spec.priority_choices:
            priority = int(spec.priority_choices[
                int(rng.integers(0, len(spec.priority_choices)))])
        req = Request(
            prompt,
            max_new_tokens=int(rng.integers(lo_n, hi_n + 1)),
            sampling=spec.sampling or SamplingParams(),
            deadline_s=deadline, priority=priority,
            tenant=tenant, adapter=adapter)
        if spec.ab_split > 0.0 or spec.shadow_frac > 0.0:
            # pure request-id hashes (lifecycle.assign_arm /
            # should_shadow) — zero draws from ``rng``, so the tags
            # ride along without perturbing any default field (pinned)
            from .lifecycle import assign_arm, should_shadow
            req.lifecycle_arm = assign_arm(
                int(req.request_id), spec.split_seed, spec.ab_split)
            req.lifecycle_shadow = should_shadow(
                int(req.request_id), spec.split_seed, spec.shadow_frac)
        out.append((float(arrivals[i]), req))
    return out


def run_open_loop(engine, spec: LoadSpec, time_scale: float = 1.0,
                  clock=time.perf_counter,
                  token_bucket: Optional[TokenBucket] = None) -> dict:
    """Drive ``engine`` through the schedule; returns
    ``engine.metrics_summary()`` augmented with offered load and the
    client-visible refusal counts. Server-side shedding
    (:class:`ServerOverloaded`) and watchdog trips
    (:class:`DecodeWatchdogError`) are COUNTED and survived — overload
    behaviour is what this driver exists to measure."""
    schedule = build_requests(spec)
    t0 = clock()
    i = 0
    rejected = throttled = watchdog_trips = 0
    consecutive_trips = 0
    while i < len(schedule) or engine.scheduler.has_work:
        now = clock() - t0
        while i < len(schedule) and \
                schedule[i][0] * time_scale <= now:
            if token_bucket is not None and \
                    not token_bucket.admit(now):
                throttled += 1
            else:
                try:
                    engine.submit(schedule[i][1])
                except ServerOverloaded:
                    rejected += 1
            i += 1
        if engine.scheduler.has_work:
            try:
                engine.step()
                consecutive_trips = 0
            except DecodeWatchdogError as e:
                # hung dispatch converted to a structured error: count
                # it and retry the step (token-exact for greedy) — but
                # a PERSISTENTLY hung backend must not become an
                # infinite retry loop that piles up abandoned threads,
                # and a trip that lost donated pools cannot retry at all
                watchdog_trips += 1
                consecutive_trips += 1
                if not e.retry_safe \
                        or consecutive_trips >= MAX_CONSECUTIVE_WATCHDOG_TRIPS:
                    raise
        elif i < len(schedule):
            # idle gap before the next arrival: sleep the remainder
            wait = schedule[i][0] * time_scale - (clock() - t0)
            if wait > 0:
                time.sleep(min(wait, 0.05))
    summary = engine.metrics_summary()
    summary["offered_rate_rps"] = spec.rate_rps / max(time_scale, 1e-9)
    summary["num_requests"] = spec.num_requests
    summary["requests_rejected"] = rejected
    summary["requests_throttled"] = throttled
    summary["watchdog_trips"] = watchdog_trips
    return summary


def run_fleet_open_loop(router, spec: LoadSpec,
                        time_scale: float = 1.0,
                        clock=time.perf_counter) -> dict:
    """Drive a :class:`~.router.FleetRouter` through the same open-loop
    arrival contract as :func:`run_open_loop`: the SAME seeded schedule
    (so a fleet run and a single-engine run see identical traffic), the
    router places each arrival, and every live replica is stepped
    round-robin between arrivals. Router-level refusals (no ready
    replica / all replicas shed) are counted, not crashed on. Returns
    ``router.summary()`` augmented with the offered load."""
    schedule = build_requests(spec)
    t0 = clock()
    i = 0
    rejected = 0
    while i < len(schedule) or any(
            r.alive and r.engine.scheduler.has_work
            for r in router.replicas.values()):
        now = clock() - t0
        while i < len(schedule) and \
                schedule[i][0] * time_scale <= now:
            try:
                router.submit(schedule[i][1])
            except ServerOverloaded:
                rejected += 1
            i += 1
        if not router.step_all() and i < len(schedule):
            wait = schedule[i][0] * time_scale - (clock() - t0)
            if wait > 0:
                time.sleep(min(wait, 0.05))
    summary = router.summary()
    summary["offered_rate_rps"] = spec.rate_rps / max(time_scale, 1e-9)
    summary["num_requests"] = spec.num_requests
    summary["requests_rejected_router"] = rejected
    return summary
