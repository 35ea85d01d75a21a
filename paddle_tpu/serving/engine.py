"""TPU-native LLM serving engine: paged decode + continuous batching
behind AOT-compiled serving signatures.

The execution model (ISSUE 6; docs/SERVING.md):

- **two program kinds**, split the way TPU serving wants them: a
  *prefill* program per ``(batch, prefill_len)`` bucket (prompt forward,
  K/V written into the paged pools, first token sampled) and ONE
  *decode* program over the full slot batch (single-token forward via
  the block tables, next token sampled per slot, inactive slots masked).
  Both are built with :class:`paddle_tpu.jit.aot.AOTProgram` — the same
  lower/compile machinery as ``TrainStep`` — so executables exist before
  traffic arrives (``warmup()``) and per-program HBM/FLOPs attribution
  comes from the exact executables that serve;
- **continuous batching**: the :class:`~.scheduler.Scheduler` admits and
  evicts requests between decode steps; every decode dispatch serves
  whatever mix of requests currently holds slots (block tables, write
  positions, sampling params and the active mask are all ARGUMENTS, so
  membership changes never recompile);
- **decode under scan**: with ``FLAGS_scan_decode`` (default on) the
  layer stack runs as one ``lax.scan`` with the K/V page pools in its
  carry (``nn.scan.scan_layers_with_cache``) — O(1) trace/compile in
  depth, same as training;
- **one pool layout**: the pools the engine holds
  (``[L, P, G, bs, (H/G)*D]``, ``serving.kv_cache``) are donated to
  every program and come back updated in place — a step writes the rows
  it produced and moves nothing else; no program holds a pool-sized
  temporary (guarded by tests/test_tpu_compile.py);
- **telemetry**: per-request TTFT / TPOT / end-to-end latency and
  queue/occupancy gauges stream into the ``paddle_tpu.monitor`` registry
  (serving metrics are always on — an engine exists to be observed; the
  FLAGS_monitor zero-write contract covers the *training* hot path), and
  ``metrics_summary()`` computes p50/p99 over the raw samples (the
  benchmark reads its occupancy counts, ``PERF.md`` section 3).
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, no_grad
from ..core.random import fold_in, trace_rng
from ..jit.aot import AOTProgram
from ..jit.functional import bind, buffer_arrays, param_arrays
from ..monitor import get_registry
from ..monitor import flight_recorder as _flight
from ..monitor import trace as _trace
from ..testing import chaos
from .detok import StreamingDetokenizer
from .kv_cache import (ContextPagedPools, PagedKVCache, PagedPools,
                       blocks_needed)
from .resilience import (DecodeWatchdogError, DispatchWorker, DrainLatch,
                         DrainReport, EngineDrained, OverloadDetector,
                         ServerOverloaded, request_spec,
                         requests_from_snapshot, save_drain_snapshot)
from .sampling import (SamplingParams, _NEG as _SAMPLING_NEG,
                       filtered_logits, sample_tokens)
from .scheduler import (QUEUE_POLICIES, AdmissionGroup, BucketTable,
                        Request, RequestState, Scheduler)

__all__ = ["ServingConfig", "ServingEngine", "WeightSwapError"]


class WeightSwapError(RuntimeError):
    """A candidate weight push was refused (torn manifest, param-tree
    mismatch, unreadable checkpoint) or a rollback had nothing retained
    to roll back to. Refusal is side-effect free: the serving weights
    did not change and traffic keeps flowing on the old tree."""

    def __init__(self, manifest_dir: str, reason: str):
        super().__init__(
            f"weight swap refused for {manifest_dir!r}: {reason}")
        self.manifest_dir = manifest_dir
        self.reason = reason

#: live engines, for test isolation (serving.reset shuts them down)
_LIVE_ENGINES: "weakref.WeakSet[ServingEngine]" = weakref.WeakSet()

#: trace/compile serialization across engines (see _mesh_scope): the
#: fleet router's per-replica serve threads must not trace concurrently
_COMPILE_LOCK = threading.Lock()


def _pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


@dataclass
class ServingConfig:
    """Engine sizing + policy.

    ``max_context_len`` bounds prompt+generation per request;
    ``num_pages`` sizes the shared KV pool (default: full residency for
    every slot, i.e. no preemption pressure — shrink it to trade HBM for
    recompute-preemptions). ``prefill_buckets``/``batch_buckets`` ARE the
    compile budget: one prefill executable per pair actually used.
    """

    max_batch_slots: int = 8
    block_size: int = 16
    max_context_len: int = 512
    num_pages: Optional[int] = None
    prefill_buckets: Optional[Tuple[int, ...]] = None
    batch_buckets: Tuple[int, ...] = (1, 2, 4)
    max_queue: int = 1024
    seed: int = 0
    cache_dtype: str = "float32"
    detokenizer: Optional[StreamingDetokenizer] = None
    #: bounded-queue shedding policy: reject-new | drop-oldest | priority
    queue_policy: str = "reject-new"
    #: queue-delay EWMA overload detector: > 0 arms it — while the EWMA
    #: of head-of-queue delay exceeds this, every new submit is shed
    #: with a typed ServerOverloaded. 0 (default) = detector off.
    overload_threshold_s: float = 0.0
    overload_alpha: float = 0.3
    overload_exit_frac: float = 0.5
    #: SLO objectives (monitor/slo.py): fractions in (0,1) arming the
    #: multi-window error-budget burn trackers — availability over
    #: request outcomes, deadline over completion slack. 0.0 (default)
    #: = no tracker, zero extra work per request.
    slo_availability: float = 0.0
    slo_deadline: float = 0.0
    slo_windows: Tuple[float, ...] = (60.0, 300.0, 3600.0)
    #: graceful-drain grace period: how long a drain keeps decoding
    #: in-flight sequences before snapshotting the rest
    drain_budget_s: float = 5.0
    #: where drain snapshots commit (drain_<n> dirs); None = drain()
    #: refuses to discard pending work
    drain_dir: Optional[str] = None
    #: tensor-parallel serving mesh (ISSUE 16): a jax Mesh whose ``mp``
    #: axis shards attention heads / MLP width across chips. The serving
    #: signatures compile under it (collectives live INSIDE the
    #: executables, via the model's Megatron specs + GSPMD) and the
    #: paged K/V pools shard over the heads dim
    #: (distributed.spmd.SERVE_KV_SPEC) — per-chip HBM holds 1/mp of
    #: params and KV, which is what serves models beyond one chip.
    #: None (default) = single-chip engine, bit-compatible.
    mesh: Optional[object] = None
    #: multi-tenant LoRA (ISSUE 17): > 0 builds a serving.lora
    #: LoRAManager with this many loadable adapter rows and threads the
    #: stacked pools + per-slot adapter ids through every serving
    #: program (the bgmv path). 0 (default) = no manager, program
    #: signatures and dispatch args unchanged — bit-compatible.
    lora_adapters: int = 0
    lora_rank: int = 8
    #: per-tenant admission cap: at most this many slots may hold
    #: requests of one tenant at a time (excess waits in the queue while
    #: OTHER tenants admit past it — the fairness floor). None
    #: (default) = no cap, admission order is byte-identical FIFO.
    tenant_quota: Optional[int] = None
    #: prompt tokens one step's prefill pass may take: > 0 runs the
    #: prefilling slots' next chunks oldest admission first while they
    #: fit (the first always runs), and the rest wait for a later step —
    #: with long chunked prompts a step is then one chunk and a decode,
    #: not a chunk for EVERY prefilling slot, so the tokens of decoding
    #: slots come at a chunk's pace. 0 (default) = every prefilling slot
    #: advances each step, the grouping as it was.
    prefill_token_budget: int = 0

    def resolve(self, model_max_positions: Optional[int]) -> None:
        if self.queue_policy not in QUEUE_POLICIES:
            raise ValueError(
                f"unknown queue_policy {self.queue_policy!r}; one of "
                f"{QUEUE_POLICIES}")
        if model_max_positions is not None:
            self.max_context_len = min(self.max_context_len,
                                       int(model_max_positions))
        if self.prefill_buckets is None:
            lo = min(max(self.block_size, 16), self.max_context_len)
            self.prefill_buckets = _pow2_buckets(lo, self.max_context_len)
        else:
            self.prefill_buckets = tuple(
                min(int(b), self.max_context_len)
                for b in self.prefill_buckets)
            if max(self.prefill_buckets) < self.max_context_len:
                # preemption re-prefills prompt+generated-so-far; the
                # table must cover the worst case
                self.prefill_buckets += (self.max_context_len,)
        self.batch_buckets = tuple(
            min(int(b), self.max_batch_slots) for b in self.batch_buckets)
        if self.num_pages is None:
            per_slot = blocks_needed(self.max_context_len, self.block_size)
            self.num_pages = 1 + self.max_batch_slots * per_slot


class ServingEngine:
    """Serve a decoder-only model (GPT-style ``forward(input_ids,
    caches=<PagedCacheView>, cache_pos=<[B] positions>)`` returning
    ``(logits, new_caches)``) with continuous batching."""

    def __init__(self, model, config: Optional[ServingConfig] = None,
                 clock=time.perf_counter):
        self.model = model
        cfg = getattr(model, "cfg", None)
        if cfg is None:
            raise ValueError("ServingEngine needs a model with a .cfg "
                             "(num_heads/head_dim/num_layers)")
        import dataclasses
        # resolve() fills model-dependent defaults — work on a copy so a
        # caller-owned config can be reused across engines/models
        self.config = dataclasses.replace(config) if config is not None \
            else ServingConfig()
        self.config.resolve(getattr(cfg, "max_position_embeddings", None))
        self.clock = clock
        model.eval()
        self.mesh = self.config.mesh
        mp = 1
        if self.mesh is not None:
            # TP-sharded serving (ISSUE 16): stamp Megatron specs on any
            # params still unplaced and lay the model out on the mesh
            # BEFORE param_arrays snapshots it, so every AOT serving
            # program compiles against sharded donors and GSPMD bakes
            # the collectives into the executables.
            from ..distributed.spmd import (apply_hybrid_specs,
                                            apply_param_shardings)
            mp = dict(self.mesh.shape).get("mp", 1)
            if cfg.num_heads % mp:
                raise ValueError(
                    f"model num_heads={cfg.num_heads} not divisible by "
                    f"mesh mp={mp}; TP serving shards KV over heads")
            apply_hybrid_specs(model)
            apply_param_shardings(model, self.mesh)
        self.params = param_arrays(model)
        self.buffers = buffer_arrays(model)
        c = self.config
        # throughput features (ISSUE 15), each behind its own
        # kill-switch flag with the flags-off path bit-compatible; read
        # ONCE here so an engine's behavior (and its compiled program
        # set) is stable for its lifetime — tests flip them with
        # flag_scope around construction
        from ..core.flags import get_flag
        self._chunk = int(get_flag("serve_prefill_chunk") or 0)
        self._spec_k = int(get_flag("serve_spec_k") or 0)
        self._spec_ngram = max(1, int(get_flag("serve_spec_ngram") or 1))
        # the model declares what it keeps in pages (`cfg.page_kinds()`:
        # K and V a head for a plain decoder; a latent and an index key
        # for a sparse-attention one) and how long a page lives; one head
        # group a chip of the mp axis is the pools' sharded axis. A
        # window lifetime is given pages for the longest program: a chunk.
        # Beside its pages a model may declare what it keeps a slot
        # (`cfg.state_kinds()`: a short convolution's last inputs)
        state_kinds = getattr(cfg, "state_kinds", tuple)()
        self.cache = PagedKVCache(
            kinds=cfg.page_kinds(),
            num_pages=c.num_pages, block_size=c.block_size,
            max_slots=c.max_batch_slots,
            max_blocks_per_slot=blocks_needed(c.max_context_len,
                                              c.block_size),
            dtype=jnp.dtype(c.cache_dtype),
            head_groups=mp,
            max_chunk=self._chunk or max(c.prefill_buckets),
            state_kinds=state_kinds)
        #: the model's ONE page kind is an MLA latent: its decode steps
        #: sweep every cached position (a family that selects positions
        #: declares an index kind beside it, and counts its own reads)
        self._dense_latent = [kd.name for kd in self.cache.kinds] \
            == ["latent"]
        if self.cache.windows:
            # what assumes that a page lives as long as its slot
            for flag, what in (
                    ("serve_prefix_cache", "the radix prefix cache donates "
                     "and maps pages that hold a prompt's first positions"),
                    ("serve_spec_k", "a rejected draft is rolled back by "
                     "truncate_slot"),
                    ("serve_kv_quant", "int8 pages are not read by the "
                     "windowed kernels")):
                if get_flag(flag):
                    raise ValueError(
                        f"FLAGS_{flag} with a window page lifetime "
                        f"({type(cfg).__name__}.page_kinds()): {what}, and "
                        "a window's pages are freed as the slot advances")
            if self.mesh is not None:
                raise ValueError(
                    "a serving mesh with a window page lifetime: the "
                    "window tables are not sharded (SERVE_KV_SPEC)")
        if self.cache.state_kinds:
            # what assumes that all a slot holds is its pages
            for flag, what in (
                    ("serve_prefix_cache", "a prefix hit maps pages, and the "
                     "state at the hit's end is not kept"),
                    ("serve_spec_k", "truncate_slot rolls pages back, and a "
                     "rejected draft's rows have already moved the state"),
                    ("serve_kv_quant", "the state is kept in the cache "
                     "dtype, and int8 pages are not read beside it")):
                if get_flag(flag):
                    raise ValueError(
                        f"FLAGS_{flag} with a state kind "
                        f"({type(cfg).__name__}.state_kinds()): {what}")
            if self.mesh is not None:
                raise ValueError(
                    "a serving mesh with a state kind: the state arrays are "
                    "not sharded (SERVE_KV_SPEC)")
        if self.mesh is not None:
            from ..distributed.spmd import shard_serving_cache
            shard_serving_cache(self.cache, self.mesh)
        self.buckets = BucketTable(c.prefill_buckets, c.batch_buckets)
        self.lora = None
        if c.lora_adapters > 0:
            from .lora import LoRAManager
            # pools sized to the fused-QKV delta (3*H*D out features) —
            # built BEFORE the scheduler, which acquires/releases
            # adapter references at the slot lifecycle choke points
            self.lora = LoRAManager(
                cfg.num_layers, cfg.hidden_size,
                3 * cfg.num_heads * cfg.head_dim,
                max_adapters=c.lora_adapters, rank=c.lora_rank)
        self.scheduler = Scheduler(self.cache, self.buckets,
                                   max_queue=c.max_queue, clock=clock,
                                   max_seq_len=c.max_context_len,
                                   policy=c.queue_policy,
                                   on_event=self._on_request_event,
                                   tenant_quota=c.tenant_quota,
                                   lora=self.lora)
        self._overload = (OverloadDetector(
            c.overload_threshold_s, alpha=c.overload_alpha,
            exit_frac=c.overload_exit_frac)
            if c.overload_threshold_s > 0 else None)
        from ..monitor.slo import SLOTracker
        self._slo_avail = (SLOTracker(
            "serve_availability", c.slo_availability,
            windows=c.slo_windows, clock=clock)
            if c.slo_availability > 0 else None)
        self._slo_deadline = (SLOTracker(
            "serve_deadline", c.slo_deadline,
            windows=c.slo_windows, clock=clock)
            if c.slo_deadline > 0 else None)
        self.prefix_cache = None
        if bool(get_flag("serve_prefix_cache")):
            from .prefix_cache import RadixPrefixCache
            self.prefix_cache = RadixPrefixCache(self.cache)
            self.cache.prefix_cache = self.prefix_cache
        self._prefix_published: Dict[str, float] = {}
        #: delta-publish cursors for the per-tenant counters (same
        #: pattern as the prefix metrics: host stats are the source of
        #: truth, the registry sees monotone deltas)
        self._quota_published: Dict[str, int] = {}
        self._drain_latch: Optional[DrainLatch] = None
        self._draining = False
        self._drained = False
        self._watchdog_threads: List[threading.Thread] = []
        self._watchdog_worker: Optional[DispatchWorker] = None
        self._programs: Dict[tuple, AOTProgram] = {}
        self._programs_info: Dict[str, dict] = {}
        self._key = jax.random.key(int(c.seed))
        #: host-side accept/reject coin for stochastic speculative
        #: sampling (ISSUE 16) — its own stream so spec on/off never
        #: perturbs the device RNG the flags-off oracle pins
        self._spec_rng = np.random.default_rng((int(c.seed) << 1) ^ 0x51EC)
        self._dispatch_seq = 0
        #: engine.step() calls so far: the `step` of the serve.* spans
        self._step_seq = 0
        self._stats = {"prefill_dispatches": 0, "decode_dispatches": 0,
                       "decode_slot_steps": 0, "decode_batch_max": 0,
                       "decode_live_pages": 0, "decode_table_pages": 0,
                       "tokens_generated": 0, "program_compiles": 0,
                       "prefill_chunks": 0, "prefill_tokens": 0,
                       "verify_dispatches": 0, "spec_proposed": 0,
                       "spec_accepted": 0, "spec_rolled_back": 0}
        self._lat: Dict[str, List[float]] = {
            "ttft": [], "tpot": [], "e2e": [], "decode_step": []}
        self._t_first_work: Optional[float] = None
        self._t_last_token: Optional[float] = None
        #: last watchdog trip (kind/timeout/dispatch) — readiness
        #: reports it until a later guarded dispatch succeeds
        self._watchdog_tripped: Optional[dict] = None
        # model lifecycle (ISSUE 20): live weight hot-swap. Flag read
        # once, same contract as the throughput features above — off ⇒
        # swap_weights raises, _retired stays empty forever and every
        # dispatch takes the single-epoch path (byte-identical to the
        # pre-lifecycle engine).
        self._hot_swap = bool(get_flag("serve_hot_swap"))
        #: live weights generation; bumped at every cutover (including
        #: rollback cutovers). Slots are stamped with the epoch whose
        #: tree wrote their first KV page and finish on that tree.
        self._weights_epoch = 0
        #: candidate staged for the next iteration-boundary cutover
        self._staged: Optional[dict] = None
        #: epoch -> param tree still referenced by in-flight slots
        self._retired: Dict[int, dict] = {}
        #: pre-swap tree retained as the rollback anchor until
        #: commit_swap() drops it (promotion) or rollback_weights()
        #: re-stages it
        self._previous: Optional[dict] = None
        self._live_manifest: Optional[str] = None
        self._swap_stats = {"staged": 0, "cutover": 0, "refused": 0,
                            "rolled_back": 0, "committed": 0,
                            "drain_swaps": 0}
        _LIVE_ENGINES.add(self)
        self._attach_admin()

    # -- live telemetry plane (monitor/server.py; ISSUE 14) ------------------
    def _attach_admin(self) -> None:
        """Join the embedded admin plane when ``FLAGS_monitor_port`` is
        set: /readyz derives from THIS engine's state machine
        (draining/shedding/watchdog-tripped ⇒ 503) and /statusz gains a
        section with scheduler occupancy, program attribution and SLO
        burn. Flag unset (default) = one flag read, no thread, no
        socket, no registry writes — the zero-overhead contract."""
        from ..monitor import server as monitor_server
        self._admin = monitor_server.maybe_start_from_flags()
        self._admin_key = f"serving_engine_{id(self)}"
        if self._admin is None:
            return
        # weakref'd providers: a collected engine returns the STALE
        # sentinel so the server PRUNES the registration — never None,
        # which /readyz would read as "ready" (fail-open). Explicit
        # shutdown() unregisters instead: that is the drain hand-off,
        # where the successor engine's own registration takes over.
        ref = weakref.ref(self)
        stale = monitor_server.STALE
        self._admin.register_readiness(
            self._admin_key,
            lambda: (lambda e: stale if e is None else e._readiness())(
                ref()))
        self._admin.register_status(
            self._admin_key,
            lambda: (lambda e: stale if e is None
                     else e._admin_status())(ref()))

    def _readiness(self) -> Optional[dict]:
        """None while this engine should receive traffic; otherwise a
        JSON reason derived from the serving state machine
        (docs/SERVING.md): the load balancer's signal to pull this
        replica. Reads live state only — a state transition is visible
        to /readyz within the same iteration it happens."""
        if self._drained:
            return {"state": "drained",
                    "detail": "engine drained; hand traffic to the "
                              "successor"}
        if self._draining or (self._drain_latch is not None
                              and self._drain_latch.triggered):
            return {"state": "draining",
                    "queue_depth": self.scheduler.queue_depth,
                    "active_slots": len(self.scheduler.active())}
        if self._overload is not None and self._overload.overloaded:
            return {"state": "shedding",
                    "ewma_s": round(self._overload.ewma_s, 4),
                    "threshold_s": self._overload.threshold_s,
                    "queue_depth": self.scheduler.queue_depth}
        if self._watchdog_tripped is not None:
            return dict(self._watchdog_tripped,
                        state="watchdog-tripped")
        return None

    def _admin_status(self) -> dict:
        """/statusz section: the live engine picture an operator reads
        before deciding to drain/restart — occupancy, outcome stats,
        program FLOPs/HBM attribution, SLO burn."""
        d: dict = {
            "scheduler": self.scheduler.state(),
            "kv_pages_in_use": self.cache.allocator.pages_in_use,
            "kv_pages_total": self.cache.allocator.num_pages,
            "engine_stats": dict(self._stats),
            "programs": dict(self._programs_info),
            "draining": self._draining,
            "drained": self._drained,
            "overloaded": (self._overload.overloaded
                           if self._overload is not None else False),
            "watchdog_tripped": self._watchdog_tripped,
        }
        if self._hot_swap:
            d["weights"] = {
                "epoch": self._weights_epoch,
                "live_manifest": self._live_manifest,
                "staged": (self._staged["manifest"]
                           if self._staged is not None else None),
                "retired_epochs": sorted(self._retired),
                "rollback_available": self._previous is not None,
                "swaps": dict(self._swap_stats),
            }
        if self.lora is not None:
            d["lora"] = {
                "loaded": self.lora.loaded(),
                "swaps": self.lora.swaps,
                "refcounts": {n: self.lora.refcount(n)
                              for n in self.lora.loaded()},
            }
        if self.scheduler.tenant_quota is not None:
            d["tenant_deferrals"] = dict(self.scheduler.tenant_deferrals)
        if self._slo_avail is not None:
            d["slo_availability"] = self._slo_avail.snapshot()
        if self._slo_deadline is not None:
            d["slo_deadline"] = self._slo_deadline.snapshot()
        return d

    def _detach_admin(self) -> None:
        admin = getattr(self, "_admin", None)
        if admin is not None:
            admin.unregister_readiness(self._admin_key)
            admin.unregister_status(self._admin_key)
            self._admin = None

    # -- program construction ----------------------------------------------
    def _next_key(self):
        self._dispatch_seq += 1
        return fold_in(self._key, self._dispatch_seq)

    @contextlib.contextmanager
    def _mesh_scope(self):
        """Activate the TP serving mesh for the dynamic extent of a
        program trace/compile: ``constrain()`` pins inside the model
        read the active mesh at TRACE time, so without this scope a
        TP engine's programs would silently compile unsharded. Dispatch
        needs no scope — the compiled executables embed their shardings.

        Also serializes traces process-wide: the fleet router drives
        one serve thread per replica, and two replicas lazily compiling
        at once would race on the global mesh (and on trace-time global
        state generally). The lock is only ever taken on a compile
        miss, never on the dispatch path."""
        with _COMPILE_LOCK:
            if self.mesh is None:
                yield
                return
            from ..distributed import env as dist_env
            prev = dist_env.get_mesh()
            dist_env.set_mesh(self.mesh)
            try:
                yield
            finally:
                dist_env.set_mesh(prev)

    def _forward(self, params, ids, pools, table, pos, lora=None,
                 ctx: bool = False, lens=None):
        """Pure model forward over the paged pools (traced inside the
        prefill/decode programs): ``(logits, pools, stats)``, the pools
        in the order they came (``cache.pool_args()``), ``stats`` what
        the model counted for the engine (None from most).
        ``ctx=True`` selects the CONTEXT-prefill attention path
        (ISSUE 15): S>1 chunks attend over everything already in the
        pages, not just themselves — chunked-prefill continuations,
        prefix-hit tails and speculative verify windows all run
        through it.

        Quantized pools (FLAGS_serve_kv_quant) arrive as
        ``(pages, scales)`` tuples and leave the same way, so
        ``cache.update`` keeps the tuple structure; ``lora`` is the
        optional ``(a_pool, b_pool, per_slot_rows)`` triple of a
        multi-tenant engine (ISSUE 17) — the view carries it down to
        the attention blocks' bgmv delta.

        Where the model declares state kinds, ``pools`` ends in their
        arrays and ``table`` in the rows' slots (``cache.table_array``),
        and ``lens`` are the rows' real positions (a decode step's: 1);
        the view carries them as ``state`` and ``rows``."""
        cls = ContextPagedPools if ctx else PagedPools
        state = rows = None
        if self.cache.state_kinds:
            n = len(self.cache.kinds)
            pools, state = pools[:n], pools[n:]
            *table, slots = table
            table = tuple(table) if len(table) > 1 else table[0]
            if lens is None:
                lens = jnp.ones(slots.shape, jnp.int32)
            state = tuple(Tensor(a) for a in state)
            rows = (Tensor(slots), Tensor(lens))
        quant = isinstance(pools[0], tuple)
        wrap = lambda t: None if t is None else Tensor(t)
        unw = lambda t: t._data if isinstance(t, Tensor) else t
        view = cls(
            tuple(Tensor(p[0] if quant else p) for p in pools),
            # a table a page lifetime where the model declares windows
            tuple(Tensor(t) for t in table) if isinstance(table, tuple)
            else Tensor(table),
            tuple(Tensor(p[1]) for p in pools) if quant else None,
            tuple(wrap(t) for t in lora) if lora is not None else None,
            state=state, rows=rows)
        with bind(self.model, params, dict(self.buffers)), no_grad(), \
                trace_rng(jax.random.key(0)):
            logits, new = self.model(Tensor(ids), caches=view,
                                     cache_pos=Tensor(pos))
        out = tuple(unw(p) for p in new.pools)
        if quant:
            out = tuple(zip(out, (unw(sc) for sc in new.scales)))
        if state is not None:
            out = out + tuple(unw(a) for a in new.state)
        stats = None if new.stats is None \
            else {k: unw(a) for k, a in new.stats.items()}
        return unw(logits), out, stats

    def _fwd(self, params, ids, k, v, table, pos, lora=None,
             ctx: bool = False):
        """:meth:`_forward` in the K/V-pair spelling: ``(logits, k, v)``.
        ONE caller, which this PR could not edit: the GPT probe of
        ``benchmark/harness/serve_runner.py``. It goes, with the
        cache's ``.k`` / ``.v``, when that probe takes
        ``cache.pool_args()`` (ROADMAP Speed 0)."""
        logits, (k, v), _ = self._forward(params, ids, (k, v), table, pos,
                                          lora=lora, ctx=ctx)
        return logits, k, v

    def _attribute(self, kind: str, lowered, compiled) -> None:
        """Per-program attribution from the serving executables (same
        sources as TrainStep: lowered.cost_analysis /
        compiled.memory_analysis)."""
        self._stats["program_compiles"] += 1
        entry: dict = {}
        try:
            from ..cost_model import CostModel
            entry = CostModel().attribute(lowered)
        except Exception:
            pass
        try:
            from ..monitor import memory as monitor_memory
            pm = monitor_memory.analyze_compiled(compiled, kind=kind)
            if pm is not None:
                entry["peak_hbm_bytes"] = pm.peak_bytes
                monitor_memory.record_program(pm)
                get_registry().gauge(
                    "serve_program_peak_hbm_bytes",
                    "static peak-HBM estimate per serving program"
                ).set(pm.peak_bytes, kind=kind)
        except Exception:
            pass
        self._programs_info[kind] = entry
        get_registry().counter(
            "serve_program_compiles_total",
            "serving executable builds by program kind").inc(kind=kind)

    def _donate(self) -> tuple:
        from ..core.flags import get_flag
        # the pools are the 2nd argument of every program kind; donation
        # keeps decode's HBM footprint at ONE pool copy. An armed
        # watchdog disables donation: a tripped dispatch is ABANDONED
        # mid-flight, and retrying the step is only sound while the live
        # pools are neither invalidated (donated away) nor mutated in
        # place by the zombie thread — the documented trade is one extra
        # pool copy for retryable trips.
        if float(get_flag("serve_watchdog_s") or 0.0) > 0.0:
            return ()
        return (1,)

    def _program(self, key: tuple, build) -> AOTProgram:
        """The compiled program under ``key``; ``build()`` returns
        ``(AOTProgram, example_args)`` on the first ask. One place
        compiles: under the mesh scope, inside a ``serve.compile``
        span."""
        prog = self._programs.get(key)
        if prog is None:
            prog, args = build()
            with self._mesh_scope(), \
                    _trace.span("serve.compile", kind=prog.kind):
                prog.compile(args)
            self._programs[key] = prog
        return prog

    def _get_decode(self) -> AOTProgram:
        return self._program(("decode",), self._decode_program)

    def _decode_program(self):
        def decode_fn(params, pools, table, pos, tokens, active, rng,
                      temps, top_ks, top_ps, poison, *lora):
            # *lora is (a_pool, b_pool, rows) on a multi-tenant engine
            # and EMPTY otherwise — the 11-arg signature and the traced
            # program are unchanged when FLAGS/config leave LoRA off
            logits, pools, stats = self._forward(
                params, tokens[:, None], pools, table, pos,
                lora=lora or None)
            # poison is all-zeros outside chaos (bit-transparent); a NaN
            # entry models a slot whose forward went non-finite. `ok` is
            # the per-slot fault-isolation flag: one bad request fails
            # alone, the rest of the batch streams on.
            with jax.named_scope("sampling"):
                row = logits[:, -1, :] + poison[:, None]
                ok = jnp.isfinite(row).all(axis=-1)
                toks = sample_tokens(row, rng, temps, top_ks, top_ps)
                toks = jnp.where(active, toks, 0)
            return toks, ok, pools, stats

        B = self.config.max_batch_slots
        prog = AOTProgram("serve_decode", decode_fn,
                          donate_argnums=self._donate(),
                          on_attribute=self._attribute)
        return prog, (self.params, self.cache.pool_args(),
                      self.cache.table_like(B),
                      jnp.zeros((B,), jnp.int32),
                      jnp.zeros((B,), jnp.int32),
                      jnp.zeros((B,), bool), self._key,
                      jnp.ones((B,), jnp.float32),
                      jnp.zeros((B,), jnp.int32),
                      jnp.ones((B,), jnp.float32),
                      jnp.zeros((B,), jnp.float32)) + self._lora_sig(B)

    def _lora_sig(self, n: int) -> tuple:
        """Compile-time LoRA argument suffix for an ``n``-row program:
        the stacked pools + an all-zero (= zero-adapter) row vector.
        Empty on a non-LoRA engine — signatures stay pinned."""
        if self.lora is None:
            return ()
        return (self.lora.a, self.lora.b, jnp.zeros((n,), jnp.int32))

    def _lora_args(self, states) -> tuple:
        """Dispatch-time LoRA argument suffix: the LIVE pools (hot-swaps
        between steps are just new arguments — never a recompile) and
        each row's adapter pool index (empty slots / base requests ride
        the zero adapter, row 0)."""
        if self.lora is None:
            return ()
        rows = self.lora.rows_for(
            [st.request.adapter if st is not None else None
             for st in states])
        return (self.lora.a, self.lora.b, rows)

    def _get_prefill(self, nb: int, sp: int) -> AOTProgram:
        return self._program(("prefill", nb, sp),
                             lambda: self._prefill_program(nb, sp))

    def _prefill_program(self, nb: int, sp: int):
        def prefill_fn(params, pools, table, ids, lens, rng, temps,
                       top_ks, top_ps, poison, *lora):
            pos = jnp.zeros((nb,), jnp.int32)
            logits, pools, _ = self._forward(params, ids, pools, table,
                                             pos, lora=lora or None,
                                             lens=lens)
            with jax.named_scope("sampling"):
                last = jnp.take_along_axis(
                    logits, (lens - 1).astype(jnp.int32)[:, None, None],
                    axis=1)[:, 0, :]
                row = last + poison[:, None]
                ok = jnp.isfinite(row).all(axis=-1)
                toks = sample_tokens(row, rng, temps, top_ks, top_ps)
            return toks, ok, pools

        prog = AOTProgram(f"serve_prefill_b{nb}_s{sp}", prefill_fn,
                          name=f"serve_prefill_{nb}x{sp}",
                          donate_argnums=self._donate(),
                          on_attribute=self._attribute)
        return prog, (self.params, self.cache.pool_args(),
                      self.cache.table_like(nb),
                      jnp.zeros((nb, sp), jnp.int32),
                      jnp.ones((nb,), jnp.int32), self._key,
                      jnp.ones((nb,), jnp.float32),
                      jnp.zeros((nb,), jnp.int32),
                      jnp.ones((nb,), jnp.float32),
                      jnp.zeros((nb,), jnp.float32)) + self._lora_sig(nb)

    def _get_prefill_ctx(self, nb: int, sp: int) -> AOTProgram:
        """Context-prefill program (ISSUE 15): same shape contract as
        the plain prefill bucket, plus a per-row ``pos`` argument — the
        chunk's rows occupy positions ``pos .. pos+lens-1`` and attend
        over every page-resident position before them. Serves chunked-
        prefill continuation chunks and prefix-cache-hit tails."""
        return self._program(("prefill_ctx", nb, sp),
                             lambda: self._prefill_ctx_program(nb, sp))

    def _prefill_ctx_program(self, nb: int, sp: int):
        def prefill_ctx_fn(params, pools, table, ids, lens, pos, rng,
                           temps, top_ks, top_ps, poison, *lora):
            logits, pools, _ = self._forward(params, ids, pools, table,
                                             pos, lora=lora or None,
                                             ctx=True, lens=lens)
            with jax.named_scope("sampling"):
                last = jnp.take_along_axis(
                    logits, (lens - 1).astype(jnp.int32)[:, None, None],
                    axis=1)[:, 0, :]
                row = last + poison[:, None]
                ok = jnp.isfinite(row).all(axis=-1)
                toks = sample_tokens(row, rng, temps, top_ks, top_ps)
            return toks, ok, pools

        prog = AOTProgram(f"serve_prefill_ctx_b{nb}_s{sp}",
                          prefill_ctx_fn,
                          name=f"serve_prefill_ctx_{nb}x{sp}",
                          donate_argnums=self._donate(),
                          on_attribute=self._attribute)
        return prog, (self.params, self.cache.pool_args(),
                      self.cache.table_like(nb),
                      jnp.zeros((nb, sp), jnp.int32),
                      jnp.ones((nb,), jnp.int32),
                      jnp.zeros((nb,), jnp.int32), self._key,
                      jnp.ones((nb,), jnp.float32),
                      jnp.zeros((nb,), jnp.int32),
                      jnp.ones((nb,), jnp.float32),
                      jnp.zeros((nb,), jnp.float32)) + self._lora_sig(nb)

    def _get_verify(self) -> AOTProgram:
        """Speculative-verify program (ISSUE 15): ONE dispatch scores
        all ``k+1`` positions of ``[last_token, d_1 .. d_k]`` per slot
        against the paged cache. Returns the row-0 token under each
        slot's sampling params (== the plain decode output), per-row
        greedy argmaxes for draft acceptance, and per-row finite flags
        (fault isolation stays per-slot AND per-used-row — pad rows
        beyond a slot's draft may read scratch garbage and are never
        consulted). For sampled slots (ISSUE 16) it additionally
        returns the residual accept/reject ingredients — the drafted
        token's probability under each row's FILTERED sampling
        distribution, a full fresh sample per row (bonus token on a
        clean sweep), and a residual redraw per row with the draft
        masked out — so the host can run point-mass-drafter
        Leviathan-style acceptance and the committed stream keeps the
        plain sampled-decode distribution exactly."""
        return self._program(("verify", self._spec_k + 1),
                             self._verify_program)

    def _verify_program(self):
        S = self._spec_k + 1

        def verify_fn(params, pools, table, pos, ids, active, rng,
                      temps, top_ks, top_ps, poison, *lora):
            logits, pools, _ = self._forward(params, ids, pools, table,
                                             pos, lora=lora or None,
                                             ctx=True)        # [B,S,V]
            with jax.named_scope("sampling"):
                row0 = logits[:, 0, :] + poison[:, None]
                ok_rows = jnp.isfinite(logits).all(axis=-1)       # [B,S]
                ok_rows = ok_rows.at[:, 0].set(
                    jnp.isfinite(row0).all(axis=-1))
                tok0 = sample_tokens(row0, rng, temps, top_ks, top_ps)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                B, V = logits.shape[0], logits.shape[-1]
                flat = filtered_logits(
                    logits.reshape(B * S, V).astype(jnp.float32),
                    jnp.repeat(temps, S), jnp.repeat(top_ks, S),
                    jnp.repeat(top_ps, S)).reshape(B, S, V)
                probs = jax.nn.softmax(flat, axis=-1)
                drafts = ids[:, 1:]                               # [B,S-1]
                # p_draft[b, i] = P(draft_i | rows 0..i) — row i's filtered
                # softmax mass on the token the drafter proposed for it
                p_draft = jnp.take_along_axis(
                    probs[:, :-1, :], drafts[..., None],
                    axis=-1)[..., 0]                              # [B,S-1]
                k_full, k_resid = jax.random.split(jax.random.fold_in(rng, 1))
                tok_full = jax.random.categorical(
                    k_full, flat, axis=-1).astype(jnp.int32)      # [B,S]
                resid = jnp.where(
                    jax.nn.one_hot(drafts, V, dtype=bool),
                    _SAMPLING_NEG, flat[:, :-1, :])
                tok_resid = jax.random.categorical(
                    k_resid, resid, axis=-1).astype(jnp.int32)    # [B,S-1]
            return (jnp.where(active, tok0, 0), greedy, ok_rows,
                    p_draft, tok_full, tok_resid, pools)

        B = self.config.max_batch_slots
        prog = AOTProgram(f"serve_verify_s{S}", verify_fn,
                          name="serve_verify",
                          donate_argnums=self._donate(),
                          on_attribute=self._attribute)
        return prog, (self.params, self.cache.pool_args(),
                      self.cache.table_like(B),
                      jnp.zeros((B,), jnp.int32),
                      jnp.zeros((B, S), jnp.int32),
                      jnp.zeros((B,), bool), self._key,
                      jnp.ones((B,), jnp.float32),
                      jnp.zeros((B,), jnp.int32),
                      jnp.ones((B,), jnp.float32),
                      jnp.zeros((B,), jnp.float32)) + self._lora_sig(B)

    def warmup(self, prefill_signatures: Optional[Sequence[Tuple[int, int]]]
               = None) -> int:
        """AOT-compile the decode program and the given (or full bucket
        table's) prefill signatures before traffic arrives — plus, when
        the ISSUE 15 features are armed, the context-prefill twins and
        the speculative-verify program, so the first prefix hit / chunk
        continuation / draft never pays a cold compile. Returns the
        number of programs now resident."""
        self._get_decode()
        for nb, sp in (prefill_signatures
                       if prefill_signatures is not None
                       else self.buckets.signatures()):
            self._get_prefill(nb, sp)
            if self._chunk > 0 or self.prefix_cache is not None:
                self._get_prefill_ctx(nb, sp)
        if self._spec_k > 0:
            self._get_verify()
        return len(self._programs)

    #: raw latency samples kept per series for exact percentiles; beyond
    #: this the oldest half is dropped (a long-running engine must not
    #: grow host memory per request — summaries then cover the recent
    #: window, which is what an SLO dashboard wants anyway)
    LAT_WINDOW = 65536

    def _observe(self, series: str, value: float) -> None:
        lst = self._lat[series]
        lst.append(value)
        if len(lst) > 2 * self.LAT_WINDOW:
            del lst[:len(lst) - self.LAT_WINDOW]

    #: deadline-slack buckets: negatives = finished past deadline (only
    #: possible within one iteration of it), small positives = tight SLO
    DEADLINE_SLACK_BUCKETS = (-1.0, -0.1, 0.0, 0.05, 0.1, 0.25, 0.5,
                              1.0, 2.0, 5.0, 30.0)

    def _requests_counter(self):
        return get_registry().counter(
            "serve_requests_total",
            "serving requests by lifecycle event")

    def _on_request_event(self, outcome: str, st: RequestState) -> None:
        """Scheduler terminal-transition hook: metrics + forensics +
        span-tree closure. Only fires on lifecycle events — never per
        step (the zero-overhead pin)."""
        self._requests_counter().inc(event=outcome)
        if st.request.tenant:
            get_registry().counter(
                "serve_tenant_requests_total",
                "serving requests by tenant and lifecycle event").inc(
                tenant=st.request.tenant, event=outcome)
        if outcome != "completed":
            self._flight_event(
                "request_failed" if outcome == "failed"
                else f"request_{outcome}",
                request_id=st.request.request_id,
                reason=st.failure, tokens=len(st.generated),
                preemptions=st.preemptions)
        if self._slo_avail is not None:
            # availability: cancelled/drained are client/operator
            # choices, not served-badly outcomes — they spend no budget
            if outcome == "completed":
                self._slo_avail.record(good=1)
            elif outcome in ("expired", "failed", "shed"):
                self._slo_avail.record(bad=1)
            self._slo_avail.publish()
        if self._slo_deadline is not None and outcome == "expired":
            # an expiry is a blown deadline whether queued or in-flight
            # (the completed-on-time case is fed from _accept_token)
            self._slo_deadline.record(bad=1)
            self._slo_deadline.publish()
        self._close_trace(st, outcome)

    def _close_trace(self, st: RequestState, outcome: str) -> None:
        """Terminal span + retention decision for a traced request (the
        ``Scheduler._terminate`` seam: every exit path lands here)."""
        tr = st.trace
        if tr is None:
            return
        now = self.clock()
        for key in ("queued", "admitted"):
            sp = st.trace_spans.pop(key, None)
            if sp is not None:
                tr.end_span(sp, t=now)
        tr.event("terminal", t=now, outcome=outcome,
                 reason=st.failure, tokens=len(st.generated),
                 preemptions=st.preemptions)
        if outcome in ("expired", "shed", "failed"):
            reason = st.failure or ""
            tr.mark_anomaly(
                "nonfinite" if "non-finite" in reason
                else ("chaos" if st.poisoned
                      else ("failed" if outcome == "failed"
                            else outcome)),
                failure=st.failure)
        tr.root.set_attrs(outcome=outcome)
        _trace.get_tracer().finish_trace(tr, t=now)
        st.trace_spans.clear()

    @staticmethod
    def _flight_enabled() -> bool:
        return _flight.enabled()

    @staticmethod
    def _flight_event(name: str, **fields) -> None:
        _flight.safe_record_event(name, **fields)

    # -- request surface ----------------------------------------------------
    def submit(self, request: Request) -> RequestState:
        if self._draining or self._drained:
            self._requests_counter().inc(event="rejected")
            raise ServerOverloaded("draining")
        if self._overload is not None and self._overload.overloaded:
            # recovery samples normally arrive from step(), but step()
            # is only driven while there is work — an IDLE engine must
            # fold the (empty-queue = 0 delay) sample here or a tripped
            # detector latches forever and sheds all future traffic
            if not self.scheduler.has_work:
                transition = self._overload.observe(0.0)
                if transition is not None:
                    self._overload_transition(transition)
            if self._overload.overloaded:
                self._requests_counter().inc(event="rejected")
                raise ServerOverloaded(
                    "overload", queue_depth=self.scheduler.queue_depth,
                    ewma_s=self._overload.ewma_s,
                    threshold_s=self._overload.threshold_s)
        if request.adapter and (
                self.lora is None
                or self.lora.row(request.adapter) is None):
            # fail fast at the door: a request naming an unknown
            # adapter can never decode (the scheduler re-checks at
            # admission, covering a hot-unload that races the queue)
            self._requests_counter().inc(event="rejected")
            raise ValueError(
                f"adapter {request.adapter!r} is not loaded"
                + ("" if self.lora is not None
                   else " (engine has no LoRA manager; set "
                        "ServingConfig.lora_adapters)"))
        try:
            st = self.scheduler.submit(request)
        except ServerOverloaded:
            # bounded queue refused the newcomer (policy produced no
            # victim). A never-admitted refusal counts as "rejected";
            # "shed" is reserved for admitted-then-evicted policy
            # victims, so offered = submitted + rejected stays exact.
            self._requests_counter().inc(event="rejected")
            raise
        if chaos.active() and chaos.probe("serve.request.poison"):
            st.poisoned = True
        if _trace.enabled():
            # one trace per request; a drain-snapshot trace_id RESUMES
            # the identity on this (successor) engine. Tail-based
            # retention needs the buffer regardless of the head coin,
            # so the trace exists for every request while the flag is
            # on — the flag OFF path allocates nothing (pinned).
            # a bare trace_id is a drain/resume identity handover; one
            # arriving WITH a parent token is just downstream context
            # from the fleet router — not a resume
            resumed = (request.trace_id is not None
                       and request.trace_parent is None)
            tr = _trace.get_tracer().start_trace(
                "serve.request", trace_id=request.trace_id,
                # the upstream (router) head decision wins when the
                # context carries one — Dapper's sampled bit, ONE coin
                # per distributed trace. Otherwise a resumed identity
                # was handed over deliberately (its first half may
                # already be retained) — never let a re-flip of the
                # head coin drop the continuation. All spans run on the
                # ENGINE clock (t=): injectable in tests, one time
                # domain per trace.
                sample=(request.trace_sampled
                        if request.trace_sampled is not None
                        else (True if resumed else None)),
                t=st.submitted_t,
                # cross-process parent link + producing-replica label
                # (ISSUE 18): the fleet merge parents this tree under
                # the router's route/hop span and renders it on this
                # replica's own Perfetto track
                process=request.trace_process,
                parent=request.trace_parent,
                request_id=request.request_id,
                prompt_len=st.prompt_len,
                max_new_tokens=request.max_new_tokens,
                resumed=resumed)
            st.trace = tr
            st.trace_spans["queued"] = tr.start_span(
                "queued", t=st.submitted_t)
            if st.poisoned:
                tr.mark_anomaly("chaos",
                                chaos_site="serve.request.poison")
        self._requests_counter().inc(event="submitted")
        if request.tenant:
            # emits-metrics: serve_tenant_requests_total
            get_registry().counter(
                "serve_tenant_requests_total",
                "serving requests by tenant and lifecycle event").inc(
                tenant=request.tenant, event="submitted")
        self._publish_gauges()
        return st

    def cancel(self, request_id: int) -> bool:
        """Client disconnect: cancel a queued request immediately or an
        in-flight one at the next iteration boundary (its pages are
        freed there). Returns False for unknown/terminal ids."""
        hit = self.scheduler.cancel(request_id)
        if hit:
            self._publish_gauges()
        return hit

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 16,
                 sampling: Optional[SamplingParams] = None,
                 eos_token_id: Optional[int] = None) -> List[np.ndarray]:
        """Batch convenience: submit, drain, return full sequences
        (prompt + generated) per request, in submission order."""
        states = [self.submit(Request(
            p, max_new_tokens=max_new_tokens,
            sampling=sampling or SamplingParams(),
            eos_token_id=eos_token_id)) for p in prompts]
        self.run()
        return [np.concatenate([st.request.prompt,
                                np.asarray(st.generated, np.int32)])
                for st in states]

    def run(self, max_steps: Optional[int] = None) -> None:
        """Drive the scheduler until the queue and slots drain. Raises
        :class:`EngineDrained` if a latched drain signal is honoured
        mid-run."""
        steps = 0
        while self.scheduler.has_work:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                return

    # -- graceful drain ------------------------------------------------------
    def enable_drain(self, snapshot_dir: str,
                     budget_s: Optional[float] = None,
                     signals=None) -> DrainLatch:
        """Install the shutdown latch (PR 5 pattern): SIGTERM (default)
        is latched by a thin handler and honoured at the next iteration
        boundary — :meth:`step` then drains and raises
        :class:`EngineDrained`. Returns the latch (``trigger()`` arms it
        programmatically; ``shutdown()`` restores the handlers)."""
        import signal as signal_mod
        self.config.drain_dir = snapshot_dir
        if budget_s is not None:
            self.config.drain_budget_s = float(budget_s)
        if self._drain_latch is not None:
            self._drain_latch.close()
        self._drain_latch = DrainLatch(
            signals if signals is not None else (signal_mod.SIGTERM,))
        return self._drain_latch

    def drain(self, snapshot_dir: Optional[str] = None,
              budget_s: Optional[float] = None) -> DrainReport:
        """Graceful shutdown: stop admission, keep decoding in-flight
        sequences up to the drain budget, then snapshot ALL undone work
        (queued + still-in-flight request specs) through the atomic
        checkpoint-commit helpers. Zero silently-lost requests: every
        submitted request either completed or is in the snapshot."""
        if snapshot_dir is None:
            snapshot_dir = self.config.drain_dir
        budget = (self.config.drain_budget_s if budget_s is None
                  else float(budget_s))
        self._draining = True            # submit() now sheds
        sched = self.scheduler
        completed_before = sched.stats["completed"]
        deadline = self.clock() + max(0.0, budget)
        while sched.active() and self.clock() < deadline:
            try:
                self.step(admit=False)
            except DecodeWatchdogError:
                break                    # hung chip: snapshot what's left
        # honour latched cancels/expiries before snapshotting: a request
        # the client disconnected from must end "cancelled", never be
        # resurrected on the successor engine as "drained" work
        sched.sweep_active()
        sched.honour_queued_cancels()
        specs = [request_spec(st) for _, st in sched.active()]
        specs += [request_spec(st) for st in sched.waiting]
        if specs and snapshot_dir is None:
            self._draining = False
            raise ValueError(
                f"drain: {len(specs)} request(s) still pending but no "
                "snapshot_dir is configured — refusing to discard work "
                "(pass snapshot_dir or ServingConfig.drain_dir)")
        path = None
        if specs:
            path = save_drain_snapshot(snapshot_dir, specs)
        for _, st in list(sched.active()):
            sched.drain_release(st)
        for st in list(sched.waiting):
            sched.drain_release(st)
        completed = sched.stats["completed"] - completed_before
        self._flight_event("drained", completed=completed,
                           snapshotted=len(specs), path=path)
        self._drained = True
        self._publish_gauges()
        return DrainReport(completed=completed, snapshotted=len(specs),
                           path=path)

    # -- live weight hot-swap (ISSUE 20) -------------------------------------
    def _swaps_counter(self):
        return get_registry().counter(
            "serve_swaps_total",
            "weight hot-swap lifecycle events (staged/cutover/refused/"
            "rolled_back/committed/drain_fallback)")

    def swap_weights(self, manifest_dir: str, mode: str = "auto") -> dict:
        """Load + verify a candidate checkpoint and swap it in WITHOUT
        dropping traffic (ISSUE 20).

        The candidate must be a committed manifest checkpoint of this
        engine's exact param tree (names/shapes/dtypes). A torn or
        mismatched push REFUSES (:class:`WeightSwapError`) with no side
        effects — the old weights keep serving. A valid push is staged
        beside the live tree and cut over atomically at the next
        iteration boundary (immediately when nothing is in flight);
        in-flight slots finish on the weights that wrote their KV pages
        (per-slot generation epoch — the LoRA pool-row convention
        generalized to the dense tree). When device memory can't hold
        two trees (``monitor.memory`` preflight), falls back to
        drain-and-restore through the PR 8 snapshot machinery: the tree
        swaps with nothing in flight and every unfinished continuation
        resubmits with its client callbacks re-attached.

        ``mode``: ``"auto"`` (preflight chooses) | ``"staged"`` |
        ``"drain"``. Returns a dict with ``mode``/``epoch`` plus
        per-mode detail. Weight swap never skips checkpoint
        verification: ``FLAGS_checkpoint_verify`` escalates the level
        but ``off`` does not disarm it."""
        if not self._hot_swap:
            raise RuntimeError(
                "FLAGS_serve_hot_swap is off — live weight swap is "
                "disarmed for this engine (the flag is read once at "
                "construction)")
        if mode not in ("auto", "staged", "drain"):
            raise ValueError(
                f"swap mode {mode!r}: expected auto|staged|drain")
        from ..core.flags import get_flag
        from ..distributed import checkpoint as ckpt
        state = None
        if chaos.active() and chaos.probe("serve.swap.torn_manifest"):
            reason = ("chaos serve.swap.torn_manifest: candidate "
                      "manifest torn mid-push")
        else:
            level = get_flag("checkpoint_verify")
            reason = ckpt.verify_checkpoint(
                manifest_dir,
                level="manifest" if level == "off" else level)
        if reason is None:
            try:
                state = ckpt.load(manifest_dir)
            except Exception as e:
                reason = f"load failed ({type(e).__name__}: {e})"
        if reason is None:
            reason = self._validate_candidate(state)
        if reason is not None:
            # refusal is side-effect free: old weights keep serving
            self._swap_stats["refused"] += 1
            self._swaps_counter().inc(event="refused")
            self._flight_event("swap_refused", manifest=manifest_dir,
                               reason=reason)
            raise WeightSwapError(manifest_dir, reason)
        # place each candidate leaf exactly like its live counterpart —
        # the compiled programs' input shardings must match untouched
        tree = {name: jax.device_put(jnp.asarray(state[name]),
                                     live.sharding)
                for name, live in self.params.items()}
        if chaos.active() and chaos.probe("serve.swap.bad_weights"):
            # corruption that SURVIVES manifest verification: plant NaN
            # into the first floating leaf. The swap path deliberately
            # does not scan finiteness (a full-tree reduction per push);
            # the damage manifests as non-finite logits in flight — the
            # signal the lifecycle controller's auto-rollback drills on.
            for name, leaf in tree.items():
                if jnp.issubdtype(leaf.dtype, jnp.inexact):
                    tree[name] = jnp.full_like(leaf, float("nan"))
                    break
        if mode == "auto":
            mode = "staged" if self._swap_headroom_ok(tree) else "drain"
        if mode == "drain":
            return self._swap_via_drain(tree, manifest_dir)
        return self._stage(tree, manifest_dir)

    def _validate_candidate(self, state) -> Optional[str]:
        """None when ``state`` is exactly this model's param tree
        (names/shapes/dtypes), else the human-readable refusal reason."""
        if not isinstance(state, dict):
            return ("candidate is not a param dict "
                    f"({type(state).__name__})")
        live, cand = set(self.params), set(state)
        if live != cand:
            missing = sorted(live - cand)[:3]
            extra = sorted(cand - live)[:3]
            return ("param tree mismatch"
                    + (f"; missing {missing}" if missing else "")
                    + (f"; unexpected {extra}" if extra else ""))
        for name, ref in self.params.items():
            arr = state[name]
            if tuple(arr.shape) != tuple(ref.shape):
                return (f"shape mismatch at {name}: candidate "
                        f"{tuple(arr.shape)} vs serving "
                        f"{tuple(ref.shape)}")
            if jnp.dtype(arr.dtype) != jnp.dtype(ref.dtype):
                return (f"dtype mismatch at {name}: candidate "
                        f"{jnp.dtype(arr.dtype).name} vs serving "
                        f"{jnp.dtype(ref.dtype).name}")
        return None

    def _swap_headroom_ok(self, tree: dict) -> bool:
        """``monitor.memory`` preflight for the staged (dual-tree) swap:
        True when the device reports room for the candidate's bytes
        with a 25% safety margin (conservative: compares the WHOLE
        tree's bytes against one device's headroom, so sharded trees
        pass early). Backends that publish no allocator stats (the CPU
        test backend) stage — the host heap is the constraint there,
        not HBM."""
        from ..monitor import memory as _memory
        stats = _memory.device_memory_stats()
        if not stats:
            return True
        limit = stats.get("bytes_limit") \
            or stats.get("bytes_reservable_limit")
        if not limit:
            return True
        need = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                   for a in tree.values())
        free = int(limit) - int(stats.get("bytes_in_use", 0))
        return free >= need * 1.25

    def _stage(self, tree: dict, manifest_dir: Optional[str]) -> dict:
        self._staged = {"params": tree, "manifest": manifest_dir}
        self._swap_stats["staged"] += 1
        self._swaps_counter().inc(event="staged")
        self._flight_event("weights_staged", manifest=manifest_dir,
                           epoch=self._weights_epoch + 1)
        if not self.scheduler.active():
            # nothing in flight: between steps IS an iteration boundary
            self._cutover()
        return {"mode": "staged", "epoch": self._weights_epoch,
                "pending": self._staged is not None}

    def _swap_via_drain(self, tree: dict,
                        manifest_dir: Optional[str]) -> dict:
        """HBM-constrained fallback: snapshot in-flight work as drain
        specs, release the slots (nothing references the old tree any
        more), cut over, then resubmit every unfinished continuation
        with its client callbacks re-attached — tokens already streamed
        stand, the continuation decodes on the new weights. Accounting
        records the interrupted residencies ``drained`` and the
        continuations as fresh submits, so the terminal-outcome
        identity still balances. The waiting queue is untouched: queued
        work never touched the old weights."""
        sched = self.scheduler
        sched.sweep_active()
        inflight = [(st, request_spec(st)) for _, st in sched.active()]
        for _, st in list(sched.active()):
            sched.drain_release(st)
        self._swap_stats["drain_swaps"] += 1
        self._swaps_counter().inc(event="drain_fallback")
        self._stage(tree, manifest_dir)   # no actives left: cuts over
        resubmitted = []
        for st_old, spec in inflight:
            reqs = requests_from_snapshot([spec])
            if not reqs:
                continue                  # already had its full budget
            req = reqs[0]
            req.on_token = st_old.request.on_token
            req.stop = st_old.request.stop
            resubmitted.append(self.submit(req))
        self._flight_event("weights_drain_swap",
                           resubmitted=len(resubmitted),
                           manifest=manifest_dir)
        return {"mode": "drain", "epoch": self._weights_epoch,
                "resubmitted": len(resubmitted),
                "states": resubmitted}

    def _cutover(self) -> None:
        """The atomic swap point (top of :meth:`step`, or immediately
        when idle): the staged tree becomes the live one. Slots in
        flight keep a reference to the tree that wrote their KV pages
        (``_retired``) until they terminate; the radix prefix tree is
        flushed — and donation detached for the transition — because
        cached pages carry the OLD weights' KV and must never seed a
        new-epoch admission."""
        staged, self._staged = self._staged, None
        old_epoch = self._weights_epoch
        actives = [st for _, st in self.scheduler.active()]
        for st in actives:
            if st.weights_epoch is None:
                # admitted before the boundary (possibly prefix-seeded
                # from old-weight pages): it belongs to the old epoch
                st.weights_epoch = old_epoch
        if actives:
            self._retired[old_epoch] = self.params
        self._previous = {"params": self.params,
                          "manifest": self._live_manifest}
        self._weights_epoch = old_epoch + 1
        self.params = staged["params"]
        self._live_manifest = staged["manifest"]
        if self.prefix_cache is not None:
            # safe with live shared-page references: the allocator is
            # refcounted, clear() just drops the tree's own refs
            self.prefix_cache.clear()
            if actives:
                # terminating old-epoch slots would DONATE old-weight
                # pages into the fresh tree: detach until they're gone
                # (free_slot skips donation while cache.prefix_cache
                # is None); _retire_unreferenced re-attaches
                self.cache.prefix_cache = None
        self._swap_stats["cutover"] += 1
        self._swaps_counter().inc(event="cutover")
        get_registry().gauge(
            "serve_weights_epoch",
            "live weights generation (increments at every hot-swap "
            "cutover, including rollback cutovers)").set(
                float(self._weights_epoch))
        self._flight_event("weights_cutover",
                           epoch=self._weights_epoch,
                           manifest=staged["manifest"],
                           in_flight_old_epoch=len(actives))

    def rollback_weights(self) -> dict:
        """Swap BACK to the pre-swap weights (the auto-rollback path).
        The previous tree is kept resident from cutover until
        :meth:`commit_swap`, so rollback needs no reload — it stages
        the retained tree and cuts over at the next iteration boundary
        (immediately when idle). After the rollback cutover the BAD
        tree becomes the retained previous; ``commit_swap()`` then
        drops it."""
        if not self._hot_swap:
            raise RuntimeError(
                "FLAGS_serve_hot_swap is off — rollback_weights is "
                "disarmed for this engine")
        prev = self._previous
        if prev is None:
            raise WeightSwapError(
                "<previous>", "no previous weights retained (already "
                "committed, or never swapped)")
        self._previous = None
        self._swap_stats["rolled_back"] += 1
        self._swaps_counter().inc(event="rolled_back")
        self._flight_event("weights_rolled_back",
                           from_epoch=self._weights_epoch,
                           to_manifest=prev["manifest"])
        return self._stage(prev["params"], prev["manifest"])

    def commit_swap(self) -> None:
        """Promotion: drop the retained pre-swap tree (the rollback
        anchor), freeing its memory. ``rollback_weights`` afterwards
        raises — the lifecycle controller calls this once the bake
        window passes (or after a rollback cutover, to drop the bad
        tree)."""
        if self._previous is not None:
            self._previous = None
            self._swap_stats["committed"] += 1
            self._swaps_counter().inc(event="committed")
            self._flight_event("weights_committed",
                               epoch=self._weights_epoch)

    def _params_for(self, epoch: Optional[int]):
        """The param tree for a slot epoch: the live tree for the live
        epoch (and for unstamped slots), a retired tree during a swap
        transition."""
        if epoch is None or epoch == self._weights_epoch:
            return self.params
        return self._retired[epoch]

    def _epoch_batches(self, pairs):
        """Partition this iteration's decodable slots into one
        (param_tree, pairs) dispatch batch per weights epoch. Outside a
        swap transition — the steady state, and always when
        ``FLAGS_serve_hot_swap`` is off — ``_retired`` is empty and
        this is ONE batch with the live tree: dispatch count and
        arguments identical to the pre-lifecycle engine (the flags-off
        pin)."""
        if not self._retired:
            return [(self.params, pairs)] if pairs else []
        by_epoch: Dict[int, list] = {}
        for slot, st in pairs:
            e = st.weights_epoch
            e = self._weights_epoch if e is None else e
            by_epoch.setdefault(e, []).append((slot, st))
        return [(self._params_for(e), by_epoch[e])
                for e in sorted(by_epoch)]

    def _retire_unreferenced(self) -> None:
        """Free retired trees no in-flight slot references any more;
        when the last one goes, the swap transition is over and prefix
        donation re-attaches (onto the flushed, new-epoch-only tree)."""
        live = {st.weights_epoch for _, st in self.scheduler.active()}
        for e in [e for e in self._retired if e not in live]:
            del self._retired[e]
            self._flight_event("weights_retired", epoch=e)
        if not self._retired and self.prefix_cache is not None \
                and self.cache.prefix_cache is None:
            self.cache.prefix_cache = self.prefix_cache

    # -- the serving iteration ----------------------------------------------
    def step(self, admit: bool = True) -> bool:
        """One scheduler iteration: honour drain/cancel/deadlines at the
        boundary, admit+prefill, then one decode dispatch over every
        active slot. Returns has_work. Raises :class:`EngineDrained`
        when a latched drain signal was honoured this step.

        The iteration is a ``serve.step`` span and each phase a child
        (docs/OBSERVABILITY.md "Step spans"); a step that outlasts its
        kin by far is reported once it returns (:meth:`_note_stall`)."""
        if self._drain_latch is not None \
                and self._drain_latch.triggered \
                and not self._draining:
            # before the span opens: the drain's own steps are steps of
            # their own, not phases of one that lasts the drain budget
            raise EngineDrained(self.drain())
        self._step_seq += 1
        built = self._stats["program_compiles"]
        with _trace.span("serve.step", step=self._step_seq) as sp:
            work = self._step(admit, sp)
        # a step that built a program (a bucket no warm-up covered, a
        # healed layout: `serve.compile`) is slow for a known reason
        if sp.t1 - sp.t0 > _trace.STALL_FLOOR_S \
                and self._stats["program_compiles"] == built:
            self._note_stall(sp)
        return work

    def _step(self, admit: bool, sp) -> bool:
        with _trace.span("serve.sweep"):
            if self._staged is not None:
                # the atomic cutover point: an iteration boundary, before
                # any admission/prefill/decode of this step
                self._cutover()
            sched = self.scheduler
            # iteration-boundary sweeps: queued expiries never touch a
            # slot; latched cancels / in-flight expiries free pages
            # immediately. Both are O(0) when no deadline/cancel exists —
            # and never write the registry except on an actual lifecycle
            # event.
            sched.expire_queued()
            sched.sweep_active()
            if self._overload is not None:
                oldest_t = sched.oldest_waiting_t()
                delay = (self.clock() - oldest_t
                         if oldest_t is not None else 0.0)
                transition = self._overload.observe(delay)
                if transition is not None:
                    self._overload_transition(transition)
        with _trace.span("serve.admit"):
            if admit:
                sched.plan_admissions()
            # ONE prefill pass per iteration over every prefilling slot —
            # newly admitted ones AND chunked prefills carried from
            # earlier iterations (they advance even under admit=False: a
            # draining engine must finish admitted work). With chunking
            # off and no prefix cache this reproduces the pre-ISSUE-15
            # groups exactly.
            groups = self._plan_prefill_groups()
        for gi, group in enumerate(groups):
            try:
                self._run_prefill(group)
            except DecodeWatchdogError:
                # every not-yet-prefilled state of this plan — the
                # tripped group AND any planned after it — holds a
                # slot but produced no token; un-admit them all in
                # one batch (admission order restored: groups are
                # bucketed by length, not arrival) or the retried
                # step() would decode slots with nothing to feed.
                # A mid-chunk state loses its chunk progress and
                # re-prefills from the queue — token-exact.
                pending = [st for g in groups[gi:] for st in g.states]
                pending.sort(key=lambda st: (st.admitted_t,
                                             st.request.request_id))
                sched.rollback_admission(pending)
                for st in pending:
                    self._trace_requeue(st, "watchdog_rollback")
                raise
        n_active = 0
        if self._decodable():
            if self._spec_k > 0:
                # drafts staged BEFORE the capacity pass so the verify
                # window's K/V writes land in real pages, never scratch
                self._stage_drafts()
            for st in sched.ensure_decode_capacity():
                # recompute-preemption: back to the queue with the SAME
                # trace — the span tree shows the second residency
                self._trace_requeue(st, "preemption")
            # one decode/verify dispatch per live weights epoch: a
            # single batch (the live tree) outside a swap transition
            for params, pairs in self._epoch_batches(self._decodable()):
                n_active += len(pairs)
                if any(st.draft for _, st in pairs):
                    self._run_verify(pairs, params)
                else:
                    self._run_decode(pairs, params)
        with _trace.span("serve.publish"):
            if self._retired:
                self._retire_unreferenced()
            self._publish_gauges()
        sp.set(n_active=n_active, n_groups=len(groups))
        return sched.has_work

    def _note_stall(self, sp) -> None:
        """A step longer than max(1 s, 5 x the ring's median step)
        (``trace.stalled``): one flight-recorder event with the step's
        phase table, and a count by the phase it sat in
        (``trace.stall_phase``). Traced or not: the stall gets a name in
        every run."""
        took = sp.t1 - sp.t0
        durs = sorted(r[2] - r[1] for r in _trace.spans(name="serve.step"))
        median = durs[len(durs) // 2]
        if not _trace.stalled(took, median):
            return
        root = (sp.name, sp.t0, sp.t1, sp.span_id)
        recs = [r for r in _trace.spans(since=sp.t0) if r[5] == sp.step]
        table = _trace.phase_table(root, recs)
        phase = _trace.stall_phase(root, recs)
        get_registry().counter(
            "serve_step_stalls_total",
            "engine steps longer than max(1 s, 5 x the median step), by "
            "the phase the step sat in").inc(phase=phase)
        self._flight_event(
            "serve_step_stall", step=sp.step, seconds=round(took, 4),
            median_step_s=round(median, 4), phase=phase,
            phases_ms={k: round(v * 1e3, 3) for k, v in table.items()},
            **(sp.attrs or {}))

    def _decodable(self) -> List[Tuple[int, RequestState]]:
        """Active slots that take a decode/verify row this iteration —
        chunked prefills still mid-prompt do not."""
        return [(slot, st) for slot, st in self.scheduler.active()
                if not st.prefilling]

    def _trace_requeue(self, st: RequestState, reason: str) -> None:
        """A request lost its slot but lives on (recompute-preemption,
        watchdog rollback): close the open admitted span and open a new
        queued one — the trace context SURVIVES, same trace_id."""
        tr = st.trace
        if tr is None:
            return
        now = self.clock()
        spn = st.trace_spans.pop("admitted", None)
        if spn is not None:
            tr.end_span(spn, t=now, requeued=reason)
        # a never-prefilled state (watchdog rollback of a later group)
        # still holds its ORIGINAL open queued span — close it, or the
        # overwrite below would leak it open forever
        old_q = st.trace_spans.pop("queued", None)
        if old_q is not None:
            tr.end_span(old_q, t=now, requeued=reason)
        st.trace_spans["queued"] = tr.start_span(
            "queued", t=now, reason=reason,
            preemptions=st.preemptions)

    def _overload_transition(self, transition: str) -> None:
        reg = get_registry()
        on = transition == "enter"
        reg.gauge("serve_overload",
                  "1 while the queue-delay overload detector is "
                  "tripped (new submits are shed)").set(float(on))
        reg.counter("serve_overload_transitions_total",
                    "overload detector state changes").inc(
            state=transition)
        self._flight_event("overload", state=transition,
                           ewma_s=round(self._overload.ewma_s, 4),
                           threshold_s=self._overload.threshold_s,
                           queue_depth=self.scheduler.queue_depth)

    def _guarded_dispatch(self, kind: str, prog, args,
                          hang: bool = False):
        """Run one serving dispatch under the wall-clock watchdog
        (``FLAGS_serve_watchdog_s``; modeled on the eager-collective
        watchdog). Flag unset and no chaos hang = direct call, zero
        overhead. On a trip the hung thread is abandoned and the caller
        gets a structured :class:`DecodeWatchdogError` plus a
        flight-recorder dump — never a silent stall."""
        from ..core.flags import get_flag
        timeout_s = float(get_flag("serve_watchdog_s") or 0.0)
        if timeout_s <= 0.0 and not hang:
            return prog(*args)
        if hang and timeout_s <= 0.0:
            raise RuntimeError(
                "chaos site 'serve.decode.hang' fired but "
                "FLAGS_serve_watchdog_s is unset — set a watchdog "
                "budget so the hang can be converted into "
                "DecodeWatchdogError (the path this site exercises)")

        def job():
            if hang:
                # host-side hang BEFORE the dispatch: the program
                # never runs, so a post-trip retry of the step is
                # safe (same positions, same K/V writes)
                chaos.hang_loop(max(timeout_s, 1.0) * 20 + 60.0)
            return prog(*args)

        # one long-lived dispatcher thread serves every guarded
        # dispatch; only a trip abandons it (stuck in the hung
        # program) and costs the next dispatch a fresh worker
        worker = self._watchdog_worker
        if worker is None or not worker.usable:
            worker = DispatchWorker()
            self._watchdog_worker = worker
            self._watchdog_threads = [x for x in self._watchdog_threads
                                      if x.is_alive()]
            self._watchdog_threads.append(worker.thread)
        result = worker.dispatch(job, timeout_s)
        if result is None:
            # /readyz reports the trip until a later guarded dispatch
            # succeeds — a replica whose chip is hanging must drop out
            # of the load balancer, not keep absorbing traffic
            self._watchdog_tripped = {
                "kind": kind, "timeout_s": timeout_s,
                "dispatch": self._dispatch_seq}
            n_active = len(self.scheduler.active())
            for _, st in self.scheduler.active():
                # tail-based sampling: every request aboard a tripped
                # dispatch is retained with its full span tree
                if st.trace is not None:
                    st.trace.mark_anomaly("watchdog",
                                          watchdog_kind=kind)
            # retry soundness: a donating program hands the live pools
            # to the abandoned dispatch (invalidated on its thread, or
            # mutated in place by a late zombie finish) — only a
            # non-donating program leaves the engine state untouched
            retry_safe = not getattr(prog, "donate_argnums", ())
            get_registry().counter(
                "serve_watchdog_trips_total",
                "serving dispatch watchdog trips").inc(kind=kind)
            self._flight_event("decode_watchdog", kind=kind,
                               timeout_s=timeout_s,
                               dispatch=self._dispatch_seq,
                               active_slots=n_active,
                               retry_safe=retry_safe)
            if self._flight_enabled():
                try:
                    _flight.trip_dump(step=self._dispatch_seq,
                                      reason="serve_watchdog",
                                      kind=kind, timeout_s=timeout_s)
                except Exception:
                    pass          # forensics must not mask the trip
            raise DecodeWatchdogError(kind, timeout_s,
                                      self._dispatch_seq, n_active,
                                      retry_safe=retry_safe)
        self._watchdog_tripped = None      # guarded dispatch returned:
        if "error" in result:              # the chip answers again
            raise result["error"]
        return result["value"]

    def _sampling_arrays(self, states: Sequence[Optional[RequestState]]):
        n = len(states)
        temps = np.ones((n,), np.float32)
        tks = np.zeros((n,), np.int32)
        tps = np.ones((n,), np.float32)
        for i, st in enumerate(states):
            if st is None:
                continue
            s = st.request.sampling
            temps[i], tks[i], tps[i] = s.temperature, s.top_k, s.top_p
        return jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps)

    def _plan_prefill_groups(self) -> List[AdmissionGroup]:
        """Group every prefilling slot's NEXT chunk into bucketed
        dispatches. Chunking off + no prefix cache ⇒ every prefilling
        state is freshly admitted with its whole effective prompt as
        the one chunk — the exact pre-ISSUE-15 grouping (same buckets,
        same dispatch count, byte-identical traffic). Chunk length is
        ``min(FLAGS_serve_prefill_chunk, remaining)``; under
        ``config.prefill_token_budget`` only the oldest admissions'
        chunks that fit it are planned; groups are keyed
        by (needs-context, length bucket) because a chunk at pos > 0
        must run the context program while pos == 0 chunks keep the
        bit-compatible plain one."""
        by_key: Dict[Tuple[int, bool, int], List[RequestState]] = {}
        states = [st for _, st in self.scheduler.active() if st.prefilling]
        budget = self.config.prefill_token_budget
        if budget > 0:
            states.sort(key=lambda s: (s.admitted_t, s.request.request_id))
            used = 0
            for n, st in enumerate(states):
                used += self._chunk_len(st)
                if n and used > budget:
                    states = states[:n]
                    break
        for st in states:
            clen = self._chunk_len(st)
            # keyed by weights epoch too (ISSUE 20): a mid-chunk prefill
            # carried across a cutover must keep its own tree, so it
            # can't share a dispatch with new-epoch admissions. The
            # epoch is constant outside a swap transition — identical
            # grouping and ordering to the pre-lifecycle planner.
            ep = st.weights_epoch
            key = (self._weights_epoch if ep is None else ep,
                   st.prefill_pos > 0, self.buckets.len_bucket(clen))
            by_key.setdefault(key, []).append(st)
        groups: List[AdmissionGroup] = []
        for ep, ctx, lb in sorted(by_key):
            sts = sorted(by_key[(ep, ctx, lb)],
                         key=lambda s: (s.admitted_t,
                                        s.request.request_id))
            mb = self.buckets.max_batch
            for i in range(0, len(sts), mb):
                chunk = sts[i:i + mb]
                groups.append(AdmissionGroup(
                    lb, self.buckets.batch_bucket(len(chunk)), chunk))
        return groups

    def _chunk_len(self, st: RequestState) -> int:
        """Tokens of ``st``'s prompt its next prefill dispatch takes."""
        remaining = st.prefill_len - st.prefill_pos
        return min(self._chunk, remaining) if self._chunk > 0 \
            else remaining

    def _run_prefill(self, group: AdmissionGroup) -> None:
        nb, sp = group.batch_bucket, group.len_bucket
        # chunk: the tokens each row prefills now; ctx: the positions
        # already in its pages (the context a chunked prefill, a prefix
        # hit or a re-prefill attends over)
        with _trace.span("serve.prefill", nb=nb, sp=sp,
                         chunk=tuple(self._chunk_len(st)
                                     for st in group.states),
                         ctx=tuple(st.prefill_pos for st in group.states)):
            self._prefill_group(group, nb, sp)

    def _prefill_group(self, group: AdmissionGroup, nb: int,
                       sp: int) -> None:
        with _trace.span("serve.prefill.build"):
            states: List[Optional[RequestState]] = list(group.states)
            states += [None] * (nb - len(states))
            ids = np.zeros((nb, sp), np.int32)
            lens = np.ones((nb,), np.int32)
            pos = np.zeros((nb,), np.int32)
            ctx = any(st is not None and st.prefill_pos > 0
                      for st in states)
            chunked = False
            # padded rows map to None -> an all-scratch table row (their
            # K/V writes must never land in a live slot's pages)
            rows: List[Optional[int]] = [None] * nb
            for i, st in enumerate(states):
                if st is None:
                    continue
                eff = st.effective_prompt()
                clen = self._chunk_len(st)
                chunked = chunked or \
                    clen < st.prefill_len - st.prefill_pos
                # COW contract: writes start at prefill_pos, which is
                # never below the shared-prefix coverage — a shared page
                # is read-only for this slot by construction
                assert st.prefill_pos >= (
                    self.cache.slot_shared_blocks(st.slot)
                    * self.cache.block_size)
                ids[i, :clen] = eff[st.prefill_pos:st.prefill_pos + clen]
                lens[i] = clen
                pos[i] = st.prefill_pos
                rows[i] = st.slot
            self._advance_windows(
                (st.slot, int(pos[i]), int(lens[i]))
                for i, st in enumerate(states) if st is not None)
            self._count_state_rows(
                pos[[i for i, st in enumerate(states) if st is not None]],
                "prefill_ctx")
            t0 = self.clock()
            if self._t_first_work is None:
                self._t_first_work = t0
            # stamp each residency's weights epoch at its FIRST chunk:
            # the KV this dispatch writes belongs to that tree, and every
            # later chunk/decode of the residency must keep using it
            # across a hot swap (groups are epoch-homogeneous by
            # construction)
            for st in group.states:
                if st.weights_epoch is None:
                    st.weights_epoch = self._weights_epoch
            params = self._params_for(group.states[0].weights_epoch)
            for st in group.states:
                tr = st.trace
                if tr is not None and "admitted" not in st.trace_spans:
                    # queued ends / admitted opens at the scheduler's
                    # admission stamp, not dispatch time — queueing delay
                    # and prefill wait attribute to the right spans (a
                    # chunked prefill opens them at its FIRST chunk only)
                    qs = st.trace_spans.pop("queued", None)
                    if qs is not None:
                        tr.end_span(qs, t=st.admitted_t)
                    st.trace_spans["admitted"] = tr.start_span(
                        "admitted", t=st.admitted_t, slot=st.slot,
                        prefix_hit_tokens=st.prefill_pos)
            if ctx:
                prog = self._get_prefill_ctx(nb, sp)
                args = (params, self.cache.pool_args(),
                        self.cache.table_array(rows), jnp.asarray(ids),
                        jnp.asarray(lens), jnp.asarray(pos),
                        self._next_key())
            else:
                prog = self._get_prefill(nb, sp)
                args = (params, self.cache.pool_args(),
                        self.cache.table_array(rows), jnp.asarray(ids),
                        jnp.asarray(lens), self._next_key())
            temps, tks, tps = self._sampling_arrays(states)
            args += (temps, tks, tps, self._poison_array(states)) \
                + self._lora_args(states)
        # a DecodeWatchdogError here propagates to step(), which rolls
        # back every not-yet-prefilled state of the plan (token-exact:
        # the tripped dispatch's pool writes died with its thread)
        with _trace.span("serve.prefill.dispatch"):
            toks, ok, pools = self._guarded_dispatch(
                "prefill", prog, args)
            self.cache.update(*pools)
        with _trace.span("serve.prefill.readback"):
            toks = np.asarray(toks)
            ok = np.asarray(ok)
        with _trace.span("serve.prefill.accept"):
            now = self.clock()
            self._stats["prefill_dispatches"] += 1
            if chunked or self._chunk > 0:
                self._stats["prefill_chunks"] += len(group.states)
                get_registry().counter(
                    "serve_prefill_chunks_total",
                    "chunked-prefill chunk rows dispatched"
                ).inc(len(group.states))
            reg = get_registry()
            reg.histogram("serve_prefill_seconds",
                          "prefill dispatch wall time").observe(
                now - t0, bucket=f"b{nb}_s{sp}")
            for i, st in enumerate(states):
                if st is None:
                    continue
                clen = int(lens[i])
                st.prefill_pos += clen
                self._stats["prefill_tokens"] += clen
                final = st.prefill_pos >= st.prefill_len
                tr = st.trace
                if tr is not None:
                    tr.end_span(tr.start_span(
                        "prefill", parent=st.trace_spans.get("admitted"),
                        t=t0, bucket=f"b{nb}_s{sp}", pos=int(pos[i]),
                        tokens=clen), t=now)
                if not ok[i]:
                    self.scheduler.fail(st, "non-finite logits at prefill")
                    continue
                if final:
                    self._accept_token(st, int(toks[i]), now)

    def _poison_array(self, states: Sequence[Optional[RequestState]]):
        """[n] f32 additive logits poison: all zeros (bit-transparent)
        unless chaos marked a request, whose row turns NaN."""
        poison = np.zeros((len(states),), np.float32)
        for i, st in enumerate(states):
            if st is not None and st.poisoned:
                poison[i] = np.nan
        return jnp.asarray(poison)

    def _decode_table(self, per_slot: Sequence[Optional[RequestState]]):
        """Block-table argument for a decode/verify dispatch: only the
        DECODABLE slots' real rows; every other row — inactive slots
        AND mid-chunk prefilling slots, which hold live (possibly
        COW-shared) pages but take no decode row — is all-scratch, so
        the dispatch's unconditional per-row K/V scatter (pos 0, token
        0 for masked rows) can never land in a resident page. Without
        chunked prefill every resident slot is decodable and this is
        exactly ``table_array()`` (bit-identical args)."""
        return self.cache.table_array(
            [st.slot if st is not None else None for st in per_slot])

    def _stage_drafts(self) -> None:
        """Prompt-lookup drafting (ISSUE 15): propose up to ``k`` draft
        tokens per decodable slot from its own history — greedy slots
        verify by argmax match, sampled slots by stochastic residual
        acceptance (ISSUE 16). Zero drafts everywhere ⇒ the iteration
        falls through to the plain decode program — the drafter costs
        nothing when traffic has no self-repetition."""
        from .spec_decode import propose_ngram
        proposed = 0
        for _, st in self._decodable():
            st.draft = []
            budget = min(self._spec_k, st.remaining_new_tokens() - 1)
            if budget <= 0:
                continue
            hist = np.concatenate([
                st.request.prompt,
                np.asarray(st.generated, np.int32)])
            st.draft = [int(t) for t in propose_ngram(
                hist, budget, max_ngram=self._spec_ngram)]
            proposed += len(st.draft)
        if proposed:
            self._stats["spec_proposed"] += proposed
            get_registry().counter(
                "serve_spec_proposed_total",
                "speculative draft tokens proposed").inc(proposed)

    def _run_verify(self, pairs, params) -> None:
        """ONE batched verify dispatch over the given decodable slots
        (one epoch's worth — all of them outside a swap transition):
        row 0 is each slot's plain decode step; rows 1..k score the
        staged drafts. The accepted prefix plus one bonus token commit
        (greedy-exact vs the non-speculative path); the rejected tail's
        pages roll back by block-table truncation."""
        with _trace.span("serve.verify", n_active=len(pairs)):
            self._verify_batch(pairs, params)

    def _verify_batch(self, pairs, params) -> None:
        with _trace.span("serve.verify.build"):
            B = self.config.max_batch_slots
            S = self._spec_k + 1
            pos = np.zeros((B,), np.int32)
            ids = np.zeros((B, S), np.int32)
            active = np.zeros((B,), bool)
            per_slot: List[Optional[RequestState]] = [None] * B
            for slot, st in pairs:
                pos[slot] = st.seq_len - 1
                ids[slot, 0] = st.generated[-1]
                n = len(st.draft)
                if n:
                    ids[slot, 1:1 + n] = st.draft
                active[slot] = True
                per_slot[slot] = st
            n_active = int(active.sum())
            t0 = self.clock()
            prog = self._get_verify()
            temps, tks, tps = self._sampling_arrays(per_slot)
            hang = chaos.active() and chaos.probe("serve.decode.hang")
            args = (params, self.cache.pool_args(),
                    self._decode_table(per_slot), jnp.asarray(pos),
                    jnp.asarray(ids), jnp.asarray(active),
                    self._next_key(), temps, tks, tps,
                    self._poison_array(per_slot)) \
                + self._lora_args(per_slot)
        with _trace.span("serve.verify.dispatch"):
            tok0, greedy, ok_rows, p_draft, tok_full, tok_resid, pools = \
                self._guarded_dispatch("verify", prog, args, hang=hang)
            self.cache.update(*pools)
        with _trace.span("serve.verify.readback"):
            tok0 = np.asarray(tok0)
            greedy = np.asarray(greedy)
            ok_rows = np.asarray(ok_rows)
            p_draft = np.asarray(p_draft)
            tok_full = np.asarray(tok_full)
            tok_resid = np.asarray(tok_resid)
        with _trace.span("serve.verify.accept"):
            now = self.clock()
            dt = now - t0
            st_ = self._stats
            st_["decode_dispatches"] += 1
            st_["verify_dispatches"] += 1
            st_["decode_slot_steps"] += n_active
            st_["decode_batch_max"] = max(st_["decode_batch_max"], n_active)
            self._observe("decode_step", dt)
            reg = get_registry()
            reg.histogram("serve_decode_step_seconds",
                          "decode dispatch wall time (all slots)").observe(dt)
            reg.histogram("serve_decode_occupancy",
                          "active slots per decode dispatch",
                          buckets=tuple(range(1, B + 1))).observe(n_active)
            accepted = rolled_back = 0
            for slot, st in [(s, x) for s, x in enumerate(per_slot)
                             if x is not None]:
                n = len(st.draft)
                tr = st.trace
                if tr is not None:
                    tr.end_span(tr.start_span(
                        f"verify[{len(st.generated)}]",
                        parent=st.trace_spans.get("admitted"), t=t0,
                        batch=n_active, proposed=n), t=now)
                if not ok_rows[slot, 0]:
                    st.draft = []
                    self.scheduler.fail(st, "non-finite logits at decode")
                    continue
                sampled = st.request.sampling.temperature > 0.0
                if not sampled:
                    # greedy acceptance: draft i survives iff it equals the
                    # verifier's argmax at the previous row AND that row's
                    # logits are finite (pad/garbage rows never commit)
                    n_acc = 0
                    while n_acc < n and ok_rows[slot, n_acc] \
                            and st.draft[n_acc] == int(greedy[slot, n_acc]):
                        n_acc += 1
                    commit = [int(tok0[slot])] + \
                        [int(greedy[slot, i]) for i in range(1, n_acc + 1)
                         if ok_rows[slot, i]]
                else:
                    # stochastic acceptance (ISSUE 16), point-mass drafter:
                    # accept draft i with probability p_i(d_i) under row i's
                    # filtered sampling distribution; on reject commit the
                    # device's residual redraw (row i with d_i masked out)
                    # and stop; on a clean sweep commit the bonus sample
                    # from row n. Marginally identical to plain sampled
                    # decode at every committed position.
                    commit = []
                    n_acc = 0
                    for i in range(n):
                        if not ok_rows[slot, i]:
                            break
                        if self._spec_rng.random() < float(p_draft[slot, i]):
                            commit.append(int(st.draft[i]))
                            n_acc += 1
                        else:
                            commit.append(int(tok_resid[slot, i]))
                            break
                    else:
                        if n == 0:
                            commit.append(int(tok0[slot]))
                        elif ok_rows[slot, n]:
                            commit.append(int(tok_full[slot, n]))
                committed = 0
                for t in commit:
                    self._accept_token(st, t, now)
                    committed += 1
                    if st.terminal or st.is_done():
                        break
                acc = min(n_acc, committed) if sampled \
                    else max(0, committed - 1)
                accepted += acc
                rolled_back += n - acc
                st.draft = []
                if not st.terminal:
                    # block-table truncation: pages holding only the
                    # rejected tail's K/V leave the table now (_accept_token
                    # already finished any done request — its pages went
                    # back wholesale through _terminate)
                    self.cache.truncate_slot(st.slot, st.seq_len)
            if accepted:
                st_["spec_accepted"] += accepted
                reg.counter("serve_spec_accepted_total",
                            "speculative draft tokens accepted and "
                            "committed").inc(accepted)
            if rolled_back:
                st_["spec_rolled_back"] += rolled_back
                reg.counter("serve_spec_rolled_back_total",
                            "speculative draft tokens rejected and rolled "
                            "back by block-table truncation").inc(
                    rolled_back)

    def _advance_windows(self, moves) -> None:
        """``(slot, pos, n)`` a slot whose next program writes positions
        ``pos .. pos+n-1``: the window page lifetimes free what no later
        query reaches and cover what is written (``cache.advance``).
        Nothing, not a span, for a model whose pages all live as long as
        their slots."""
        if not self.cache.windows:
            return
        with _trace.span("serve.kv.release") as sp:
            freed = sum(self.cache.advance(*m) for m in moves)
            sp.set(freed=freed)
        if freed:
            # emits-metrics: serve_kv_window_pages_freed_total
            self._count("serve_kv_window_pages_freed_total", freed,
                        "pages of a window lifetime freed because their "
                        "positions left every later query's reach")

    def _count(self, name: str, n: float, doc: str, **labels) -> None:
        """One count into the registry and into ``_stats
        ["model_counters"]`` under the series' name, as
        :meth:`_count_model_stats` keeps what the model counted."""
        get_registry().counter(name, doc).inc(n, **labels)
        series = name + ("{" + ",".join(
            f"{k}={v}" for k, v in labels.items()) + "}" if labels else "")
        totals = self._stats.setdefault("model_counters", {})
        totals[series] = totals.get(series, 0) + n

    def _count_window_reads(self, pos: np.ndarray, sp) -> None:
        """What a decode step over ``pos`` (the active slots') holds and
        has to read in each page lifetime, host arithmetic: into the
        counters and onto the ``serve.decode`` span."""
        bs = self.cache.block_size
        whole = pos // bs + 1
        held = {"slot": int(whole.sum())}
        read = {"slot": int((pos + 1).sum())}
        for w in self.cache.windows:
            first = np.maximum(pos - w.window + 1, 0)
            held["window"] = held.get("window", 0) \
                + int((whole - first // bs).sum())
            read["window"] = read.get("window", 0) \
                + int((pos + 1 - first).sum())
        # emits-metrics: serve_kv_pages_live_total, serve_kv_pages_unwindowed_total, serve_attn_read_positions_total
        for life, n in held.items():
            self._count("serve_kv_pages_live_total", n,
                        "pages the active slots of decode steps hold, by "
                        "page lifetime", lifetime=life)
        self._count("serve_kv_pages_unwindowed_total",
                    held["slot"] * len(self.cache.windows),
                    "pages the window lifetimes would hold if nothing were "
                    "freed (ceil((pos + 1) / block_size) a slot)")
        for life, n in read.items():
            self._count("serve_attn_read_positions_total", n,
                        "positions a decode step's kernels have to read in "
                        "a layer of that page lifetime", lifetime=life)
        sp.set(read_full=read["slot"], read_window=read["window"])

    def _count_latent_reads(self, pos: np.ndarray, sp) -> None:
        """What a decode step over ``pos`` (the active slots') has to
        read of a latent pool that attention sweeps DENSELY: every
        cached position, ``pos + 1`` a slot a layer; host arithmetic,
        into the counter and onto the ``serve.decode`` span."""
        read = int((pos + 1).sum())
        # emits-metrics: serve_attn_read_positions_total
        self._count("serve_attn_read_positions_total", read,
                    "positions a decode step's kernels have to read in "
                    "a layer of that page lifetime", lifetime="slot")
        sp.set(read_latent=read)

    def _count_state_rows(self, pos: np.ndarray, program: str) -> None:
        """The rows of a program over a model's state kinds, by where
        their state came from, host arithmetic on their first positions
        ``pos``: a row at position 0 began from a fresh state (it reads
        zeros, whatever its slot held), a row past it from the state its
        slot carried (``program``: ``prefill_ctx`` or ``decode``, the
        programs such a row runs in). Nothing for a model whose state is
        all in pages."""
        if not self.cache.state_kinds:
            return
        fresh = int((pos == 0).sum())
        carried = len(pos) - fresh
        # emits-metrics: serve_conv_state_fresh_total, serve_conv_state_carried_total
        if fresh:
            self._count("serve_conv_state_fresh_total", fresh,
                        "program rows that began from a fresh state "
                        "(position 0: the slot's state is not read)")
        if carried:
            self._count("serve_conv_state_carried_total", carried,
                        "program rows that began from the state their slot "
                        "carried, by program", program=program)

    def _run_decode(self, pairs, params) -> None:
        with _trace.span("serve.decode", n_active=len(pairs)) as sp:
            self._decode_batch(pairs, params, sp)

    def _decode_batch(self, pairs, params, sp) -> None:
        with _trace.span("serve.decode.build"):
            B = self.config.max_batch_slots
            pos = np.zeros((B,), np.int32)
            tokens = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            per_slot: List[Optional[RequestState]] = [None] * B
            for slot, st in pairs:
                # the newest generated token is not yet in the cache:
                # this step writes its K/V at position seq_len-1 and
                # attends over everything up to and including it
                pos[slot] = st.seq_len - 1
                tokens[slot] = st.generated[-1]
                active[slot] = True
                per_slot[slot] = st
            n_active = int(active.sum())
            # how much of the block table this step's attention walks:
            # a slot at position p owns p // block_size + 1 entries of
            # its table row (the paged-decode kernel sweeps those only)
            live_pages = int((pos[active] // self.cache.block_size + 1)
                             .sum())
            table_pages = n_active * self.cache.max_blocks_per_slot
            if self.cache.windows:
                self._advance_windows(
                    (slot, int(pos[slot]), 1) for slot, _ in pairs)
                self._count_window_reads(pos[active], sp)
            elif self._dense_latent:
                self._count_latent_reads(pos[active], sp)
            self._count_state_rows(pos[active], "decode")
            t0 = self.clock()
            prog = self._get_decode()
            temps, tks, tps = self._sampling_arrays(per_slot)
            hang = chaos.active() and chaos.probe("serve.decode.hang")
            args = (params, self.cache.pool_args(),
                    self._decode_table(per_slot), jnp.asarray(pos),
                    jnp.asarray(tokens), jnp.asarray(active),
                    self._next_key(), temps, tks, tps,
                    self._poison_array(per_slot)) \
                + self._lora_args(per_slot)
        with _trace.span("serve.decode.dispatch"):
            toks, ok, pools, stats = self._guarded_dispatch(
                "decode", prog, args, hang=hang)
            self.cache.update(*pools)
        with _trace.span("serve.decode.readback"):
            toks = np.asarray(toks)
            ok = np.asarray(ok)
            if stats is not None:
                stats = {k: np.asarray(a) for k, a in stats.items()}
        with _trace.span("serve.decode.accept"):
            now = self.clock()
            dt = now - t0
            st_ = self._stats
            st_["decode_dispatches"] += 1
            st_["decode_slot_steps"] += n_active
            st_["decode_batch_max"] = max(st_["decode_batch_max"],
                                          n_active)
            st_["decode_live_pages"] += live_pages
            st_["decode_table_pages"] += table_pages
            self._observe("decode_step", dt)
            reg = get_registry()
            reg.counter("serve_decode_live_pages_total",
                        "block-table entries the active slots of decode "
                        "steps own (pos // block_size + 1 a slot)"
                        ).inc(live_pages)
            reg.counter("serve_decode_table_pages_total",
                        "block-table entries of those slots' rows "
                        "(active slots x table width)").inc(table_pages)
            reg.histogram("serve_decode_step_seconds",
                          "decode dispatch wall time (all slots)"
                          ).observe(dt)
            reg.histogram("serve_decode_occupancy",
                          "active slots per decode dispatch",
                          buckets=tuple(range(1, B + 1))
                          ).observe(n_active)
            if stats is not None:
                self._count_model_stats(stats, active)
            for slot, st in list(pairs):
                tr = st.trace
                if tr is not None:
                    # decode[i]: this request's share of the batched
                    # decode dispatch that produced token i (i counts
                    # generated tokens; prefill produced token 0)
                    tr.end_span(tr.start_span(
                        f"decode[{len(st.generated)}]",
                        parent=st.trace_spans.get("admitted"), t=t0,
                        batch=n_active), t=now)
                if not ok[slot]:
                    self.scheduler.fail(st, "non-finite logits at decode")
                    continue
                self._accept_token(st, int(toks[slot]), now)

    def _count_model_stats(self, stats: Dict[str, np.ndarray],
                           active: np.ndarray) -> None:
        """What the model counted in a decode step, a row a slot, into
        the registry and ``_stats["model_counters"]``, the active slots'
        rows only. A key is a counter's name; ``name:label`` with a
        ``[B, K]`` array is one series a column, labelled ``label=k``
        (``serve_moe_routed_tokens_total:expert``); ``name#nonzero``
        with a ``[B, K]`` array counts the columns the active rows give
        anything (``serve_moe_experts_read_total#nonzero``: the experts
        given a pair, a column an expert of each layer)."""
        reg = get_registry()
        totals = self._stats.setdefault("model_counters", {})
        for key, arr in stats.items():
            name, nonzero, _ = key.partition("#nonzero")
            name, _, label = name.partition(":")
            col = arr[active].sum(axis=0)
            if nonzero:
                col = np.count_nonzero(col)
            counter = reg.counter(name, "counted by the model in decode "
                                        "steps, active slots only")
            if label:
                for i, n in enumerate(col.tolist()):
                    if n:
                        counter.inc(n, **{label: str(i)})
                        series = f"{name}{{{label}={i}}}"
                        totals[series] = totals.get(series, 0) + n
            else:
                counter.inc(float(col))
                totals[name] = totals.get(name, 0) + float(col)

    def _accept_token(self, st: RequestState, token: int,
                      now: float) -> None:
        tr = st.trace
        # histogram exemplars: a latency bucket links to the concrete
        # trace that landed in it (None = no-op, the pre-trace path)
        ex = tr.trace_id if tr is not None else None
        first = st.first_token_t is None
        if first:
            st.first_token_t = now
            ttft = now - st.submitted_t
            self._observe("ttft", ttft)
            get_registry().histogram(
                "serve_ttft_seconds",
                "submit -> first token latency").observe(
                ttft, exemplar=ex)
        st.generated.append(token)
        self._stats["tokens_generated"] += 1
        self._t_last_token = now
        get_registry().counter(
            "serve_tokens_generated_total",
            "tokens sampled across all requests").inc()
        req = st.request
        det_sp = None
        if tr is not None and (req.on_token is not None
                               or req.stop is not None
                               or self.config.detokenizer is not None):
            det_sp = tr.start_span("detok", t=self.clock(),
                                   parent=st.trace_spans.get("admitted"))
        try:
            if chaos.active() and chaos.probe("serve.detok.raise"):
                raise chaos.ChaosFault("serve.detok.raise")
            if req.on_token is not None:
                text = None
                if self.config.detokenizer is not None:
                    text = self.config.detokenizer.piece(
                        token, is_first=len(st.generated) == 1)
                req.on_token(req, token, text)
            if req.stop is not None and req.stop(list(st.generated)):
                st.stop_hit = True
        except Exception as e:
            # fault isolation: a raising detokenizer / client callback /
            # malformed stop condition fails ONLY this request — the
            # rest of the batch streams on
            if det_sp is not None:
                tr.end_span(det_sp, t=self.clock(), error=repr(e))
            self.scheduler.fail(
                st, f"detokenizer/callback error: {e!r}")
            return
        if det_sp is not None:
            tr.end_span(det_sp, t=self.clock())
        if st.is_done():
            self.scheduler.finish(st)
            e2e = now - st.submitted_t
            self._observe("e2e", e2e)
            n = len(st.generated)
            if n > 1 and st.first_token_t is not None:
                tpot = (now - st.first_token_t) / (n - 1)
                self._observe("tpot", tpot)
                get_registry().histogram(
                    "serve_tpot_seconds",
                    "mean per-token decode latency per request"
                ).observe(tpot, exemplar=ex)
            reg = get_registry()
            reg.histogram("serve_e2e_seconds",
                          "submit -> completion latency").observe(
                e2e, exemplar=ex)
            if st.deadline_t is not None:
                slack = st.deadline_t - now
                reg.histogram(
                    "serve_deadline_slack_seconds",
                    "deadline minus completion time for deadline-"
                    "carrying requests (negative = finished late)",
                    buckets=self.DEADLINE_SLACK_BUCKETS).observe(
                    slack, exemplar=ex)
                if self._slo_deadline is not None:
                    self._slo_deadline.record(
                        good=1 if slack >= 0 else 0,
                        bad=0 if slack >= 0 else 1)
                    self._slo_deadline.publish()

    def _publish_gauges(self) -> None:
        reg = get_registry()
        reg.gauge("serve_queue_depth",
                  "requests waiting for a batch slot").set(
            self.scheduler.queue_depth)
        reg.gauge("serve_active_slots", "requests holding a batch slot"
                  ).set(len(self.scheduler.active()))
        reg.gauge("serve_kv_pages_in_use",
                  "allocated KV pages (of the shared pool)").set(
            self.cache.allocator.pages_in_use)
        if self.cache.quant:
            # emits-metrics: serve_kv_quant_bytes_per_token
            reg.gauge(
                "serve_kv_quant_bytes_per_token",
                "HBM bytes per cached token position under "
                "FLAGS_serve_kv_quant (int8 pages + f32 per-head "
                "scales)").set(float(self.cache.kv_bytes_per_token()))
        if self.scheduler.tenant_quota is not None:
            # delta-publish the per-tenant quota deferrals (prefix-
            # metrics convention: scheduler counts, engine publishes)
            for tenant, n in self.scheduler.tenant_deferrals.items():
                delta = n - self._quota_published.get(tenant, 0)
                if delta > 0:
                    # emits-metrics: serve_tenant_quota_deferrals_total
                    reg.counter(
                        "serve_tenant_quota_deferrals_total",
                        "admissions deferred by the per-tenant slot "
                        "quota").inc(delta, tenant=tenant)
                    self._quota_published[tenant] = n
        if self.prefix_cache is not None:
            self._publish_prefix_metrics(reg)

    def _publish_prefix_metrics(self, reg) -> None:
        """Delta-publish the prefix cache's host-side stats (the cache
        itself never touches the registry — recsys tier convention).
        Flag off ⇒ this is never called: zero new series."""
        pc = self.prefix_cache
        reg.gauge("serve_prefix_cached_pages",
                  "KV pages resident in the radix prefix cache").set(
            pc.cached_pages)
        for stat, name, help_ in (
                ("hits", "serve_prefix_hits_total",
                 "admissions that matched a cached prefix"),
                ("misses", "serve_prefix_misses_total",
                 "admissions with no cached prefix"),
                ("hit_tokens", "serve_prefix_hit_tokens_total",
                 "prompt tokens served from cached pages instead of "
                 "prefill"),
                ("evicted_pages", "serve_prefix_evicted_pages_total",
                 "cached pages evicted under allocation pressure")):
            delta = pc.stats[stat] - self._prefix_published.get(stat, 0)
            if delta > 0:
                # emits-metrics: serve_prefix_hits_total, serve_prefix_misses_total
                # emits-metrics: serve_prefix_hit_tokens_total, serve_prefix_evicted_pages_total
                reg.counter(name, help_).inc(delta)
                self._prefix_published[stat] = pc.stats[stat]

    # -- observability -------------------------------------------------------
    def stats(self) -> dict:
        d = dict(self._stats)
        d.update(self.scheduler.stats)
        d["programs"] = dict(self._programs_info)
        d["resident_programs"] = len(self._programs)
        d["queue_depth"] = self.scheduler.queue_depth
        d["active_slots"] = len(self.scheduler.active())
        d["kv_pages_in_use"] = self.cache.allocator.pages_in_use
        return d

    def metrics_summary(self) -> dict:
        """Host-side latency/throughput summary (exact percentiles over
        the raw per-request samples)."""

        def pct(xs, q):
            return float(np.percentile(np.asarray(xs), q)) if xs else None

        elapsed = None
        if self._t_first_work is not None and \
                self._t_last_token is not None:
            elapsed = max(self._t_last_token - self._t_first_work, 1e-9)
        lat = self._lat
        sstats = self.scheduler.stats
        return {
            "requests_completed": sstats["completed"],
            "requests_submitted": sstats["submitted"],
            "requests_expired": sstats["expired"],
            "requests_expired_queued": sstats["expired_queued"],
            "requests_shed": sstats["shed"],
            "requests_cancelled": sstats["cancelled"],
            "requests_failed": sstats["failed"],
            "requests_drained": sstats["drained"],
            "preemptions": self.scheduler.stats["preemptions"],
            "tokens_generated": self._stats["tokens_generated"],
            "elapsed_s": elapsed,
            "tokens_per_sec": (self._stats["tokens_generated"] / elapsed
                               if elapsed else None),
            "ttft_p50_s": pct(lat["ttft"], 50),
            "ttft_p99_s": pct(lat["ttft"], 99),
            "tpot_p50_s": pct(lat["tpot"], 50),
            "tpot_p99_s": pct(lat["tpot"], 99),
            "decode_step_p50_s": pct(lat["decode_step"], 50),
            "decode_step_p99_s": pct(lat["decode_step"], 99),
            "decode_dispatches": self._stats["decode_dispatches"],
            "mean_decode_occupancy": (
                self._stats["decode_slot_steps"]
                / self._stats["decode_dispatches"]
                if self._stats["decode_dispatches"] else None),
            # share of the decode steps' block-table rows that was live
            "serve_decode_live_pages_total":
                self._stats["decode_live_pages"],
            "serve_decode_table_pages_total":
                self._stats["decode_table_pages"],
            "ttft_p99_s": pct(lat["ttft"], 99),
            "prefill_tokens": self._stats["prefill_tokens"],
            "prefill_chunks": self._stats["prefill_chunks"],
            "verify_dispatches": self._stats["verify_dispatches"],
            # prefix hit rate: share of prompt positions served from
            # cached pages instead of prefill compute
            "prefix_hit_pct": (
                100.0 * self.prefix_cache.stats["hit_tokens"]
                / max(1, self.prefix_cache.stats["hit_tokens"]
                      + self._stats["prefill_tokens"])
                if self.prefix_cache is not None else None),
            "prefix_hit_tokens": (
                self.prefix_cache.stats["hit_tokens"]
                if self.prefix_cache is not None else 0),
            # draft acceptance: committed draft tokens per proposed
            "spec_accept_pct": (
                100.0 * self._stats["spec_accepted"]
                / self._stats["spec_proposed"]
                if self._stats["spec_proposed"] else None),
            "spec_proposed": self._stats["spec_proposed"],
            "spec_accepted": self._stats["spec_accepted"],
            "spec_rolled_back": self._stats["spec_rolled_back"],
            # multi-tenant serving (ISSUE 17)
            "kv_bytes_per_token": self.cache.kv_bytes_per_token(),
            "kv_quant": self.cache.quant or None,
            "lora_adapters_loaded": (self.lora.num_loaded
                                     if self.lora is not None else 0),
            "lora_swaps": (self.lora.swaps
                           if self.lora is not None else 0),
            "quota_deferred": sstats.get("quota_deferred", 0),
            # model lifecycle (ISSUE 20)
            "weights_epoch": self._weights_epoch,
            "weight_swaps": self._swap_stats["cutover"],
            "weight_swaps_refused": self._swap_stats["refused"],
            "weight_swap_rollbacks": self._swap_stats["rolled_back"],
        }

    def shutdown(self) -> None:
        """Drop compiled programs, cache pools, the drain latch (signal
        handlers restored), admin-plane registrations and any live
        watchdog threads (test isolation / explicit teardown)."""
        self._detach_admin()
        if self._drain_latch is not None:
            self._drain_latch.close()
            self._drain_latch = None
        if self._watchdog_worker is not None:
            self._watchdog_worker.close()
            self._watchdog_worker = None
        if self._watchdog_threads:
            # a thread abandoned in a chaos hang exits as soon as the
            # hang is cancelled; one stuck in a real dispatch is daemon
            # and joins best-effort
            chaos.cancel_hangs()
            for t in self._watchdog_threads:
                t.join(timeout=0.5)
            self._watchdog_threads = []
            # this engine's teardown must not neutralize still-armed
            # hang sites for other live engines
            chaos.rearm_hangs()
        self._programs.clear()
        self.scheduler.waiting.clear()
        for slot, _ in list(self.scheduler.active()):
            self.cache.free_slot(slot)
            self.scheduler.slots[slot] = None
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
            self.cache.prefix_cache = None
            self.prefix_cache = None
        # unstage any half-loaded candidate tree and drop retained /
        # retired trees, clearing the epoch latch (ISSUE 20 fix): an
        # aborted swap must not leak a full param tree of device memory
        # into the next engine constructed in this process
        self._staged = None
        self._retired.clear()
        self._previous = None
        self.cache.pools = dict.fromkeys(self.cache.pools)
