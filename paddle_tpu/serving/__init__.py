"""paddle_tpu.serving — TPU-native inference serving runtime (ISSUE 6).

The path from a trained model to traffic (ROADMAP item 1, the
millions-of-users north star), built on two ideas from the serving
literature mapped onto static-shape XLA programs:

- **paged KV decode** (:mod:`.kv_cache`): block-structured K/V pools
  shared by all requests with per-slot block tables — the
  vLLM/PagedAttention memory model, generalized from ``StaticCache`` so
  it composes with scan-over-layers
  (``nn.scan.scan_layers_with_cache``, ``FLAGS_scan_decode``);
- **continuous batching** (:mod:`.scheduler`): iteration-level
  admission/eviction into fixed batch slots (Orca), with bucketed
  ``(batch, prefill_len)`` prefill shapes bounding the compile count
  and recompute-preemption when the page pool runs dry;
- the :class:`~.engine.ServingEngine` glues them behind AOT-compiled
  serving signatures (``jit.aot.AOTProgram``, the TrainStep machinery),
  streaming per-token callbacks and TTFT/TPOT/throughput metrics into
  the :mod:`paddle_tpu.monitor` registry;
- :mod:`.loadgen` is a synthetic open-loop driver that only tests call
  (the benchmark has its own, ``benchmark/harness/loadgen.py``);
- :mod:`.router` scales one engine to a fleet (ISSUE 16): a
  prefix-affine front-end over N replicas with telemetry-driven load
  balancing and chaos-proof drain/death migration;
- :mod:`.lifecycle` pushes new weights through that fleet with zero
  downtime (ISSUE 20): live hot-swap with per-slot weight epochs
  (:meth:`~.engine.ServingEngine.swap_weights`), shadow/A-B traffic
  splitting, and an SLO-guarded promote-or-rollback controller.

See docs/SERVING.md for architecture, bucketing policy, the flag
matrix and the fleet topology.
"""

from .detok import StreamingDetokenizer  # noqa: F401
from .engine import (ServingConfig, ServingEngine,  # noqa: F401
                     WeightSwapError)
from .lifecycle import (LifecycleConfig, LifecycleController,  # noqa: F401
                        TrafficSplit, assign_arm, should_shadow)
from .kv_cache import (BlockAllocator, ContextPagedCacheView,  # noqa: F401
                       ContextPagedLayerCache, PagedCacheView,
                       PagedKVCache, PagedLayerCache)
from .prefix_cache import RadixPrefixCache  # noqa: F401
from .spec_decode import propose_ngram  # noqa: F401
from .loadgen import (LoadSpec, TokenBucket, build_requests,  # noqa: F401
                      run_fleet_open_loop, run_open_loop)
from .router import FleetRouter, ReplicaHandle, RouterConfig  # noqa: F401
from .resilience import (DecodeWatchdogError, DrainLatch,  # noqa: F401
                         DrainReport, EngineDrained, OverloadDetector,
                         ServerOverloaded, load_drain_snapshot,
                         requests_from_snapshot, save_drain_snapshot)
from .sampling import (SamplingParams, filtered_logits,  # noqa: F401
                       sample_tokens)
from .scheduler import (TERMINAL_OUTCOMES, BucketTable,  # noqa: F401
                        Request, Scheduler)

__all__ = [
    "ServingConfig", "ServingEngine", "Request", "SamplingParams",
    "BucketTable", "Scheduler", "PagedKVCache", "PagedCacheView",
    "PagedLayerCache", "BlockAllocator", "StreamingDetokenizer",
    "LoadSpec", "TokenBucket", "build_requests", "run_open_loop",
    "ServerOverloaded", "EngineDrained", "DecodeWatchdogError",
    "DrainLatch", "DrainReport", "OverloadDetector",
    "save_drain_snapshot", "load_drain_snapshot",
    "requests_from_snapshot", "TERMINAL_OUTCOMES", "reset",
    "RadixPrefixCache", "propose_ngram", "ContextPagedCacheView",
    "ContextPagedLayerCache", "FleetRouter", "ReplicaHandle",
    "RouterConfig", "run_fleet_open_loop", "filtered_logits",
    "WeightSwapError", "TrafficSplit", "LifecycleConfig",
    "LifecycleController", "assign_arm", "should_shadow",
]


def reset() -> None:
    """Tear down process-global serving state (conftest autouse): shut
    down live engines — which restores any drain-latch signal handlers
    and joins/abandons live watchdog threads (their chaos hangs are
    cancelled first, so a hung worker cannot outlive its test) — then
    restart the request-id counter and clear the scan-fallback warn-once
    set + counter so fallback-telemetry assertions are
    order-independent."""
    from . import engine as _engine, scheduler as _scheduler
    from ..nn import scan as _scan
    for e in list(_engine._LIVE_ENGINES):
        try:
            e.shutdown()
        except Exception:
            pass
    _engine._LIVE_ENGINES.clear()
    _scheduler._reset_request_ids()
    _scan.SCAN_STATS["fallbacks"] = 0
    _scan._FALLBACK_WARNED.clear()
