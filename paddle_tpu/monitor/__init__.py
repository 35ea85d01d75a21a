"""paddle_tpu.monitor — unified training telemetry.

Time-domain pillars (ISSUE 3; see docs/OBSERVABILITY.md):

1. a structured **metrics registry** (:mod:`.metrics`): thread-safe
   Counter/Gauge/Histogram with labels, Prometheus text + append-only
   JSONL export, a process-global default registry plus
   :func:`scoped_registry` for tests;
2. **step-time instrumentation** in :class:`~paddle_tpu.jit.to_static.
   TrainStep` — ``TrainStep.stats()`` snapshots compiles/recompiles,
   eager-cache hit rates and (under ``FLAGS_monitor``) per-step
   wall/dispatch timings streamed into the registry;
3. **collective tracing** (:mod:`paddle_tpu.distributed.collective`):
   every eager collective records op/group/bytes/latency counters and a
   host-timeline RecordEvent;
4. the **NaN/Inf watchdog** (:mod:`.numerics`): eager post-step checks
   that name the first offending parameter/gradient and step index,
   AMP-GradScaler aware.

Memory/cost/forensics pillars (ISSUE 4, the space-domain counterpart):

5. **HBM memory accounting** (:mod:`.memory`): static per-program
   budgets from ``compiled.memory_analysis()`` (surfaced per program
   kind in ``TrainStep.stats()['programs']``), the flag-gated OOM
   pre-flight check (``FLAGS_memory_preflight``), a live-buffer census
   over ``jax.live_arrays()`` with :class:`~.memory.LeakMonitor`
   growth detection, and :func:`~.memory.memory_summary`;
6. **per-program cost attribution** — FLOPs/bytes/arithmetic intensity
   from ``lowered.cost_analysis()`` via :mod:`paddle_tpu.cost_model`
   (one shared source of truth with ``CostModel.profile_measure``);
7. the **crash flight recorder** (:mod:`.flight_recorder`): a bounded
   ring of recent step records + events + an environment fingerprint,
   dumped to JSON on unhandled exceptions, NaN-watchdog trips, or
   explicit ``dump()``, with faulthandler wiring for hard crashes.

Where-did-the-time-go pillars (ISSUE 11):

8. **structured tracing** (:mod:`.trace`): per-request / per-step span
   trees with trace ids, head-rate + tail-based anomaly sampling
   (``FLAGS_trace`` / ``FLAGS_trace_sample``), a unified Perfetto
   export merged with the profiler host timeline, histogram exemplars,
   and trace attachment to flight-recorder dumps;
9. **SLO burn rate** (:mod:`.slo`): multi-window error-budget burn
   tracking over serving outcomes with SRE-workbook multiwindow alert
   arithmetic.

Live telemetry plane (ISSUE 14 — the pull-while-running half):

10. the **embedded admin server** (:mod:`.server`,
    ``FLAGS_monitor_port``): ``/metrics`` (Prometheus text with
    exemplars), ``/healthz`` + ``/readyz`` wired to the serving
    engine's state machine, ``/statusz``, and on-demand
    ``/debug/{flight,trace,profile}`` capture from the LIVE process;
11. the **timeseries ring** (:mod:`.timeseries`): bounded per-scrape
    registry snapshots turning cumulative counters into rates
    (``tools/monitor_top.py``), plus **multi-host aggregation**
    (``MetricsRegistry.merge`` / ``tools/aggregate_metrics.py``).

Fleet observability plane (ISSUE 18 — one pane for many processes):

12. the **fleet federator** (:mod:`.fleet`, ``FLAGS_fleet_monitor_*``):
    a scrape loop federating every replica's ``/metrics`` page (plus
    the router's registry) into ONE host-labelled fleet registry with
    its own admin plane, cross-process trace merging
    (:func:`~.fleet.merge_fleet_traces` joins the router's
    ``fleet.request`` tree with each replica's ``serve.request`` tree
    under one trace_id), fleet SLO burn over the federated counters,
    and anomaly-triggered, rate-limited incident bundles.

Training goodput & model health (ISSUE 19 — the training-side plane):

13. the **goodput ledger** (:mod:`.goodput`, ``FLAGS_train_goodput``):
    every second of trainer wall-clock attributed to ONE exclusive
    bucket (productive_dispatch / compile / data_wait /
    checkpoint_stall / nonfinite_rollback / restart_gap / host_other),
    persisted across SIGTERM→resume through the CheckpointManager
    sidecar, published as ``train_goodput_pct`` +
    ``train_badput_seconds_total{bucket}`` with a /statusz section and
    ``data_wait`` spans on the step trace;
14. **per-layer model health** (``FLAGS_train_health_every``): f32
    grad-norm / param-norm / update-ratio side-outputs compiled into
    the step program (scan layouts included), ``train_layer_*`` gauges,
    and the :class:`~.goodput.LayerHealthMonitor` EWMA spike detector
    that tail-marks step traces and feeds flight-recorder dumps.

The registry is always importable and writable; the HOT paths only write
to it when ``FLAGS_monitor`` is set (zero-overhead default, pinned by
the write_count guard in tests/test_monitor.py; the flight recorder has
the same contract via ``FLAGS_flight_recorder`` and its
``record_count`` probe).
"""

from . import (fleet, flight_recorder, goodput, memory,  # noqa: F401
               slo, timeseries, trace)
from .goodput import GoodputLedger, LayerHealthMonitor  # noqa: F401
from .flight_recorder import (FlightRecorder,  # noqa: F401
                              get_flight_recorder, set_flight_recorder)
from .memory import (LeakMonitor, MemoryBudgetError,  # noqa: F401
                     ProgramMemory, live_buffer_census, memory_summary,
                     preflight_check)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,  # noqa: F401
                      get_registry, lint_exposition, load_jsonl,
                      load_registry_jsonl, scoped_registry)
from .timeseries import TimeseriesRing  # noqa: F401
from .numerics import (NaNWatchdog, NonFiniteError, all_finite,  # noqa: F401
                       check_numerics, first_nonfinite, nonfinite_entries)
from .slo import SLOTracker  # noqa: F401
from .trace import (Span, Trace, Tracer, export_perfetto,  # noqa: F401
                    get_tracer, set_tracer, start_trace)
from .fleet import FleetFederator, merge_fleet_traces  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "scoped_registry", "load_jsonl", "load_registry_jsonl",
    "lint_exposition", "TimeseriesRing",
    "NaNWatchdog", "NonFiniteError", "all_finite", "check_numerics",
    "first_nonfinite", "nonfinite_entries",
    "ProgramMemory", "MemoryBudgetError", "LeakMonitor",
    "live_buffer_census", "memory_summary", "preflight_check",
    "FlightRecorder", "get_flight_recorder", "set_flight_recorder",
    "enabled",
    "Span", "Trace", "Tracer", "get_tracer", "set_tracer",
    "start_trace", "export_perfetto", "SLOTracker",
    "FleetFederator", "merge_fleet_traces",
    "GoodputLedger", "LayerHealthMonitor",
]


def enabled() -> bool:
    """True when ``FLAGS_monitor`` is set — hot paths consult this before
    writing per-step samples into the registry."""
    from ..core.flags import get_flag
    return bool(get_flag("monitor"))
