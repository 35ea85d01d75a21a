"""Compilation observability: compile / trace-count counters.

CI analogue of the reference's op-benchmark gate for COMPILE cost: the
scan-over-layers work (nn/scan.py) makes trace+compile O(1) in stack depth,
and this module gives tests a way to PIN that property so a layer-loop
re-trace can't silently regress it.

Counts come from two sources:
- jax's monitoring events (``/jax/core/compile/backend_compile_duration``
  fires once per XLA backend compile; ``/jax/compilation_cache/
  cache_misses`` fires when the persistent compilation cache misses —
  jax.monitoring has no unregister, so one process-wide listener feeds
  monotonic counters and :class:`CompileCounter` diffs snapshots);
- nn.scan's Python-level body-trace counter (``SCAN_STATS``), which is
  backend-independent and exact.

Usage::

    with CompileCounter() as c:
        step(ids, labels)           # cold: traces + compiles
    assert c.scan_body_traces <= 2  # one fwd trace (+1 remat), not O(L)
    with CompileCounter() as c:
        step(ids, labels)           # warm: cached executable
    assert c.backend_compiles == 0
"""

from __future__ import annotations

import threading

import jax

__all__ = ["CompileCounter", "compile_counts", "publish_compile_counts"]

_LOCK = threading.Lock()
_COUNTS = {"backend_compiles": 0, "cache_misses": 0, "jaxpr_traces": 0}
_installed = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    with _LOCK:
        if event == "/jax/core/compile/backend_compile_duration":
            _COUNTS["backend_compiles"] += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            _COUNTS["jaxpr_traces"] += 1


def _on_event(event: str, **kwargs) -> None:
    with _LOCK:
        if event == "/jax/compilation_cache/cache_misses":
            _COUNTS["cache_misses"] += 1


def _install() -> None:
    global _installed
    with _LOCK:
        if _installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def compile_counts() -> dict:
    """Process-lifetime monotonic counters (installs listeners on first
    use; counting starts then)."""
    _install()
    with _LOCK:
        return dict(_COUNTS)


def publish_compile_counts(registry=None) -> dict:
    """Bridge the process-lifetime compile counters into the monitor
    metrics registry as gauges (``jax_backend_compiles``,
    ``jax_cache_misses``, ``jax_jaxpr_traces``, plus nn.scan's
    ``scan_body_traces``/``scan_calls``), so a registry dump carries
    recompile counts. Returns the raw counts dict."""
    counts = compile_counts()
    try:
        from ..nn.scan import SCAN_STATS
        counts = dict(counts, scan_body_traces=SCAN_STATS["body_traces"],
                      scan_calls=SCAN_STATS["scan_calls"])
    except Exception:
        pass
    from ..monitor import get_registry
    reg = registry if registry is not None else get_registry()
    for k, v in counts.items():
        name = k if k.startswith("scan_") else "jax_" + k
        # emits-metrics: jax_backend_compiles, jax_cache_misses,
        # emits-metrics: jax_jaxpr_traces, scan_body_traces, scan_calls
        reg.gauge(name, "process-lifetime compile/trace counter "
                        "(utils.compilation)").set(v)
    return counts


class CompileCounter:
    """Context manager: compile/trace activity within the block.

    Attributes after (or during) the block:
    - ``backend_compiles``: XLA backend compiles started in the block
    - ``cache_misses``: persistent compilation-cache misses
    - ``jaxpr_traces``: entries into jit's Python tracing path. jax
      (0.9) records the event around its tracing-cache lookup, so it
      fires whenever a jitted function — any ``jnp`` ufunc included —
      is called with tracers (an eager ``vmap``/``vjp`` around it) or
      misses the C++ fast path, even when the jaxpr itself is memoized.
      A warm compiled call fires none, so zero on a warm loop still
      means "no host-side tracing work per step"; a nonzero count
      names eager transforms left on the hot path, not necessarily a
      re-trace
    - ``scan_body_traces`` / ``scan_calls``: nn.scan body traces — the
      "one trace per stack, not per layer" pin
    """

    def __enter__(self):
        from ..nn.scan import SCAN_STATS
        _install()
        self._scan_stats = SCAN_STATS
        with _LOCK:
            self._snap = dict(_COUNTS)
        self._scan_snap = dict(SCAN_STATS)
        return self

    def __exit__(self, *exc):
        return False

    def _delta(self, key: str) -> int:
        with _LOCK:
            return _COUNTS[key] - self._snap[key]

    @property
    def backend_compiles(self) -> int:
        return self._delta("backend_compiles")

    @property
    def cache_misses(self) -> int:
        return self._delta("cache_misses")

    @property
    def jaxpr_traces(self) -> int:
        return self._delta("jaxpr_traces")

    @property
    def scan_body_traces(self) -> int:
        return self._scan_stats["body_traces"] - self._scan_snap["body_traces"]

    @property
    def scan_calls(self) -> int:
        return self._scan_stats["scan_calls"] - self._scan_snap["scan_calls"]
