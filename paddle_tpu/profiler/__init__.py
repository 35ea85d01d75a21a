"""Profiler.

Reference: paddle/fluid/platform/profiler.h (host RecordEvent) +
device_tracer.cc (CUPTI timeline) + python fluid/profiler.py.

TPU answer: wrap jax.profiler (XPlane traces viewable in TensorBoard /
Perfetto) and keep a lightweight host-side event aggregation for op tables.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from typing import Optional

import jax

from ..monitor import trace as _trace

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "profiler", "start_profiler", "stop_profiler",
           "summary", "profile_train_step", "export_chrome_tracing",
           "export_tensorboard", "chrome_trace_doc"]

_tls = threading.local()
_events = defaultdict(lambda: [0, 0.0])  # name -> [count, total_sec]
_active = [False]
# host timeline: (name, t_start_us, dur_us, thread_id); bounded so a long
# run cannot grow without limit (the chrome trace keeps the newest events)
_TIMELINE_CAP = 200_000
_timeline = []


def _timeline_add(name: str, t0: float, t1: float):
    if len(_timeline) >= _TIMELINE_CAP:
        del _timeline[: _TIMELINE_CAP // 2]
    _timeline.append((name, t0 * 1e6, (t1 - t0) * 1e6,
                      threading.get_ident()))


class RecordEvent(_trace.span):
    """Host-side RAII event marker (platform/profiler.h RecordEvent
    analogue): :class:`paddle_tpu.monitor.trace.span` under the
    ``paddle.profiler`` name — the span ring, the xplane annotation
    while a ``jax.profiler`` session is on — plus a row in the host
    table and timeline between ``start_profiler()`` and
    ``stop_profiler()``."""

    __slots__ = ()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        if _active[0]:
            rec = _events[self.name]
            rec[0] += 1
            rec[1] += self.t1 - self.t0
            _timeline_add(self.name, self.t0, self.t1)
        return False


def _op_hook(name: str, seconds: float):
    # bounded behind _active: the hook may still be installed (or called
    # from a racing thread) after stop_profiler — without this guard eager
    # op events accumulate in _events/_timeline forever on long runs
    if not _active[0]:
        return
    rec = _events["op::" + name]
    rec[0] += 1
    rec[1] += seconds
    t1 = time.perf_counter()
    _timeline_add("op::" + name, t1 - seconds, t1)


def start_profiler(state="All", tracer_option="Default", log_dir=None):
    """Begin host-event + per-op aggregation; with ``log_dir`` also start
    a jax.profiler XPlane trace there (view in TensorBoard/Perfetto —
    reference analogue: device_tracer.cc:464 timeline capture).

    Workflow::

        profiler.start_profiler(log_dir="/tmp/trace")
        ... train steps ...
        profiler.stop_profiler()
        print(profiler.summary())           # host events + eager op table
        # device timeline: tensorboard --logdir /tmp/trace
    """
    _active[0] = True
    _events.clear()
    _timeline.clear()
    from ..core.tensor import set_op_profile_hook
    set_op_profile_hook(_op_hook)
    if log_dir:
        try:
            jax.profiler.start_trace(log_dir)
            _tls.trace_dir = log_dir
        except Exception as e:  # host aggregation must survive a backend
            import warnings      # that cannot produce an xplane trace
            warnings.warn(f"xplane trace not started ({e!r}); host-side "
                          "event aggregation continues", RuntimeWarning)


def stop_profiler(sorted_key=None, profile_path=None):
    """End aggregation. With ``profile_path`` the summary table (sorted by
    ``sorted_key``: 'calls'/'total'/'avg', default total) is written there —
    fluid.profiler.stop_profiler parity, which dumped its per-op table to
    that path."""
    _active[0] = False
    from ..core.tensor import set_op_profile_hook
    set_op_profile_hook(None)
    if getattr(_tls, "trace_dir", None):
        try:
            jax.profiler.stop_trace()
        finally:
            _tls.trace_dir = None
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(summary(sorted_key or "total") + "\n")


# fluid's 'min'/'max' sort keys are NOT accepted: per-event min/max are
# not tracked here, and silently sorting by total instead would misreport
# — unknown keys raise so the caller learns the supported set
_SUMMARY_KEYS = {
    "calls": lambda cnt, tot: cnt,
    "total": lambda cnt, tot: tot,
    "avg": lambda cnt, tot: tot / max(cnt, 1),
    "ave": lambda cnt, tot: tot / max(cnt, 1),   # fluid alias for avg
}


def summary(sorted_by="total"):
    """Host-event + eager-op table, sorted DESC by ``sorted_by``
    ('calls' | 'total' | 'avg')."""
    keyfn = _SUMMARY_KEYS.get(sorted_by or "total")
    if keyfn is None:
        raise ValueError(f"summary: sorted_by must be one of "
                         f"{sorted(_SUMMARY_KEYS)}, got {sorted_by!r}")
    rows = sorted(_events.items(), key=lambda kv: -keyfn(kv[1][0], kv[1][1]))
    lines = [f"{'Event':<40} {'Calls':>8} {'Total(ms)':>12} {'Avg(ms)':>12}"]
    for name, (count, total) in rows:
        lines.append(f"{name:<40} {count:>8} {total * 1e3:>12.3f} "
                     f"{total * 1e3 / max(count, 1):>12.3f}")
    return "\n".join(lines)


def chrome_trace_doc() -> dict:
    """The host-timeline chrome-trace document as a dict (what
    ``export_chrome_tracing`` writes) — served in-memory by the admin
    server's ``/debug/profile`` endpoint."""
    events = [{"name": name, "ph": "X", "ts": ts, "dur": dur,
               "pid": 0, "tid": tid % 100000, "cat": "host"}
              for name, ts, dur, tid in _timeline]
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _write_chrome_trace(path: str) -> str:
    import json

    with open(path, "w") as f:
        json.dump(chrome_trace_doc(), f)
    return path


def export_chrome_tracing(path: str, worker_name: Optional[str] = None):
    """Chrome-trace exporter, two forms (chrome://tracing / Perfetto JSON;
    the reference emits its profiler.proto timeline the same way,
    device_tracer.cc GenProfile:496).

    - Direct: a ``*.json`` path writes the current host timeline NOW and
      returns the path.
    - Handler factory (paddle.profiler.export_chrome_tracing parity): any
      other path is treated as a directory and a callable is returned for
      ``Profiler(on_trace_ready=...)``; each closed record window writes
      ``<dir>/<worker>_chrome_trace_<n>.json``.

    Device-side kernels live in the XPlane trace captured via
    ``start_profiler(log_dir=...)`` / ``export_tensorboard``; this file
    covers the host lanes (RecordEvent blocks + eager op dispatches)."""
    import os

    if path.endswith(".json"):
        return _write_chrome_trace(path)

    dir_name, worker = path, worker_name or "host"
    counter = [0]

    def handler(prof) -> str:
        os.makedirs(dir_name, exist_ok=True)
        counter[0] += 1
        return _write_chrome_trace(os.path.join(
            dir_name, f"{worker}_chrome_trace_{counter[0]}.json"))

    handler.dir_name = dir_name
    return handler


def export_tensorboard(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler for TensorBoard: the device-side XPlane
    trace is captured into ``dir_name`` (Profiler adopts it as its
    ``log_dir`` — jax.profiler writes plugins/profile/<ts> subdirs there,
    viewable with ``tensorboard --logdir dir_name``), and each closed
    window also writes the host summary table next to it."""
    import os

    counter = [0]

    def handler(prof) -> str:
        os.makedirs(dir_name, exist_ok=True)
        counter[0] += 1
        path = os.path.join(
            dir_name, f"{worker_name or 'host'}_summary_{counter[0]}.txt")
        with open(path, "w") as f:
            f.write(summary() + "\n")
        return path

    handler.log_dir = dir_name        # Profiler picks this up for xplane
    return handler


@contextlib.contextmanager
def profiler(state="All", tracer_option="Default", log_dir=None,
             sorted_key="total"):
    """fluid.profiler.profiler context analogue."""
    start_profiler(state, tracer_option, log_dir)
    try:
        yield
    finally:
        stop_profiler()
        print(summary(sorted_key))


def profile_train_step(step, batch, iters: int = 10, warmup: int = 2):
    """Attribute a TrainStep's wall time: compile vs host prep vs dispatch
    vs device execute (reference analogue: the per-op timeline totals of
    platform/profiler.cc, collapsed to the phases that exist under XLA's
    one-executable-per-step model).

    Returns a dict:
      compile_s       time of the first (cold) call incl. compilation;
                      ~0 when the persistent compile cache is warm
      host_ms         python-side prep per step (batch placement, flatten,
                      signature lookup) — measured by timing dispatch-only
                      calls minus the jitted dispatch itself
      dispatch_ms     time for step() to RETURN (async dispatch)
      step_ms         full step latency incl. device work (readback-timed)
      device_ms_est   step_ms minus host prep: device execute + dispatch
                      enqueue time (>= 0)
    """
    import numpy as np

    def readback(loss):
        return float(np.asarray(loss._data if hasattr(loss, "_data")
                                else loss))

    t0 = time.perf_counter()
    readback(step(*batch))
    compile_s = time.perf_counter() - t0

    for _ in range(warmup):
        step(*batch)
    readback(step(*batch))

    # host-side prep: everything __call__ does before the XLA dispatch
    t0 = time.perf_counter()
    for _ in range(iters):
        raw = [b._data if hasattr(b, "_data") else b for b in batch]
        raw = step._place_batch(raw)
        jax.tree_util.tree_flatten(raw)
    host_ms = (time.perf_counter() - t0) / iters * 1e3

    # dispatch: call returns as soon as XLA enqueues
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(*batch)
    dispatch_ms = (time.perf_counter() - t0) / iters * 1e3
    readback(loss)

    # full latency: readback forces device completion each step
    t0 = time.perf_counter()
    for _ in range(iters):
        readback(step(*batch))
    step_ms = (time.perf_counter() - t0) / iters * 1e3

    return {
        "compile_s": compile_s,
        "host_ms": host_ms,
        "dispatch_ms": dispatch_ms,
        "step_ms": step_ms,
        "device_ms_est": max(0.0, step_ms - host_ms),
    }


class ProfilerState:
    """paddle.profiler.ProfilerState parity: the per-step scheduler
    states. RECORD_AND_RETURN marks the LAST record step of a window —
    the step after it closes the window and fires on_trace_ready."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget:
    """paddle.profiler.ProfilerTarget parity tokens. On this stack the
    host lanes (CPU) and the XLA device trace (captured together in the
    XPlane file) are not separately selectable — targets are accepted and
    recorded for API parity."""
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0):
    """paddle.profiler.make_scheduler parity: a step->ProfilerState
    function cycling CLOSED(closed) -> READY(ready) -> RECORD(record),
    with the window's last record step flagged RECORD_AND_RETURN.
    ``repeat=0`` cycles forever; ``skip_first`` steps are CLOSED before
    the first cycle."""
    if record <= 0:
        raise ValueError("make_scheduler: record must be >= 1")
    if closed < 0 or ready < 0 or repeat < 0 or skip_first < 0:
        raise ValueError("make_scheduler: closed/ready/repeat/skip_first "
                         "must be >= 0")
    cycle = closed + ready + record

    def scheduler(step: int) -> int:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        return (ProfilerState.RECORD_AND_RETURN if pos == cycle - 1
                else ProfilerState.RECORD)

    return scheduler


class Profiler:
    """paddle.profiler.Profiler parity over jax.profiler + the host
    aggregation above.

    ``scheduler`` is a step->ProfilerState callable (see
    :func:`make_scheduler`) or a ``(start, end)`` tuple recording steps in
    ``[start, end)``; None records everything between start() and stop().
    Each closed record window fires ``on_trace_ready(self)`` (see
    :func:`export_chrome_tracing` / :func:`export_tensorboard` for
    handler factories). ``step()`` advances the schedule — call it once
    per training step.
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 log_dir="./profiler_log", timer_only=False):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            if not (0 <= start < end):
                raise ValueError(f"scheduler tuple must be 0 <= start < "
                                 f"end, got {scheduler!r}")
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=end - start, repeat=1)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        # a TensorBoard handler carries the xplane dir it wants traces in
        self.log_dir = getattr(on_trace_ready, "log_dir", None) or log_dir
        self.timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._recording = False
        self.windows = 0          # closed record windows so far

    # -- window plumbing ---------------------------------------------------
    def _begin_window(self):
        if self._recording:
            return
        start_profiler(log_dir=None if self.timer_only else self.log_dir)
        self._recording = True

    def _end_window(self):
        if not self._recording:
            return
        stop_profiler()
        self._recording = False
        self.windows += 1
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def _apply(self, state: int):
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._begin_window()
        elif self._recording:
            self._end_window()
        self.state = state

    # -- public API --------------------------------------------------------
    def start(self):
        self.step_num = 0
        self._apply(self.scheduler(0) if self.scheduler
                    else ProfilerState.RECORD)
        return self

    def step(self, num_samples=None):
        """Advance one training step; closes a window right after its
        RECORD_AND_RETURN step, per the reference scheduler contract."""
        if self.state == ProfilerState.RECORD_AND_RETURN:
            self._end_window()
        self.step_num += 1
        if self.scheduler is not None:
            self._apply(self.scheduler(self.step_num))

    def stop(self):
        # a window open at stop() — unscheduled run, early loop break,
        # exception mid-RECORD — is exported like any other: partial data
        # beats silently discarding everything recorded so far (the
        # reference Profiler.stop() also exports from RECORD states)
        self._end_window()
        self.state = ProfilerState.CLOSED

    def summary(self, sorted_by="total"):
        return summary(sorted_by)

    def export(self, path: str, format: str = "json") -> str:
        """Write the newest host timeline as a chrome trace (format
        'json'; paddle's Profiler.export parity)."""
        if format != "json":
            raise ValueError(f"export: only 'json' (chrome trace) is "
                             f"supported, got {format!r}")
        return _write_chrome_trace(path)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
