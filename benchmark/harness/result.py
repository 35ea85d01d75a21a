"""What a run carries from a runner to the per-layer readers and to the
one line the driver reads."""

from __future__ import annotations

import contextlib
import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


class BenchFailure(RuntimeError):
    """The run cannot stand: no result line is printed."""


@dataclass
class Run:
    cell: str
    config: dict                  # benchmark/configs/<config>.json
    mix: dict                     # benchmark/traffic/<traffic>.json
    system: dict                  # benchmark/workloads/<cell>.json
    chips: int
    seed: int
    seconds: float
    traced: bool
    rehearse: bool
    t_start: float                # perf_counter at process start
    deadline: float               # perf_counter by which the run has to end
    trace_dir: str = ""           # where a --trace 1 run puts the profiler's files
    model: Any = None             # benchmark/models/<family>.py, imported
    #: end-to-end values by metric name
    e2e: Dict[str, float] = field(default_factory=dict)
    #: host-clock samples (seconds) by span name, taken inside the window
    spans: Dict[str, List[float]] = field(default_factory=dict)
    #: counts the program keeps, and the benchmark's own
    counts: Dict[str, float] = field(default_factory=dict)
    #: trace_reduce.reduce(...) of the traced window, None when not traced
    trace: Optional[dict] = None
    #: (name, ok, detail) — `correct` is the conjunction
    checks: List[tuple] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    #: printed on earlier lines, never read by the driver
    notes: Dict[str, Any] = field(default_factory=dict)
    device_kind: str = ""

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.checks.append((name, bool(ok), detail))
        say(f"  check {name}: {'ok' if ok else 'FAILED'} {detail}")
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(ok for _, ok, _ in self.checks)


def rel_err(got, ref) -> float:
    """max|got - ref| / max|ref| in float32, reduced on the device."""
    import jax.numpy as jnp
    g, r = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(g - r)) / (jnp.max(jnp.abs(r)) + 1e-12))


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileLedger:
    """Listens to jax's monitoring events for the whole process:
    backend compiles and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self) -> tuple:
        return (self.compiles, self.hits, self.misses)


def arm_deadline(run: Run) -> None:
    """A run that hangs dumps every thread's stack and dies at
    `run.deadline` (faulthandler keeps one timer: whoever borrows it for
    a shorter watch arms this one again)."""
    import faulthandler
    faulthandler.dump_traceback_later(
        max(1.0, run.deadline - time.perf_counter()), exit=True)


def annotate(run: Run, name: str):
    """A `bench.*` span in the profiler's own trace when the run is
    traced, nothing otherwise."""
    if not run.traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Timed:
    """`with Timed(run, "bench.step_call"):` — host-clock sample into
    `run.spans[name]` and, in a traced run, the same span in the trace."""

    def __init__(self, run: Run, name: str, keep: bool = True):
        self.run, self.name, self.keep = run, name, keep
        self.ann = annotate(run, name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        if self.keep:
            self.run.spans.setdefault(self.name, []).append(self.seconds)
        return False


def hbm_read(devices) -> list:
    """(held, peak held) bytes of each chip now, as its allocator reports
    them: held = `bytes_in_use` (the arrays of the process: weights,
    optimizer state, pools, batches) + `bytes_reserved` (what the runtime
    has set aside for the temporaries of the loaded programs, which no
    array can take). On the chip the two and the largest free block add
    up to `bytes_limit` within 30 MB in both one-chip cells (my chip
    runs, PR 25). The peak is the sum of the two peaks; the reservation
    does not move once every program has run."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append((int(st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved", 0)),
                    int(st.get("peak_bytes_in_use", 0))
                    + int(st.get("peak_bytes_reserved", 0))))
    return out


def hbm_account(run: Run, devices, opened: list, programs) -> None:
    """Call when the window closes, with `hbm_read` from when it opened.

    `hbm_peak_bytes`: the peak held on the fullest chip over the whole
    process, set-up and its checks included — the result line's
    `memory_peak_bytes`. `hbm_window_bytes`: the fullest chip's figure
    for the WINDOW — the peak if the window raised it, else the larger
    of what was held when it opened and when it closed (the peak cannot
    be reset, and a set-up that builds a model on one chip before
    placing it on a mesh, or gathers it there for the reference, leaves
    a peak no step ever reaches). The compiler's own count of the
    largest program's temporaries (`compiled.memory_analysis()`) and the
    allocator's whole reading are printed beside them (`notes.hbm`)."""
    closed = hbm_read(devices)
    run.counts["hbm_peak_bytes"] = max(p for _, p in closed)
    run.counts["hbm_window_bytes"] = max(
        p1 if p1 > p0 else max(u0, u1)
        for (u0, p0), (u1, p1) in zip(opened, closed))
    temps = 0
    for compiled in programs:
        ma = compiled.memory_analysis() if compiled is not None else None
        temps = max(temps, int(getattr(ma, "temp_size_in_bytes", 0) or 0))
    run.notes["hbm"] = {
        "process_peak_bytes": run.counts["hbm_peak_bytes"],
        "window_bytes": run.counts["hbm_window_bytes"],
        "largest_program_temp_bytes": temps,
        "per_chip_held_open_close_peak": [
            [u0, u1, p1] for (u0, _), (u1, p1) in zip(opened, closed)],
        "per_chip_memory_stats": [
            {k: int(v) for k, v in (d.memory_stats() or {}).items()}
            for d in devices]}


def device_block(run: Run) -> dict:
    """The `device` object of the result line, as jax reports it."""
    import jax
    dev = jax.devices()[0]
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(run.counts.get("hbm_peak_bytes", 0))}
    if run.traced and run.trace:
        out["busy_s"] = run.trace["busy_s"]
        out["window_s"] = run.trace["window_s"]
    return out


def result_line(run: Run, metrics: Dict[str, dict], device: dict) -> str:
    line = {"correct": run.correct, "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics, "device": device}
    if run.traced and run.trace:
        line["breakdown"] = run.trace["breakdown"]
    return json.dumps(line)
