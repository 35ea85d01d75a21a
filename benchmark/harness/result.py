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
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        if self.keep:
            self.run.spans.setdefault(self.name, []).append(dt)
        return False


def hbm_peak_bytes(run: Run, devices, programs) -> int:
    """`memory_stats()["peak_bytes_in_use"]` of the fullest chip: the
    arrays the process held at its peak (weights, optimizer state, pools,
    batches). It does not see a program's temporaries — on the chip GPT-2
    345M's train step read 4.31 GB, exactly its arguments (my chip run,
    PR 25) — and they cannot simply be added: the serving programs'
    `temp_size_in_bytes` (9.16 GB) on top of the 8.86 GB of arrays would
    pass the chip's 15.75 GiB, and the cell runs. So the largest
    program's temporaries, from `compiled.memory_analysis()`, are printed
    beside it (`notes.hbm`) and not counted."""
    arrays = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in devices)
    temps = 0
    for compiled in programs:
        ma = compiled.memory_analysis() if compiled is not None else None
        temps = max(temps, int(getattr(ma, "temp_size_in_bytes", 0) or 0))
    run.notes["hbm"] = {"allocator_peak_bytes": arrays,
                        "largest_program_temp_bytes": temps}
    return arrays


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
