"""From a profiler trace (`*.xplane.pb`) to numbers — one reducer for
every cell, read with nothing but `jax.profiler.ProfileData`.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
instruction, named by the instruction's whole text (`%flash_fwd.19 =
(bf16[...]) custom-call(...), custom_call_target="tpu_custom_call"`; a
`while` covers the events of its body, so events nest), and whose line
`XLA Modules` has one event per executed program (`jit_step(<id>)`); and
`/host:CPU`, whose lines are host threads carrying the benchmark's own
`jax.profiler.TraceAnnotation` spans (`bench.*`). All on one clock.

    busy      union of the op intervals of one chip inside the window
    self time an op's duration minus what its nested children cover
    idle gaps the window minus the busy union, each gap named by the
              `bench.*` host span that covers most of it
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC = 'custom_call_target="tpu_custom_call"'
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = Tuple[float, float, str]          # start_ns, end_ns, name


class WindowTrace:
    """The profiler over the last part of a measured window: `start()`
    from inside the runner's loop, `stop()` after it; what lies between
    is the `bench.window` span every reduction is clipped to. The Python
    tracer stays off: the device lines and the `bench.*` annotations are
    all the reducer reads, and a traced Python loop runs slower than the
    one that is measured."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.span = None

    @property
    def started(self) -> bool:
        return self.span is not None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()

    def stop(self) -> Optional[dict]:
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return reduce(load(find_xplane(self.trace_dir)))


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@dataclass
class Trace:
    #: chip id -> op intervals, named by instruction (`flash_fwd.19`)
    devices: Dict[int, List[Interval]] = field(default_factory=dict)
    #: chip id -> program executions (`jit_step`)
    modules: Dict[int, List[Interval]] = field(default_factory=dict)
    #: host `bench.*` spans
    spans: List[Interval] = field(default_factory=list)
    #: names of the instructions that are Mosaic (Pallas) kernels
    mosaic: Set[str] = field(default_factory=set)


def op_name(text: str) -> str:
    """`%flash_fwd.19 = (bf16[...]) custom-call(...)` -> `flash_fwd.19`.
    Only the instruction's own name: its operands name other ops."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = tr.devices.setdefault(dev, [])
                    for e in line.events:
                        name = op_name(e.name)
                        if MOSAIC in e.name:
                            tr.mosaic.add(name)
                        ops.append((e.start_ns, e.start_ns + e.duration_ns, name))
                elif line.name == MODULES_LINE:
                    tr.modules[dev] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split("(", 1)[0]) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return tr


def clip(ivs: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in ivs
            if e > lo and s < hi]


def union(ivs: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Merged, sorted [start, end) pieces covered by any interval."""
    out: List[List[float]] = []
    for s, e, _ in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(ivs: Iterable[Interval]) -> Dict[str, float]:
    """name -> summed self nanoseconds, for events that nest on one line."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []                    # [end, name, self_ns]

    def pop():
        end, name, own = stack.pop()
        out[name] += max(own, 0.0)

    for s, e, n in sorted(ivs, key=lambda t: (t[0], -(t[1] - t[0]))):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    while stack:
        pop()
    return dict(out)


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def name_gap(gap: Tuple[float, float], spans: List[Interval]) -> str:
    """The innermost `bench.*` span that covers most of an idle gap
    (`bench.window` only when nothing inside it does)."""
    best, best_cover = "outside_any_span", 0.0
    for s, e, n in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        if n == WINDOW_SPAN:
            cover *= 1e-6                 # any narrower span wins
        if cover > best_cover:
            best, best_cover = n, cover
    return best


def base_name(op: str) -> str:
    """`fusion.123` -> `fusion`, `flash_fwd.2` -> `flash_fwd`: the
    instruction's name without its numbering."""
    return re.sub(r"[.\d]+$", "", op) or op


def reduce(tr: Trace, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Optional[dict]:
    """The traced window in numbers, or None when no device op ran.
    Times in seconds. `by_op` is chip 0's (lowest id) self time by
    instruction name, `modules` its programs as name -> (executions
    wholly inside the window, their summed seconds, seconds of all its
    executions clipped to the window), `busy_s` the mean over the
    chips."""
    devices, spans = tr.devices, tr.spans
    if not devices or not any(devices.values()):
        return None
    if window is None:
        wins = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
        if wins:
            window = max(wins, key=lambda w: w[1] - w[0])
        else:
            evs = [iv for d in devices.values() for iv in d]
            window = (min(s for s, _, _ in evs), max(e for _, e, _ in evs))
    lo, hi = window
    spans = clip(spans, lo, hi)
    first = min(devices)
    busy_by_dev = {dev: union(clip(ivs, lo, hi)) for dev, ivs in devices.items()}
    busy_s = {dev: sum(e - s for s, e in b) / 1e9 for dev, b in busy_by_dev.items()}
    by_op = {n: ns / 1e9
             for n, ns in self_times(clip(devices[first], lo, hi)).items()}
    idle_by: Dict[str, float] = defaultdict(float)
    for g in gaps(busy_by_dev[first], lo, hi):
        idle_by[name_gap(g, spans)] += (g[1] - g[0]) / 1e9
    span_counts: Dict[str, int] = defaultdict(int)
    for _, _, n in spans:
        span_counts[n] += 1
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, 0.0])
    for s, e, n in tr.modules.get(first, []):
        if s >= lo and e <= hi:
            modules[n][0] += 1
            modules[n][1] += (e - s) / 1e9
        modules[n][2] += max(0.0, min(e, hi) - max(s, lo)) / 1e9
    by_base: Dict[str, float] = defaultdict(float)
    for n, sec in by_op.items():
        by_base[base_name(n)] += sec

    def rank(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busy_s_device0": busy_s[first],
        "by_op": by_op,
        "mosaic_s": sum(sec for n, sec in by_op.items() if n in tr.mosaic),
        "modules": {n: tuple(v) for n, v in modules.items()},
        "span_counts": dict(span_counts),
        "breakdown": {"device_ops": rank(by_base),
                      "idle_gaps": rank(idle_by)},
    }


def main_program(summary: dict) -> Optional[Tuple[float, float]]:
    """(seconds one execution of the window's largest program takes on
    chip 0, how many executions the window held, parts counted as
    parts) — a training step, whose in-flight executions straddle the
    window's edges."""
    whole = {n: m for n, m in summary["modules"].items() if m[0] > 0}
    if not whole:
        return None
    count, total, clipped = max(whole.values(), key=lambda m: m[2])
    return total / count, clipped / (total / count)


def time_in(by_op: Dict[str, float], needles: Iterable[str]) -> float:
    """Summed self seconds of the ops whose name contains a needle."""
    needles = tuple(needles)
    return sum(s for n, s in by_op.items() if any(k in n for k in needles))


def describe(path: str, top: int = 25) -> str:
    """What is in a trace file, for a first look by hand."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            tot: Dict[str, float] = defaultdict(float)
            for e in evs:
                tot[e.name] += e.duration_ns
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for n, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"      {ns / 1e6:10.3f} ms  {n[:110]}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys
    xplane = find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1]) \
        else sys.argv[1]
    print(describe(xplane))
    summary = reduce(load(xplane))
    if summary:
        summary.pop("by_op")
        print(json.dumps(summary, indent=1))
