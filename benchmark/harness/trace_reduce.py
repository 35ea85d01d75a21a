"""From a profiler trace (`*.xplane.pb`) to numbers — one reducer for
every cell, read with nothing but `jax.profiler.ProfileData`.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
instruction (a `while` covers the events of its body, so events nest),
and `/host:CPU`, whose lines are host threads carrying the benchmark's
own `jax.profiler.TraceAnnotation` spans (`bench.*`). All on one clock.

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
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = Tuple[float, float, str]          # start_ns, end_ns, name


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Tuple[Dict[int, List[Interval]], List[Interval]]:
    """(device id -> op intervals, host `bench.*` spans) of a trace file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


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
    """`fusion.123` -> `fusion`, `%flash_fwd.2` -> `flash_fwd`: the
    instruction's name without its numbering."""
    return re.sub(r"[.\d]+$", "", op.lstrip("%")) or op


def reduce(devices: Dict[int, List[Interval]], spans: List[Interval],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Optional[dict]:
    """The traced window in numbers, or None when no device op ran.
    Times in seconds; `by_op` is device 0's (lowest id) self time by
    full instruction name, `busy_s` the mean over the chips."""
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
    busy_by_dev = {}
    for dev, ivs in devices.items():
        busy_by_dev[dev] = union(clip(ivs, lo, hi))
    busy_s = [sum(e - s for s, e in b) / 1e9 for b in busy_by_dev.values()]
    by_op_ns = self_times(clip(devices[first], lo, hi))
    by_op = {n: ns / 1e9 for n, ns in by_op_ns.items()}
    idle_by: Dict[str, float] = defaultdict(float)
    for g in gaps(busy_by_dev[first], lo, hi):
        idle_by[name_gap(g, spans)] += (g[1] - g[0]) / 1e9
    span_counts: Dict[str, int] = defaultdict(int)
    for _, _, n in spans:
        span_counts[n] += 1
    by_base: Dict[str, float] = defaultdict(float)
    for n, s in by_op.items():
        by_base[base_name(n)] += s
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "busy_s_device0": busy_s[sorted(devices).index(first)],
        "by_op": by_op,
        "span_counts": dict(span_counts),
        "breakdown": {"device_ops": rank(by_base),
                      "idle_gaps": rank(idle_by)},
    }


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
