"""What the PROGRAM kept for the readers: its span ring and its
instruction -> block index.

`harness/trace_reduce.py` keeps host spans named `bench.*` only and the
profiler's files are gone before a reader runs, so the readers of the
program's own spans do not go through the device trace. They read

    paddle_tpu.monitor.trace.spans()   the ring of `serve.*` / `train.*`
                                       spans, `time.perf_counter` seconds,
                                       records `(name, t0, t1, span_id,
                                       parent_id, step, attrs)`
    paddle_tpu.jit.aot.scopes(module)  {instruction: (block, phase)} of
                                       the newest executable built under
                                       an HLO module name

and join them with what a `Run` carries: the window on the same clock
(`t_start + setup_s`, `window_s`) and the traced window's `by_op` and
`modules`. A program that has neither (the parent of the PR that added
them), a run that is not traced, a `--rehearse` on the CPU: every
function returns None or nothing, and none raises.

Host-span metrics cover the WHOLE measured window (the ring holds it);
device metrics the traced part of it, like every other trace reader.
"""

from __future__ import annotations

import importlib
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from .loadgen import percentile
from .result import say
from .trace_reduce import base_name

NAME, T0, T1, ID, PARENT, STEP, ATTRS = range(7)
#: runs whose table has been printed (`report` is called by every reader)
_reported: set = set()


def _program(module: str, attr: str):
    """`paddle_tpu.<module>` when the program has `attr` there, else
    None (the parent of the PR that added it)."""
    try:
        mod = importlib.import_module("paddle_tpu." + module)
    except ImportError:
        return None
    return mod if hasattr(mod, attr) else None


def _ring():
    return _program("monitor.trace", "spans")


def _scopes(module: str) -> Optional[dict]:
    aot = _program("jit.aot", "scopes")
    return aot.scopes(module) if aot else None


def window(run) -> Optional[Tuple[float, float]]:
    """The measured window on `time.perf_counter`."""
    setup, length = run.e2e.get("setup_s"), run.counts.get("window_s")
    if setup is None or length is None:
        return None
    t_w0 = run.t_start + setup
    return t_w0, t_w0 + length


def records(run, name: Optional[str] = None) -> List[tuple]:
    """The program's spans that overlap the window (all names, or one)."""
    ring, win = _ring(), window(run)
    if ring is None or win is None:
        return []
    return ring.spans(since=win[0], until=win[1], name=name)


def inside(recs: List[tuple], win: Tuple[float, float]) -> List[tuple]:
    """Those that begin and end inside the window."""
    return [r for r in recs if r[T0] >= win[0] and r[T1] <= win[1]]


def durations(run, name: str) -> List[float]:
    """Seconds of every span of that name wholly inside the window."""
    win = window(run)
    return [r[T1] - r[T0] for r in inside(records(run, name), win)] if win else []


def median_ms(run, name: str) -> Optional[float]:
    report(run)
    xs = durations(run, name)
    return statistics.median(xs) * 1e3 if xs else None


def children(recs: List[tuple]) -> Dict[int, List[tuple]]:
    out: Dict[int, List[tuple]] = defaultdict(list)
    for r in recs:
        if r[PARENT] is not None:
            out[r[PARENT]].append(r)
    return out


def self_time(rec: tuple, kids: List[tuple]) -> float:
    """A span's duration minus the part of it its child spans cover
    (children of one thread nest and do not overlap; clipped to the
    parent, so a `record`ed wait that began earlier takes nothing)."""
    covered = sum(max(0.0, min(k[T1], rec[T1]) - max(k[T0], rec[T0]))
                  for k in kids)
    return max(0.0, (rec[T1] - rec[T0]) - covered)


def minus_descendants_ms(run, name: str, suffix: str) -> Optional[float]:
    """Median over the spans `name` of: duration minus its descendants
    whose name ends with `suffix` (`serve.step` minus every
    `.readback`: the host's own share of a step)."""
    report(run)
    ring, win = _ring(), window(run)
    if ring is None or win is None:
        return None
    recs = records(run)
    xs = [(r[T1] - r[T0]) - sum(sec for phase, sec
                                in ring.phase_table(r, recs).items()
                                if phase.endswith(suffix))
          for r in inside(recs, win) if r[NAME] == name]
    return statistics.median(xs) * 1e3 if xs else None


def ending_in_window_ms(run, name: str, q: float) -> Optional[float]:
    """Percentile of the spans `name` that END in the window (a wait in
    the queue counts where it was felt)."""
    report(run)
    win = window(run)
    xs = [(r[T1] - r[T0]) * 1e3 for r in records(run, name)
          if win[0] <= r[T1] <= win[1]] if win else []
    return percentile(xs, q)


def module_ms(run, prefix: str) -> Optional[float]:
    """Device milliseconds per execution, on chip 0, of the programs
    whose name starts with `prefix`: their executions wholly inside the
    traced window, seconds over count."""
    report(run)
    mods = (run.trace or {}).get("modules") or {}
    hit = [m for n, m in mods.items() if n.startswith(prefix) and m[0] > 0]
    count = sum(m[0] for m in hit)
    return sum(m[1] for m in hit) / count * 1e3 if count else None


def by_block(run) -> Optional[dict]:
    """Chip 0's self seconds of the traced window split by the block of
    the model each instruction came from: `by_op` joined with the
    program's scope index of the window's largest module.

    {"module", "busy_s", "unscoped_s", "blocks": {block: {phase: s}},
     "kinds": {block: {instruction name without its number: s}},
     "unscoped_top": [(instruction, s)]} — or None without a device
    trace or an index. Instructions of OTHER programs in the window
    count as unscoped: in a train window there are next to none."""
    tr = run.trace
    if not tr or not tr.get("by_op") or not tr.get("modules"):
        return None
    module = max(tr["modules"], key=lambda n: tr["modules"][n][2])
    index = _scopes(module)
    if index is None:
        return None
    blocks: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    kinds: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    loose: Dict[str, float] = {}
    for op, sec in tr["by_op"].items():
        hit = index.get(op)
        if hit is None:
            loose[op] = sec
        else:
            blocks[hit[0]][hit[1]] += sec
            kinds[hit[0]][base_name(op)] += sec
    return {"module": module, "busy_s": sum(tr["by_op"].values()),
            "unscoped_s": sum(loose.values()),
            "blocks": {b: dict(p) for b, p in blocks.items()},
            "kinds": {b: dict(k) for b, k in kinds.items()},
            "unscoped_top": sorted(loose.items(), key=lambda kv: -kv[1])[:8]}


def block_pct(run, names: Tuple[str, ...]) -> Optional[float]:
    """Share of chip 0's busy time in the instructions of these blocks
    (`()`: in no block), %."""
    report(run)
    split = by_block(run)
    if not split or split["busy_s"] <= 0:
        return None
    sec = (sum(sum(split["blocks"].get(b, {}).values()) for b in names)
           if names else split["unscoped_s"])
    return 100.0 * sec / split["busy_s"]


# -- the one table of a traced run ---------------------------------------------

def report(run) -> None:
    """Printed once per run, before the result line: per span name the
    count, median, p95 and max inside the window; how the steps' time
    splits over their children; every stalled step with its phases; the
    queue waits; the device time by block."""
    if id(run) in _reported:
        return
    _reported.add(id(run))
    win = window(run)
    recs = records(run)
    if win and recs:
        _report_spans(_ring(), recs, win)
    split = by_block(run)
    if split:
        _report_blocks(split)


def _report_spans(ring, recs: List[tuple], win: Tuple[float, float]) -> None:
    whole = inside(recs, win)
    by_name: Dict[str, List[float]] = defaultdict(list)
    for r in whole:
        by_name[r[NAME]].append((r[T1] - r[T0]) * 1e3)
    say(f"  program spans inside the window ({len(whole)} records; ms):")
    say(f"    {'span':<26}{'count':>7}{'median':>10}{'p95':>10}{'max':>10}")
    for name in sorted(by_name):
        xs = by_name[name]
        say(f"    {name:<26}{len(xs):>7}{statistics.median(xs):>10.3f}"
            f"{percentile(xs, 95):>10.3f}{max(xs):>10.3f}")
    kids = children(recs)
    for root in ("serve.step", "train.step"):
        steps = [r for r in whole if r[NAME] == root]
        if not steps:
            continue
        own = [self_time(r, [k for k in kids.get(r[ID], ()) if k[T0] >= r[T0]])
               for r in steps]
        total = sum(r[T1] - r[T0] for r in steps)
        say(f"    {root}: children cover {100.0 * (1 - sum(own) / total):.2f}% of "
            f"{total:.3f}s in {len(steps)} steps; median self time "
            f"{statistics.median(own) * 1e3:.3f} ms")
        median = statistics.median(r[T1] - r[T0] for r in steps)
        for r in steps:
            took = r[T1] - r[T0]
            if ring.stalled(took, median):           # the program's own rule
                say(f"    STALLED {root} step {r[STEP]} at "
                    f"{r[T0] - win[0]:.3f}s into the window: {took * 1e3:.1f} ms, "
                    f"sat in {ring.stall_phase(r, recs)}; "
                    + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in
                                sorted(ring.phase_table(r, recs).items(),
                                       key=lambda kv: -kv[1])))
    waits = [(r[T1] - r[T0]) * 1e3 for r in recs
             if r[NAME] == "serve.queued" and win[0] <= r[T1] <= win[1]]
    if waits:
        say(f"    serve.queued ending in the window: {len(waits)} admissions, "
            f"median {statistics.median(waits):.3f} p95 {percentile(waits, 95):.3f} "
            f"max {max(waits):.3f} ms")


def _report_blocks(split: dict) -> None:
    busy = split["busy_s"]
    say(f"  device time by block, chip 0, program {split['module']} "
        f"(busy {busy:.3f}s; % of busy):")
    say(f"    {'block':<12}{'all':>8}{'fwd':>8}{'bwd':>8}{'remat':>8}")
    for block, phases in sorted(split["blocks"].items(),
                                key=lambda kv: -sum(kv[1].values())):
        cells = "".join(f"{100.0 * phases.get(p, 0.0) / busy:>8.2f}"
                        for p in ("fwd", "bwd", "remat"))
        top = sorted(split["kinds"][block].items(), key=lambda kv: -kv[1])[:4]
        say(f"    {block:<12}{100.0 * sum(phases.values()) / busy:>8.2f}{cells}   "
            + ", ".join(f"{k} {100.0 * s / busy:.2f}" for k, s in top))
    say(f"    {'(unscoped)':<12}{100.0 * split['unscoped_s'] / busy:>8.2f}   "
        + ", ".join(f"{op} {100.0 * s / busy:.2f}" for op, s in split["unscoped_top"]))
