"""Benchmark regression gate.

Compares the metric lines of two bench records (bench.py's JSON lines)
and fails loudly when a metric regressed beyond tolerance — the analogue
of the reference's op-benchmark CI gate
(/root/reference/tools/check_op_benchmark_result.py:1, which diffs op
timings against the develop branch and fails the PR over threshold).

Usage:
    python tools/check_bench.py older_record.json newer_record.json
    python tools/check_bench.py --tolerance 0.15 old.json new.json

Metric direction is derived from the unit: cost-like units (ms, s, us,
bytes — compile time, step time, peak-HBM estimates) regress when they
grow; rate-like units (tokens/s, img/s, steps/s) regress when they
shrink. The default tolerance is 10%; the run-to-run spread of the
legs on the chip has not been measured against it.

Exit code: 0 = no regression, 1 = regression(s), 2 = usage/parse error.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

DEFAULT_TOLERANCE = 0.10
# cost-like units: growth is the regression (memory units gate the
# *_peak_hbm_bytes budget lines the same way time units gate compile/step
# time). bytes/token and bytes/slot are per-unit KV-cache footprints
# (BENCH_serve, serve_kv_bytes_per_token / serve_kv_bytes_per_slot):
# growth means the int8 paged-KV compression (FLAGS_serve_kv_quant)
# regressed toward full precision, so they self-gate like memory.
_TIME_UNITS = {"ms", "s", "us", "ms/step", "seconds", "bytes", "kib",
               "mib", "gib", "bytes/token", "bytes/slot"}
# bounded 0-100 cost rates (growth is the regression) gate on ABSOLUTE
# percentage points: the healthy baseline is 0, where a relative ratio
# is undefined and the v_old==0 skip would otherwise make the metric
# ungateable ("%" alone stays rate-like and relative:
# serve_availability_pct regresses when it shrinks). bubble% is the
# pipeline-schedule idle share (MULTICHIP record); drop% is the MoE
# router's dropped-assignment share (BENCH_moe); overhead% is the
# measured tracing tokens/s cost (BENCH_serve) — same shape, healthy
# baseline ~0.
_ABS_POINT_UNITS = {"shed%", "bubble%", "exposed%", "drop%",
                    "overhead%"}
# bounded 0-100 QUALITY rates (a drop is the regression), also gated on
# absolute points: weak-scaling efficiency sits near 100, where the
# relative 10% band would hide a 9-point efficiency loss; balance is the
# MoE expert-load balance (100 = uniform), gated the same way so
# BENCH_moe trips on routing-health collapse, not just throughput.
# hit% is a recsys tier hit rate (BENCH_recsys): a drop means the hot
# set fell out of its tier — a perf cliff even when examples/s survives
# on a fast host — and a healthy hot tier can sit anywhere in 0-100, so
# points, not ratios, are the meaningful band. accept% is the
# speculative-decoding draft acceptance rate (BENCH_serve,
# serve_spec_accept_pct): a drop means drafts stopped matching the
# verifier and every verify dispatch degrades toward a plain decode
# step — the same anywhere-in-0-100 shape as hit%, so absolute points.
# goodput% is the training goodput ledger's productive share
# (BENCH_train, train_goodput_pct): a drop means wall-clock leaked into
# a badput bucket — a point loss is a point loss whether the baseline
# sat at 99 or at 60, so absolute points again. swap% is the
# hot-swap-drill availability (BENCH_serve, serve_swap_availability_pct:
# fleet availability through 3 consecutive live weight swaps under mmpp
# load): it lives at ~100 where the relative band would hide a 9-point
# outage, so absolute points — a drop means the zero-downtime cutover
# started shedding or failing live requests.
_ABS_POINT_HIGHER_UNITS = {"weak%", "balance", "hit%", "accept%",
                           "goodput%", "swap%"}
# recsys rate-like units (BENCH_recsys) ride the default direction:
# examples/s (training/serving throughput) and ratio (dedup ratio —
# mean ids served per row fetched, >= 1) are higher-is-better relative,
# like tokens/s; listed here so the unit table is exhaustive.
# "adapters" (BENCH_serve, serve_lora_adapters_per_chip: distinct LoRA
# adapters servable per chip at the fixed p99 budget) is a capacity
# count — higher is better, default relative gating, like tokens/s.
_RATE_UNIT_EXAMPLES = {"examples/s", "ratio", "adapters"}


def _metric_list(record) -> List[dict]:
    """A BENCH record's parsed field is one metric dict (old rounds) or a
    list (round 5+); raw metric-line lists are accepted directly. Falls
    back to scraping JSON lines out of the stored stdout tail."""
    if isinstance(record, list):
        return [m for m in record if isinstance(m, dict) and "metric" in m]
    if isinstance(record, dict):
        if "metric" in record:
            return [record]
        parsed = record.get("parsed")
        if parsed is not None:
            return _metric_list(parsed)
        tail = record.get("tail", "")
        out = []
        for line in tail.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(d, dict) and "metric" in d:
                    out.append(d)
        return out
    return []


def lower_is_better(unit: str) -> bool:
    return unit.strip().lower() in _TIME_UNITS


def compare(old: List[dict], new: List[dict],
            tolerance: float = DEFAULT_TOLERANCE) -> List[str]:
    """Returns a list of human-readable regression messages (empty = ok)."""
    prev: Dict[str, dict] = {m["metric"]: m for m in old}
    problems: List[str] = []
    for m in new:
        name = m["metric"]
        ref = prev.get(name)
        if ref is None:
            continue                      # new metric: nothing to gate
        try:
            v_new, v_old = float(m["value"]), float(ref["value"])
        except (KeyError, TypeError, ValueError):
            problems.append(f"{name}: malformed value "
                            f"({m.get('value')!r} vs {ref.get('value')!r})")
            continue
        unit = str(m.get("unit", ref.get("unit", "")))
        if unit.strip().lower() in _ABS_POINT_UNITS:
            delta = v_new - v_old             # growth is the regression
            if delta > tolerance * 100.0:
                problems.append(
                    f"{name}: {v_old:g} -> {v_new:g} {unit} "
                    f"(+{delta:.1f} points, tolerance "
                    f"{tolerance * 100:.0f} points)")
            continue
        if unit.strip().lower() in _ABS_POINT_HIGHER_UNITS:
            delta = v_old - v_new             # a drop is the regression
            if delta > tolerance * 100.0:
                problems.append(
                    f"{name}: {v_old:g} -> {v_new:g} {unit} "
                    f"(-{delta:.1f} points, tolerance "
                    f"{tolerance * 100:.0f} points)")
            continue
        if v_old == 0:
            continue
        if lower_is_better(unit):
            ratio = v_new / v_old         # >1 means slower
            if ratio > 1 + tolerance:
                problems.append(
                    f"{name}: {v_old:g} -> {v_new:g} {unit} "
                    f"(+{(ratio - 1) * 100:.1f}%, tolerance "
                    f"{tolerance * 100:.0f}%)")
        else:
            ratio = v_new / v_old         # <1 means less throughput
            if ratio < 1 - tolerance:
                problems.append(
                    f"{name}: {v_old:g} -> {v_new:g} {unit} "
                    f"(-{(1 - ratio) * 100:.1f}%, tolerance "
                    f"{tolerance * 100:.0f}%)")
    missing = set(prev) - {m["metric"] for m in new}
    for name in sorted(missing):
        problems.append(f"{name}: metric disappeared from the new record")
    return problems


def compare_common(old: List[dict], new: List[dict],
                   tolerance: float = DEFAULT_TOLERANCE) -> List[str]:
    """Gate only the metrics present in BOTH records (no 'disappeared'
    check). This is the in-run self-gate's comparator: a ``--quick`` bench
    run (BERT only) or a run where a diagnostic leg failed must not log
    every intentionally-skipped benchmark as a false regression; the full
    cross-record CLI gate (:func:`compare`) keeps the disappearance check
    for CI use."""
    names = {m["metric"] for m in new}
    return compare([m for m in old if m.get("metric") in names], new,
                   tolerance)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    tol = DEFAULT_TOLERANCE
    if "--tolerance" in argv:
        i = argv.index("--tolerance")
        try:
            tol = float(argv[i + 1])
        except (IndexError, ValueError):
            print("--tolerance needs a float", file=sys.stderr)
            return 2
        del argv[i:i + 2]
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        with open(argv[0]) as f:
            old = _metric_list(json.load(f))
        with open(argv[1]) as f:
            new = _metric_list(json.load(f))
    except (OSError, json.JSONDecodeError) as e:
        print(f"cannot read records: {e}", file=sys.stderr)
        return 2
    if not old:
        print(f"{argv[0]}: no metric lines found (nothing to gate)")
        return 0
    problems = compare(old, new, tol)
    if problems:
        print("BENCH REGRESSION:")
        for p in problems:
            print("  " + p)
        return 1
    print(f"bench gate ok: {len(new)} metric(s), none regressed beyond "
          f"{tol * 100:.0f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
