"""Render a monitor-registry JSONL dump as a human-readable report.

CI/tooling companion of paddle_tpu.monitor (the analogue of the
reference's profiler summary tables, but fed from the metrics registry):
given the append-only JSONL written by ``MetricsRegistry.dump_jsonl``
(or an hapi ``MonitorCallback`` stream) — prints:

- the top-k slowest timing histograms (by total seconds);
- compile/recompile counters (TrainStep jit entries + the process-wide
  jax backend-compile / persistent-cache / scan-trace gauges);
- comms traffic: bytes/ops/mean dispatch latency by (op, group);
- with ``--memory``: per-program HBM budget table
  (``train_step_program_*`` gauges) + the live-buffer census
  (``live_buffer_bytes`` by category, from monitor.memory);
- with ``--comms``: the pipeline schedule's comm-model gauges
  (``pipeline_comm_ops_per_step`` / ``pipeline_bubble_fraction``,
  docs/PARALLELISM.md). Exposed collective TIME is a device metric:
  ``collective.time_pct`` of the mesh cell (``PERF.md`` section 3);
- with ``--moe``: the MoE router-health view — a per-layer table of the
  ``moe_router_*`` gauges (balance/drop/entropy + per-expert load
  spread), the dropped-token counter, and expert-parallel fallback
  counts (docs/MOE.md; rendered next to the --comms output);
- with ``--serve``: the serving engine's per-request latency histograms
  (TTFT/TPOT/e2e/decode-step with approximate p50/p99), decode batching
  occupancy, queue-depth/slot/page gauges, serving program HBM
  budgets, and the multi-tenant view — per-tenant request outcomes and
  quota deferrals plus the LoRA adapter pool and quantized-KV
  footprint (``serve_*``/``serve_tenant_*``/``serve_lora_*`` series
  from paddle_tpu.serving; docs/SERVING.md);
- with ``--fleet``: the fleet router's per-replica table (queue depth,
  prefix hit%, shed counts) and routing/migration counters + route
  latency (``serve_router_*`` series from paddle_tpu.serving.router;
  docs/SERVING.md fleet topology; rendered before --serve so router
  series appear here, once);
- with ``--recsys``: the embedding-tier view — per-table occupancy and
  hit rates across the HBM/host/SSD tiers, promotion/eviction
  counters, per-table HBM attribution and sharded-lookup fallbacks
  (``recsys_*`` series from paddle_tpu.recsys; docs/RECSYS.md;
  rendered next to --serve/--moe);
- with ``--slo``: the error-budget burn table from the ``slo_*`` gauges
  (monitor/slo.py) — per SLO the objective, period budget remaining and
  burn rate per window (1.0 = spending exactly the budget; rendered
  next to --serve, which tells you *what* is failing while this tells
  you *how fast the budget goes*);
- with ``--lifecycle``: the zero-downtime model-push view — hot-swap
  event counters (``serve_swaps_total``), the live weights epoch and
  promotion-controller state, the state/epoch timeline from repeated
  dumps, per-arm shadow/A-B outcomes + latency and greedy
  shadow-divergence counts (``serve_lifecycle_*``/``serve_arm_*``
  series from paddle_tpu.serving.lifecycle; docs/SERVING.md "Model
  lifecycle"; rendered next to --serve/--slo);
- with ``--goodput``: the training goodput view — the
  ``train_goodput_pct`` gauge, cumulative badput seconds by exclusive
  bucket (``train_badput_seconds_total``), and the per-layer model
  health table (``train_layer_{grad_norm,param_norm,update_ratio}``
  gauges + ``train_health_spikes_total``) from the goodput ledger
  (monitor/goodput.py; docs/OBSERVABILITY.md "Training goodput & model
  health");
- with ``--fallbacks``: every counted degradation in ONE table — scan
  loop-layout, Pallas-kernel XLA, pipeline sequential-GSPMD, MoE and
  recsys auto-path fallbacks with reason labels ("why is this run
  slow" starts here, not at five separate counters);
- everything else (counters/gauges) as a flat table.

``--kernels`` needs no input file: it enumerates the live
``paddle_tpu.ops.pallas`` kernel registry — per kernel the kill-switch
flag and its current value, whether dispatch would serve the Pallas body
on THIS backend (``live``), the XLA fallback that serves otherwise, and
any fallback counts observed in this process (``PALLAS_STATS``; the
persistent view is the ``pallas_fallback_total{kernel,reason}`` counter
in a monitor dump, rendered by the default counter table).

``--flight`` switches input format entirely: the argument is a crash
flight-recorder dump (monitor/flight_recorder.py JSON) and the report
shows trip reason, environment fingerprint, a *recovery timeline*
(checkpoint commits/fallbacks, collective timeouts, non-finite skips,
preemptions, chaos fires — docs/FAULT_TOLERANCE.md), the event log and
the last-N step records.

``--trace`` also switches input format: the argument is a structured
trace dump (``monitor.trace.Tracer.dump`` JSON, or a flight-recorder
dump carrying a ``traces`` section) and the report renders each span
tree with per-span duration, EXCLUSIVE time and the critical path
(``*``), plus an exclusive-time-by-span attribution table
(docs/OBSERVABILITY.md "Structured tracing").

Usage:
    python tools/monitor_report.py monitor.jsonl [--top 10] [--memory] [--serve] [--fleet] [--slo] [--lifecycle] [--goodput] [--comms] [--moe] [--recsys] [--fallbacks]
    python tools/monitor_report.py --flight flight_recorder_123.json [--last 20]
    python tools/monitor_report.py --trace traces.json [--last 20]
    python tools/monitor_report.py --kernels

Exit code: 0 on success (including an empty report), 2 on usage/read
errors. Append-only input is expected: the NEWEST sample per
(name, labels) wins.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Tuple


def _latest_samples(rows: List[dict]) -> Dict[Tuple[str, tuple], dict]:
    """Newest line per (name, labels) — file order breaks ts ties, so the
    last appended dump wins."""
    out: Dict[Tuple[str, tuple], dict] = {}
    for row in rows:
        labels = tuple(sorted((row.get("labels") or {}).items()))
        out[(row["name"], labels)] = row
    return out


def _fmt_labels(labels: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in labels) if labels else "-"


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:,.1f} {unit}"
        n /= 1024
    return f"{n:,.1f} TiB"


def _table(title: str, headers: List[str],
           rows: List[List[str]]) -> List[str]:
    if not rows:
        return []
    widths = [max(len(h), *(len(r[i]) for r in rows))
              for i, h in enumerate(headers)]
    lines = [f"== {title} ==",
             "  ".join(h.ljust(w) for h, w in zip(headers, widths))]
    for r in rows:
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    lines.append("")
    return lines


def _comms_section(latest, used) -> List[str]:
    """--comms: the pipeline schedule's comm model (collectives a step,
    bubble fraction, counted fallbacks)."""
    out: List[str] = []
    m_rows = []
    for key in sorted(latest):
        name, labels = key
        if name in ("pipeline_comm_ops_per_step",
                    "pipeline_bubble_fraction",
                    "pipeline_fallback_total"):
            used.add(key)
            m_rows.append([name, _fmt_labels(labels),
                           f"{latest[key].get('value', 0):g}"])
    out += _table("Pipeline schedule comm model",
                  ["metric", "labels", "value"], m_rows)
    if not m_rows:
        out.append("(no pipeline gauges in this dump — run a pipeline "
                   "TrainStep with FLAGS_monitor on)")
        out.append("")
    return out


def _goodput_section(latest, used) -> List[str]:
    """--goodput: training goodput ledger + per-layer model health.
    Buckets are EXCLUSIVE and sum to trainer wall-clock (the ledger's
    exhaustiveness invariant), so the badput table reads as a complete
    where-did-the-time-go attribution, not a sample."""
    out: List[str] = []
    g_rows = []
    for key in sorted(latest):
        name, labels = key
        if name in ("train_goodput_pct", "train_step_mfu"):
            used.add(key)
            g_rows.append([name, _fmt_labels(labels),
                           f"{latest[key].get('value', 0.0):,.2f}"])
    out += _table("Training goodput (FLAGS_train_goodput)",
                  ["metric", "labels", "value"], g_rows)
    b_rows = []
    for key, row in latest.items():
        name, labels = key
        if name != "train_badput_seconds_total":
            continue
        used.add(key)
        b_rows.append([str(dict(labels).get("bucket", "?")),
                       float(row.get("value", 0.0))])
    b_rows.sort(key=lambda r: -r[1])
    out += _table("Badput by bucket (exclusive, cumulative seconds)",
                  ["bucket", "seconds"],
                  [[b, f"{s:,.2f}"] for b, s in b_rows])
    # per-layer health gauges fold into one row per layer, worst grad
    # norm first — the monitor_top "top offenders" view, in full
    per: Dict[str, dict] = {}
    short = {"train_layer_grad_norm": "grad",
             "train_layer_param_norm": "param",
             "train_layer_update_ratio": "update"}
    for key, row in latest.items():
        name, labels = key
        if name in short:
            used.add(key)
            layer = str(dict(labels).get("layer", "?"))
            per.setdefault(layer, {})[short[name]] = \
                float(row.get("value", 0.0))
        elif name == "train_health_spikes_total":
            used.add(key)
            layer = str(dict(labels).get("layer", "?"))
            per.setdefault(layer, {})["spikes"] = \
                float(row.get("value", 0.0))
    l_rows = [[layer, f"{d.get('grad', 0.0):,.4g}",
               f"{d.get('param', 0.0):,.4g}",
               f"{d.get('update', 0.0):,.2e}",
               f"{d.get('spikes', 0.0):g}"]
              for layer, d in sorted(per.items(),
                                     key=lambda kv:
                                     -kv[1].get("grad", 0.0))]
    out += _table("Per-layer model health (FLAGS_train_health_every)",
                  ["layer", "grad norm", "param norm", "update ratio",
                   "spikes"], l_rows)
    if not g_rows and not b_rows and not l_rows:
        out.append("(no goodput series in this dump — train with "
                   "FLAGS_train_goodput on; per-layer health additionally "
                   "needs FLAGS_train_health_every=N)")
        out.append("")
    return out


def _moe_section(latest, used) -> List[str]:
    """--moe: per-layer router-health table from the ``moe_router_*``
    gauges MoE layers publish (balance/drop/entropy + per-expert load
    min/max spread), the dropped-token counter, and any
    ``moe_fallback_total`` telemetry — the routing-health companion to
    --comms' comm-overlap view (docs/MOE.md)."""
    per: Dict[str, dict] = {}
    loads: Dict[str, Dict[int, float]] = {}
    for key, row in latest.items():
        name, labels = key
        d = dict(labels)
        if name in ("moe_router_balance_pct", "moe_router_drop_pct",
                    "moe_router_entropy", "moe_dropped_tokens_total"):
            used.add(key)
            per.setdefault(str(d.get("layer", "-")), {})[name] = \
                row.get("value", 0.0)
        elif name == "moe_expert_load_share":
            used.add(key)
            loads.setdefault(str(d.get("layer", "-")), {})[
                int(d.get("expert", 0))] = row.get("value", 0.0)
    def _layer_key(name: str):
        # "layer10" must sort after "layer2": split the trailing int out
        import re
        m = re.match(r"^(.*?)(\d+)$", name)
        return (m.group(1), int(m.group(2))) if m else (name, -1)

    rows = []
    for layer in sorted(per | loads, key=_layer_key):
        d = per.get(layer, {})
        ld = loads.get(layer, {})
        spread = (f"{min(ld.values()):.3f}/{max(ld.values()):.3f}"
                  if ld else "-")
        rows.append([
            layer,
            f"{d.get('moe_router_balance_pct', 0.0):.1f}",
            f"{d.get('moe_router_drop_pct', 0.0):.1f}",
            f"{d.get('moe_router_entropy', 0.0):.3f}",
            spread,
            f"{d.get('moe_dropped_tokens_total', 0.0):g}"])
    out = _table("MoE router health (per layer)",
                 ["layer", "balance%", "drop%", "entropy",
                  "load min/max", "dropped total"], rows)
    f_rows = []
    for key in sorted(latest):
        name, labels = key
        if name == "moe_fallback_total":
            used.add(key)
            f_rows.append([name, _fmt_labels(labels),
                           f"{latest[key].get('value', 0):g}"])
    out += _table("MoE expert-parallel fallbacks",
                  ["counter", "labels", "value"], f_rows)
    if not rows and not f_rows:
        out.append("(no moe_router_* gauges in this dump — run an eager "
                   "MoE forward with FLAGS_monitor on, or "
                   "publish_moe_telemetry/publish_router_stats)")
        out.append("")
    return out


def _recsys_section(latest, used) -> List[str]:
    """--recsys: per-table tier occupancy, hit rates, promotion/eviction
    counters and HBM attribution from the ``recsys_*`` gauges the tier
    manager publishes (docs/RECSYS.md) — the embedding-tier companion
    to --serve's latency view."""
    occ: Dict[str, Dict[str, float]] = {}
    rates: Dict[str, Dict[str, float]] = {}
    hits: Dict[str, Dict[str, float]] = {}
    flow: Dict[str, Dict[str, float]] = {}
    hbm: Dict[str, float] = {}
    for key, row in latest.items():
        name, labels = key
        d = dict(labels)
        table = str(d.get("table", "-"))
        tier = str(d.get("tier", "-"))
        if name == "recsys_table_rows":
            used.add(key)
            occ.setdefault(table, {})[tier] = row.get("value", 0.0)
        elif name == "recsys_tier_hit_pct":
            used.add(key)
            rates.setdefault(table, {})[tier] = row.get("value", 0.0)
        elif name == "recsys_tier_hits_total":
            used.add(key)
            hits.setdefault(table, {})[tier] = row.get("value", 0.0)
        elif name in ("recsys_tier_promotions_total",
                      "recsys_tier_demotions_total",
                      "recsys_tier_evictions_total"):
            used.add(key)
            flow.setdefault(table, {})[
                name[len("recsys_tier_"):-len("_total")]] = \
                row.get("value", 0.0)
        elif name == "recsys_table_hbm_bytes":
            used.add(key)
            hbm[table] = row.get("value", 0.0)
    rows = []
    for table in sorted(set(occ) | set(rates) | set(hits) | set(flow)
                        | set(hbm)):
        o, r, f = occ.get(table, {}), rates.get(table, {}), \
            flow.get(table, {})
        rows.append([
            table,
            "/".join(f"{int(o.get(t, 0))}" for t in ("hbm", "host",
                                                     "ssd")),
            "/".join(f"{r.get(t, 0.0):.1f}" for t in ("hbm", "host",
                                                      "ssd")),
            f"{sum(hits.get(table, {}).values()):g}",
            f"{f.get('promotions', 0):g}",
            f"{f.get('evictions', 0):g}",
            _fmt_bytes(hbm.get(table, 0.0))])
    out = _table("Recsys embedding tiers (per table)",
                 ["table", "rows hbm/host/ssd", "hit% hbm/host/ssd",
                  "fetches", "promoted", "evicted", "HBM bytes"], rows)
    f_rows = []
    for key in sorted(latest):
        name, labels = key
        if name == "recsys_fallback_total":
            used.add(key)
            f_rows.append([name, _fmt_labels(labels),
                           f"{latest[key].get('value', 0):g}"])
    out += _table("Recsys sharded-lookup fallbacks",
                  ["counter", "labels", "value"], f_rows)
    if not rows and not f_rows:
        out.append("(no recsys_* gauges in this dump — call "
                   "publish_tier_metrics() first)")
        out.append("")
    return out


def _slo_section(latest, used) -> List[str]:
    """--slo: error-budget burn table from the ``slo_*`` gauges
    (monitor/slo.py; PR 11 emits them, this mode renders them) — per
    SLO the configured objective, the period budget remaining, and the
    burn rate per configured window (1.0 = spending exactly the
    budget; the SRE-workbook alert pairs fire around 6-14x). Rendered
    next to --serve/--trace/--fallbacks."""
    objective: Dict[str, float] = {}
    remaining: Dict[str, float] = {}
    burns: Dict[str, Dict[str, float]] = {}
    for key, row in latest.items():
        name, labels = key
        d = dict(labels)
        if name == "slo_objective":
            used.add(key)
            objective[str(d.get("slo", "-"))] = row.get("value", 0.0)
        elif name == "slo_error_budget_remaining":
            used.add(key)
            remaining[str(d.get("slo", "-"))] = row.get("value", 0.0)
        elif name == "slo_burn_rate":
            used.add(key)
            burns.setdefault(str(d.get("slo", "-")), {})[
                str(d.get("window", "?"))] = row.get("value", 0.0)

    def _window_key(w: str):
        try:
            return (0, float(w.rstrip("s")))
        except ValueError:
            return (1, 0.0)

    windows = sorted({w for d in burns.values() for w in d},
                     key=_window_key)
    rows = []
    for slo in sorted(set(objective) | set(remaining) | set(burns)):
        b = burns.get(slo, {})
        rem = remaining.get(slo)
        rows.append(
            [slo,
             f"{objective.get(slo, 0.0):.4g}" if slo in objective
             else "-",
             (f"{rem:.3f}" + (" (BLOWN)" if rem < 0 else ""))
             if rem is not None else "-"]
            + [f"{b[w]:.2f}" if w in b else "-" for w in windows])
    out = _table("SLO error-budget burn (1.0 = on budget)",
                 ["slo", "objective", "budget left"]
                 + [f"burn {w}" for w in windows], rows)
    if not rows:
        out = ["== SLO burn ==",
               "(no slo_* gauges in this dump — arm "
               "ServingConfig.slo_availability / slo_deadline, or call "
               "SLOTracker.publish())", ""]
    return out


#: lifecycle-state gauge codes (serve_lifecycle_state) — fallback copy
#: for a standalone checkout; the live tuple is
#: paddle_tpu.serving.lifecycle.STATES and a sync-pin test keeps them
#: from drifting
_LIFECYCLE_STATES_FALLBACK = ("serving", "staging", "baking", "promoted",
                              "rolled-back")


def _lifecycle_states() -> tuple:
    try:
        from paddle_tpu.serving.lifecycle import STATES
        return tuple(STATES)
    except Exception:
        return _LIFECYCLE_STATES_FALLBACK


def _lifecycle_timeline(rows: List[dict], used) -> List[str]:
    """Controller-state timeline from EVERY serve_lifecycle_state and
    serve_weights_epoch sample in the (append-only) dump, in file
    order — repeated registry dumps trace a staged push through
    staging -> baking -> promoted (or rolled-back), interleaved with
    the epoch bumps of each cutover."""
    states = _lifecycle_states()
    samples = [r for r in rows
               if r.get("name") in ("serve_lifecycle_state",
                                    "serve_weights_epoch")]
    if not samples:
        return []
    t0 = next((r["ts"] for r in samples
               if isinstance(r.get("ts"), (int, float))), None)
    out, last = [], {}
    for r in samples:
        name = r["name"]
        used.add((name, tuple(sorted((r.get("labels") or {}).items()))))
        v = r.get("value")
        if name == "serve_lifecycle_state":
            code = int(v or 0)
            what = (states[code] if 0 <= code < len(states)
                    else f"state {code}")
        else:
            what = f"weights epoch -> {v:g}" if v is not None else "-"
        if last.get(name) == what:
            continue
        last[name] = what
        ts = r.get("ts")
        rel = (f"+{ts - t0:.2f}s"
               if isinstance(ts, (int, float)) and t0 is not None
               else "-")
        out.append([rel, what])
    return _table("Lifecycle timeline", ["t", "event"], out)


def _lifecycle_section(latest, used,
                       raw_rows: Optional[List[dict]] = None) -> List[str]:
    """--lifecycle: the zero-downtime model-push view (docs/SERVING.md
    "Model lifecycle") — hot-swap event counters
    (``serve_swaps_total{event}``), the live weights epoch and
    controller state, the state/epoch timeline, per-arm shadow/A-B
    outcomes + latency (``serve_arm_*``) and greedy shadow-divergence
    counts, plus the candidate's burn gauges when an SLOTracker named
    ``lifecycle_*`` published (peeked, not claimed — ``--slo`` still
    renders the full burn table). Rendered next to --serve/--slo."""
    states = _lifecycle_states()
    swap_rows, s_rows = [], []
    arm_counts: Dict[str, Dict[str, float]] = {}
    arm_lat: Dict[str, dict] = {}
    divergence = None
    for key in sorted(latest):
        name, labels = key
        row = latest[key]
        d = dict(labels)
        if name == "serve_swaps_total":
            used.add(key)
            swap_rows.append([str(d.get("event", "-")),
                              f"{row.get('value', 0):g}"])
        elif name == "serve_weights_epoch":
            used.add(key)
            s_rows.append(["live weights epoch",
                           f"{row.get('value', 0):g}"])
        elif name == "serve_lifecycle_state":
            used.add(key)
            code = int(row.get("value") or 0)
            s_rows.append(["controller state",
                           states[code] if 0 <= code < len(states)
                           else f"state {code}"])
        elif name == "serve_lifecycle_transitions_total":
            used.add(key)
            s_rows.append([f"transitions -> {d.get('to', '-')}",
                           f"{row.get('value', 0):g}"])
        elif name == "serve_arm_requests_total":
            used.add(key)
            arm_counts.setdefault(str(d.get("arm", "-")), {})[
                str(d.get("event", "-"))] = row.get("value", 0.0)
        elif name == "serve_arm_e2e_seconds":
            used.add(key)
            arm_lat[str(d.get("arm", "-"))] = row
        elif name == "serve_shadow_divergence_total":
            used.add(key)
            divergence = row.get("value", 0.0)
    out = _table("Lifecycle (hot-swap push state)",
                 ["what", "value"], s_rows)
    out += _table("Weight-swap events (serve_swaps_total)",
                  ["event", "count"], swap_rows)
    a_rows = []
    for arm in sorted(set(arm_counts) | set(arm_lat)):
        counts = arm_counts.get(arm, {})
        lat = arm_lat.get(arm)
        n = int(lat.get("count") or 0) if lat else 0
        mean = (lat["sum"] / n * 1e3) if lat and n else 0.0
        p99 = _hist_pct(lat, 0.99) if lat else None
        a_rows.append(
            [arm, f"{sum(counts.values()):g}",
             ",".join(f"{e}={v:g}" for e, v in sorted(counts.items()))
             or "-",
             f"{mean:,.2f}" if n else "-",
             f"<= {p99 * 1e3:,.1f}" if p99 is not None else "-"])
    out += _table("Shadow/A-B arms",
                  ["arm", "requests", "outcomes", "mean e2e ms",
                   "~p99 ms"], a_rows)
    if divergence is not None:
        out += [f"  greedy shadow divergences: {divergence:g}", ""]
    # candidate burn at a glance — peek the lifecycle_* SLO gauges
    # WITHOUT used.add so --slo (rendered before this section) keeps
    # its full table and the generic tables stay deduplicated there
    b_rows = []
    for key in sorted(latest):
        name, labels = key
        d = dict(labels)
        if (name == "slo_burn_rate"
                and str(d.get("slo", "")).startswith("lifecycle")):
            b_rows.append([str(d.get("slo")), str(d.get("window", "?")),
                           f"{latest[key].get('value', 0.0):.2f}"])
    out += _table("Candidate burn (slo_burn_rate, 1.0 = on budget)",
                  ["slo", "window", "burn"], b_rows)
    out += _lifecycle_timeline(raw_rows or [], used)
    if not out:
        out = ["== Lifecycle ==",
               "(no serve_swaps_total / serve_lifecycle_* metrics in "
               "this dump — enable FLAGS_serve_hot_swap and push a "
               "manifest through ServingEngine.swap_weights or "
               "LifecycleController.begin first)", ""]
    return out


#: the counted-degradation counters every subsystem publishes when its
#: primary path cannot serve (docs: PARALLELISM/MOE/RECSYS; nn/scan.py;
#: ops/pallas/__init__.py); one table answers "why is this run slow"
#: instead of five separate counter greps
_FALLBACK_COUNTERS = ("scan_fallback_total", "pallas_fallback_total",
                      "pipeline_fallback_total", "moe_fallback_total",
                      "recsys_fallback_total")


def _fallbacks_section(latest, used) -> List[str]:
    """--fallbacks: every counted degradation in one table — scan
    loop-layout fallbacks, Pallas-kernel XLA fallbacks, pipeline
    sequential-GSPMD degradations and MoE auto-path fallbacks, each
    with its reason labels."""
    rows = []
    total = 0.0
    for cname in _FALLBACK_COUNTERS:
        for key in sorted(latest):
            name, labels = key
            if name != cname:
                continue
            used.add(key)
            v = float(latest[key].get("value", 0.0))
            total += v
            rows.append([name[:-len("_fallback_total")],
                         _fmt_labels(labels), f"{v:g}"])
    if not rows:
        return ["== Fallbacks / degradations ==",
                "(no *_fallback_total counters in this dump — every "
                "subsystem served its primary path, or FLAGS_monitor "
                "was off while they fell back)", ""]
    return _table(f"Fallbacks / degradations ({total:g} total)",
                  ["subsystem", "reason", "count"], rows)


def _memory_section(latest, used) -> List[str]:
    """--memory: per-program HBM budgets + the live-buffer census."""
    prog: Dict[str, dict] = {}
    for key, row in latest.items():
        name, labels = key
        if name.startswith("train_step_program_"):
            used.add(key)
            kind = dict(labels).get("kind", "-")
            prog.setdefault(kind, {})[
                name[len("train_step_program_"):]] = row.get("value", 0.0)
    p_rows = []
    for kind in sorted(prog):
        d = prog[kind]
        flops, acc = d.get("flops", 0.0), d.get("bytes_accessed", 0.0)
        p_rows.append([kind, _fmt_bytes(d.get("peak_hbm_bytes", 0.0)),
                       f"{flops:.3e}", _fmt_bytes(acc),
                       f"{flops / acc:.1f}" if acc else "-"])
    out = _table("Program HBM budgets (static, per kind)",
                 ["kind", "peak HBM est.", "flops", "bytes accessed",
                  "arith. int."], p_rows)
    c_rows = []
    for key in sorted(latest):
        name, labels = key
        if name in ("live_buffer_bytes", "live_buffer_count"):
            used.add(key)
            if name == "live_buffer_bytes":
                cat = dict(labels).get("category", "-")
                n = latest.get(("live_buffer_count", labels), {})
                c_rows.append([cat,
                               _fmt_bytes(latest[key].get("value", 0.0)),
                               f"{n.get('value', 0):g}"])
    out += _table("Live-buffer census", ["category", "bytes", "arrays"],
                  c_rows)
    return out


def _hist_pct(row: dict, q: float) -> Optional[float]:
    """Approximate quantile from a cumulative-`le` histogram sample: the
    smallest bucket upper bound covering fraction ``q`` of observations
    (None when empty or when the quantile falls past the last bucket)."""
    count = row.get("count") or 0
    if not count:
        return None
    target = q * count
    for le, cum in row.get("buckets") or []:
        if cum >= target:
            return float(le)
    return None


#: canonical request-outcome order for the --serve table: offered
#: traffic first (submitted + never-admitted rejections), then the
#: terminal outcomes per paddle_tpu.serving.scheduler.TERMINAL_OUTCOMES
_OUTCOME_ORDER = ("submitted", "rejected", "completed", "expired",
                  "shed", "cancelled", "failed", "drained")


def _serve_outcomes(latest, used) -> List[str]:
    """Request-outcome table from serve_requests_total{event=...}: where
    every request ended up (zero-lost accounting — docs/SERVING.md,
    "Operating under overload and failure"). Terminal outcomes are a
    share of SUBMITTED requests; "rejected" (refused at admission,
    never submitted) is a share of OFFERED = submitted + rejected."""
    counts = {}
    for key, row in latest.items():
        name, labels = key
        if name != "serve_requests_total":
            continue
        used.add(key)
        counts[dict(labels).get("event", "?")] = row.get("value", 0.0)
    if not counts:
        return []
    submitted = counts.get("submitted", 0.0)
    offered = submitted + counts.get("rejected", 0.0)
    rows = []
    for ev in list(_OUTCOME_ORDER) + sorted(set(counts) -
                                            set(_OUTCOME_ORDER)):
        if ev not in counts:
            continue
        if ev == "submitted":
            pct = (f"{100.0 * submitted / offered:.1f}% of offered"
                   if offered else "-")
        elif ev == "rejected":
            pct = (f"{100.0 * counts[ev] / offered:.1f}% of offered"
                   if offered else "-")
        else:
            pct = (f"{100.0 * counts[ev] / submitted:.1f}% of submitted"
                   if submitted else "-")
        rows.append([ev, f"{counts[ev]:g}", pct])
    return _table("Request outcomes", ["event", "count", "share"],
                  rows)


def _prefix_cache_section(latest, used) -> List[str]:
    """Radix prefix cache (ISSUE 15): occupancy, hit/miss/evict
    counters and the token-level hit share — the 'is chat traffic
    actually sharing prefixes' panel next to the outcome table."""
    vals = {}
    for key, row in latest.items():
        name, _ = key
        if name in ("serve_prefix_cached_pages",
                    "serve_prefix_hits_total",
                    "serve_prefix_misses_total",
                    "serve_prefix_hit_tokens_total",
                    "serve_prefix_evicted_pages_total"):
            used.add(key)
            vals[name] = row.get("value", 0.0)
    if not vals:
        return []
    hits = vals.get("serve_prefix_hits_total", 0.0)
    misses = vals.get("serve_prefix_misses_total", 0.0)
    lookups = hits + misses
    rows = [
        ["cached pages", f"{vals.get('serve_prefix_cached_pages', 0):g}"],
        ["admission hits", f"{hits:g}"
         + (f"  ({100.0 * hits / lookups:.1f}% of lookups)"
            if lookups else "")],
        ["admission misses", f"{misses:g}"],
        ["tokens served from cache",
         f"{vals.get('serve_prefix_hit_tokens_total', 0):g}"],
        ["pages evicted",
         f"{vals.get('serve_prefix_evicted_pages_total', 0):g}"],
    ]
    return _table("Prefix cache (radix tree over KV pages)",
                  ["stat", "value"], rows)


def _spec_decode_section(latest, used) -> List[str]:
    """Speculative decoding (ISSUE 15): proposed/accepted/rolled-back
    draft counters and the acceptance rate — accepted tokens rode a
    shared verify dispatch instead of their own decode step."""
    vals = {}
    for key, row in latest.items():
        name, _ = key
        if name in ("serve_spec_proposed_total",
                    "serve_spec_accepted_total",
                    "serve_spec_rolled_back_total"):
            used.add(key)
            vals[name] = row.get("value", 0.0)
    if not vals:
        return []
    prop = vals.get("serve_spec_proposed_total", 0.0)
    acc = vals.get("serve_spec_accepted_total", 0.0)
    rows = [
        ["drafts proposed", f"{prop:g}"],
        ["drafts accepted", f"{acc:g}"
         + (f"  ({100.0 * acc / prop:.1f}% acceptance)" if prop else "")],
        ["drafts rolled back",
         f"{vals.get('serve_spec_rolled_back_total', 0):g}"],
    ]
    return _table("Speculative decoding (n-gram drafts)",
                  ["stat", "value"], rows)


def _tenant_section(latest, used) -> List[str]:
    """Multi-tenant serving (ISSUE 17): the per-tenant table — requests
    by lifecycle event from ``serve_tenant_requests_total{tenant,event}``
    and quota deferrals from
    ``serve_tenant_quota_deferrals_total{tenant}`` — plus the engine-
    wide LoRA pool (adapters loaded / hot-swaps) and quantized-KV
    footprint lines. Runs before the generic serve_* catch-all so the
    tenant-labeled series render here, once."""
    per: Dict[str, dict] = {}
    pool = {}
    for key, row in latest.items():
        name, labels = key
        if name == "serve_tenant_requests_total":
            used.add(key)
            lab = dict(labels)
            d = per.setdefault(lab.get("tenant", "?"), {})
            d[lab.get("event", "?")] = row.get("value", 0.0)
        elif name == "serve_tenant_quota_deferrals_total":
            used.add(key)
            per.setdefault(dict(labels).get("tenant", "?"),
                           {})["quota"] = row.get("value", 0.0)
        elif name in ("serve_lora_swaps_total",
                      "serve_lora_adapters_loaded",
                      "serve_kv_quant_bytes_per_token"):
            used.add(key)
            pool[name] = row.get("value", 0.0)
    out: List[str] = []
    rows = [
        [t,
         f"{d.get('submitted', 0):g}",
         f"{d.get('completed', 0):g}",
         f"{d.get('failed', 0) + d.get('expired', 0) + d.get('shed', 0):g}",
         f"{d.get('quota', 0):g}"]
        for t, d in sorted(per.items())]
    out += _table("Tenants", ["tenant", "submitted", "completed",
                              "failed/expired/shed", "quota deferrals"],
                  rows)
    if pool:
        prows = []
        if "serve_lora_adapters_loaded" in pool:
            prows.append(["LoRA adapters loaded",
                          f"{pool['serve_lora_adapters_loaded']:g}"])
        if "serve_lora_swaps_total" in pool:
            prows.append(["LoRA adapter hot-swaps",
                          f"{pool['serve_lora_swaps_total']:g}"])
        if "serve_kv_quant_bytes_per_token" in pool:
            prows.append(["quantized KV bytes/token",
                          f"{pool['serve_kv_quant_bytes_per_token']:g}"])
        out += _table("Multi-tenant pool (LoRA + quantized KV)",
                      ["stat", "value"], prows)
    return out


def _overload_timeline(rows: List[dict], used) -> List[str]:
    """Overload-state timeline from EVERY serve_overload sample in the
    (append-only) dump, in file order — each registry dump contributes
    one point, so repeated dumps trace the shedding episodes."""
    samples = [r for r in rows if r.get("name") == "serve_overload"]
    if not samples:
        return []
    used.add(("serve_overload", tuple()))
    t0 = next((r["ts"] for r in samples
               if isinstance(r.get("ts"), (int, float))), None)
    out, last = [], None
    for r in samples:
        state = "OVERLOADED (shedding)" if r.get("value") else "normal"
        if state == last:
            continue
        last = state
        ts = r.get("ts")
        rel = (f"+{ts - t0:.2f}s"
               if isinstance(ts, (int, float)) and t0 is not None
               else "-")
        out.append([rel, state])
    return _table("Overload state timeline", ["t", "state"], out)


def _fleet_section(latest, used) -> List[str]:
    """--fleet: the router's per-replica table (queue depth, prefix
    hit%, shed count — the ``serve_router_replica_*`` gauges) plus the
    fleet routing/migration counters and route-decision latency
    (docs/SERVING.md fleet topology). Runs BEFORE --serve's generic
    serve_* catch-all so router series render here, once."""
    per: Dict[str, dict] = {}
    totals = []
    for key in sorted(latest):
        name, labels = key
        if not name.startswith("serve_router_"):
            continue
        row = latest[key]
        used.add(key)
        lab = dict(labels)
        rep = lab.get("replica")
        if rep is not None:
            per.setdefault(rep, {})[name] = row.get("value", 0)
        elif name == "serve_router_route_seconds":
            n = int(row.get("count") or 0)
            mean = (row["sum"] / n * 1e3) if n else 0.0
            p99 = _hist_pct(row, 0.99)
            totals.append([name, _fmt_labels(labels),
                           f"{n} routed, mean {mean:,.3f} ms, ~p99 <= "
                           f"{(p99 or 0) * 1e3:,.3f} ms"])
        else:
            totals.append([name, _fmt_labels(labels),
                           f"{row.get('value', 0):g}"])
    rep_rows = [
        [rep,
         f"{d.get('serve_router_replica_queue_depth', 0):g}",
         f"{d.get('serve_router_replica_prefix_hit_pct', 0):.1f}",
         f"{d.get('serve_router_replica_shed_requests', 0):g}"]
        for rep, d in sorted(per.items())]
    out = _table("Fleet replicas (router view)",
                 ["replica", "queue depth", "prefix hit%", "shed"],
                 rep_rows)
    out += _table("Fleet router counters", ["metric", "labels", "value"],
                  totals)
    if not out:
        out = ["== Fleet ==", "(no serve_router_* metrics in this dump "
               "— run a FleetRouter first)", ""]
    return out


def _serve_section(latest, used, raw_rows: Optional[List[dict]] = None) \
        -> List[str]:
    """--serve: per-request latency histograms, request outcomes, the
    overload timeline + queue/occupancy gauges from the serving engine's
    registry stream (docs/SERVING.md)."""
    lat_rows = []
    for name in ("serve_ttft_seconds", "serve_tpot_seconds",
                 "serve_e2e_seconds", "serve_decode_step_seconds",
                 "serve_prefill_seconds"):
        for key, row in sorted(latest.items()):
            if key[0] != name or row.get("type") != "histogram":
                continue
            used.add(key)
            n = int(row.get("count") or 0)
            mean = (row["sum"] / n * 1e3) if n else 0.0
            p50, p99 = _hist_pct(row, 0.50), _hist_pct(row, 0.99)
            fmt = lambda v: f"<= {v * 1e3:,.1f}" if v is not None else "-"
            lat_rows.append([name[len("serve_"):], _fmt_labels(key[1]),
                             str(n), f"{mean:,.2f}", fmt(p50), fmt(p99)])
    out = _table("Serving latency (per-request histograms)",
                 ["series", "labels", "count", "mean ms", "~p50 ms",
                  "~p99 ms"], lat_rows)
    out += _serve_outcomes(latest, used)
    out += _prefix_cache_section(latest, used)
    out += _spec_decode_section(latest, used)
    out += _tenant_section(latest, used)
    out += _overload_timeline(raw_rows or [], used)
    occ_rows, g_rows, c_rows, prog_rows = [], [], [], []
    for key in sorted(latest):
        name, labels = key
        if not name.startswith("serve_") or key in used:
            continue
        row = latest[key]
        used.add(key)
        if name == "serve_decode_occupancy":
            n = int(row.get("count") or 0)
            mean = row["sum"] / n if n else 0.0
            occ_rows.append([str(n), f"{mean:,.2f}",
                             f"{_hist_pct(row, 1.0) or 0:g}"])
        elif name == "serve_program_peak_hbm_bytes":
            prog_rows.append([dict(labels).get("kind", "-"),
                              _fmt_bytes(row.get("value", 0.0))])
        elif row.get("type") == "gauge":
            g_rows.append([name, _fmt_labels(labels),
                           f"{row.get('value', 0):g}"])
        elif row.get("type") == "counter":
            c_rows.append([name, _fmt_labels(labels),
                           f"{row.get('value', 0):g}"])
    out += _table("Decode batching", ["dispatches", "mean occupancy",
                                      "max bucket"], occ_rows)
    out += _table("Queue / slots / pages (gauges)",
                  ["gauge", "labels", "value"], g_rows)
    out += _table("Serving counters", ["counter", "labels", "value"],
                  c_rows)
    out += _table("Serving program HBM budgets",
                  ["kind", "peak HBM est."], prog_rows)
    if not out:
        out = ["== Serving ==", "(no serve_* metrics in this dump — "
               "run a ServingEngine first)", ""]
    return out


# recovery-timeline event names: the canonical tuple lives in
# paddle_tpu.monitor.flight_recorder.RECOVERY_EVENTS and is imported
# lazily; this fallback copy ONLY serves a standalone checkout where
# the framework cannot import (and a sync-pin test asserts it can
# never drift from the canonical tuple)
_RECOVERY_EVENTS_FALLBACK = (
    "checkpoint_commit", "checkpoint_fallback", "collective_timeout",
    "nonfinite_skip", "preempted", "trip", "chaos", "request_failed",
    "request_expired", "request_cancelled", "request_drained",
    "request_shed", "decode_watchdog", "overload", "drained",
    "replica_migration", "health_spike", "serve_step_stall")


def _recovery_events() -> tuple:
    try:
        from paddle_tpu.monitor.flight_recorder import RECOVERY_EVENTS
        return RECOVERY_EVENTS
    except Exception:
        return _RECOVERY_EVENTS_FALLBACK


def _recovery_section(events: List[dict]) -> List[str]:
    """Chronological fault/recovery timeline: what failed, what the
    runtime did about it, relative to the first recovery event."""
    recov = [r for r in events if r.get("event") in _recovery_events()]
    if not recov:
        return []
    t0 = next((r["ts"] for r in recov
               if isinstance(r.get("ts"), (int, float))), None)
    rows = []
    for r in recov:
        ts = r.get("ts")
        rel = (f"+{ts - t0:.2f}s" if isinstance(ts, (int, float))
               and t0 is not None else "-")
        detail = ", ".join(f"{k}={v}" for k, v in sorted(r.items())
                           if k not in ("event", "ts"))
        rows.append([rel, str(r.get("event")), detail])
    return _table(f"Recovery timeline ({len(recov)} events)",
                  ["t", "event", "detail"], rows)


def render_flight(doc: dict, last: int = 10) -> str:
    """Render a flight-recorder dump: trip reason, fingerprint, the
    fault/recovery timeline, events, last-N step records."""
    lines = ["== Flight recorder dump =="]
    reason = doc.get("reason", "?")
    trip = doc.get("trip_step")
    lines.append(f"reason: {reason}"
                 + (f" (trip at step {trip})" if trip is not None else ""))
    if doc.get("exception"):
        lines.append(f"exception: {doc['exception']}")
    fp = doc.get("fingerprint") or {}
    lines.append("fingerprint: " + (", ".join(
        f"{k}={fp[k]}" for k in sorted(fp) if k != "argv") or "(none)"))
    lines.append("")
    # goodput dump provider (monitor/goodput.py): the ledger snapshot
    # at trip time — how much of the run's wall-clock was productive
    # when this dump fired, and where the rest went
    gp = doc.get("goodput")
    if isinstance(gp, dict):
        lines.append(f"goodput: {float(gp.get('goodput_pct', 0)):,.1f}% "
                     f"of {float(gp.get('elapsed_s', 0)):,.1f}s "
                     f"productive ({int(gp.get('restarts', 0))} "
                     "prior restarts)")
        b_rows = [[b, f"{float(s):,.2f}"]
                  for b, s in sorted((gp.get("buckets") or {}).items(),
                                     key=lambda kv: -float(kv[1]))
                  if float(s) > 0]
        if b_rows:
            lines.append("")
            lines += _table("Goodput buckets at dump (seconds)",
                            ["bucket", "seconds"], b_rows)
    lh = doc.get("layer_health")
    if isinstance(lh, dict) and lh.get("layers"):
        h_rows = [[layer, f"{float(d.get('grad_norm', 0)):,.4g}",
                   f"{float(d.get('param_norm', 0)):,.4g}",
                   f"{float(d.get('update_ratio', 0)):,.2e}"]
                  for layer, d in sorted(
                      lh["layers"].items(),
                      key=lambda kv:
                      -float(kv[1].get("grad_norm", 0)))]
        lines.append("")
        lines += _table("Last layer-health vector "
                        f"(step {lh.get('step', '?')})",
                        ["layer", "grad norm", "param norm",
                         "update ratio"], h_rows)
    ev = doc.get("events") or []
    lines += _recovery_section(ev)
    e_rows = [[str(r.get("event", "?")),
               str(r.get("kind", r.get("op", "-"))),
               str(r.get("step", "-")),
               ", ".join(f"{k}={v}" for k, v in sorted(r.items())
                         if k not in ("event", "kind", "op", "step",
                                      "ts"))]
              for r in ev[-last:]]
    lines += _table(f"Events (last {min(last, len(ev))} of {len(ev)})",
                    ["event", "what", "step", "detail"], e_rows)
    steps = doc.get("steps") or []
    s_rows = []
    for r in steps[-last:]:
        def num(v, fmt="{:.3f}"):
            return fmt.format(v) if isinstance(v, (int, float)) \
                else (str(v) if v is not None else "-")
        s_rows.append([str(r.get("step", "-")), str(r.get("kind", "-")),
                       num(r.get("loss"), "{:.5f}"),
                       num(r.get("wall_ms")), num(r.get("dispatch_ms")),
                       str(r.get("seed", "-"))])
    lines += _table(f"Step records (last {min(last, len(steps))} of "
                    f"{len(steps)}, ring capacity "
                    f"{doc.get('capacity', '?')})",
                    ["step", "kind", "loss", "wall ms", "dispatch ms",
                     "seed"], s_rows)
    if not ev and not steps:
        lines.append("(no step records or events in this dump)")
    return "\n".join(lines).rstrip() + "\n"


def _span_times(tdoc: dict):
    """(spans, children, dur, end) helpers for one trace dict; open
    spans render as zero-duration at their start."""
    spans = [s for s in (tdoc.get("spans") or [])
             if s.get("t0") is not None]
    by_id = {s["span_id"]: s for s in spans}
    children: Dict[int, List[dict]] = {}
    roots: List[dict] = []
    for s in spans:
        pid = s.get("parent_id")
        if pid is None or pid not in by_id:
            roots.append(s)
        else:
            children.setdefault(pid, []).append(s)
    for v in children.values():
        v.sort(key=lambda s: (s["t0"], s["span_id"]))

    def end(s):
        return s["t1"] if s.get("t1") is not None else s["t0"]

    def dur(s):
        return max(0.0, end(s) - s["t0"])

    return spans, roots, children, dur, end


def _render_one_trace(tdoc: dict,
                      agg: Dict[str, List[float]]) -> List[str]:
    """One trace's span tree: per-span duration, EXCLUSIVE time
    (duration minus direct children — where the time actually went) and
    a ``*`` on the critical path (the root-to-leaf chain through each
    level's latest-ending child). ``agg`` accumulates exclusive time by
    normalized span name across traces."""
    import re
    spans, roots, children, dur, end = _span_times(tdoc)
    # critical path: descend into the child that finishes last
    crit = set()
    for r in roots:
        node = r
        while node is not None:
            crit.add(node["span_id"])
            kids = children.get(node["span_id"])
            node = max(kids, key=end) if kids else None
    excl = {}
    for s in spans:
        kids = children.get(s["span_id"], [])
        excl[s["span_id"]] = max(
            0.0, dur(s) - sum(dur(k) for k in kids))
        agg.setdefault(re.sub(r"\[\d+\]$", "", s["name"]),
                       [0.0, 0])[0] += excl[s["span_id"]]
        agg[re.sub(r"\[\d+\]$", "", s["name"])][1] += 1
    head = (f"-- trace {tdoc.get('trace_id', '?')} "
            f"({tdoc.get('name', '?')})")
    if tdoc.get("anomaly"):
        head += f"  ANOMALY: {tdoc['anomaly']}"
    if not tdoc.get("finished", True):
        head += "  [open]"
    head += ("  [head-sampled]" if tdoc.get("head_sampled")
             else "  [tail-kept]")
    lines = [head,
             f"  {'span':<34} {'ms':>9} {'excl ms':>9}  detail"]

    def walk(s, depth):
        mark = "*" if s["span_id"] in crit else " "
        detail = ", ".join(
            f"{k}={v}" for k, v in sorted((s.get("attrs") or {}).items())
            if v is not None)
        name = ("  " * depth + s["name"])[:34]
        lines.append(f"{mark} {name:<34} {dur(s) * 1e3:>9.3f} "
                     f"{excl[s['span_id']] * 1e3:>9.3f}  {detail}")
        for k in children.get(s["span_id"], []):
            walk(k, depth + 1)

    for r in roots:
        walk(r, 0)
    lines.append("")
    return lines


def render_traces(traces: List[dict], last: int = 10) -> str:
    """--trace: span trees with critical-path (*) and exclusive-time
    attribution, from a ``Tracer.dump`` file (or the ``traces`` section
    of a flight-recorder dump)."""
    if not traces:
        return ("(no traces in this dump — run with FLAGS_trace on; "
                "healthy traffic is head-sampled at FLAGS_trace_sample, "
                "anomalies are always kept)\n")
    anom = sum(1 for t in traces if t.get("anomaly"))
    lines = [f"== Traces ({len(traces)} retained, {anom} anomalous) ==",
             ""]
    agg: Dict[str, List[float]] = {}
    for tdoc in traces[-last:]:
        lines += _render_one_trace(tdoc, agg)
    if len(traces) > last:
        lines.append(f"  ... {len(traces) - last} more traces "
                     "(raise --last)")
        lines.append("")
    a_rows = [[name, f"{tot * 1e3:,.3f}", str(n)]
              for name, (tot, n) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][0])]
    lines += _table("Exclusive time by span (rendered traces)",
                    ["span", "total excl ms", "count"], a_rows)
    return "\n".join(lines).rstrip() + "\n"


def render(rows: List[dict], top: int = 10, memory: bool = False,
           serve: bool = False, comms: bool = False,
           moe: bool = False, fallbacks: bool = False,
           recsys: bool = False, slo: bool = False,
           fleet: bool = False, goodput: bool = False,
           lifecycle: bool = False) -> str:
    latest = _latest_samples(rows)
    used = set()

    # -- fleet router (--fleet) first: it must claim the serve_router_*
    # series before --serve's generic serve_* catch-all slurps them ------
    serve_out: List[str] = (_fleet_section(latest, used)
                            if fleet else [])
    # -- serving (--serve) next: its histograms would otherwise be
    # swallowed by the generic slowest-events table ----------------------
    serve_out += (_serve_section(latest, used, raw_rows=rows)
                  if serve else [])
    # -- SLO burn (--slo) renders next to --serve ------------------------
    serve_out += _slo_section(latest, used) if slo else []
    # -- model lifecycle (--lifecycle) renders AFTER --slo so the burn
    # table keeps every slo_* gauge (this section only peeks them) -------
    serve_out += (_lifecycle_section(latest, used, raw_rows=rows)
                  if lifecycle else [])
    # -- training goodput (--goodput) claims the train_* ledger series
    # before the generic counter tables ----------------------------------
    serve_out += _goodput_section(latest, used) if goodput else []
    # -- comm overlap (--comms) also claims its gauges early -------------
    comms_out: List[str] = (_comms_section(latest, used) if comms else [])
    # -- MoE router health (--moe) renders next to --comms ---------------
    comms_out += _moe_section(latest, used) if moe else []
    # -- recsys embedding tiers (--recsys) next to --serve/--moe ---------
    comms_out += _recsys_section(latest, used) if recsys else []
    # -- unified degradation view (--fallbacks) ---------------------------
    comms_out += _fallbacks_section(latest, used) if fallbacks else []

    # -- slowest timing histograms ----------------------------------------
    timings = []
    for key, row in latest.items():
        name, labels = key
        if key in used:
            continue                 # --serve already rendered these
        if row.get("type") == "histogram" and row.get("count"):
            timings.append((row.get("sum", 0.0), name, labels, row))
            used.add(key)
    timings.sort(reverse=True, key=lambda t: t[0])
    t_rows = [[name, _fmt_labels(labels), str(int(r["count"])),
               f"{s:,.3f}", f"{s / r['count'] * 1e3:,.3f}"]
              for s, name, labels, r in timings[:top]]
    out = serve_out + comms_out + _table(
        f"Slowest events (top {top} by total time)",
        ["event", "labels", "count", "total s", "mean ms"], t_rows)
    if len(timings) > top:
        out.append(f"  ... {len(timings) - top} more timing series "
                   "(raise --top)\n")

    # -- compile / recompile ----------------------------------------------
    c_rows = []
    for key in sorted(latest):
        name, labels = key
        if ("compile" in name or name.startswith(("jax_", "scan_"))
                or "trace" in name) and key not in used:
            row = latest[key]
            if "value" in row:
                c_rows.append([name, _fmt_labels(labels),
                               f"{row['value']:g}"])
                used.add(key)
    out += _table("Compile / trace counters", ["metric", "labels", "value"],
                  c_rows)

    # -- comms by (op, group) ---------------------------------------------
    comm: Dict[tuple, dict] = {}
    for key, row in latest.items():
        name, labels = key
        if not name.startswith("comm_"):
            continue
        used.add(key)
        d = comm.setdefault(labels, {})
        if name == "comm_bytes_total":
            d["bytes"] = row.get("value", 0.0)
        elif name == "comm_ops_total":
            d["ops"] = row.get("value", 0.0)
        elif name == "comm_latency_seconds" and row.get("count"):
            d["lat_ms"] = row["sum"] / row["count"] * 1e3
    m_rows = [[_fmt_labels(labels), f"{d.get('ops', 0):g}",
               _fmt_bytes(d.get("bytes", 0.0)),
               f"{d.get('lat_ms', 0.0):,.3f}"]
              for labels, d in sorted(comm.items(),
                                      key=lambda kv: -kv[1].get("bytes", 0))]
    out += _table("Collectives (eager dispatch)",
                  ["op/group", "ops", "bytes", "mean dispatch ms"], m_rows)

    # -- memory (--memory) -------------------------------------------------
    if memory:
        out += _memory_section(latest, used)

    # -- everything else ---------------------------------------------------
    o_rows = []
    for key in sorted(latest):
        if key in used:
            continue
        name, labels = key
        row = latest[key]
        val = (f"count={int(row['count'])} sum={row.get('sum', 0):g}"
               if row.get("type") == "histogram"
               else f"{row.get('value', 0):g}")
        o_rows.append([name, _fmt_labels(labels), val])
    out += _table("Other metrics", ["metric", "labels", "value"], o_rows)

    if not out:
        return "(no metric samples found)"
    return "\n".join(out).rstrip() + "\n"


def render_kernels() -> str:
    """--kernels: the live ops.pallas kernel-layer inventory (flag
    matrix, dispatch status on this backend, observed fallbacks)."""
    from paddle_tpu.ops import pallas as pallas_ops
    rows = []
    for r in pallas_ops.kernels():
        flag = r["flag"] or "(shape gate)"
        if r["flag_value"] is not None:
            flag += f"={'on' if r['flag_value'] else 'off'}"
        seen = ", ".join(f"{k}:{v}" for k, v in
                         sorted(r["fallbacks_seen"].items())) or "-"
        rows.append([r["kernel"], flag,
                     "live" if r["live"] else "fallback",
                     r["fallback"], seen])
    lines = _table("ops.pallas kernel layer (this backend)",
                   ["kernel", "kill switch", "dispatch", "XLA fallback",
                    "fallbacks seen"], rows)
    lines.append("(paddle_tpu/ops/pallas/__init__.py; persistent "
                 "fallback counts: pallas_fallback_total in a monitor dump)")
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)

    def int_opt(flag: str, default: int) -> Optional[int]:
        if flag not in argv:
            return default
        i = argv.index(flag)
        try:
            v = int(argv[i + 1])
        except (IndexError, ValueError):
            print(f"{flag} needs an int", file=sys.stderr)
            return None
        del argv[i:i + 2]
        return v

    top = int_opt("--top", 10)
    last = int_opt("--last", 10)
    if top is None or last is None:
        return 2
    flight = "--flight" in argv
    if flight:
        argv.remove("--flight")
    traces = "--trace" in argv
    if traces:
        argv.remove("--trace")
    memory = "--memory" in argv
    if memory:
        argv.remove("--memory")
    serve = "--serve" in argv
    if serve:
        argv.remove("--serve")
    fleet = "--fleet" in argv
    if fleet:
        argv.remove("--fleet")
    comms = "--comms" in argv
    if comms:
        argv.remove("--comms")
    moe = "--moe" in argv
    if moe:
        argv.remove("--moe")
    recsys = "--recsys" in argv
    if recsys:
        argv.remove("--recsys")
    slo = "--slo" in argv
    if slo:
        argv.remove("--slo")
    lifecycle = "--lifecycle" in argv
    if lifecycle:
        argv.remove("--lifecycle")
    goodput = "--goodput" in argv
    if goodput:
        argv.remove("--goodput")
    fallbacks = "--fallbacks" in argv
    if fallbacks:
        argv.remove("--fallbacks")
    kernels = "--kernels" in argv
    if kernels:
        argv.remove("--kernels")
    if len(argv) != (0 if kernels else 1):
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    if kernels:
        print(render_kernels(), end="")
        return 0
    if flight or traces:
        import json
        try:
            with open(argv[0]) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read {argv[0]}: {e}", file=sys.stderr)
            return 2
        if traces:
            # a Tracer.dump file, a bare trace list, or a flight dump
            # whose provider attached a `traces` section
            tlist = doc if isinstance(doc, list) \
                else list(doc.get("traces") or [])
            print(render_traces(tlist, last=last), end="")
            return 0
        print(render_flight(doc, last=last), end="")
        return 0
    try:
        from paddle_tpu.monitor import load_jsonl
        rows = load_jsonl(argv[0])
    except OSError as e:
        print(f"cannot read {argv[0]}: {e}", file=sys.stderr)
        return 2
    print(render(rows, top=top, memory=memory, serve=serve, comms=comms,
                 moe=moe, fallbacks=fallbacks, recsys=recsys, slo=slo,
                 fleet=fleet, goodput=goodput, lifecycle=lifecycle),
          end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
