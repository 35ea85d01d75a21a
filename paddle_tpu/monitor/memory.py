"""HBM memory accounting: per-program budgets, pre-flight checks, and a
live-buffer census.

Reference analogue: Paddle's allocator stats surface
(``paddle.device.cuda.memory_allocated / max_memory_allocated /
memory_summary`` over the BFC allocator counters). On TPU, XLA owns HBM,
so the framework-level answers come from two different places:

- **static budgets** from the compiled executable itself
  (``compiled.memory_analysis()``): argument / output / temp /
  generated-code bytes per TrainStep program kind, known BEFORE the
  first step runs — which is what makes an OOM *pre-flight* check
  possible (:func:`preflight_check`, gated by ``FLAGS_memory_preflight``);
- **live actuals** from the runtime (``device.memory_stats()`` where the
  backend publishes them, plus a :func:`live_buffer_census` over
  ``jax.live_arrays()`` that attributes bytes to params / optimizer
  state / activations / unattributed and lets :class:`LeakMonitor` flag
  step-over-step growth).

``memory_summary()`` renders both halves in the spirit of
``paddle.device.cuda.memory_summary``. See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import threading
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

__all__ = [
    "ProgramMemory", "MemoryBudgetError", "analyze_compiled",
    "record_program", "programs", "device_memory_stats", "device_hbm_bytes",
    "preflight_check", "live_buffer_census", "live_bytes",
    "publish_census", "LeakMonitor", "memory_summary", "fmt_bytes",
]


def fmt_bytes(n: Optional[float]) -> str:
    # same unit ladder as tools/monitor_report.py (the tool keeps a
    # standalone copy so it imports without the package on sys.path)
    if n is None:
        return "n/a"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:,.1f} {unit}"
        n /= 1024
    return f"{n:,.1f} TiB"


class MemoryBudgetError(RuntimeError):
    """Pre-flight says the program will not fit device HBM; carries the
    numbers for programmatic handling."""

    def __init__(self, message: str, estimate_bytes: int = 0,
                 limit_bytes: int = 0):
        super().__init__(message)
        self.estimate_bytes = estimate_bytes
        self.limit_bytes = limit_bytes


@dataclass
class ProgramMemory:
    """Static HBM budget of ONE compiled executable, from XLA's
    ``memory_analysis()`` (CompiledMemoryStats)."""

    kind: str
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    generated_code_bytes: int = 0

    @property
    def peak_bytes(self) -> int:
        """Peak HBM the executable needs live at once: inputs + outputs
        + scratch + program text, minus input/output aliasing (donated
        buffers are counted once, not twice)."""
        return max(0, self.argument_bytes + self.output_bytes
                   + self.temp_bytes + self.generated_code_bytes
                   - self.alias_bytes)

    def as_dict(self) -> Dict[str, int]:
        return {"kind": self.kind,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "alias_bytes": self.alias_bytes,
                "generated_code_bytes": self.generated_code_bytes,
                "peak_bytes": self.peak_bytes}


def analyze_compiled(compiled, kind: str = "step") \
        -> Optional[ProgramMemory]:
    """Extract a :class:`ProgramMemory` from a ``jax.stages.Compiled``;
    None when the backend publishes no memory analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def b(attr: str) -> int:
        return int(getattr(ma, attr, 0) or 0)

    return ProgramMemory(
        kind=kind,
        argument_bytes=b("argument_size_in_bytes"),
        output_bytes=b("output_size_in_bytes"),
        temp_bytes=b("temp_size_in_bytes"),
        alias_bytes=b("alias_size_in_bytes"),
        generated_code_bytes=b("generated_code_size_in_bytes"))


# ---------------------------------------------------------------------------
# Process-global program table (memory_summary's data source)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_PROGRAMS: Dict[str, ProgramMemory] = {}


def record_program(pm: ProgramMemory) -> None:
    """Register a compiled program's budget in the process-global table
    (newest executable per kind wins — a recompile replaces its entry)."""
    with _LOCK:
        _PROGRAMS[pm.kind] = pm


def programs() -> Dict[str, ProgramMemory]:
    """Snapshot of the process-global per-kind program budgets."""
    with _LOCK:
        return dict(_PROGRAMS)


# ---------------------------------------------------------------------------
# Device actuals
# ---------------------------------------------------------------------------

def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """Runtime allocator stats of ``device`` (default: first visible), or
    None where the backend publishes none (the CPU test backend)."""
    import jax
    try:
        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


def device_hbm_bytes(device=None) -> Optional[int]:
    """Total HBM the runtime will let us allocate, or None when unknown."""
    stats = device_memory_stats(device)
    if stats is None:
        return None
    return stats.get("bytes_limit") or stats.get("bytes_reservable_limit")


def _preflight_limit(limit_bytes: Optional[int], device) -> Optional[int]:
    if limit_bytes is not None:
        return int(limit_bytes)
    from ..core.flags import get_flag
    mb = int(get_flag("memory_preflight_limit_mb") or 0)
    if mb > 0:
        return mb << 20
    return device_hbm_bytes(device)


def preflight_check(program: "ProgramMemory | Dict[str, ProgramMemory]",
                    limit_bytes: Optional[int] = None, device=None,
                    action: Optional[str] = None) -> Optional[dict]:
    """OOM pre-flight: compare a program's static HBM estimate against
    the device budget BEFORE the first step runs.

    ``action`` defaults to ``FLAGS_memory_preflight`` ('' = off, 'warn',
    'raise'); the limit comes from ``limit_bytes``, else
    ``FLAGS_memory_preflight_limit_mb``, else the device. Returns
    ``{'estimate_bytes', 'limit_bytes', 'fits', 'kind'}`` — or None when
    the check is off or no budget is known (nothing to compare on the
    CPU test backend without an explicit limit)."""
    from ..core.flags import get_flag
    act = action if action is not None else get_flag("memory_preflight")
    if not act:
        return None
    if act not in ("warn", "raise"):
        raise ValueError(f"memory_preflight: unknown action {act!r} "
                         "(expected '', 'warn' or 'raise')")
    limit = _preflight_limit(limit_bytes, device)
    if not limit:
        return None
    progs = ({program.kind: program} if isinstance(program, ProgramMemory)
             else dict(program))
    if not progs:
        return None
    worst_kind, worst = max(progs.items(), key=lambda kv: kv[1].peak_bytes)
    est = worst.peak_bytes
    result = {"estimate_bytes": est, "limit_bytes": int(limit),
              "fits": est <= limit, "kind": worst_kind}
    if est <= limit:
        return result
    msg = (f"memory pre-flight: program {worst_kind!r} needs an estimated "
           f"{fmt_bytes(est)} of HBM "
           f"(args {fmt_bytes(worst.argument_bytes)}, "
           f"outputs {fmt_bytes(worst.output_bytes)}, "
           f"temps {fmt_bytes(worst.temp_bytes)}, "
           f"aliased -{fmt_bytes(worst.alias_bytes)}) but the budget is "
           f"{fmt_bytes(limit)} — this config is expected to OOM. "
           "Shrink the batch, enable recompute/ZeRO, or raise "
           "FLAGS_memory_preflight_limit_mb if the budget is wrong "
           "(docs/OBSERVABILITY.md).")
    try:
        from .metrics import get_registry
        get_registry().counter(
            "memory_preflight_failures_total",
            "programs whose static HBM estimate exceeded the budget"
        ).inc(kind=worst_kind)
    except Exception:
        pass
    if act == "warn":
        warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return result
    raise MemoryBudgetError(msg, estimate_bytes=est, limit_bytes=int(limit))


# ---------------------------------------------------------------------------
# Live-buffer census (jax.live_arrays)
# ---------------------------------------------------------------------------

def _leaf_ids(tree) -> set:
    import jax
    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape"):
            out.add(id(leaf))
    return out


def live_buffer_census(train_step=None) -> Dict[str, Dict[str, int]]:
    """Walk ``jax.live_arrays()`` and attribute bytes to where they came
    from: ``params`` / ``optimizer`` / ``buffers`` (matched by identity
    against ``train_step``'s state when one is given), ``activations``
    (floating-point arrays the step does not own — batches, activations,
    user tensors), and ``unattributed`` (everything else: int/bool
    arrays, RNG keys). Returns ``{category: {'bytes', 'count'}}`` plus a
    ``total`` entry.

    This is the live ACTUAL next to the static budget of
    :func:`analyze_compiled` — a growing gap between successive censuses
    is how leaks show up (:class:`LeakMonitor`)."""
    import jax
    import jax.numpy as jnp

    param_ids = opt_ids = buf_ids = frozenset()
    if train_step is not None:
        param_ids = _leaf_ids({**getattr(train_step, "params", {}),
                               **getattr(train_step, "frozen", {})})
        opt_ids = _leaf_ids(getattr(train_step, "opt_state", {}))
        buf_ids = _leaf_ids(getattr(train_step, "buffers", {}))

    cats = {c: {"bytes": 0, "count": 0}
            for c in ("params", "optimizer", "buffers", "activations",
                      "unattributed", "total")}

    def add(cat: str, nbytes: int) -> None:
        cats[cat]["bytes"] += nbytes
        cats[cat]["count"] += 1

    for arr in jax.live_arrays():
        try:
            if arr.is_deleted():
                continue
            nbytes = int(arr.nbytes)
        except Exception:
            continue
        i = id(arr)
        if i in param_ids:
            add("params", nbytes)
        elif i in opt_ids:
            add("optimizer", nbytes)
        elif i in buf_ids:
            add("buffers", nbytes)
        elif jnp.issubdtype(arr.dtype, jnp.floating):
            add("activations", nbytes)
        else:
            add("unattributed", nbytes)
        add("total", nbytes)
    return cats


def live_bytes() -> int:
    """Total bytes across all live jax arrays in this process."""
    return live_buffer_census()["total"]["bytes"]


def publish_census(train_step=None, registry=None) \
        -> Dict[str, Dict[str, int]]:
    """Run a census and publish it as ``live_buffer_bytes`` /
    ``live_buffer_count`` gauges labelled by category (rendered by
    ``tools/monitor_report.py --memory``). Returns the census."""
    census = live_buffer_census(train_step)
    from .metrics import get_registry
    reg = registry if registry is not None else get_registry()
    for cat, c in census.items():
        reg.gauge("live_buffer_bytes",
                  "live jax-array bytes by attribution category "
                  "(monitor.memory census)").set(c["bytes"], category=cat)
        reg.gauge("live_buffer_count",
                  "live jax arrays by attribution category"
                  ).set(c["count"], category=cat)
    return census


class LeakMonitor:
    """Flags monotonic step-over-step growth of live-buffer bytes.

    ::

        leak = LeakMonitor(window=4, tolerance_bytes=1 << 20)
        for step, batch in enumerate(loader):
            train_step(*batch)
            if leak.observe():          # reads live_bytes() by default
                ...                     # warned + counted already

    A leak is suspected when the last ``window`` observations grew
    STRICTLY at every step and the total growth over the window exceeds
    ``tolerance_bytes`` (steady-state training holds live bytes flat:
    donated buffers replace themselves). Suspicion warns
    (RuntimeWarning), bumps ``memory_leak_suspected_total`` in the
    metrics registry, and sets :attr:`suspected`."""

    def __init__(self, window: int = 4, tolerance_bytes: int = 1 << 20,
                 registry=None):
        if window < 2:
            raise ValueError("LeakMonitor: window must be >= 2")
        self.window = int(window)
        self.tolerance_bytes = int(tolerance_bytes)
        self._registry = registry
        self._history: List[int] = []
        self.suspected = 0

    def observe(self, total_bytes: Optional[int] = None,
                step: Optional[int] = None) -> bool:
        """Record one sample (default: :func:`live_bytes` now); True when
        this sample completes a suspicious growth window."""
        v = int(live_bytes() if total_bytes is None else total_bytes)
        self._history.append(v)
        # bounded history: one window is all the detector looks at
        if len(self._history) > self.window + 1:
            del self._history[:-(self.window + 1)]
        h = self._history
        if len(h) < self.window + 1:
            return False
        grew = all(b > a for a, b in zip(h, h[1:]))
        if not grew or h[-1] - h[0] <= self.tolerance_bytes:
            return False
        self.suspected += 1
        growth = h[-1] - h[0]
        at = f" at step {step}" if step is not None else ""
        warnings.warn(
            f"live-buffer leak suspected{at}: live bytes grew "
            f"{fmt_bytes(growth)} over the last {self.window} "
            f"observations ({fmt_bytes(h[0])} -> {fmt_bytes(h[-1])}); "
            "steady-state training should hold live bytes flat — look "
            "for tensors retained across steps (loss history kept as "
            "device arrays, growing python lists of activations)",
            RuntimeWarning, stacklevel=2)
        try:
            from .metrics import get_registry
            reg = self._registry if self._registry is not None \
                else get_registry()
            reg.counter("memory_leak_suspected_total",
                        "LeakMonitor growth-window trips").inc()
        except Exception:
            pass
        return True


# ---------------------------------------------------------------------------
# memory_summary
# ---------------------------------------------------------------------------

def memory_summary(train_step=None, device=None) -> str:
    """Human-readable memory report in the spirit of
    ``paddle.device.cuda.memory_summary``: device actuals (where the
    runtime publishes them), static per-program HBM budgets (from
    ``train_step`` when given, else every program recorded process-wide),
    and the live-buffer census."""
    lines = ["=== paddle_tpu memory summary ==="]

    import jax
    try:
        dev = device if device is not None else jax.devices()[0]
        lines.append(f"device: {dev.device_kind} ({dev.platform})")
    except Exception:
        dev = None
    stats = device_memory_stats(dev)
    if stats is None:
        lines.append("allocator stats: n/a (backend publishes no "
                     "memory_stats — CPU test backend)")
    else:
        lines.append(
            "allocator: in use " + fmt_bytes(stats.get("bytes_in_use"))
            + ", peak " + fmt_bytes(stats.get("peak_bytes_in_use"))
            + ", limit " + fmt_bytes(stats.get("bytes_limit")))

    progs: Dict[str, ProgramMemory]
    if train_step is not None and getattr(train_step, "_program_memory",
                                          None):
        progs = dict(train_step._program_memory)
    else:
        progs = programs()
    if progs:
        lines.append("")
        lines.append("compiled programs (static budget, "
                     "compiled.memory_analysis):")
        hdr = f"  {'kind':<10} {'args':>12} {'outputs':>12} " \
              f"{'temps':>12} {'code':>10} {'peak est.':>12}"
        lines.append(hdr)
        for kind in sorted(progs):
            pm = progs[kind]
            lines.append(
                f"  {kind:<10} {fmt_bytes(pm.argument_bytes):>12} "
                f"{fmt_bytes(pm.output_bytes):>12} "
                f"{fmt_bytes(pm.temp_bytes):>12} "
                f"{fmt_bytes(pm.generated_code_bytes):>10} "
                f"{fmt_bytes(pm.peak_bytes):>12}")

    census = live_buffer_census(train_step)
    lines.append("")
    lines.append("live buffers (jax.live_arrays census):")
    for cat in ("params", "optimizer", "buffers", "activations",
                "unattributed", "total"):
        c = census[cat]
        lines.append(f"  {cat:<14} {fmt_bytes(c['bytes']):>12} "
                     f"in {c['count']} array(s)")
    return "\n".join(lines) + "\n"
