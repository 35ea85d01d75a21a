"""Cost model (reference: python/paddle/cost_model/cost_model.py —
profile_measure running a program under the profiler to collect op
costs).

TPU-native: XLA's cost analysis gives static FLOP/byte counts for the
compiled program and a timed run gives wall cost; both come from the
same jitted callable a user would train with.

This module is the ONE source of truth for program cost numbers:
:func:`normalize_cost_analysis` (shared with the per-program attribution
in ``jit/to_static.TrainStep``) and the per-chip peak-FLOPs table that
the host-clock MFU gauge divides by.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

__all__ = ["CostModel", "normalize_cost_analysis", "device_peak_flops",
           "PEAK_FLOPS"]

# Peak dense matmul FLOP/s per chip (bf16). f32 params are fine: the
# default matmul policy lowers f32 gemms to bf16 passes on TPU. Keys
# are matched as prefixes of jax's device_kind string.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e
}


def normalize_cost_analysis(analysis) -> Dict[str, float]:
    """Normalize ``Lowered.cost_analysis()`` output to one flat dict.

    jax returns a plain dict on current versions, but a LIST of
    per-computation dicts on some older ones (and None when the backend
    has no cost model). Numeric values of duplicate keys are summed —
    for a multi-computation program the total is what budget/MFU math
    wants. Shared by ``CostModel.profile_measure`` and the per-program
    attribution in ``TrainStep`` (one helper, both callers)."""
    if analysis is None:
        return {}
    if isinstance(analysis, (list, tuple)):
        merged: Dict[str, float] = {}
        for d in analysis:
            for k, v in (d or {}).items():
                if isinstance(v, (int, float)):
                    merged[k] = merged.get(k, 0.0) + float(v)
        return merged
    return {k: float(v) for k, v in analysis.items()
            if isinstance(v, (int, float))}


def device_peak_flops(device=None, default: Optional[float] = None) \
        -> Optional[float]:
    """Peak dense FLOP/s of ``device`` (default: first visible device)
    from :data:`PEAK_FLOPS`; ``default`` (None) when the chip is unknown
    — e.g. the CPU test backend, where an MFU number would be fiction."""
    import jax
    try:
        kind = (device or jax.devices()[0]).device_kind
    except Exception:
        return default
    for prefix, peak in PEAK_FLOPS.items():
        if kind.startswith(prefix):
            return peak
    return default


class CostModel:
    def attribute(self, lowered) -> Dict[str, float]:
        """Static cost attribution of a ``jax.stages.Lowered``:
        ``{'flops', 'bytes_accessed', 'arithmetic_intensity'}`` (zeros
        when the backend publishes no cost model). The same numbers
        ``TrainStep.stats()['programs']`` reports per program kind."""
        try:
            analysis = normalize_cost_analysis(lowered.cost_analysis())
        except Exception:
            analysis = {}
        flops = float(analysis.get("flops", 0.0))
        nbytes = float(analysis.get("bytes accessed", 0.0))
        return {"flops": flops, "bytes_accessed": nbytes,
                "arithmetic_intensity": flops / nbytes if nbytes else 0.0}

    def mfu(self, flops_per_step: float, step_seconds: float,
            device=None, peak_flops: Optional[float] = None) \
            -> Optional[float]:
        """Model-FLOPs utilization from an attributed FLOP count and a
        measured step time; None when the chip's peak is unknown."""
        peak = peak_flops if peak_flops is not None \
            else device_peak_flops(device)
        if not peak or step_seconds <= 0:
            return None
        return flops_per_step / step_seconds / peak

    def profile_measure(self, fn, args: Sequence = (), iters: int = 10,
                        warmup: int = 2) -> Dict[str, float]:
        """Measure a callable over example args.

        Returns {'flops', 'bytes_accessed', 'wall_ms', 'achieved_tflops'}.
        """
        import jax

        raw = [a._data if hasattr(a, "_data") else a for a in args]
        jitted = jax.jit(lambda *xs: fn(*xs))
        lowered = jitted.lower(*raw)
        analysis = normalize_cost_analysis(lowered.cost_analysis())
        # AOT-compile the lowering we just analyzed: the timed loop runs
        # the exact executable the numbers describe, and compilation cost
        # stays out of the warmup loop (no extra pre-warmup execution)
        compiled = lowered.compile()
        out = None
        for _ in range(max(1, warmup)):
            out = compiled(*raw)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compiled(*raw)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / iters
        flops = float(analysis.get("flops", 0.0))
        return {
            "flops": flops,
            "bytes_accessed": float(analysis.get("bytes accessed", 0.0)),
            "wall_ms": wall * 1e3,
            "achieved_tflops": flops / wall / 1e12 if wall > 0 else 0.0,
        }
