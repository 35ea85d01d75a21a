"""paddle_tpu.ops.pallas — the framework's hand-written TPU kernel layer.

The TPU-native replacement for the reference's fused-CUDA operator
library (PAPER.md: the operators/fused/ layer — fused_attention,
softmax_with_cross_entropy, the slim int8 kernels). Every kernel here
follows ONE dispatch convention:

- a ``FLAGS_*`` kill switch (see :func:`kernels` for the flag matrix)
  whose *off* position routes to an XLA fallback that is bit-compatible
  with the pre-kernel implementation;
- TPU-only by default: on other backends the kernel falls back to XLA
  unless ``FLAGS_pallas_interpret`` forces the Pallas interpreter (the
  ``pallas`` pytest marker does this — parity tests run the REAL kernel
  bodies on CPU);
- every fallback is counted: :func:`note_fallback` feeds the
  ``pallas_fallback_total{kernel,reason}`` counter (monitor mode) and
  the always-on :data:`PALLAS_STATS` dict, so ``tools/monitor_report.py
  --kernels`` can show which kernels are live vs degraded. Under a
  multi-device mesh GSPMD cannot partition a Mosaic kernel: one either
  runs per shard in a ``shard_map`` (``flash_attention``, through
  ``distributed.spmd.shard_kernel``) or takes its XLA path counted as
  ``mesh`` (every other kernel, until it has a per-shard form);
- a parity test in interpret mode (forward, and backward where the
  kernel has a custom VJP) plus a kill-switch test pinning the fallback
  bit-identical, in tests/test_pallas_kernels.py; a compile for a
  described v5e at the GPT-2 345M shapes in tests/test_tpu_compile.py
  (what Mosaic refuses on the chip is refused there); flash attention,
  chunked CE and paged decode against their XLA references ON the chip
  in ``chip_smoke.py``'s kernel phase.
  Kernel TIME is a cell's: ``flash_attention_roofline`` and
  ``paged_decode_roofline`` (``PERF.md`` section 3).

What never reaches a kernel: a mask that is not an additive key-padding
mask ``[B, 1, 1, Sk]``, a head dim outside 64/128/256, a sequence that is
short (under 256) or not a multiple of 128
(``ops.attention._flash_supported``: flash); soft labels
and the dense mp-sharded ``ParallelCrossEntropy`` (chunked CE); prefill,
S > 1 (paged decode); K or N not a multiple of 128, counted as ``shape``
(int8 matmul); an engine built with ``lora_adapters == 0`` (bgmv).
``FLAGS_amp_int8_matmul`` (off) routes eligible ``F.linear`` calls
under ``amp.auto_cast`` through ``int8_amp_linear``: an experiment.

Kernel inventory:

==================  ==========================  =========================
kernel              flag                        XLA fallback
==================  ==========================  =========================
flash_attention     (shape gate in ops.         _sdpa_xla softmax
                    attention, TPU-only)        composition
chunked_ce          FLAGS_pallas_ce             nn.chunked_ce fori_loop
                                                streaming path
paged_decode        FLAGS_pallas_paged_decode   gather_pages + masked
                                                SDPA (models/gpt.py)
int8_matmul         FLAGS_pallas_int8           slim dequant-to-float /
                                                XLA int8 dot
bgmv                FLAGS_pallas_bgmv           XLA adapter gather +
                                                einsum shrink/expand
==================  ==========================  =========================

Every kernel but one has its operands blocked by BlockSpecs over a grid.
``paged_decode`` leaves the page pools in HBM and copies from them
itself: one grid step a (slot, head group), a loop over the slot's LIVE
table entries (``pos // bs + 1``), several pages a copy group into one
of two VMEM buffers (a grid over all table entries, one page a step,
is 98,304 grid steps a decode step of the serve cell, 70% of them past
the slots' positions). Query heads may share a K/V head (the query is
laid out block-diagonally over a K/V head's lanes) and a slot may have a
first visible position (a window: the sweep starts at its table entry).
Its LATENT form, ``paged_mla_decode`` (same flag, same sweep, same
precision): ONE pool whose rows are the keys of every head and, their
first ``value_width`` lanes, the values (MLA's ``[c_kv ; rope(k_r)]``,
576 values in 640 lanes); a page is copied ONCE a group where the pool
handed in as K and as V would be copied twice, each head's absorbed
query reads the whole row (no block diagonal), and the output is the
weighted latent a head (``models/xing4.py`` folds ``W_kvb`` into the
query before and applies its value half after).
"""

from __future__ import annotations

import threading
from typing import Dict, List

from ...core.flags import get_flag

__all__ = [
    "flash_attention", "chunked_ce_loss", "paged_decode_attention",
    "paged_decode_attention_quant", "paged_mla_decode",
    "int8_matmul", "int8_linear", "int8_amp_linear", "quantize_per_channel",
    "bgmv", "bgmv_xla",
    "kernels", "kernel_enabled", "note_fallback", "backend_supported",
    "interpret",
    "PALLAS_STATS", "reset_pallas_stats", "FLASH_RESIDUAL_NAMES",
    "FLASH_CAUSAL_WORK", "note_flash_causal_work",
]

#: ``checkpoint_name`` tags of what flash attention's differentiated
#: forward keeps for its backward kernel: the output ``o`` (the size of
#: the layer's input) and one column of the log-sum-exp rows (``[B, H,
#: S]`` float32). A remat policy that saves these names never re-runs the
#: forward kernel to rebuild them, and every policy that
#: ``fleet.utils.recompute.resolve_checkpoint_policy`` builds does,
#: except ``"full"``. Here, not in ``flash_attention.py``, so that
#: resolving a policy does not load Pallas.
FLASH_RESIDUAL_NAMES = ("flash_attention_o", "flash_attention_lse")

#: always-on fallback observability (monitor-independent, like
#: nn.scan.SCAN_STATS): {(kernel, reason): count}
PALLAS_STATS: Dict[tuple, int] = {}
#: always-on, filled as programs are TRACED: ``{kernel: [computed,
#: kept]}``, the score elements the causal calls of ``flash_fwd`` and
#: ``flash_bwd`` compute and the elements of those the causal mask
#: keeps, summed over the calls traced so far. What is computed and not
#: kept is the price of the diagonal (``flash_attention._causal_plan``);
#: 1 - kept / computed is the benchmark's ``flash.masked_work_pct.train``.
FLASH_CAUSAL_WORK: Dict[str, List[int]] = {}
_STATS_LOCK = threading.Lock()

#: the registry rows behind :func:`kernels` — name -> (flag, fallback
#: description). flash_attention predates the flag convention: its gate
#: is the shape/backend check in ops.attention._flash_supported.
_REGISTRY = {
    "flash_attention": (None, "XLA softmax composition (ops.attention."
                              "_sdpa_xla); gate: _flash_supported"),
    "chunked_ce": ("pallas_ce", "pure-XLA fori_loop streaming CE "
                                "(nn.chunked_ce._ce_hard)"),
    "paged_decode": ("pallas_paged_decode", "gather_pages + masked SDPA "
                                            "(models/gpt.py)"),
    "int8_matmul": ("pallas_int8", "weight dequantize-to-float matmul / "
                                   "XLA int8 dot (slim.QuantizedLinear)"),
    "bgmv": ("pallas_bgmv", "XLA adapter gather + einsum shrink/expand "
                            "(ops.pallas.bgmv.bgmv_xla)"),
}


def reset_pallas_stats() -> None:
    with _STATS_LOCK:
        PALLAS_STATS.clear()
        FLASH_CAUSAL_WORK.clear()


def note_flash_causal_work(kernel: str, computed: int, kept: int) -> None:
    """Add one traced causal call of a flash kernel to
    :data:`FLASH_CAUSAL_WORK`."""
    with _STATS_LOCK:
        row = FLASH_CAUSAL_WORK.setdefault(kernel, [0, 0])
        row[0] += computed
        row[1] += kept


def note_fallback(kernel: str, reason: str) -> None:
    """Record that a kernel-eligible call degraded to its XLA fallback.

    Bumps :data:`PALLAS_STATS` always and the
    ``pallas_fallback_total{kernel,reason}`` registry counter in monitor
    mode. Reasons: ``flag_off`` (kill switch), ``cpu_backend`` (non-TPU
    without FLAGS_pallas_interpret), ``shape`` (unsupported geometry,
    e.g. int8 gemm dims not 128-aligned), ``mesh`` (a compiled kernel
    under a multi-device mesh, which GSPMD cannot partition).
    """
    with _STATS_LOCK:
        PALLAS_STATS[(kernel, reason)] = \
            PALLAS_STATS.get((kernel, reason), 0) + 1
    from ...monitor import enabled as _mon_enabled
    if _mon_enabled():
        from ...monitor import get_registry
        get_registry().counter(
            "pallas_fallback_total",
            "ops.pallas kernel calls that degraded to the XLA fallback, "
            "by kernel and cause").inc(kernel=kernel, reason=reason)


def interpret() -> bool:
    """The ``interpret=`` argument of every ``pallas_call`` in this
    package: the Pallas interpreter runs a kernel body only when
    ``FLAGS_pallas_interpret`` asks for it (the ``pallas`` pytest marker
    does). It is never inferred from the backend, so a process that did
    not get the chip fails to compile a kernel instead of interpreting
    it in silence; dispatch (:func:`kernel_enabled`) is what routes
    other backends to the counted XLA fallbacks."""
    return bool(get_flag("pallas_interpret"))


def backend_supported() -> bool:
    """True when Pallas kernel bodies can execute here: a real TPU, or
    any backend with the interpreter forced (``FLAGS_pallas_interpret``,
    flipped by the ``pallas`` pytest marker)."""
    import jax
    return jax.default_backend() == "tpu" or interpret()


def kernel_enabled(name: str, note: bool = True) -> bool:
    """One gate for every kernel call site: flag on AND backend capable.

    ``note=False`` suppresses fallback accounting for probe-style calls
    (``kernels()`` uses it to report status without inflating counters).
    """
    flag, _ = _REGISTRY[name]
    if flag is not None and not get_flag(flag):
        if note:
            note_fallback(name, "flag_off")
        return False
    if not backend_supported():
        if note:
            note_fallback(name, "cpu_backend")
        return False
    if not interpret():
        # GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot
        # be automatically partitioned" at lowering): under a mesh these
        # call sites have no per-shard form yet, so they take the XLA
        # path, which it can. flash_attention has one (ops.attention).
        from ...distributed.spmd import auto_axes
        if auto_axes():
            if note:
                note_fallback(name, "mesh")
            return False
    return True


def kernels() -> List[dict]:
    """Enumerate the kernel layer: name, kill-switch flag (and its
    current value), whether dispatch would serve the Pallas body right
    now (``live``), the XLA fallback that serves otherwise, and the
    fallback counts observed so far; the ``flash_attention`` row also
    holds :data:`FLASH_CAUSAL_WORK` (``causal_work``). Consumed by
    ``tools/monitor_report.py --kernels`` and the registry tests."""
    import jax
    rows = []
    for name, (flag, fallback) in _REGISTRY.items():
        if name == "flash_attention":
            live = jax.default_backend() == "tpu"
        else:
            live = kernel_enabled(name, note=False)
        with _STATS_LOCK:
            fb = {k[1]: v for k, v in PALLAS_STATS.items()
                  if k[0] == name}
            work = {"causal_work": {
                k: {"computed": c, "kept": m}
                for k, (c, m) in FLASH_CAUSAL_WORK.items()}} \
                if name == "flash_attention" else {}
        rows.append({
            "kernel": name,
            "flag": f"FLAGS_{flag}" if flag else None,
            "flag_value": bool(get_flag(flag)) if flag else None,
            "live": bool(live),
            "fallback": fallback,
            "fallbacks_seen": fb,
            **work,
        })
    return rows


# -- kernel entry points (lazy imports: pallas/jax.experimental loads
# only when a kernel is actually called) ----------------------------------

def flash_attention(*args, **kw):
    from .flash_attention import flash_attention as _fa
    return _fa(*args, **kw)


def chunked_ce_loss(*args, **kw):
    from .chunked_ce import chunked_ce_loss as _ce
    return _ce(*args, **kw)


def paged_decode_attention(*args, **kw):
    from .paged_decode import paged_decode_attention as _pd
    return _pd(*args, **kw)


def paged_decode_attention_quant(*args, **kw):
    from .paged_decode import paged_decode_attention_quant as _pd
    return _pd(*args, **kw)


def paged_mla_decode(*args, **kw):
    from .paged_decode import paged_mla_decode as _pd
    return _pd(*args, **kw)


def int8_matmul(*args, **kw):
    from .quant_matmul import int8_matmul as _mm
    return _mm(*args, **kw)


def int8_linear(*args, **kw):
    from .quant_matmul import int8_linear as _ln
    return _ln(*args, **kw)


def int8_amp_linear(*args, **kw):
    from .quant_matmul import int8_amp_linear as _al
    return _al(*args, **kw)


def quantize_per_channel(*args, **kw):
    from .quant_matmul import quantize_per_channel as _q
    return _q(*args, **kw)


def bgmv(*args, **kw):
    from .bgmv import bgmv as _b
    return _b(*args, **kw)


def bgmv_xla(*args, **kw):
    from .bgmv import bgmv_xla as _b
    return _b(*args, **kw)
