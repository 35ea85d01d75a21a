"""Global configuration flags.

TPU-native analogue of the reference's three-tier flag system
(reference: paddle/fluid/platform/flags.cc — 48 gflags settable via env
``FLAGS_*`` and ``paddle.set_flags``; pybind/global_value_getter_setter.cc).

Here flags live in a single registry; values are read from the environment
(``FLAGS_<name>``) at first access and can be overridden with
:func:`set_flags` / read with :func:`get_flags`.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass
class _Flag:
    name: str
    default: Any
    help: str
    parser: Callable[[str], Any]
    value: Any = None
    explicitly_set: bool = False


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


_REGISTRY: Dict[str, _Flag] = {}


def define_flag(name: str, default: Any, help: str = "") -> None:
    if isinstance(default, bool):
        parser: Callable[[str], Any] = _parse_bool
    elif isinstance(default, int):
        parser = int
    elif isinstance(default, float):
        parser = float
    else:
        parser = str
    _REGISTRY[name] = _Flag(name, default, help, parser)


def get_flag(name: str) -> Any:
    flag = _REGISTRY.get(name)
    if flag is None:
        raise KeyError(f"Unknown flag: {name!r}")
    if flag.explicitly_set:
        return flag.value
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        return flag.parser(env)
    return flag.default


def set_flags(flags: Dict[str, Any]) -> None:
    """paddle.set_flags analogue."""
    for name, value in flags.items():
        flag = _REGISTRY.get(name)
        if flag is None:
            raise KeyError(f"Unknown flag: {name!r}")
        flag.value = value
        flag.explicitly_set = True


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    return {n: get_flag(n) for n in names}


@contextlib.contextmanager
def flag_scope(name: str, value: Any):
    """Temporarily override a flag for a with-block.

    Restores BOTH the previous value and the explicitly-set bit —
    ``set_flags`` alone cannot do that (it forces ``explicitly_set``,
    which would permanently shadow a ``FLAGS_*`` env override)."""
    flag = _REGISTRY.get(name)
    if flag is None:
        raise KeyError(f"Unknown flag: {name!r}")
    saved = (flag.value, flag.explicitly_set)
    flag.value = value
    flag.explicitly_set = True
    try:
        yield
    finally:
        flag.value, flag.explicitly_set = saved


# ---------------------------------------------------------------------------
# Core flag set (TPU-relevant subset of the reference's platform/flags.cc)
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf after each eager op.")
define_flag("benchmark", False, "Synchronize after each op for benchmarking.")
define_flag("eager_jit_ops", True, "Cache-jit elementary eager ops.")
define_flag("default_dtype", "float32", "Default floating dtype.")
define_flag("allocator_strategy", "xla", "Kept for API parity; XLA owns HBM on TPU.")
define_flag("check_finite", False, "Check gradients finite after backward.")
define_flag("tpu_matmul_precision", "highest",
            "Precision for dot ops with f32 operands (matmul/linear/einsum/"
            "attention, the flash-attention kernels included). 'highest' = "
            "full f32 (reference CUDA parity); 'default' lets the backend "
            "pick (one bf16 pass on TPU). bf16 operands never needed it and "
            "do not read it: their products are exact in one bf16 pass with "
            "f32 accumulation. Convolutions follow the XLA backend default; "
            "use AMP/bf16 for the MXU fast path.")
define_flag("jit_channels_last", True,
            "Run 2-D NCHW conv/BN/pool chains channels-last (NHWC, the TPU "
            "MXU-native conv layout) inside jitted TrainStep traces: one "
            "transpose at model entry/exit instead of per-op NCHW dimension "
            "numbers. Public API layout is unchanged (docs/PARITY.md, "
            "internal-layout contract).")
define_flag("fused_conv_bn", True,
            "Fuse Conv2D+BatchNorm(+ReLU) chains in the vision models into "
            "one op (nn.functional.fused_conv_bn): conv epilogue fusion in "
            "XLA, one tape node in eager. f32 EMA buffers preserved under "
            "AMP.")
define_flag("log_level", "0", "Verbose log level (VLOG analogue).")
define_flag("scan_layers", True,
            "Run homogeneous transformer decoder/encoder stacks as ONE "
            "jax.lax.scan over layer-stacked parameters (nn.scan): trace+"
            "compile cost drops from O(num_layers) to O(1). Per-layer "
            "state_dict names and the LayerList API are unchanged "
            "(docs/PARITY.md internal-layout contract). Models opt in via "
            "their config (GPTConfig/BertConfig/ErnieConfig.scan_layers); "
            "this flag is the global kill switch.")
define_flag("scan_decode", True,
            "Run paged-KV-cache decode/prefill through the SAME "
            "scan-over-layers program layout as training (nn.scan."
            "scan_layers_with_cache): the KV page pools ride the scan's "
            "carry whole (never sliced by layer; layer l addresses pages "
            "l*P + block_table and scatters its new rows in place), so "
            "the decode program's trace+compile cost stays O(1) in depth. "
            "Off = the per-layer Python loop over the same pools with the "
            "same addressing (same math, O(num_layers) trace; the kill "
            "switch if a backend mishandles the carried cache). Legacy "
            "list-of-StaticCache decoding always uses the loop and "
            "records a scan_fallback_total counter.")
define_flag("chunked_ce_threshold", 4096,
            "Vocab size at or above which softmax cross-entropy streams "
            "over vocab chunks (nn.chunked_ce): online logsumexp with f32 "
            "accumulation, never materializing the full-vocab f32 logits/"
            "log-probs. 0 disables the chunked path.")
define_flag("chunked_ce_chunk", 8192,
            "Vocab chunk width for the streamed cross-entropy (rounded "
            "down to the vocab size; any remainder tail is masked, so "
            "non-multiple vocab sizes are exact).")
define_flag("monitor", False,
            "Stream hot-path telemetry into the paddle_tpu.monitor metrics "
            "registry: per-step TrainStep wall/dispatch timings, compile/"
            "recompile counters, grad-accum and LocalSGD sync boundaries. "
            "Off (default) = ZERO per-step registry writes on the train "
            "step hot path (tests pin this). Eager collective tracing is "
            "always on (registry writes are noise next to a shard_map "
            "dispatch) and the check_numerics watchdog is its own "
            "TrainStep argument — neither is gated by this flag.")
define_flag("memory_preflight", "",
            "OOM pre-flight check: when a TrainStep program compiles, "
            "compare its static HBM estimate (monitor.memory, from "
            "compiled.memory_analysis()) against the device HBM budget "
            "BEFORE step 1 touches real capacity. '' (default) = off; "
            "'warn' = RuntimeWarning when the estimate exceeds the "
            "budget; 'raise' = MemoryBudgetError. No-op when the budget "
            "is unknown (CPU test backend) and no explicit limit is set.")
define_flag("memory_preflight_limit_mb", 0,
            "Explicit HBM budget (MiB) for the pre-flight check; 0 = ask "
            "the device (memory_stats()['bytes_limit']). Set it to a "
            "TARGET chip's HBM to answer 'will this config fit a v5e?' "
            "from any dev machine.")
define_flag("flight_recorder", False,
            "Record every TrainStep into the crash flight recorder ring "
            "buffer even with FLAGS_monitor off, and install the "
            "unhandled-exception + faulthandler dump hooks at the first "
            "TrainStep construction. (FLAGS_monitor on also records "
            "steps; this flag adds the hooks and keeps recording when "
            "the registry stream is off.)")
define_flag("flight_recorder_dir", "",
            "Directory for flight-recorder dump files "
            "(flight_recorder_*.json); empty = current directory.")
define_flag("flight_recorder_capacity", 256,
            "Ring-buffer size of the flight recorder: how many recent "
            "step records survive to a crash dump.")
define_flag("checkpoint_verify", "manifest",
            "Checkpoint validation level for distributed.checkpoint "
            "restores and latest_step scans. 'manifest' (default) = a "
            "committed manifest must exist and every file it lists must "
            "be present with the recorded size (catches uncommitted and "
            "torn directories); 'full' = additionally re-checksum every "
            "file against the manifest CRCs (catches silent bit "
            "corruption, costs one read of the checkpoint); 'off' = "
            "existence check only (restores legacy pre-manifest "
            "checkpoints). CRCs are RECORDED at commit time only under "
            "'full' (they cost a full re-read of the staged tree); "
            "manifests without CRCs still verify at 'manifest' level.")
define_flag("collective_timeout_s", 0.0,
            "Watchdog timeout (seconds) for EAGER collectives in "
            "distributed.collective: a dispatch that does not return "
            "within the budget raises CollectiveTimeoutError (with a "
            "collective_timeout flight-recorder event) instead of "
            "hanging the controller forever. 0 (default) = no watchdog, "
            "direct dispatch. The budget covers the whole dispatch "
            "including a first-call trace+compile — set it well above "
            "the cold-start time.")
define_flag("chaos", "",
            "Deterministic fault-injection spec for "
            "paddle_tpu.testing.chaos (tests and fault drills): "
            "comma-separated 'site[@N|:prob][*times]' entries, e.g. "
            "'ckpt.write.torn@2,collective.hang:0.1'. Empty (default) = "
            "no injection, zero probe overhead.")
define_flag("chaos_seed", 0,
            "Seed for probability-based chaos sites: the same "
            "(seed, site, occurrence) triple always makes the same "
            "fire/no-fire decision, so chaos runs replay exactly.")
define_flag("serve_watchdog_s", 0.0,
            "Wall-clock watchdog (seconds) for serving prefill/decode "
            "dispatches (paddle_tpu.serving.engine): a dispatch that "
            "does not return within the budget raises "
            "DecodeWatchdogError (with a decode_watchdog flight event "
            "and dump) instead of stalling the serving loop forever. "
            "0 (default) = no watchdog, direct dispatch — zero "
            "overhead. The budget covers a whole dispatch including a "
            "cold compile; warmup() first, or set it well above "
            "cold-start time. Modeled on FLAGS_collective_timeout_s.")
define_flag("serve_prefix_cache", False,
            "Radix-tree prefix cache over the serving KV page pools "
            "(paddle_tpu.serving.prefix_cache): completed/evicted "
            "requests donate their full pages into a token-keyed radix "
            "tree with per-page refcounts; admission walks the tree and "
            "maps shared pages copy-on-write into the new slot's block "
            "table, so chat traffic with shared system prompts skips "
            "the redundant prefix prefill (vLLM PagedAttention / SGLang "
            "RadixAttention). Off (default) = the pre-cache path, "
            "bit-compatible — every admission prefills from token 0. "
            "Read at ServingEngine construction.")
define_flag("serve_prefill_chunk", 0,
            "Chunked prefill (paddle_tpu.serving.engine): > 0 = long "
            "prompts prefill in chunks of at most this many tokens, one "
            "chunk per engine iteration interleaved with the decode "
            "dispatches, so a long admission no longer stalls running "
            "decodes for its whole prompt (the TTFT-spike killer under "
            "bursty load). Later chunks attend over the pages earlier "
            "chunks wrote (the context-prefill program). 0 (default) = "
            "one-shot prefill, bit-compatible with the pre-chunking "
            "path. Read at ServingEngine construction.")
define_flag("serve_spec_k", 0,
            "Speculative decoding draft length (paddle_tpu.serving."
            "spec_decode): > 0 = an n-gram/prompt-lookup drafter (no "
            "second model) proposes up to k tokens per slot and ONE "
            "batched verify dispatch scores all k+1 positions against "
            "the paged cache; the accepted prefix plus one bonus token "
            "commit, rejected tails roll back by block-table truncation. "
            "Greedy output is token-identical to the non-speculative "
            "path (pinned); sampled slots run stochastic residual "
            "accept/reject (ISSUE 16), distribution-identical to plain "
            "sampled decode. 0 (default) = one decode dispatch per "
            "token, bit-compatible. Read at ServingEngine construction.")
define_flag("serve_spec_ngram", 3,
            "Longest suffix n-gram the speculative drafter matches "
            "against the request's own prompt+generated history "
            "(prompt-lookup decoding); it backs off to shorter n-grams "
            "down to 1 before giving up on a slot for the iteration.")
define_flag("pallas_ce", True,
            "Serve the streamed (chunked) hard-label cross-entropy with "
            "the fused Pallas kernel (ops.pallas.chunked_ce): online f32 "
            "logsumexp forward + one-pass dlogits backward, one VMEM-"
            "resident [rows, chunk] tile per grid step. Off = the pure-XLA "
            "fori_loop streaming path (nn.chunked_ce, bit-identical to "
            "the pre-kernel implementation). Soft labels and the dense "
            "mp-sharded path never use the kernel.")
define_flag("pallas_paged_decode", True,
            "Serve paged-KV decode attention (serving, S==1) with the "
            "Pallas flash-decode kernel (ops.pallas.paged_decode): K/V "
            "block-table pages are read in place via scalar-prefetch "
            "indexing — the [B, MB*bs, H, D] gathered context never "
            "materializes in HBM. Off = the XLA gather_pages + masked "
            "SDPA composition (bit-identical to the pre-kernel path).")
define_flag("pallas_int8", True,
            "Serve slim.QuantizedLinear matmuls with the Pallas int8 "
            "kernel (ops.pallas.quant_matmul): per-output-channel-scaled "
            "int8 x int8 -> int32 with a dequantize epilogue; weights "
            "stay int8 through the gemm (weight-only mode quantizes the "
            "activations dynamically per tensor). Off = the pre-kernel "
            "XLA paths (weight-only: dequantize-to-float matmul; static "
            "act_scale: XLA int8 dot).")
define_flag("pallas_bgmv", True,
            "Serve batched-LoRA shrink/expand projections (serving, "
            "multi-tenant decode) with the Pallas bgmv kernel "
            "(ops.pallas.bgmv): each slot's adapter id scalar-prefetch-"
            "indexes the stacked [n_adapters, r, d] A/B pools so the "
            "per-slot adapter weights are DMA'd straight from the pool "
            "— the gathered [B, r, d] copies never materialize in HBM. "
            "Off = the XLA gather + einsum composition (bit-identical "
            "to the pre-kernel math).")
define_flag("serve_kv_quant", "",
            "Quantized paged KV cache (paddle_tpu.serving.kv_cache): "
            "'int8' stores the K/V page pools as int8 with per-page, "
            "per-token-row, per-head absmax scales in a parallel f32 "
            "scale pool — roughly halving bytes per cached token vs "
            "bf16 (so ~2x slots per chip) at a documented greedy-decode "
            "parity bound. Quantization happens at write_pages; both "
            "the Pallas paged flash-decode kernel and the XLA gather "
            "fallback dequantize at read. Empty (default) = the "
            "bit-compatible full-precision pools (the flags-off "
            "oracle). Read once at engine/cache construction.")
define_flag("amp_int8_matmul", False,
            "EXPERIMENTAL: under an active amp.auto_cast region, run "
            "eligible nn.functional.linear matmuls through the Pallas "
            "int8 kernel with dynamic per-tensor activation/per-channel "
            "weight quantization and a straight-through dense backward "
            "(gradients flow to the UNquantized operands). Requires "
            "FLAGS_pallas_int8; off by default — int8 training is a "
            "numerics experiment, not the production AMP path.")
define_flag("pallas_interpret", False,
            "Run the ops.pallas kernel layer on non-TPU backends through "
            "the Pallas interpreter instead of falling back to XLA. "
            "SLOW — for kernel parity tests on CPU (the `pallas` pytest "
            "marker flips it); production CPU dispatch keeps the XLA "
            "fallbacks. This flag is the ONLY thing that selects the "
            "interpreter: a kernel called directly with it off compiles "
            "for the backend or fails. flash_attention's DISPATCH keeps "
            "its own shape/backend gate in ops.attention.")
define_flag("pipeline_schedule", "",
            "Global pipeline-schedule override for SPMD pipeline stacks: "
            "'1f1b' (one-forward-one-backward combined program) or "
            "'fill_drain' (GPipe fwd scan + autodiff mirror — the "
            "kill-switch-compatible fallback). Empty = resolve from the "
            "model/fleet strategy (pipeline_configs['schedule_mode']).")
define_flag("moe_dispatch", "sort",
            "MoE token dispatch/combine implementation "
            "(paddle_tpu.incubate.moe): 'sort' (default) = argsort-by-"
            "expert + static-shape gather/scatter — O(T·k·D) memory "
            "traffic, the TPU-efficient path; 'einsum' = the GShard "
            "one-hot dispatch/combine einsums that materialize "
            "O(T·E·C) tensors — the parity oracle and kill switch "
            "(bit-compatible with the pre-sort implementation). Both "
            "paths share one router, so capacity clipping and drop "
            "decisions are identical.")
define_flag("moe_expert_parallel", True,
            "Run stacked-expert MoE layers through the EXPLICIT "
            "expert-parallel program (shard_map manual over the 'ep' "
            "mesh axis + lax.all_to_all token exchange, double-buffered "
            "in capacity chunks so the all-to-alls overlap expert "
            "compute) when an ep>1 mesh is active and the backend can "
            "compile it. Off (or on incapable backends — XLA:CPU with "
            "another nontrivial mesh axis) = the GSPMD auto path: "
            "expert weights keep their P('ep', ...) specs and XLA "
            "inserts the collectives (counted moe_fallback_total "
            "telemetry, nn.scan fallback convention).")
define_flag("moe_a2a_chunks", 2,
            "Capacity-dim chunks of the expert-parallel all_to_all "
            "double buffer: each chunk's tokens-out all_to_all issues "
            "before any expert compute and its tokens-back all_to_all "
            "issues right after that chunk's FFN, so XLA's async "
            "scheduler can hide chunk i+1's exchange behind chunk i's "
            "compute (the PR 9 ppermute double-buffer recipe applied "
            "to ISSUE 10's expert exchange). 1 = no chunking.")
define_flag("recsys_dedup", True,
            "Unique/dedup embedding lookups in paddle_tpu.recsys "
            "(docs/RECSYS.md): sort-unique the batch ids, gather each "
            "distinct row ONCE, inverse-permute back — duplicate ids in "
            "a batch (the criteo hot-id regime) cost one row fetch, and "
            "sparse gradients accumulate over the unique set before the "
            "optimizer row update (the reference SparseTable push "
            "semantics). Off = the naive per-id gather/scatter — the "
            "parity oracle and kill switch (same math, O(batch) instead "
            "of O(unique) row traffic).")
define_flag("recsys_sharded_lookup", True,
            "Run ShardedEmbeddingTable lookups/updates through the "
            "EXPLICIT mesh program (shard_map manual over the 'ps' "
            "axis: each shard gathers the unique rows it owns, one "
            "psum assembles the batch — the PR 9/10 manual-collectives "
            "recipe) when a ps>1 mesh is active and the backend can "
            "compile it. Off (or on incapable backends — XLA:CPU with "
            "another nontrivial mesh axis) = the GSPMD auto path: the "
            "row-sharded table keeps its P('ps', ...) spec and XLA "
            "inserts the collectives (counted recsys_fallback_total "
            "telemetry, moe/nn.scan fallback convention).")
define_flag("trace", False,
            "Structured request/step tracing (monitor/trace.py): span "
            "trees with trace ids through the serving request lifecycle "
            "and the training step. Off (default) = zero span "
            "allocations and zero trace registry writes — the same "
            "zero-overhead contract as FLAGS_monitor, pinned by test.")
define_flag("trace_sample", 0.01,
            "Head sampling rate for structured traces (fraction of "
            "traces retained at random). Tail-based sampling keeps any "
            "trace containing an expired/shed/failed/watchdog/chaos/"
            "nonfinite event REGARDLESS of this rate, so anomalies "
            "always ship a full span tree.")
define_flag("trace_ring", 64,
            "Capacity of the retained-trace ring (flight-recorder "
            "model: newest N traces survive to a dump/export).")
define_flag("monitor_port", 0,
            "TCP port for the embedded admin/telemetry HTTP server "
            "(paddle_tpu.monitor.server): GET /metrics (Prometheus "
            "text with exemplars), /healthz, /readyz (503 while the "
            "serving engine is draining/shedding/watchdog-tripped), "
            "/statusz (fingerprint, flags, program table, occupancy, "
            "rates, SLO burn), /debug/flight, /debug/trace "
            "(?format=perfetto) and /debug/profile?seconds=N (arms a "
            "live profiler window, returns the chrome trace). Started "
            "by the serving engine and (opt-in) TrainStep when set; "
            "-1 = an ephemeral OS-assigned port (tests). 0 (default) "
            "= OFF: no thread, no socket, no registry writes — the "
            "zero-overhead contract, pinned by test.")
define_flag("monitor_host", "127.0.0.1",
            "Bind address for the admin server. Loopback by default — "
            "the plane exposes flags, program tables and profiles, so "
            "exposing it beyond the host is an explicit operator "
            "decision (front it with real auth if you must).")
define_flag("fleet_monitor_port", 0,
            "TCP port for the fleet federator's admin plane "
            "(paddle_tpu.monitor.fleet): a scrape loop pulls every "
            "configured replica /metrics page plus the router's "
            "registry into ONE host-labelled fleet registry and serves "
            "its own /metrics, /statusz (per-replica table), /healthz "
            "and /readyz (quorum of replica readiness). -1 = ephemeral "
            "OS-assigned port. 0 (default) = OFF: no scrape thread, no "
            "socket, no registry series — the same zero-overhead "
            "contract as FLAGS_monitor_port, pinned by test.")
define_flag("fleet_monitor_targets", "",
            "Comma-separated scrape targets for the fleet federator, "
            "each 'name=http://host:port' (the /metrics path is "
            "implied; /readyz and /debug/* derive from the same base). "
            "Empty (default) = federate the local process registry "
            "under the single host label 'fleet' — the in-process "
            "fleet shape, where router and replicas share one "
            "registry.")
define_flag("fleet_monitor_interval_s", 1.0,
            "Fleet federator scrape period in seconds. 1 Hz default — "
            "windowed fleet rates resolve at scrape granularity, and "
            "each scrape costs one /metrics page per target (see the "
            "scrape-interval guidance in docs/OBSERVABILITY.md).")
define_flag("fleet_monitor_slo", 0.0,
            "Fleet availability SLO objective as a fraction (e.g. "
            "0.999). Computed over the FEDERATED serve_requests_total "
            "deltas (good=completed; bad=expired/failed/shed) via the "
            "PR 11 SLOTracker; burn gauges publish as "
            "slo_burn_rate{slo='fleet_availability'}. 0 (default) = "
            "no fleet SLO tracker.")
define_flag("fleet_monitor_incident_dir", "",
            "Directory for anomaly-triggered incident bundles: when a "
            "fleet SLO burn alert fires or a tail-retained anomaly "
            "trace lands, the federator captures the implicated "
            "replica's flight-recorder doc, the merged Perfetto trace, "
            "the fleet statusz snapshot and the federated metrics page "
            "into a timestamped incident_* subdir (rate-limited; "
            "bundle dirs are .gitignore'd). Empty (default) = no "
            "incident capture.")
define_flag("train_goodput", False,
            "Training goodput ledger (monitor/goodput.py): attribute "
            "every second of trainer wall-clock to one exclusive "
            "bucket (productive_dispatch / compile / data_wait / "
            "checkpoint_stall / nonfinite_rollback / restart_gap / "
            "host_other), persist the totals in the CheckpointManager "
            "sidecar across SIGTERM->resume, and publish "
            "train_goodput_pct + train_badput_seconds_total{bucket} "
            "under FLAGS_monitor. Off (default) = one flag read per "
            "seam, no ledger allocation, no registry series — the "
            "zero-overhead contract, pinned by tests/test_goodput.py.")
define_flag("train_health_every", 0,
            "Per-layer model-health telemetry cadence: N > 0 compiles "
            "f32 per-layer grad-norm / param-norm / update-ratio "
            "side-outputs INTO the train step program (no extra "
            "dispatch; scan-over-layers stacks keep their per-layer "
            "param names) and publishes train_layer_* gauges every N "
            "optimizer steps, with an EWMA spike detector that "
            "tail-marks the step trace (reason 'health_spike') and "
            "attaches the last vector to flight-recorder dumps. "
            "0 (default) = OFF: the step program is bit-identical and "
            "nothing is computed or published.")
define_flag("serve_hot_swap", False,
            "Zero-downtime model lifecycle (serving/engine.py, ISSUE "
            "20): arm ServingEngine.swap_weights — load + verify a "
            "candidate manifest checkpoint, stage the new param tree "
            "beside the live one and cut over atomically at the next "
            "iteration boundary, in-flight slots finishing on the "
            "weights they started on (per-slot generation epoch; "
            "drain-and-restore fallback when HBM headroom can't hold "
            "two trees). Off (default) = swap_weights raises, no epoch "
            "bookkeeping exists, dispatch traffic is byte-identical to "
            "pre-lifecycle engines (pinned). Read once at engine "
            "construction.")
define_flag("serve_traffic_split", False,
            "Shadow/A-B traffic splitting (serving/router.py, ISSUE "
            "20): arm FleetRouter.set_traffic_split — a TrafficSplit "
            "policy hash-splits a deterministic fraction of requests "
            "onto a candidate replica (A/B) and/or mirrors a fraction "
            "as shadow copies (responses discarded but fully "
            "measured), with per-arm request counters, latency "
            "histograms and greedy-divergence counters. Off (default) "
            "= set_traffic_split raises, zero per-request overhead and "
            "zero new registry series (pinned). Read once at router "
            "construction.")
define_flag("serve_lifecycle", False,
            "SLO-guarded promotion controller (serving/lifecycle.py, "
            "ISSUE 20): arm LifecycleController — stage a candidate "
            "manifest on one replica, bake it under a traffic split "
            "while an SLOTracker watches the candidate arm's "
            "availability burn / non-finite rate / greedy divergence, "
            "then either promote (rolling swap, never two replicas "
            "down at once) or auto-roll-back to the previous weights, "
            "emitting flight events and an incident bundle on "
            "rollback. Off (default) = the controller refuses to "
            "construct; nothing else changes. Read once at controller "
            "construction.")
define_flag("compilation_cache", True,
            "Persist compiled XLA executables to disk so warm starts skip "
            "the first compile (reference analogue: the CUDA "
            "kernel/program caches). Applied at package import. The "
            "directory is placed from OUTSIDE: $JAX_COMPILATION_CACHE_DIR "
            "when set, else `.jax_cache` at the root of this checkout.")

#: where the cache goes when the environment does not place it: one fixed
#: directory inside the checkout. The path is part of jax's cache key, so
#: a per-user or per-machine location (~/.cache, $XDG_CACHE_HOME) never
#: hits across the machines a run moves between.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def apply_compilation_cache() -> Optional[str]:
    """Enable jax's persistent compilation cache. Called once at package
    import; safe to call again after set_flags. Returns the cache dir
    (or None when FLAGS_compilation_cache is off).

    ``JAX_COMPILATION_CACHE_DIR`` wins and nothing in code sets another
    directory over it; without it, a directory already given to
    ``jax.config`` is kept (the test suite's), else
    :data:`DEFAULT_COMPILATION_CACHE_DIR`."""
    if not get_flag("compilation_cache"):
        return None
    import jax
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or jax.config.jax_compilation_cache_dir
                 or DEFAULT_COMPILATION_CACHE_DIR)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        # a read-only checkout must still import: run uncached, say so
        import warnings
        warnings.warn(f"compilation cache disabled: cannot create "
                      f"{cache_dir!r} ({e})", RuntimeWarning, stacklevel=2)
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def matmul_precision():
    """Resolve the tpu_matmul_precision flag to a jax `precision=` value."""
    v = get_flag("tpu_matmul_precision")
    if v in (None, "", "default"):
        return None
    return v
