"""SPMD pipeline parallelism: mesh-placed stages in ONE jitted program.

reference parity: fleet/meta_parallel/pipeline_parallel.py:80-151 (1F1B
schedule, one process per stage), pp_utils/p2p_communication.py:25-443
(NCCL p2p activation send/recv), framework/section_worker.cc:153 (per-stage
worker threads).

TPU-native redesign — collective-permute pipelining (the GSPMD/scaling-book
formulation) instead of a process-per-stage runtime:

- The pipeline body is N identical blocks whose parameters are STACKED
  along a leading layer axis ([L, ...] per leaf) and sharded over the
  ``pp`` mesh axis, so stage s physically owns layers
  [s*L/S, (s+1)*L/S) — the analogue of the reference's per-stage
  parameter placement, expressed as a layout. Inside each stage the local
  layers run as one ``jax.lax.scan`` (the nn/scan.py scan-over-layers
  recipe), so trace/compile cost is O(1) in depth.
- TWO schedules share that layout (selected by ``fleet.strategy``'s
  ``pipeline_configs['schedule_mode']`` / ``FLAGS_pipeline_schedule``;
  see :func:`resolve_schedule`):

  ``fill_drain`` (GPipe) — one ``lax.scan`` over T = M + S - 1 ticks
  advances every stage in lockstep inside a partial-manual ``shard_map``
  (manual over ``pp``, auto/GSPMD over dp/mp/sp — tensor parallelism
  keeps working inside each stage). Each tick ``lax.ppermute`` rotates
  activations stage -> stage+1 over ICI. Backward is plain ``jax.grad``
  through the scan (ppermute transposes to the reverse rotation), with
  ``jax.checkpoint`` on the stage body. This is the kill-switch fallback:
  forward-only execution (eval, logits) always uses it.

  ``1f1b`` — the real one-forward-one-backward schedule as ONE combined
  fwd+bwd program (:meth:`PipelineStageStack.train_loss`). A scan over
  T = 2(M + S - 1) slots; at slot t, stage s runs the FORWARD of
  microbatch m_f = (t - s)/2 when t ≡ s (mod 2) and the BACKWARD of
  m_b = (t - (2S-1-s))/2 on the opposite parity (``lax.switch`` on a
  per-device predicate — real branch divergence, not masking). The loss
  head runs on the LAST stage inside its forward slot, so each
  microbatch's backward starts one slot after its forward finishes —
  the canonical 1F1B timetable: bubble (S-1)/(M+S-1), in-flight
  activations bounded by S - s stage INPUTS per stage (a ring buffer;
  backward recomputes the stage from its saved input — activation
  memory O(S), not O(M)). The O(S) bound is for INTER-LAYER
  activations; the microbatched model input x_mb and its gradient
  buffer are O(B) on every rank (replicated in-spec + dx carry used
  only where s == 0) — both schedules pay that, it is the price of
  returning dx for the embedding backward at this interface. Both ppermutes (activations down, cotangents
  up) issue every slot OUTSIDE the branch so XLA's async scheduler can
  overlap them with the slot's compute; parameter gradients accumulate
  per stage and the DP reduction of the accumulated grads is left to
  GSPMD, which schedules it against the backward tail.

  The 1F1B program computes loss AND gradients in its forward pass and
  exposes them through ``jax.custom_vjp`` whose backward merely scales
  by the incoming loss cotangent — exact for any LINEAR consumer of the
  loss (sums, means, AMP loss scaling), which is every trainer here.

Numerical parity: both schedules only reorder *which device* computes a
microbatch — parity with sequential execution is exact up to float
reassociation of the per-microbatch loss sums (pinned in
tests/test_pipeline_1f1b.py at 1e-6). Stochastic models: both schedules
derive stage RNG from the same (microbatch, stage) fold, so dropout
masks are schedule-invariant and the kill switch preserves trajectories
for dropout > 0 too (pinned); the NON-pipelined sequential path keys
per layer over the whole batch instead of per microbatch, so dropout>0
parity holds between schedules but not vs single-device execution.

Backend capability: XLA:CPU's SPMD partitioner cannot compile
manual-subgroup collectives (a ``ppermute``/``psum`` inside a shard_map
that is manual over ``pp`` but auto over a NONTRIVIAL dp/mp axis
hard-aborts the process: ``Check failed: IsManualSubgroup``; plain
``axis_index`` raises ``PartitionId ... not supported``). TPU is fine.
:func:`manual_collectives_ok` gates every pipelined program; unsupported
meshes degrade to sequential GSPMD execution of the SAME pp-sharded
stacked parameters (bit-identical math, no schedule) with a one-time
warning + ``pipeline_fallback_total`` counter, mirroring nn/scan.py's
fallback telemetry.

Fault tolerance: eager dispatches of pipeline programs run under the PR 5
collective watchdog (``FLAGS_collective_timeout_s`` + chaos site
``collective.hang``), so a hung stage handoff raises a structured
:class:`~paddle_tpu.distributed.collective.CollectiveTimeoutError`
instead of stalling the controller; TrainStep applies the same guard to
its whole step program when the model contains a pipeline (see
jit/to_static.py).
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...core.flags import get_flag
from ...core.random import make_rng, trace_rng
from ...core.tensor import Tensor, apply
from ...nn.layer import Layer
from .. import env as dist_env

__all__ = ["PP_AXIS", "PipelineStageStack", "resolve_schedule",
           "manual_collectives_ok", "bubble_fraction", "schedule_slots",
           "schedule_timetable", "pipeline_comm_model", "PIPELINE_STATS",
           "reset_pipeline_stats", "note_pipeline_fallback"]

PP_AXIS = "pp"

SCHEDULES = ("fill_drain", "1f1b")

#: observability (the nn/scan.py SCAN_STATS convention): programs built,
#: eager dispatches, and schedule fallbacks (pp mesh present but the
#: pipelined program could not run — backend capability or config).
PIPELINE_STATS = {"programs_built": 0, "dispatches": 0, "fallbacks": 0}

_FALLBACK_WARNED: set = set()


def reset_pipeline_stats():
    PIPELINE_STATS["programs_built"] = 0
    PIPELINE_STATS["dispatches"] = 0
    PIPELINE_STATS["fallbacks"] = 0
    _FALLBACK_WARNED.clear()


def note_pipeline_fallback(reason: str, detail: str = "") -> None:
    """A pp>1 mesh is active but the pipelined program degraded to
    sequential GSPMD execution — make the silent-degradation loud
    (one-time RuntimeWarning per reason) and countable."""
    PIPELINE_STATS["fallbacks"] += 1
    key = (reason, detail)
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        warnings.warn(
            f"SPMD pipeline degraded to sequential execution (reason: "
            f"{reason}{'; ' + detail if detail else ''}); the math is "
            "unchanged but no pipeline schedule runs. On XLA:CPU this is "
            "expected for meshes with nontrivial dp/mp axes (the SPMD "
            "partitioner cannot compile manual-subgroup collectives); on "
            "TPU check FLAGS_pipeline_schedule and the mesh axes.",
            RuntimeWarning, stacklevel=3)
    from ...monitor import enabled as _mon_enabled
    if _mon_enabled():
        from ...monitor import get_registry
        get_registry().counter(
            "pipeline_fallback_total",
            "pp meshes that degraded to sequential execution, by cause",
        ).inc(reason=reason)


def manual_collectives_ok(mesh, axis: str = PP_AXIS) -> bool:
    """Can this backend compile collectives inside a shard_map manual over
    ``axis`` with the other mesh axes auto?

    XLA:CPU is held to meshes whose other axes are trivial. Under jax
    0.4.37 its SPMD partitioner aborted the process on manual-subgroup
    collectives (``Check failed: IsManualSubgroup``). Under jaxlib 0.9.0
    that no longer holds for the three programs probed with this check
    forced open (PR 22: the pp2 x mp2 x dp2 GPT pipeline, the ep4 x dp2
    MoE exchange and the ps4 x dp2 table lookup all compiled and ran),
    so the exclusion is now only conservative: it is
    kept because lifting it makes the counted mixed-mesh fallbacks of
    the pipeline, MoE and recsys layers unreachable, and that deletion
    (with the rest of the mesh matrix re-run) is its own change — see
    ROADMAP, Design. Other backends: yes, but no pipeline schedule has
    been compiled for a TPU yet.
    """
    if mesh is None or axis not in mesh.axis_names:
        return False
    if jax.default_backend() != "cpu":
        return True
    return all(int(mesh.shape[a]) == 1
               for a in mesh.axis_names if a != axis)


def resolve_schedule(explicit: Optional[str] = None) -> str:
    """Pipeline schedule resolution: ``FLAGS_pipeline_schedule`` (global
    kill switch) > explicit constructor/config arg > the fleet strategy's
    ``pipeline_configs['schedule_mode']`` (reference spelling ``1F1B`` /
    ``F-then-B`` normalized) > ``1f1b`` default."""
    flag = str(get_flag("pipeline_schedule") or "").strip()
    for cand in (flag, explicit or ""):
        norm = _normalize_schedule(cand)
        if norm:
            return norm
    try:
        from ..fleet import _strategy
        mode = _strategy().pipeline_configs.get("schedule_mode", "1F1B")
    except Exception:
        mode = "1F1B"
    return _normalize_schedule(str(mode)) or "1f1b"


def _normalize_schedule(name: str) -> Optional[str]:
    s = name.strip().lower().replace("-", "_")
    if not s:
        return None
    if s in ("1f1b", "one_f_one_b"):
        return "1f1b"
    if s in ("fill_drain", "f_then_b", "fthenb", "gpipe"):
        return "fill_drain"
    raise ValueError(
        f"unknown pipeline schedule {name!r}; expected one of "
        f"{SCHEDULES} (FLAGS_pipeline_schedule / "
        "strategy.pipeline_configs['schedule_mode'])")


def schedule_slots(schedule: str, S: int, M: int) -> int:
    """Lockstep slots the schedule occupies. fill_drain counts forward
    ticks only (backward is the autodiff mirror, same count); 1f1b counts
    combined fwd+bwd slots."""
    if S <= 1:
        return M
    return (M + S - 1) if schedule == "fill_drain" else 2 * (M + S - 1)


def bubble_fraction(schedule: str, S: int, M: int) -> float:
    """Idle-slot fraction of the schedule. Both fill_drain (fwd scan +
    its autodiff mirror) and 1f1b sit at the canonical
    (S-1)/(M+S-1) — 1f1b's win over fill_drain is the O(S) activation
    memory, not the bubble."""
    if S <= 1:
        return 0.0
    return (S - 1) / (M + S - 1)


def schedule_timetable(schedule: str, S: int, M: int) -> Dict[str, np.ndarray]:
    """Host-side occupancy grid of the IMPLEMENTED schedule predicates.

    Returns ``{"fwd": [S, T], "bwd": [S, T], "busy": [S, T],
    "bubble_fraction": float}`` where ``fwd[s, t]`` is True iff stage s
    does useful forward work in slot t. For ``1f1b`` this replays the
    exact integer predicates the traced program branches on
    (``f_valid``/``b_valid`` in :meth:`PipelineStageStack._1f1b_fn`), so
    the bubble here is *measured from the implementation's timetable*,
    not the closed-form formula — bench/tests gate the two against each
    other. ``fill_drain`` models the forward scan plus its autodiff
    mirror (same occupancy, time-reversed)."""
    T = schedule_slots(schedule, S, M)
    s = np.arange(S)[:, None]
    t = np.arange(T)[None, :]
    if S <= 1:
        fwd = np.ones((S, T), bool)
        bwd = np.zeros((S, T), bool)
    elif schedule == "fill_drain":
        # forward tick t runs microbatch t - s on stage s when valid; the
        # backward mirror has identical occupancy reversed in time
        fwd = (t - s >= 0) & (t - s < M)
        bwd = fwd[:, ::-1]
    else:
        m_f = (t - s) // 2
        f_par = (t - s) % 2 == 0
        fwd = f_par & (m_f >= 0) & (m_f < M)
        m_b = (t - (2 * S - 1 - s)) // 2
        bwd = (~f_par) & (m_b >= 0) & (m_b < M)
    if schedule == "fill_drain" and S > 1:
        # fwd scan and bwd mirror are two sequential passes of T ticks
        busy = np.concatenate([fwd, bwd], axis=1)
    else:
        busy = fwd | bwd
    frac = 1.0 - float(busy.sum()) / busy.size if busy.size else 0.0
    return {"fwd": fwd, "bwd": bwd, "busy": busy,
            "bubble_fraction": frac}


def pipeline_comm_model(schedule: str, S: int, M: int,
                        boundary_bytes: int) -> Dict[str, float]:
    """Static per-step comm model of the schedule's stage handoffs:
    ppermute ops and bytes moved per optimizer step (per device).
    fill_drain: one activation permute per forward tick + its transpose
    per backward tick; 1f1b: one activation + one cotangent permute per
    slot. ``boundary_bytes`` = bytes of ONE microbatch's stage-boundary
    activation."""
    if S <= 1:
        return {"ops": 0, "bytes": 0, "slots": schedule_slots(
            schedule, S, M), "bubble_fraction": 0.0}
    slots = schedule_slots(schedule, S, M)
    # one permute pair per slot either way: 1f1b sends activation +
    # cotangent every slot; fill_drain sends one activation per forward
    # tick plus its transpose in the backward mirror
    ops = 2 * slots
    return {"ops": float(ops), "bytes": float(ops) * boundary_bytes,
            "slots": float(slots),
            "bubble_fraction": bubble_fraction(schedule, S, M)}


def _reg_name(template_name: str) -> str:
    """Dotted template param path -> attribute-safe registration name."""
    return "stacked__" + template_name.replace(".", "__")


def _pp_group(S: int):
    """Lightweight Group handle naming the pp axis for watchdog/telemetry
    labels (no ring bootstrap — the axis name IS the communicator)."""
    from ..collective import Group
    return Group(list(range(S)), gid=-101, axis_name=PP_AXIS)


def _guarded_dispatch(op: str, S: int, fn, *args):
    """Eager pipeline-program dispatch under the PR 5 collective watchdog
    (FLAGS_collective_timeout_s / chaos ``collective.hang``): a hung stage
    handoff becomes a structured CollectiveTimeoutError. Traced calls
    (inside an outer jit) bypass — the enclosing TrainStep guards its own
    dispatch."""
    if any(isinstance(a, jax.core.Tracer)
           for a in jax.tree_util.tree_leaves(args)):
        return fn(*args)
    PIPELINE_STATS["dispatches"] += 1
    from ..collective import _run_collective
    return _run_collective(op, _pp_group(S), fn, *args)


class PipelineStageStack(Layer):
    """N structurally-identical blocks stacked into [L, ...] parameters and
    executed as an SPMD pipeline over the ``pp`` mesh axis.

    ``layer_factory() -> Layer`` is called once per layer for
    initialization (each draws its own init RNG) and once more for the
    *template* whose forward() is traced per stage. Blocks must map an
    input of shape X to an output of the same shape (residual blocks) and
    must not own buffers.

    Without a mesh (or with pp degree 1) the stack degrades to sequential
    execution of the same stacked parameters — bit-identical math, no
    pipeline machinery, so one model definition serves 1..S stages. The
    same degradation applies (with a warning + counter) on backends that
    cannot compile the pipelined program (see
    :func:`manual_collectives_ok`).

    ``schedule`` picks the training schedule for :meth:`train_loss`
    (``None`` = resolve from FLAGS/fleet strategy at call time);
    :meth:`forward` (logits/eval) always runs the fill-drain forward.
    """

    def __init__(self, layer_factory: Callable[[], Layer], num_layers: int,
                 axis: str = PP_AXIS,
                 num_microbatches: Optional[int] = None, remat: bool = True,
                 schedule: Optional[str] = None):
        super().__init__()
        self.axis = axis
        self.num_layers = int(num_layers)
        self.num_microbatches = num_microbatches
        self.remat = remat
        if schedule is not None:
            _normalize_schedule(schedule)       # validate eagerly
        self.schedule = schedule

        template = layer_factory()
        if dict(template.named_buffers()):
            raise ValueError(
                "PipelineStageStack blocks must not own buffers (got "
                f"{list(dict(template.named_buffers()))}); fold running "
                "stats out of the pipelined body")
        # the template is a tracing vehicle, not a child module: its params
        # are placeholders that bind() swaps for stacked slices
        self.__dict__["_template"] = template

        # stack per-layer initializations: [L, ...] leaves
        per_layer = [dict((k, p._data) for k, p in
                          template.named_parameters())]
        for _ in range(self.num_layers - 1):
            blk = layer_factory()
            per_layer.append({k: p._data
                              for k, p in blk.named_parameters()})

        self._name_map: Dict[str, str] = {}
        t_params = dict(template.named_parameters())
        for tname, tparam in t_params.items():
            stacked = jnp.stack([d[tname] for d in per_layer])
            rname = _reg_name(tname)
            self._name_map[rname] = tname
            param = self.create_parameter(
                stacked.shape, dtype=str(stacked.dtype),
                default_initializer=lambda shape, dtype, _a=stacked: _a)
            tspec = getattr(tparam, "spec", None) or P()
            param.spec = P(self.axis, *tuple(tspec))
            setattr(self, rname, param)

    # -- degree bookkeeping ------------------------------------------------
    def _pp_degree(self) -> int:
        mesh = dist_env.get_mesh()
        if mesh is not None and self.axis in mesh.axis_names:
            return int(mesh.shape[self.axis])
        return 1

    def resolved_schedule(self) -> str:
        return resolve_schedule(self.schedule)

    def _sync_template_mode(self):
        tmpl = self.__dict__["_template"]
        tmpl.training = self.training
        for sub in tmpl.sublayers():
            sub.training = self.training

    def _stage_apply(self, local_params, h, key):
        """Run this stage's L/S layers over raw arrays (template-bound).

        Composes the nn/scan.py scan-over-layers recipe inside the stage:
        the local layer slice runs as ONE ``jax.lax.scan`` (trace cost
        O(1) in local depth, each layer folding its index into the stage
        RNG key) — the ``FLAGS_scan_layers`` kill switch restores the
        per-layer Python loop."""
        from ...jit.functional import bind
        tmpl = self.__dict__["_template"]
        n_local = int(local_params[next(iter(local_params))].shape[0])
        if not get_flag("scan_layers") or n_local < 2:
            with trace_rng(key):
                for j in range(n_local):
                    sl = {k: v[j] for k, v in local_params.items()}
                    with bind(tmpl, sl):
                        h = tmpl(Tensor(h))._data
            return h

        from ...nn.scan import SCAN_STATS
        SCAN_STATS["scan_calls"] += 1

        def body(carry, xs):
            SCAN_STATS["body_traces"] += 1
            sl, j = xs
            with trace_rng(jax.random.fold_in(key, j)), bind(tmpl, sl):
                out = tmpl(Tensor(carry))._data
            return out.astype(carry.dtype), None

        h_out, _ = jax.lax.scan(
            body, h,
            (dict(local_params), jnp.arange(n_local, dtype=jnp.int32)))
        return h_out

    def _can_pipeline(self, S: int, note: bool = True) -> bool:
        """pp > 1 AND the backend can compile the manual-pp program.
        ``note=False`` probes without counting — train_loss's schedule
        pick probes first and then delegates to forward(), whose own
        check records the ONE fallback for the degraded dispatch."""
        if S <= 1:
            return False
        mesh = dist_env.get_mesh()
        if not manual_collectives_ok(mesh, self.axis):
            if note:
                note_pipeline_fallback(
                    "manual_collectives_unsupported",
                    f"backend={jax.default_backend()} mesh="
                    f"{dict(mesh.shape) if mesh is not None else None}")
            return False
        return True

    def _resolve_M(self, num_microbatches: Optional[int], S: int,
                   B: int) -> int:
        M = int(num_microbatches or self.num_microbatches or S)
        if B % M:
            raise ValueError(f"batch {B} not divisible into {M} "
                             "microbatches")
        return M

    # -- execution ---------------------------------------------------------
    def forward(self, x, num_microbatches: Optional[int] = None):
        self._sync_template_mode()
        S = self._pp_degree()
        rnames = list(self._name_map)
        params = [getattr(self, r) for r in rnames]

        if not self._can_pipeline(S):
            def seq_fn(h, *leaves):
                local = {self._name_map[r]: a
                         for r, a in zip(rnames, leaves)}
                return self._stage_apply(local, h, make_rng("pipeline"))
            return apply(seq_fn, x, *params, name="pipeline_seq")

        if self.num_layers % S:
            raise ValueError(f"pp degree {S} must divide num_layers "
                             f"{self.num_layers}")
        M = self._resolve_M(num_microbatches, S, x.shape[0])
        mesh = dist_env.get_mesh()
        mb = x.shape[0] // M
        pipe = self._pipe_program(mesh, S, M, mb)

        def pipe_fn(x_raw, *leaves):
            x_mb = x_raw.reshape((M, mb) + x_raw.shape[1:])
            out_mb = _guarded_dispatch(
                "pipeline.fill_drain", S, pipe, x_mb,
                make_rng("pipeline"), *leaves)
            return out_mb.reshape((x_raw.shape[0],) + out_mb.shape[2:])

        return apply(pipe_fn, x, *params, name="spmd_pipeline")

    def _pipe_program(self, mesh, S: int, M: int, mb: int):
        """Cached jitted shard_map fill-drain program for (mesh, S, M, mb,
        training). The jax.jit object must persist across forward() calls
        or every eager call would recompile; it inlines when tracing."""
        cache = self.__dict__.setdefault("_pipe_cache", {})
        ckey = (id(mesh), "fill_drain", S, M, mb, self.training, self.remat)
        cached = cache.get(ckey)
        if cached is not None:
            return cached

        axis = self.axis
        rnames = list(self._name_map)
        T = M + S - 1
        stage = self._stage_apply
        if self.remat:
            from ..fleet.utils.recompute import flash_residuals_policy
            stage = jax.checkpoint(stage, policy=flash_residuals_policy())

        def shard_body(xs, key, *local_leaves):
            local = {self._name_map[r]: a
                     for r, a in zip(rnames, local_leaves)}

            def tick(carry, t):
                idx = jax.lax.axis_index(axis)
                x_sel = jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
                h = jnp.where(idx == 0, x_sel, carry)
                # stage RNG keyed by (microbatch, stage) — the SAME fold
                # the 1F1B program uses (stage_key in _1f1b_fn), so
                # dropout masks are schedule-invariant and the
                # FLAGS_pipeline_schedule kill switch stays 1e-6-parity
                # even for stochastic models. At tick t this stage works
                # on microbatch t - idx (clipped on fill/drain garbage
                # ticks, whose outputs are discarded).
                m = jnp.clip(t - idx, 0, M - 1)
                tkey = jax.random.fold_in(jax.random.fold_in(key, m), idx)
                y = stage(local, h, tkey)
                nxt = jax.lax.ppermute(
                    y, axis, [(i, i + 1) for i in range(S - 1)])
                return nxt, y

            _, ys = jax.lax.scan(tick, jnp.zeros_like(xs[0]),
                                 jnp.arange(T))
            # valid outputs live on the last stage at ticks S-1..T-1
            out = ys[S - 1:]
            idx = jax.lax.axis_index(axis)
            return jax.lax.psum(
                jnp.where(idx == S - 1, out, jnp.zeros([], out.dtype)),
                axis)

        # partial-manual shard_map (manual pp, auto dp/mp/sp) is only
        # legal under jit; jax.jit inlines when we are already inside an
        # outer trace and compiles (once, cached) for eager calls
        pipe = jax.jit(dist_env.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), P()) + (P(axis),) * len(rnames),
            out_specs=P(), axis_names={axis}, check_vma=False))
        cache[ckey] = pipe
        PIPELINE_STATS["programs_built"] += 1
        self._publish_comm_model("fill_drain", S, M)
        return pipe

    # -- schedule-aware training loss --------------------------------------
    def train_loss(self, x, head_apply: Callable, head_leaves: Sequence,
                   mb_args: Sequence = (),
                   num_microbatches: Optional[int] = None,
                   head_token=None):
        """Pipelined training loss under the resolved schedule.

        ``head_apply(head_leaf_arrays, y, *mb_arg_arrays) ->
        (loss_sum, denom)``: the loss head applied AFTER the stack — raw
        jax arrays in, two f32 scalars out (sum of per-token losses and
        the normalizer, e.g. the loss-mask sum). The same function serves
        every schedule (on the last stage, per microbatch, under 1f1b; on
        the full batch under fill_drain/sequential), so the math is
        identical up to summation order. Returns the scalar loss Tensor
        ``loss_sum / max(denom, 1)``.

        ``head_leaves``/``mb_args`` are Tensors: head parameters (receive
        gradients) and per-sample data (labels/masks, split into
        microbatches along dim 0 for 1f1b; no cotangents — data).
        ``head_token``: hashable identity for ``head_apply`` so cached
        traces survive across calls (pass something stable).

        Schedule selection: :func:`resolve_schedule`; 1f1b additionally
        requires training mode, pp > 1 and a capable backend, otherwise
        it falls back to fill_drain (counted when the cause is backend
        capability).
        """
        self._sync_template_mode()
        S = self._pp_degree()
        sched = self.resolved_schedule()
        use_1f1b = (sched == "1f1b" and self.training
                    and self._can_pipeline(S, note=False))
        n_mb = len(mb_args)

        if not use_1f1b:
            out = self.forward(x, num_microbatches=num_microbatches)

            def head_fn(y, *rest):
                return head_apply(list(rest[n_mb:]), y, *rest[:n_mb])

            ls, dn = apply(head_fn, out, *mb_args, *head_leaves,
                           name="pipeline_head",
                           _cache_token=("pipe_head", head_token, n_mb,
                                         self.training))
            return apply(lambda a, b: a / jnp.maximum(b, 1.0), ls, dn,
                         name="pipeline_loss",
                         _cache_token=("pipeline_loss",))

        if self.num_layers % S:
            raise ValueError(f"pp degree {S} must divide num_layers "
                             f"{self.num_layers}")
        M = self._resolve_M(num_microbatches, S, x.shape[0])
        mesh = dist_env.get_mesh()
        mb = x.shape[0] // M
        rnames = list(self._name_map)
        params = [getattr(self, r) for r in rnames]
        n_stack = len(params)
        fn = self._1f1b_fn(mesh, S, M, head_apply, n_mb, n_stack,
                           len(head_leaves), head_token)

        def big(x_raw, *rest):
            x_mb = x_raw.reshape((M, mb) + x_raw.shape[1:])
            from ..spmd import constrain
            x_mb = constrain(x_mb, None, "__batch__")
            key = make_rng("pipeline")
            key = key._data if isinstance(key, Tensor) else key
            # typed keys cannot cross custom_vjp (no tangent type): ship
            # the raw uint32 key data, rewrap inside the program
            if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                key = jax.random.key_data(key)
            mb_raw = tuple(
                a.reshape((M, mb) + a.shape[1:]) for a in rest[:n_mb])
            sid = jnp.arange(S, dtype=jnp.int32)
            return fn(x_mb, key, sid, *mb_raw, *rest[n_mb:])

        ls, dn = apply(big, x, *mb_args, *params, *head_leaves,
                       name="spmd_pipeline_1f1b",
                       _cache_token=("pipe_1f1b", id(mesh), S, M, mb,
                                     head_token, n_mb, self.training))
        # token-keyed: a fresh lambda would miss the eager op cache and
        # run an eager jax.vjp (Python-path tracing) on every warm call
        return apply(lambda a, b: a / jnp.maximum(b, 1.0), ls, dn,
                     name="pipeline_loss", _cache_token=("pipeline_loss",))

    def _1f1b_fn(self, mesh, S: int, M: int, head_apply, n_mb: int,
                 n_stack: int, n_head: int, head_token):
        """Build (and cache) the custom_vjp 1F1B combined program.

        Signature of the returned fn (all positional):
            (x_mb [M,mb,...], key_data, sid [S], *mb_args [M,mb,...],
             *stack_leaves [L,...], *head_leaves) -> (loss_sum, denom)
        """
        cache = self.__dict__.setdefault("_pipe_cache", {})
        ckey = (id(mesh), "1f1b", S, M, self.training, head_token, n_mb,
                n_stack, n_head)
        cached = cache.get(ckey)
        if cached is not None:
            return cached

        axis = self.axis
        rnames = list(self._name_map)
        tnames = [self._name_map[r] for r in rnames]
        T = 2 * (M + S - 1)
        stage = self._stage_apply

        def program(x_mb, kd, sid, *rest):
            mb_raw = rest[:n_mb]
            stack_loc = {t: a for t, a in zip(tnames, rest[n_mb:n_mb +
                                                           n_stack])}
            head_raw = list(rest[n_mb + n_stack:])
            key = jax.random.wrap_key_data(kd)
            s = sid[0]

            def stage_key(m):
                return jax.random.fold_in(jax.random.fold_in(key, m), s)

            zero_h = jnp.zeros_like(x_mb[0])
            zero_head = [jnp.zeros_like(a) for a in head_raw]
            zero_stack = {t: jnp.zeros_like(a)
                          for t, a in stack_loc.items()}

            def slot(carry, sigma):
                (h_recv, g_recv, g_self, fbuf, dxbuf, gacc, hacc,
                 loss_sum, denom) = carry
                m_f = (sigma - s) // 2
                f_par = (sigma - s) % 2 == 0
                f_valid = f_par & (m_f >= 0) & (m_f < M)
                m_b = (sigma - (2 * S - 1 - s)) // 2
                b_valid = (~f_par) & (m_b >= 0) & (m_b < M)
                m_f_c = jnp.clip(m_f, 0, M - 1)
                m_b_c = jnp.clip(m_b, 0, M - 1)

                x_sel = jax.lax.dynamic_index_in_dim(
                    x_mb, m_f_c, 0, keepdims=False)
                h_in = jnp.where(s == 0, x_sel, h_recv)
                mb_f = tuple(jax.lax.dynamic_index_in_dim(
                    a, m_f_c, 0, keepdims=False) for a in mb_raw)

                def f_branch(_):
                    y = stage(stack_loc, h_in, stage_key(m_f_c))

                    def do_head(_):
                        (ls, dn), vjp = jax.vjp(
                            lambda hl, yy: head_apply(hl, yy, *mb_f),
                            head_raw, y)
                        dhead, dy = vjp((jnp.float32(1.0),
                                         jnp.float32(0.0)))
                        return dy, dhead, ls, dn

                    def no_head(_):
                        return (jnp.zeros_like(y), zero_head,
                                jnp.float32(0.0), jnp.float32(0.0))

                    dy, dhead, ls, dn = jax.lax.cond(
                        s == S - 1, do_head, no_head, None)
                    new_fbuf = jax.lax.dynamic_update_index_in_dim(
                        fbuf, h_in, m_f_c % S, 0)
                    return dict(y_send=y, g_send=zero_h, g_self=dy,
                                fbuf=new_fbuf, dxbuf=dxbuf,
                                dstack=zero_stack, dhead=dhead, ls=ls,
                                dn=dn)

                def b_branch(_):
                    h_saved = jax.lax.dynamic_index_in_dim(
                        fbuf, m_b_c % S, 0, keepdims=False)
                    g_in = jnp.where(s == S - 1, g_self, g_recv)
                    _, vjp = jax.vjp(
                        lambda p, h: stage(p, h, stage_key(m_b_c)),
                        stack_loc, h_saved)
                    dstack, dh = vjp(g_in.astype(h_saved.dtype)
                                     if g_in.dtype != h_saved.dtype
                                     else g_in)
                    new_dx = jnp.where(
                        s == 0,
                        jax.lax.dynamic_update_index_in_dim(
                            dxbuf, dh.astype(dxbuf.dtype), m_b_c, 0),
                        dxbuf)
                    return dict(y_send=zero_h, g_send=dh, g_self=g_self,
                                fbuf=fbuf, dxbuf=new_dx, dstack=dstack,
                                dhead=zero_head, ls=jnp.float32(0.0),
                                dn=jnp.float32(0.0))

                def idle(_):
                    return dict(y_send=zero_h, g_send=zero_h,
                                g_self=g_self, fbuf=fbuf, dxbuf=dxbuf,
                                dstack=zero_stack, dhead=zero_head,
                                ls=jnp.float32(0.0), dn=jnp.float32(0.0))

                branch = jnp.where(f_valid, 0, jnp.where(b_valid, 1, 2))
                o = jax.lax.switch(branch, [f_branch, b_branch, idle],
                                   None)
                # stage handoffs OUTSIDE the branch, both directions each
                # slot — double-buffered into the carry (sent this slot,
                # consumed next slot) so XLA can overlap the permutes with
                # the slot's compute
                h_next = jax.lax.ppermute(
                    o["y_send"], axis, [(i, i + 1) for i in range(S - 1)])
                g_next = jax.lax.ppermute(
                    o["g_send"], axis, [(i + 1, i) for i in range(S - 1)])
                gacc2 = {t: gacc[t] + o["dstack"][t] for t in gacc}
                hacc2 = [a + d for a, d in zip(hacc, o["dhead"])]
                return ((h_next, g_next, o["g_self"], o["fbuf"],
                         o["dxbuf"], gacc2, hacc2, loss_sum + o["ls"],
                         denom + o["dn"]), None)

            carry0 = (zero_h, zero_h, zero_h,
                      jnp.zeros((S,) + x_mb.shape[1:], x_mb.dtype),
                      jnp.zeros_like(x_mb), zero_stack, zero_head,
                      jnp.float32(0.0), jnp.float32(0.0))
            carry, _ = jax.lax.scan(slot, carry0,
                                    jnp.arange(T, dtype=jnp.int32))
            (_, _, _, _, dxbuf, gacc, hacc, loss_sum, denom) = carry
            last = s == S - 1
            loss_sum = jax.lax.psum(jnp.where(last, loss_sum, 0.0), axis)
            denom = jax.lax.psum(jnp.where(last, denom, 0.0), axis)
            dx = jax.lax.psum(
                jnp.where(s == 0, dxbuf, jnp.zeros_like(dxbuf)), axis)
            hgrads = [jax.lax.psum(a, axis) for a in hacc]
            return (loss_sum, denom, dx,
                    tuple(gacc[t] for t in tnames), tuple(hgrads))

        in_specs = ((P(), P(), P(axis)) + (P(),) * n_mb
                    + (P(axis),) * n_stack + (P(),) * n_head)
        out_specs = (P(), P(), P(), (P(axis),) * n_stack, (P(),) * n_head)
        pipe = jax.jit(dist_env.shard_map(
            program, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names={axis}, check_vma=False))

        def run(*args):
            return _guarded_dispatch("pipeline.1f1b", S, pipe, *args)

        @jax.custom_vjp
        def f(*args):
            ls, dn, _, _, _ = run(*args)
            return ls, dn

        def f_fwd(*args):
            ls, dn, dx, gstack, ghead = run(*args)
            # keep the non-diff args so bwd can shape their zero/float0
            # cotangents (labels/masks/key/sid are data, not parameters)
            return (ls, dn), (dx, gstack, ghead, args[1], args[2],
                              args[3:3 + n_mb])

        def f_bwd(res, g):
            dx, gstack, ghead, kd, sid, mb_raw = res
            g_ls, _g_dn = g

            def data_cot(a):
                if jnp.issubdtype(a.dtype, jnp.floating):
                    return jnp.zeros_like(a)
                return np.zeros(a.shape, dtype=jax.dtypes.float0)

            return ((dx * g_ls, data_cot(kd), data_cot(sid))
                    + tuple(data_cot(a) for a in mb_raw)
                    + tuple(gl * g_ls for gl in gstack)
                    + tuple(gh * g_ls for gh in ghead))

        f.defvjp(f_fwd, f_bwd)

        cache[ckey] = f
        PIPELINE_STATS["programs_built"] += 1
        self._publish_comm_model("1f1b", S, M)
        return f

    # -- observability ----------------------------------------------------
    def _publish_comm_model(self, schedule: str, S: int, M: int) -> None:
        """Registry gauges describing the schedule's comm structure (the
        traced collectives the eager comm_* series cannot see):
        per-step ppermute ops/bytes and the analytic bubble fraction.
        tools/monitor_report.py --comms renders them next to the eager
        collectives table. Monitor off = zero registry writes."""
        try:
            from ...monitor import enabled as _mon_enabled
            if not _mon_enabled():
                return
            from ...monitor import get_registry
            reg = get_registry()
            labels = {"op": "ppermute", "schedule": schedule, "pp": S,
                      "microbatches": M}
            model = pipeline_comm_model(schedule, S, M, 0)
            reg.gauge(
                "pipeline_comm_ops_per_step",
                "traced stage-handoff collectives per optimizer step "
                "(schedule model)").set(model["ops"], **labels)
            reg.gauge(
                "pipeline_bubble_fraction",
                "analytic schedule bubble (idle-slot share)").set(
                    model["bubble_fraction"], **labels)
        except Exception:
            pass

    # -- interop -----------------------------------------------------------
    def layer_state_dict(self, i: int) -> Dict[str, jax.Array]:
        """Per-layer view of the stacked parameters (template names)."""
        return {self._name_map[r]: getattr(self, r)._data[i]
                for r in self._name_map}

    def load_from_layers(self, layers):
        """Restack parameters from a list of per-layer Layers (e.g. a
        non-pipelined model's blocks) — resume/convert path."""
        if len(layers) != self.num_layers:
            raise ValueError("layer count mismatch")
        dicts = [{k: p._data for k, p in l.named_parameters()}
                 for l in layers]
        for rname, tname in self._name_map.items():
            getattr(self, rname)._data = jnp.stack(
                [d[tname] for d in dicts])
