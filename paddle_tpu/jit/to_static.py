"""to_static: compile Layers/functions to cached XLA executables.

Design (vs reference program_translator.py:768):
- Forward inference: one jitted pure function per input signature.
- Eager-tape training through a StaticFunction: the whole compiled call
  becomes ONE tape node; its backward re-runs the compiled VJP (forward
  rematerialised inside the compiled backward — everything stays in XLA).
- The real training hot path is :class:`TrainStep`, which compiles
  forward+loss+grad+optimizer into a single donated-buffer executable
  (the analogue of the reference's whole-Program execution).
"""

from __future__ import annotations

import contextlib
import functools
import time
import weakref
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..core.random import make_rng, trace_rng
from ..core.tensor import TapeNode, Tensor, is_grad_enabled, no_grad
from ..monitor import trace as trace_mod
from ..nn.layer import Layer
from ..testing import chaos as _chaos
from .functional import (bind, buffer_arrays, param_arrays,
                         trainable_param_arrays, unwrap, wrap)
from .input_spec import InputSpec


def _sig_of(arrays):
    leaves, treedef = jax.tree_util.tree_flatten(arrays)
    return (tuple((a.shape, str(a.dtype)) if hasattr(a, "shape") else (type(a), a)
                  for a in leaves), treedef)


_CONTROL_FLOW_GUIDANCE = (
    "\n\nThis happened while compiling (tracing) the model: python "
    "control flow branched on a TRACED tensor value, which has no "
    "concrete value at compile time (reference analogue: the AST "
    "translator of program_translator.py rewrites `if`/`while` on "
    "tensors into conditional_block/while ops). The TPU-native fixes:\n"
    "  - paddle.static.nn.cond(pred, true_fn, false_fn) for tensor-"
    "dependent branches (compiles both, selects on device);\n"
    "  - paddle.static.nn.while_loop(cond_fn, body_fn, vars) for "
    "tensor-dependent loops;\n"
    "  - jnp.where / paddle.where for elementwise selects;\n"
    "  - move the branch decision to host data (python scalars) if it "
    "is static per call."
)


@contextlib.contextmanager
def _control_flow_guidance():
    """Append framework guidance to tracer-concretization errors (the
    exception object is re-raised with an amended message so user
    except-clauses keep matching the jax type)."""
    import jax.errors
    try:
        yield
    except jax.errors.ConcretizationTypeError as e:
        e.args = (str(e) + _CONTROL_FLOW_GUIDANCE,)
        raise


class StaticFunction:
    """Callable wrapping a Layer's forward (or a plain fn) with jit caching."""

    def __init__(self, function: Callable, layer: Optional[Layer] = None,
                 input_spec=None):
        self._fn = function
        self._layer = layer
        self._input_spec = input_spec
        self._cache: Dict[Any, Callable] = {}
        self._bwd_cache: Dict[Any, Callable] = {}
        functools.update_wrapper(self, function)

    # -- pure function factory ---------------------------------------------
    def _pure(self, treedef, kwargs):
        layer = self._layer
        fn = self._fn
        training = layer.training if layer is not None else False

        def pure(p_arrays, b_arrays, key, flat_inputs):
            inputs = jax.tree_util.tree_unflatten(treedef, flat_inputs)
            tensors = [Tensor(a) if isinstance(a, (jax.Array, jnp.ndarray)) or
                       hasattr(a, "dtype") else a for a in inputs]
            bufs = dict(b_arrays)
            with trace_rng(key), no_grad():
                if layer is not None:
                    with bind(layer, p_arrays, bufs):
                        out = fn(*tensors, **kwargs)
                else:
                    out = fn(*tensors, **kwargs)
            return unwrap(out), bufs

        return pure

    def __call__(self, *args, **kwargs):
        layer = self._layer
        p_arrays = param_arrays(layer) if layer is not None else {}
        b_arrays = buffer_arrays(layer) if layer is not None else {}
        raw_inputs = [a._data if isinstance(a, Tensor) else a for a in args]
        flat_inputs, treedef = jax.tree_util.tree_flatten(raw_inputs)
        key = make_rng("to_static")

        sig = (_sig_of(flat_inputs)[0], treedef,
               tuple(sorted(kwargs.items())) if kwargs else (),
               layer.training if layer is not None else False)

        jitted = self._cache.get(sig)
        if jitted is None:
            pure = self._pure(treedef, kwargs)
            jitted = jax.jit(pure)
            self._cache[sig] = jitted

        needs_grad = False
        if is_grad_enabled() and layer is not None:
            needs_grad = any(not p.stop_gradient
                             for p in layer.parameters())

        if not needs_grad:
            with _control_flow_guidance():
                out_arrays, new_bufs = jitted(p_arrays, b_arrays, key,
                                              flat_inputs)
            if layer is not None:
                for k, b in layer.named_buffers():
                    if k in new_bufs:
                        b._data = new_bufs[k]
            return wrap(out_arrays)

        # training path: one fused tape node, compiled remat backward
        t_params = {k: p for k, p in layer.named_parameters()
                    if not p.stop_gradient}
        t_arrays = {k: p._data for k, p in t_params.items()}
        frozen = {k: v for k, v in p_arrays.items() if k not in t_arrays}

        pure = self._pure(treedef, kwargs)

        with _control_flow_guidance():
            out_arrays, new_bufs = jitted(p_arrays, b_arrays, key,
                                          flat_inputs)

        bwd = self._bwd_cache.get(sig)
        if bwd is None:
            # key/buffers/frozen are explicit arguments (NOT closed over):
            # the cached executable must rematerialize the forward with the
            # *current* call's RNG key and buffers, or dropout masks in the
            # recomputed forward would come from the first call.
            def bwd_fn(t_a, frozen_a, b_a, k, flat_in, cotangents):
                def f(t_a_inner, flat_inner):
                    out, _ = pure({**frozen_a, **t_a_inner}, b_a, k, flat_inner)
                    return out
                _, vjp = jax.vjp(f, t_a, flat_in)
                return vjp(cotangents)
            bwd = jax.jit(bwd_fn)
            self._bwd_cache[sig] = bwd

        # tape node over (param tensors + diff input tensors)
        diff_inputs = [a for a in args if isinstance(a, Tensor)
                       and not a.stop_gradient]
        node_inputs = list(t_params.values()) + diff_inputs

        out_leaves, out_treedef = jax.tree_util.tree_flatten(out_arrays)
        out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_leaves]

        captured_inputs = list(flat_inputs)
        captured_key = key
        captured_bufs = b_arrays

        def vjp_fn(cots):
            cot_list = list(cots) if isinstance(cots, tuple) else [cots]
            cot_tree = jax.tree_util.tree_unflatten(out_treedef, cot_list)
            g_params, g_inputs = bwd(t_arrays, frozen, captured_bufs,
                                     captured_key, captured_inputs, cot_tree)
            grads = [g_params[k] for k in t_params.keys()]
            # map input grads back to diff tensor positions
            flat_gin, _ = jax.tree_util.tree_flatten(g_inputs)
            idx = 0
            for a in args:
                if isinstance(a, Tensor) and not a.stop_gradient:
                    grads.append(flat_gin[idx])
                if isinstance(a, Tensor):
                    idx += 1
            return tuple(grads)

        node = TapeNode(vjp_fn, node_inputs, out_avals, name="to_static")
        out_tensors = []
        for i, arr in enumerate(out_leaves):
            t = Tensor(arr, stop_gradient=not jnp.issubdtype(arr.dtype, jnp.floating))
            if not t.stop_gradient:
                t._node = node
                t._out_idx = i
                node.out_refs[i] = weakref.ref(t)
            out_tensors.append(t)
        if layer is not None:
            for k, b in layer.named_buffers():
                if k in new_bufs:
                    b._data = new_bufs[k]
        return jax.tree_util.tree_unflatten(out_treedef, out_tensors)

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)

    def concrete_program(self, *args):
        return None  # parity shim


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper compiling a Layer or function."""

    def _decorate(obj):
        from .dy2static import convert_to_static
        if isinstance(obj, Layer):
            fwd = convert_to_static(type(obj).forward).__get__(obj)
            static = StaticFunction(fwd, layer=obj, input_spec=input_spec)
            obj.forward = static
            return obj
        # plain function or unbound Layer.forward; python if/while over
        # tensors is functionalized by the dy2static AST pass (reference:
        # program_translator.py ProgramTranslator)
        fn = getattr(obj, "__func__", obj)
        bound = getattr(obj, "__self__", None)
        converted = convert_to_static(fn)
        if bound is not None:
            converted = converted.__get__(bound)
        return StaticFunction(converted, layer=bound,
                              input_spec=input_spec)

    if function is not None:
        return _decorate(function)
    return _decorate


def _layer_key(name: str) -> str:
    """Group a state-dict parameter name into its layer bucket: the
    prefix up to and including the first numeric path component
    (``layers.0.attn.qkv_weight`` → ``layers.0``), else the first
    component (``embed.weight`` → ``embed``). Scan-over-layers keeps
    per-layer state-dict names (nn/scan.py stacks at trace time only),
    so the grouping is layout-invariant."""
    parts = name.split(".")
    for i, p in enumerate(parts[:-1]):
        if p.isdigit():
            return ".".join(parts[:i + 1])
    return parts[0]


def _layer_health_outputs(old_params, new_params, grads):
    """Per-layer f32 health vectors computed INSIDE the compiled step
    (FLAGS_train_health_every): grad norm, post-update param norm, and
    the update ratio ||new-old|| / (||old|| + eps) — the classic
    training-health triple. A handful of reductions fused into the step
    program; no extra dispatch."""
    groups: Dict[str, list] = {}
    for k in grads:
        groups.setdefault(_layer_key(k), []).append(k)

    def sumsq(tree, ks):
        tot = jnp.zeros((), jnp.float32)
        for k in ks:
            a = tree[k]
            tot = tot + jnp.sum(jnp.square(a.astype(jnp.float32)))
        return tot

    out = {}
    for layer, ks in sorted(groups.items()):
        old_norm = jnp.sqrt(sumsq(old_params, ks))
        upd = jnp.sqrt(sum(
            jnp.sum(jnp.square((new_params[k] - old_params[k]
                                ).astype(jnp.float32))) for k in ks))
        out[layer] = {
            "grad_norm": jnp.sqrt(sumsq(grads, ks)),
            "param_norm": jnp.sqrt(sumsq(new_params, ks)),
            "update_ratio": upd / (old_norm + 1e-12),
        }
    return out


class TrainStep:
    """Compile (model, loss, optimizer) into ONE donated XLA train step.

    The TPU-native answer to the reference's static-graph training loop
    (Program + Executor): params/opt-state live as device arrays owned by
    this object; each step is a single compiled call with buffer donation.

    SPMD: pass ``mesh`` (or have fleet.init set one) and a ``data_spec``
    PartitionSpec for the batch; parameters are laid out per their
    ``Parameter.spec`` annotations, optimizer slots inherit the param
    sharding, and ``zero_axis`` additionally shards replicated slots over
    that mesh axis — ZeRO-1 optimizer-state partitioning (reference:
    fleet/meta_optimizers/sharding_optimizer.py:72; here a layout
    declaration, the weight-update all-gather is inserted by XLA).

    `sync_to_layer()` writes values back into the Layer for checkpointing /
    eager inspection.
    """

    def __new__(cls, layer=None, loss_fn=None, optimizer=None, *args,
                **kwargs):
        # fleet meta-optimizer dispatch (reference: strategy_compiler.py
        # picks the meta-optimizer from the strategy attached at
        # fleet.distributed_optimizer): a strategy snapshot carried by the
        # optimizer selects the LocalSGD step implementation. Strategy is
        # read ONLY from the optimizer — never from process globals — so
        # a bare optimizer always gets the plain step.
        strat = getattr(optimizer, "_fleet_strategy", None)
        if cls is TrainStep and strat is not None and (
                strat.localsgd or strat.adaptive_localsgd):
            from ..distributed.fleet.meta_optimizers import LocalSGDTrainStep
            from ..distributed.fleet.topology import (
                get_hybrid_communicate_group)
            hcg = get_hybrid_communicate_group()
            if hcg is None:
                raise RuntimeError(
                    "strategy.localsgd requires fleet.init() first (the dp "
                    "mesh axis hosts the per-replica parameter copies)")
            if strat.gradient_merge:
                raise NotImplementedError(
                    "strategy combines localsgd with gradient_merge; the "
                    "LocalSGD step does not accumulate gradients — pick "
                    "one (the reference's meta-optimizer chain rejects "
                    "this pairing too)")
            # arguments the LocalSGD step cannot honor must fail loudly,
            # not vanish (the silent-rewiring failure mode this dispatch
            # exists to eliminate)
            unsupported = {k: v for k, v in kwargs.items()
                           if k not in ("mesh", "data_spec") and
                           v is not None and v is not True}
            if args or unsupported:
                raise TypeError(
                    "strategy.localsgd builds a LocalSGDTrainStep, which "
                    f"does not accept {list(unsupported) or 'positional'} "
                    "arguments (metrics_fn/zero_axis/grad_accum_*); "
                    "construct distributed.fleet.meta_optimizers."
                    "LocalSGDTrainStep directly for custom wiring")
            adaptive = bool(strat.adaptive_localsgd)
            cfg = (strat.adaptive_localsgd_configs if adaptive
                   else strat.localsgd_configs)
            k = int(cfg.get("init_k_steps" if adaptive else "k_steps", 1))
            return LocalSGDTrainStep(
                layer, loss_fn, optimizer,
                kwargs.get("mesh") or hcg.mesh, k_steps=k,
                axis="dp", adaptive=adaptive)
        return super().__new__(cls)

    def __init__(self, layer: Layer, loss_fn: Callable, optimizer,
                 metrics_fn: Optional[Callable] = None, donate: bool = True,
                 mesh=None, data_spec=None, zero_axis: Optional[str] = None,
                 grad_accum_steps: Optional[int] = None,
                 grad_accum_avg: Optional[bool] = None,
                 check_numerics=False,
                 skip_nonfinite_budget: int = 0):
        from ..distributed import env as dist_env
        self.layer = layer
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.metrics_fn = metrics_fn
        if grad_accum_steps is None:
            # gradient merge comes ONLY from the strategy snapshot that
            # fleet.distributed_optimizer attached to this optimizer
            # (reference: gradient_merge_optimizer.py, applied by the
            # meta-optimizer chain at the distributed_optimizer boundary).
            # A bare optimizer is never silently rewired by fleet.init.
            grad_accum_steps = 1
            strat = getattr(optimizer, "_fleet_strategy", None)
            if strat is not None and strat.gradient_merge:
                cfg = strat.gradient_merge_configs
                grad_accum_steps = int(cfg["k_steps"])
                if grad_accum_avg is None:
                    grad_accum_avg = bool(cfg.get("avg", True))
        self.grad_accum_steps = max(1, int(grad_accum_steps))
        self.grad_accum_avg = True if grad_accum_avg is None \
            else grad_accum_avg
        self._acc_grads = None
        self._micro_count = 0
        self.mesh = mesh if mesh is not None else (
            dist_env.get_mesh() if data_spec is not None or zero_axis else None)
        self.data_spec = data_spec
        self.zero_axis = zero_axis
        if self.mesh is not None:
            if dist_env.get_mesh() is None:
                dist_env.set_mesh(self.mesh)
            from ..distributed.spmd import apply_param_shardings
            apply_param_shardings(layer, self.mesh)
        self.params = trainable_param_arrays(layer)
        self.frozen = {k: v for k, v in param_arrays(layer).items()
                       if k not in self.params}
        self.buffers = buffer_arrays(layer)
        self.opt_state = optimizer.init_state(self.params)
        if self.mesh is not None:
            self._layout_opt_state()
        self.step_count = 0
        self._jitted: Dict[Any, Callable] = {}
        self._donate = donate
        # -- telemetry (paddle_tpu.monitor; docs/OBSERVABILITY.md) ---------
        # check_numerics: opt-in eager NaN/Inf watchdog — the post-step
        # loss check runs OUTSIDE the compiled program (XLA fusion
        # untouched; contrast FLAGS_check_nan_inf, which compiles finite
        # flags into the step). The post-mortem grads pass needs the
        # PRE-update params/buffers alive after the step, so donation is
        # off in this mode. Values: False | True/"raise" | "warn".
        self._check_numerics = check_numerics
        # skip_nonfinite_budget: graceful degradation on a transient
        # numeric fault (GradScaler-style, docs/FAULT_TOLERANCE.md). On
        # a non-finite loss the whole update (params/opt-state/step
        # count) is ROLLED BACK and training continues; only after N
        # CONSECUTIVE skips does the trip raise — a single bad batch on
        # a week-long run is an event, not a crash. Needs the watchdog's
        # pre-update state alive, so donation is off in this mode too.
        self.skip_nonfinite_budget = max(0, int(skip_nonfinite_budget))
        self._consecutive_skips = 0
        if check_numerics or self.skip_nonfinite_budget:
            self._donate = False
        self._kinds_compiled: set = set()
        self._stats = {"compiles": 0, "recompiles": 0,
                       "grad_accum_syncs": 0, "nonfinite_trips": 0,
                       "nonfinite_skips": 0, "health_spikes": 0}
        # EWMA spike detector over the per-layer health side-outputs;
        # allocated on the first publish (FLAGS_train_health_every > 0)
        self._health_mon = None
        # per-program-kind attribution (ISSUE 4): cost from
        # lowered.cost_analysis(), HBM budget from
        # compiled.memory_analysis() — captured once per compile (never
        # on the step hot path), readable via stats() with monitor off
        self._programs: Dict[str, dict] = {}
        self._program_memory: Dict[str, Any] = {}
        self._wall_ema: Dict[str, float] = {}
        self._peak_flops_cache = None
        from ..core.flags import get_flag
        if get_flag("flight_recorder"):
            # crash forensics opt-in: excepthook + faulthandler dump
            # hooks from the first TrainStep on (docs/OBSERVABILITY.md)
            from ..monitor.flight_recorder import get_flight_recorder
            get_flight_recorder().install()
        if int(get_flag("monitor_port") or 0):
            # live telemetry plane opt-in for training runs: /metrics,
            # /statusz (this step registers its stats() as a section),
            # /debug/profile on the live process. Flag unset = one int
            # read, nothing else (docs/OBSERVABILITY.md).
            from ..monitor import server as monitor_server
            srv = monitor_server.maybe_start_from_flags()
            if srv is not None:
                import weakref
                ref = weakref.ref(self)
                stale = monitor_server.STALE
                srv.register_status(
                    f"train_step-{id(self)}",
                    lambda: (lambda s: s.stats() if s is not None
                             else stale)(ref()))
                from ..monitor import goodput as _goodput
                srv.register_status("goodput", _goodput.statusz_section)
        from ..core.tensor import eager_cache_stats
        from ..utils.compilation import compile_counts
        self._cc0 = compile_counts()
        self._ec0 = eager_cache_stats()
        # pipeline-aware dispatch guard: when the model carries an SPMD
        # pipeline over a pp>1 mesh, the whole step program IS the
        # pipeline dispatch path — run it under the PR 5 collective
        # watchdog (FLAGS_collective_timeout_s + chaos collective.hang)
        # so a hung stage handoff raises CollectiveTimeoutError on the
        # controller instead of stalling training (docs/PARALLELISM.md).
        self._pp_degree = 0
        try:
            from ..distributed.meta_parallel.spmd_pipeline import (
                PipelineStageStack)
            for sub in layer.sublayers(include_self=True):
                if isinstance(sub, PipelineStageStack):
                    self._pp_degree = max(self._pp_degree,
                                          sub._pp_degree())
        except Exception:
            pass
        # same guard for models carrying expert-parallel MoE layers over
        # an ep>1 mesh: the step program contains the expert all_to_alls
        # (ISSUE 10 — a hung expert exchange must raise structured)
        self._ep_degree = 0
        if not self._pp_degree:
            try:
                from ..incubate.moe import MoELayer
                for sub in layer.sublayers(include_self=True):
                    if isinstance(sub, MoELayer) and sub._stacked:
                        self._ep_degree = max(self._ep_degree,
                                              sub._ep_degree())
            except Exception:
                pass

    def _dispatch(self, jitted, *args):
        """Invoke a compiled step program; pipeline- and expert-parallel-
        carrying steps run under the collective watchdog (zero overhead
        with the timeout flag unset and no chaos armed)."""
        if self._pp_degree > 1:
            from ..distributed import collective as _coll
            from ..distributed.meta_parallel.spmd_pipeline import _pp_group
            return _coll._run_collective(
                "pipeline_step", _pp_group(self._pp_degree), jitted, *args)
        if self._ep_degree > 1:
            from ..distributed import collective as _coll
            from ..incubate.moe import moe_ep_group
            return _coll._run_collective(
                "moe_step", moe_ep_group(self._ep_degree), jitted, *args)
        return jitted(*args)

    # -- SPMD layout -------------------------------------------------------
    def _param_specs(self):
        from jax.sharding import PartitionSpec as P

        from ..distributed.spmd import degrade_spec
        specs = {}
        for k, p in self.layer.named_parameters():
            if k in self.params:
                spec = getattr(p, "spec", None) or P()
                # spec axes absent from THIS mesh degrade to replicated —
                # e.g. mp-annotated weights on an ep-only mesh
                if self.mesh is not None:
                    spec = degrade_spec(spec, self.mesh)
                specs[k] = spec
        return specs

    def _slot_spec(self, k, shape):
        """Optimizer-slot spec: param spec, plus ZeRO sharding of the first
        free, divisible dim over ``zero_axis``."""
        from jax.sharding import PartitionSpec as P
        spec = tuple(self._specs.get(k, P()))
        spec = spec + (None,) * (len(shape) - len(spec))
        if self.zero_axis and self.zero_axis in self.mesh.axis_names:
            z = self.mesh.shape[self.zero_axis]
            for i, (s, d) in enumerate(zip(spec, shape)):
                if s is None and d % z == 0 and d >= z:
                    spec = spec[:i] + (self.zero_axis,) + spec[i + 1:]
                    break
        return P(*spec)

    def _layout_opt_state(self):
        from jax.sharding import NamedSharding

        self._specs = self._param_specs()

        def place(k, slot):
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    a, NamedSharding(self.mesh, self._slot_spec(k, a.shape)))
                if hasattr(a, "shape") and a.ndim > 0 else a, slot)

        self.opt_state = {k: place(k, v) for k, v in self.opt_state.items()}

    def _place_batch(self, raw):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self.mesh is None or self.data_spec is None:
            return raw
        spec = tuple(self.data_spec)

        def put(a):
            if not hasattr(a, "ndim"):
                return a
            s = spec[:a.ndim] + (None,) * max(0, a.ndim - len(spec))
            return jax.device_put(a, NamedSharding(self.mesh, P(*s)))

        return [put(a) for a in raw]

    def _loss_and_grads(self, treedef):
        """Shared fwd+bwd kernel: (params, buffers, key, flat_batch) ->
        ((loss, new_bufs), grads)."""
        from ..core.flags import get_flag
        from ..nn import layout as nn_layout
        layer, loss_fn, frozen = self.layer, self.loss_fn, self.frozen
        # automatic NHWC rewrite (FLAGS_jit_channels_last): the trace runs
        # under the channels-last planner, so any 2-D NCHW conv/BN/pool
        # chain in the model compiles MXU-native — one layout transpose at
        # model entry/exit instead of per-op NCHW dimension numbers. Pure
        # python tracing state: numerics are layout-invariant (covered by
        # the NCHW/NHWC parity tests) and the flag is read at trace time.
        channels_last = bool(get_flag("jit_channels_last"))

        def run(params, buffers, key, flat_batch):
            batch = jax.tree_util.tree_unflatten(treedef, flat_batch)

            def compute_loss(p):
                tensors = [Tensor(b) for b in batch]
                bufs = dict(buffers)
                with trace_rng(key), no_grad(), \
                        nn_layout.channels_last_scope(channels_last):
                    with bind(layer, {**frozen, **p}, bufs):
                        loss = loss_fn(layer, *tensors)
                loss_arr = loss._data if isinstance(loss, Tensor) else loss
                return loss_arr.astype(jnp.float32), bufs

            return jax.value_and_grad(compute_loss, has_aux=True)(params)

        return run

    def _make_step(self, treedef, training=True, check_finite=False,
                   health=False):
        optimizer = self.optimizer
        run = self._loss_and_grads(treedef)

        def step(params, buffers, opt_state, lr, t, key, flat_batch):
            (loss, new_bufs), grads = run(params, buffers, key, flat_batch)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.apply_gradients(
                    params, grads, opt_state, lr, t)
            out = (new_params, new_bufs, new_opt, loss)
            if check_finite:
                # NaN/Inf debug under jit (reference: FLAGS_check_nan_inf +
                # nan_inf_utils: per-op device-side scan; here per-gradient
                # + loss flags, cheap booleans fetched with the loss)
                flags = {"loss": jnp.isfinite(loss)}
                for k, g in grads.items():
                    flags["grad:" + k] = jnp.isfinite(g).all()
                out = out + (flags,)
            if health:
                # FLAGS_train_health_every: per-layer health vectors as
                # side-outputs of the SAME program (always last element)
                out = out + (_layer_health_outputs(params, new_params,
                                                   grads),)
            return out

        return step

    # -- gradient merge (k-step accumulation) ------------------------------
    # reference: fleet/meta_optimizers/gradient_merge_optimizer.py — the
    # program rewrite that accumulates grads into persistent buffers and
    # gates the optimizer on step % k. TPU-native: two compiled programs
    # (accumulate-only and accumulate+update) over a donated accumulator
    # pytree; no cond divergence inside one program.
    def _make_accum_step(self, treedef):
        run = self._loss_and_grads(treedef)

        def step(params, buffers, acc, key, flat_batch):
            (loss, new_bufs), grads = run(params, buffers, key, flat_batch)
            new_acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return new_bufs, new_acc, loss

        return step

    def _make_apply_step(self, treedef, check_finite=False, health=False):
        optimizer = self.optimizer
        k = self.grad_accum_steps
        avg = self.grad_accum_avg
        run = self._loss_and_grads(treedef)

        def step(params, buffers, opt_state, acc, lr, t, key, flat_batch):
            (loss, new_bufs), grads = run(params, buffers, key, flat_batch)
            total = jax.tree_util.tree_map(jnp.add, acc, grads)
            if avg:
                total = jax.tree_util.tree_map(lambda g: g / k, total)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.apply_gradients(
                    params, total, opt_state, lr, t)
            zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
            out = (new_params, new_bufs, new_opt, zero, loss)
            if check_finite:
                flags = {"loss": jnp.isfinite(loss)}
                for key_, g in total.items():
                    flags["grad:" + key_] = jnp.isfinite(g).all()
                out = out + (flags,)
            if health:
                # health rides the optimizer-update boundary only: the
                # MERGED gradient is the one the update consumed
                out = out + (_layer_health_outputs(params, new_params,
                                                   total),)
            return out

        return step

    # -- telemetry (paddle_tpu.monitor) ------------------------------------
    def _note_compile(self, kind: str, mon: bool, fr: bool = False):
        """A jit-cache miss: a new executable is about to be built. A miss
        for a program KIND that already has a compiled entry is a
        RECOMPILE (shape change, flag flip) — the event the scan-layer
        work exists to eliminate, surfaced here so it can't regress
        silently."""
        st = self._stats
        st["compiles"] += 1
        recompile = kind in self._kinds_compiled
        if recompile:
            st["recompiles"] += 1
        self._kinds_compiled.add(kind)
        if fr:
            from ..monitor.flight_recorder import get_flight_recorder
            get_flight_recorder().record_event(
                "recompile" if recompile else "compile", kind=kind,
                step=self.step_count)
        if mon:
            from ..monitor import get_registry
            reg = get_registry()
            reg.counter("train_step_compiles_total",
                        "TrainStep executable builds by program kind"
                        ).inc(kind=kind)
            if recompile:
                reg.counter("train_step_recompiles_total",
                            "TrainStep recompiles (new signature for an "
                            "already-compiled program kind)").inc(kind=kind)

    def _compile_program(self, kind: str, fn: Callable, donate_argnums,
                         example_args, mon: bool):
        """Build one program's executable AOT (``lower`` + ``compile``)
        so its cost/memory attribution comes from the SAME lowering and
        executable the step will run — one trace, one backend compile,
        exactly like the dispatch path, but with the ``Lowered`` and
        ``Compiled`` stages in hand for ``cost_analysis()`` /
        ``memory_analysis()`` (the dispatch path hides both). The
        lower/compile + sharding-drift self-heal machinery lives in
        :class:`paddle_tpu.jit.aot.AOTProgram` (shared with the serving
        engine's bucketed signatures)."""
        from ..monitor import goodput as _goodput
        from .aot import AOTProgram
        with trace_mod.span("train.compile", kind=kind), \
                _goodput.measure("compile"):
            return AOTProgram(
                kind, fn, donate_argnums=donate_argnums,
                on_attribute=lambda k, lowered, compiled:
                    self._attribute_program(k, lowered, compiled, mon),
                name=f"train_{kind}",
            ).compile(example_args)

    def _attribute_program(self, kind: str, lowered, compiled, mon: bool):
        """Capture per-program FLOPs/bytes and the static HBM budget,
        register the budget process-wide, run the flag-gated OOM
        pre-flight, and (monitor on) publish attribution gauges."""
        from ..cost_model import CostModel
        from ..monitor import memory as monitor_memory
        entry = CostModel().attribute(lowered)
        pm = monitor_memory.analyze_compiled(compiled, kind=kind)
        if pm is not None:
            entry.update(peak_hbm_bytes=pm.peak_bytes,
                         argument_bytes=pm.argument_bytes,
                         output_bytes=pm.output_bytes,
                         temp_bytes=pm.temp_bytes,
                         generated_code_bytes=pm.generated_code_bytes)
            self._program_memory[kind] = pm
            monitor_memory.record_program(pm)
        self._programs[kind] = entry
        if mon:
            from ..monitor import get_registry
            reg = get_registry()
            reg.gauge("train_step_program_flops",
                      "static FLOPs per execution by program kind "
                      "(lowered.cost_analysis)").set(entry["flops"],
                                                     kind=kind)
            reg.gauge("train_step_program_bytes_accessed",
                      "static bytes accessed per execution by program "
                      "kind").set(entry["bytes_accessed"], kind=kind)
            if pm is not None:
                reg.gauge("train_step_program_peak_hbm_bytes",
                          "static peak-HBM estimate by program kind "
                          "(compiled.memory_analysis)"
                          ).set(pm.peak_bytes, kind=kind)
        if pm is not None:
            # OOM pre-flight BEFORE step 1 touches real capacity;
            # no-op unless FLAGS_memory_preflight is set
            monitor_memory.preflight_check(pm)

    def _record_step_metrics(self, t_wall: float, dispatch_s: float,
                             kind: str = "step"):
        from ..monitor import get_registry
        wall = time.perf_counter() - t_wall
        # per-kind wall EMA feeds the stats() MFU gauge (monitor-mode
        # only; meaningful when the loop blocks per step, as bench does)
        prev = self._wall_ema.get(kind)
        self._wall_ema[kind] = wall if prev is None \
            else 0.8 * prev + 0.2 * wall
        reg = get_registry()
        # goodput metrics ride the same monitor-mode publish cadence
        from ..monitor import goodput as _goodput
        led = _goodput.active_ledger()
        if led is not None:
            led.publish(reg)
        reg.counter("train_step_steps_total",
                    "TrainStep calls by program kind").inc(kind=kind)
        reg.histogram("train_step_dispatch_seconds",
                      "time for the jitted call to return (async XLA "
                      "dispatch)").observe(dispatch_s, kind=kind)
        reg.histogram("train_step_wall_seconds",
                      "full TrainStep.__call__ wall time (host prep + "
                      "dispatch)").observe(wall, kind=kind)
        # live-plane MFU: the same flops/(wall·peak) arithmetic stats()
        # computes on demand, published as a gauge so /metrics scrapers
        # and monitor_top see utilization without calling stats().
        # Absent on unknown chips (CPU test backend: peak is None).
        peak = self._peak_flops_cache
        if peak is None:
            try:
                from ..cost_model import device_peak_flops
                peak = device_peak_flops()
            except Exception:
                peak = 0.0
            self._peak_flops_cache = peak or 0.0
        flops = self._programs.get(kind, {}).get("flops")
        if peak and flops:
            reg.gauge("train_step_mfu",
                      "model FLOPs utilization by program kind (wall "
                      "EMA vs chip peak)").set(
                flops / (self._wall_ema[kind] * peak), kind=kind)

    def _publish_health(self, hvec, mon: bool):
        """Host side of the per-layer health pipeline, every
        FLAGS_train_health_every optimizer steps: read the f32 scalars
        back (the ONLY extra device sync of the feature, at publish
        cadence), publish train_layer_* gauges (monitor mode), run the
        EWMA spike detector, tail-mark the step trace and feed the
        flight recorder on a spike."""
        from ..monitor import goodput as _goodput
        host = {layer: {k: float(v) for k, v in vals.items()}
                for layer, vals in hvec.items()}
        _goodput.note_layer_health(host, step=self.step_count)
        if self._health_mon is None:
            self._health_mon = _goodput.LayerHealthMonitor()
        spikes = self._health_mon.observe(host)
        if mon:
            from ..monitor import get_registry
            reg = get_registry()
            g = reg.gauge("train_layer_grad_norm",
                          "per-layer gradient L2 norm (f32 side-output "
                          "of the compiled step; "
                          "FLAGS_train_health_every)")
            p = reg.gauge("train_layer_param_norm",
                          "per-layer post-update parameter L2 norm")
            u = reg.gauge("train_layer_update_ratio",
                          "per-layer ||update|| / ||param|| — the "
                          "classic learning-rate health signal")
            for layer, vals in host.items():
                g.set(vals["grad_norm"], layer=layer)
                p.set(vals["param_norm"], layer=layer)
                u.set(vals["update_ratio"], layer=layer)
        if spikes:
            self._stats["health_spikes"] += len(spikes)
            cur = trace_mod.current_trace()
            if cur is not None:
                cur.mark_anomaly("health_spike", step=self.step_count,
                                 layers=sorted(spikes))
            if mon:
                from ..monitor import get_registry
                ctr = get_registry().counter(
                    "train_health_spikes_total",
                    "per-layer grad-norm EWMA spike detections")
                for layer in spikes:
                    ctr.inc(layer=layer)
            from ..monitor.flight_recorder import safe_record_event
            safe_record_event("health_spike", step=self.step_count,
                              layers=sorted(spikes))

    def _watchdog(self, loss, prev_params, prev_buffers, key, flat,
                  treedef, step_index: int, step_kind: str = "step",
                  rollback=None):
        """check_numerics post-step check (eager, outside the compiled
        step). Cost while healthy: ONE scalar readback per step (which
        also synchronizes dispatch — this is a debugging mode). On a trip:
        a grads-only diagnosis pass re-runs fwd+bwd at the PRE-update
        state with the same RNG key and batch, naming the first (sorted)
        non-finite gradient/parameter. ``step_kind`` disambiguates the
        two step clocks: accum-only trips report the MICROSTEP index,
        optimizer-update trips the step (optimizer) index.

        With ``skip_nonfinite_budget`` set, a trip within the budget
        calls ``rollback`` (restoring the pre-step state the caller
        captured) and returns instead of raising; the trip still lands
        in the stats, registry and flight recorder as a
        ``nonfinite_skip`` event. The budget counts CONSECUTIVE skips —
        any finite step resets it — and exhaustion raises
        :class:`NonFiniteError` whatever the check_numerics action is."""
        if bool(jnp.isfinite(loss).all()):
            self._consecutive_skips = 0
            return
        # goodput: a rolled-back step made no progress — move its
        # dispatch seconds out of productive_dispatch and attribute the
        # whole trip handling (diagnosis pass, rollback) to
        # nonfinite_rollback
        from ..monitor import goodput as _goodput
        led = _goodput.active_ledger()
        if led is None:
            return self._watchdog_trip(loss, prev_params, prev_buffers,
                                       key, flat, treedef, step_index,
                                       step_kind, rollback)
        led.reattribute_last("nonfinite_rollback")
        with led.measure("nonfinite_rollback"):
            return self._watchdog_trip(loss, prev_params, prev_buffers,
                                       key, flat, treedef, step_index,
                                       step_kind, rollback)

    def _watchdog_trip(self, loss, prev_params, prev_buffers, key, flat,
                       treedef, step_index: int, step_kind: str,
                       rollback):
        self._stats["nonfinite_trips"] += 1
        cur_trace = trace_mod.current_trace()
        if cur_trace is not None:
            # tail-retain the step trace even when the trip is handled
            # (warn mode / within skip_nonfinite_budget — no raise)
            cur_trace.mark_anomaly("nonfinite", step=step_index,
                                   step_kind=step_kind)
        from ..monitor import get_registry
        from ..monitor.numerics import NonFiniteError, first_nonfinite
        # the param scan needs no compilation — run it before (and
        # independently of) the fallible grads re-trace
        bad_param = bad_grad = None
        try:
            bad_param = first_nonfinite(prev_params)
        except Exception:
            pass
        try:
            sig = ("diag", _sig_of(flat)[0], treedef)
            diag = self._jitted.get(sig)
            if diag is None:
                diag = jax.jit(self._loss_and_grads(treedef))
                self._jitted[sig] = diag
            (_dloss, _dbufs), grads = diag(prev_params, prev_buffers, key,
                                           flat)
            bad_grad = first_nonfinite(grads)
        except Exception:
            pass                      # diagnosis is best-effort
        get_registry().counter(
            "numerics_nonfinite_total",
            "NaN/Inf watchdog trips by kind").inc(what="train_step")
        parts = [f"non-finite loss at {step_kind} {step_index}"]
        if bad_param is not None:
            parts.append(f"parameter {bad_param!r} was already non-finite "
                         "before this step")
        if bad_grad is not None:
            parts.append(f"first non-finite gradient: {bad_grad!r}")
        msg = ("; ".join(parts)
               + " (TrainStep check_numerics watchdog; the in-graph "
               "variant is FLAGS_check_nan_inf)")
        offender = bad_grad or bad_param or "loss"
        from ..monitor import flight_recorder as _flight
        budget = self.skip_nonfinite_budget
        if budget and rollback is not None:
            self._consecutive_skips += 1
            if self._consecutive_skips <= budget:
                # within budget: revert the whole update and continue —
                # the GradScaler skip model generalized to any
                # non-finite trip. The event is recorded everywhere a
                # post-mortem would look, but the run lives.
                rollback()
                self._stats["nonfinite_skips"] += 1
                get_registry().counter(
                    "nonfinite_skips_total",
                    "non-finite steps skipped under "
                    "skip_nonfinite_budget").inc()
                if _flight.enabled():
                    _flight.get_flight_recorder().record_event(
                        "nonfinite_skip", step=step_index,
                        step_kind=step_kind, offender=offender,
                        consecutive=self._consecutive_skips,
                        budget=budget)
                import warnings
                warnings.warn(
                    msg + f"; update skipped and rolled back "
                    f"({self._consecutive_skips}/{budget} consecutive)",
                    RuntimeWarning, stacklevel=3)
                return
            # exhaustion: roll back too before raising — a supervisor
            # that catches NonFiniteError and checkpoints for handoff
            # must persist the last-known-good state, not the NaN update
            # every within-budget trip carefully reverted
            rollback()
            msg += (f"; skip_nonfinite_budget exhausted "
                    f"({budget} consecutive non-finite steps; state "
                    "rolled back to the last finite step)")
        # crash forensics: a watchdog trip dumps the flight recorder
        # (ring of recent steps + fingerprint), naming the trip step —
        # best-effort, the NonFiniteError below must win
        dump_path = _flight.trip_dump(step=step_index,
                                      reason="nan_watchdog",
                                      offender=offender,
                                      step_kind=step_kind)
        if dump_path:
            msg += f"; flight recorder dump: {dump_path}"
        if self._check_numerics == "warn" and not (
                budget and self._consecutive_skips > budget):
            import warnings
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            return
        raise NonFiniteError(msg, offender=offender, step=step_index)

    def aot_programs(self) -> list:
        """The :class:`~paddle_tpu.jit.aot.AOTProgram` behind every
        program kind and batch signature built so far — ``.compiled``
        is the executable the next call runs (``as_text()``,
        ``memory_analysis()``), ``.builds``/``.heals`` count its
        compiles and sharding-drift re-compiles."""
        from .aot import AOTProgram
        return [p for p in self._jitted.values()
                if isinstance(p, AOTProgram)]

    def stats(self) -> dict:
        """Telemetry snapshot since construction: our jit-entry builds
        (``compiles``/``recompiles`` — a warm scan-layer GPT shows exactly
        1 and 0), XLA backend-compile / persistent-cache / trace deltas
        (process-wide window, via utils.compilation), eager op-cache hit
        rates, accumulation/watchdog counters, and per-program-kind
        attribution under ``programs``: static flops / bytes_accessed /
        arithmetic_intensity (lowered.cost_analysis) and the
        ``peak_hbm_bytes`` budget (compiled.memory_analysis), plus an
        ``mfu`` gauge when the chip's peak FLOP/s is known and monitor
        mode has a wall-time EMA for the kind (None otherwise — e.g. the
        CPU test backend). Plain-dict reads — no device sync, callable
        every step."""
        from ..core.tensor import eager_cache_stats
        from ..utils.compilation import compile_counts
        cc = compile_counts()
        ec = eager_cache_stats()
        d = dict(self._stats)
        try:
            from ..cost_model import device_peak_flops
            peak = device_peak_flops()
        except Exception:
            peak = None
        programs = {}
        for kind, entry in self._programs.items():
            e = dict(entry)
            wall = self._wall_ema.get(kind)
            e["mfu"] = (e["flops"] / (wall * peak)
                        if peak and wall and e.get("flops") else None)
            programs[kind] = e
        d["programs"] = programs
        d.update(
            steps=self.step_count,
            microsteps=self._micro_count,
            grad_accum_steps=self.grad_accum_steps,
            backend_compiles=(cc["backend_compiles"]
                              - self._cc0["backend_compiles"]),
            persistent_cache_misses=(cc["cache_misses"]
                                     - self._cc0["cache_misses"]),
            jaxpr_traces=cc["jaxpr_traces"] - self._cc0["jaxpr_traces"],
            eager_cache_hits=ec["hits"] - self._ec0["hits"],
            eager_cache_misses=ec["misses"] - self._ec0["misses"],
        )
        seen = d["eager_cache_hits"] + d["eager_cache_misses"]
        d["eager_cache_hit_rate"] = (d["eager_cache_hits"] / seen
                                     if seen else None)
        # the goodput ledger view, so single-process trainers (and the
        # /statusz TrainStep.stats() section) see it without the admin
        # plane; absent with FLAGS_train_goodput off
        from ..monitor import goodput as _goodput
        led = _goodput.get_ledger()
        if led is not None and _goodput.active():
            d["goodput"] = led.snapshot()
        return d

    def _call_accum(self, flat, treedef, check, mon, fr, t_wall):
        """Gradient-merge path: k-1 accumulate-only microsteps, then one
        accumulate+update microstep."""
        from ..core.flags import get_flag
        if self._acc_grads is None:
            self._acc_grads = jax.tree_util.tree_map(
                jnp.zeros_like, self.params)
        key = make_rng("train_step")
        self._micro_count += 1
        watch = bool(self._check_numerics) or self.skip_nonfinite_budget > 0
        prev = ((self.params, self.buffers, self._acc_grads,
                 self.opt_state) if watch else None)
        is_update = self._micro_count % self.grad_accum_steps == 0
        if not is_update:
            sig = ("acc", _sig_of(flat)[0], treedef)
            jitted = self._jitted.get(sig)
            if jitted is None:
                self._note_compile("accum", mon, fr)
                fn = self._make_accum_step(treedef)
                jitted = self._compile_program(
                    "accum", fn, (2,) if self._donate else (),
                    (self.params, self.buffers, self._acc_grads, key,
                     flat), mon)
                self._jitted[sig] = jitted
            from ..monitor import goodput as _goodput
            t0 = time.perf_counter() if mon else 0.0
            with _control_flow_guidance(), \
                    trace_mod.span("train.dispatch", kind="accum"), \
                    _goodput.measure("productive_dispatch",
                                     on_error="host_other"):
                self.buffers, self._acc_grads, loss = self._dispatch(
                    jitted, self.params, self.buffers, self._acc_grads,
                    key, flat)
            dispatch_s = time.perf_counter() - t0 if mon else None
            if _chaos.active() and _chaos.probe("grad.nonfinite"):
                loss = jnp.full_like(loss, jnp.nan)
            if mon:
                self._record_step_metrics(t_wall, dispatch_s,
                                          kind="accum")
            if fr:
                from ..monitor.flight_recorder import get_flight_recorder
                get_flight_recorder().record_step(
                    self._micro_count, loss=loss, kind="accum",
                    dispatch_ms=None if dispatch_s is None
                    else dispatch_s * 1e3)
            if watch:
                def rollback():
                    (self.params, self.buffers, self._acc_grads,
                     self.opt_state) = prev
                    self._micro_count -= 1
                self._watchdog(loss, prev[0], prev[1], key, flat, treedef,
                               self._micro_count, step_kind="microstep",
                               rollback=rollback)
            return Tensor(loss)
        self.step_count += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        t = jnp.asarray(self.step_count, jnp.int32)
        health_every = int(get_flag("train_health_every") or 0)
        health = health_every > 0
        sig = ("apply", _sig_of(flat)[0], treedef, check, health)
        jitted = self._jitted.get(sig)
        if jitted is None:
            self._note_compile("apply", mon, fr)
            fn = self._make_apply_step(treedef, check_finite=check,
                                       health=health)
            jitted = self._compile_program(
                "apply", fn,
                (0, 2, 3) if self._donate else (),
                (self.params, self.buffers, self.opt_state,
                 self._acc_grads, lr, t, key, flat), mon)
            self._jitted[sig] = jitted
        from ..monitor import goodput as _goodput
        t0 = time.perf_counter() if mon else 0.0
        with _control_flow_guidance(), \
                trace_mod.span("train.dispatch", kind="apply"), \
                _goodput.measure("productive_dispatch",
                                 on_error="host_other"):
            out = self._dispatch(jitted, self.params, self.buffers,
                                 self.opt_state, self._acc_grads, lr, t,
                                 key, flat)
        # the k-th microstep is the accumulation SYNC boundary: grads are
        # folded into the optimizer here (reference: the gated update
        # block of gradient_merge_optimizer.py)
        self._stats["grad_accum_syncs"] += 1
        dispatch_s = time.perf_counter() - t0 if mon else None
        if mon:
            self._record_step_metrics(t_wall, dispatch_s, kind="apply")
            from ..monitor import get_registry
            get_registry().counter(
                "train_step_grad_accum_syncs_total",
                "gradient-accumulation optimizer-update boundaries").inc()
        hvec = None
        if health:
            hvec, out = out[-1], out[:-1]
        if check:
            (self.params, self.buffers, self.opt_state, self._acc_grads,
             loss, flags) = out
            bad = [k_ for k_, ok in flags.items() if not bool(ok)]
            if bad:
                raise RuntimeError(
                    f"NaN/Inf detected at step {self.step_count} in: "
                    f"{', '.join(sorted(bad))} (FLAGS_check_nan_inf)")
        else:
            (self.params, self.buffers, self.opt_state, self._acc_grads,
             loss) = out
        if hvec is not None and self.step_count % health_every == 0:
            self._publish_health(hvec, mon)
        if _chaos.active() and _chaos.probe("grad.nonfinite"):
            loss = jnp.full_like(loss, jnp.nan)
        if fr:
            from ..monitor.flight_recorder import get_flight_recorder
            get_flight_recorder().record_step(
                self.step_count, loss=loss, kind="apply",
                wall_ms=(time.perf_counter() - t_wall) * 1e3 if mon
                else None,
                dispatch_ms=None if dispatch_s is None
                else dispatch_s * 1e3)
        if watch:
            def rollback():
                (self.params, self.buffers, self._acc_grads,
                 self.opt_state) = prev
                self._micro_count -= 1
                self.step_count -= 1
            self._watchdog(loss, prev[0], prev[1], key, flat, treedef,
                           self.step_count, rollback=rollback)
        return Tensor(loss)

    def __call__(self, *batch):
        with trace_mod.span("train.step", step=self.step_count + 1):
            if not trace_mod.enabled():
                return self._call_impl(*batch)
            return self._call_traced(*batch)

    def _call_traced(self, *batch):
        # one trace per step: dispatch / grad-accum sync spans attach
        # inside, eager collectives and checkpoint commits through the
        # activate() context. A non-finite trip tail-retains the trace
        # whatever FLAGS_trace_sample said.
        tr = trace_mod.get_tracer().start_trace(
            "train.step", step=self.step_count + 1)
        # the wait for THIS step's batch happened before the trace
        # existed — attach it retroactively with explicit timestamps
        # (same perf_counter clock) so where-did-the-time-go reads on
        # one timeline: data_wait → dispatch → sync
        from ..monitor import goodput as _goodput
        led = _goodput.get_ledger()
        if led is not None and _goodput.active():
            dw = led.pop_pending_data_wait()
            if dw is not None:
                sp = tr.start_span("data_wait", t=dw[0])
                tr.end_span(sp, t=dw[1])
        try:
            with trace_mod.activate(tr):
                return self._call_impl(*batch)
        except BaseException as e:
            from ..monitor.numerics import NonFiniteError
            tr.mark_anomaly(
                "nonfinite" if isinstance(e, NonFiniteError)
                else "failed", error=f"{type(e).__name__}: {e}")
            raise
        finally:
            trace_mod.get_tracer().finish_trace(tr)

    def _call_impl(self, *batch):
        from ..core.flags import get_flag
        mon = bool(get_flag("monitor"))
        t_wall = time.perf_counter() if mon else 0.0
        with trace_mod.span("train.place_batch"):
            raw = [b._data if isinstance(b, Tensor) else jnp.asarray(b)
                   for b in batch]
            raw = self._place_batch(raw)
            flat, treedef = jax.tree_util.tree_flatten(raw)
        check = bool(get_flag("check_nan_inf"))
        fr = mon or bool(get_flag("flight_recorder"))
        if self.grad_accum_steps > 1:
            return self._call_accum(flat, treedef, check, mon, fr, t_wall)
        self.step_count += 1
        with trace_mod.span("train.args"):
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            t = jnp.asarray(self.step_count, jnp.int32)
            key = make_rng("train_step")
        # health folds into the jit-cache signature: flag off keeps the
        # exact program (and dispatch args) of every prior PR — the
        # zero-overhead pin; flag on only ADDS f32 scalar outputs
        health_every = int(get_flag("train_health_every") or 0)
        health = health_every > 0
        sig = (_sig_of(flat)[0], treedef, check, health)
        jitted = self._jitted.get(sig)
        if jitted is None:
            self._note_compile("step", mon, fr)
            fn = self._make_step(treedef, check_finite=check,
                                 health=health)
            donate = (0, 2) if self._donate else ()
            jitted = self._compile_program(
                "step", fn, donate,
                (self.params, self.buffers, self.opt_state, lr, t, key,
                 flat), mon)
            self._jitted[sig] = jitted
        watch = bool(self._check_numerics) or self.skip_nonfinite_budget > 0
        prev = ((self.params, self.buffers, self.opt_state) if watch
                else None)
        from ..monitor import goodput as _goodput
        t0 = time.perf_counter() if mon else 0.0
        with _control_flow_guidance(), \
                trace_mod.span("train.dispatch"), \
                _goodput.measure("productive_dispatch",
                                 on_error="host_other"):
            out = self._dispatch(jitted, self.params, self.buffers,
                                 self.opt_state, lr, t, key, flat)
        dispatch_s = time.perf_counter() - t0 if mon else None
        if mon:
            self._record_step_metrics(t_wall, dispatch_s)
        hvec = None
        if health:
            hvec, out = out[-1], out[:-1]
        if check:
            self.params, self.buffers, self.opt_state, loss, flags = out
            bad = [k for k, ok in flags.items() if not bool(ok)]
            if bad:
                raise RuntimeError(
                    f"NaN/Inf detected at step {self.step_count} in: "
                    f"{', '.join(sorted(bad))} (FLAGS_check_nan_inf)")
        else:
            self.params, self.buffers, self.opt_state, loss = out
        if hvec is not None and self.step_count % health_every == 0:
            self._publish_health(hvec, mon)
        if _chaos.active() and _chaos.probe("grad.nonfinite"):
            loss = jnp.full_like(loss, jnp.nan)
        if fr:
            from ..monitor.flight_recorder import get_flight_recorder
            get_flight_recorder().record_step(
                self.step_count, loss=loss, kind="step",
                wall_ms=(time.perf_counter() - t_wall) * 1e3 if mon
                else None,
                dispatch_ms=None if dispatch_s is None
                else dispatch_s * 1e3)
        if watch:
            def rollback():
                self.params, self.buffers, self.opt_state = prev
                self.step_count -= 1
            self._watchdog(loss, prev[0], prev[1], key, flat, treedef,
                           self.step_count, rollback=rollback)
        return Tensor(loss)

    def sync_to_layer(self):
        merged = {**self.frozen, **self.params}
        for k, p in self.layer.named_parameters():
            if k in merged:
                p._data = merged[k]
        for k, b in self.layer.named_buffers():
            if k in self.buffers:
                b._data = self.buffers[k]

    # -- checkpoint/resume -------------------------------------------------
    def state_dict(self):
        """Full training state: params + frozen + buffers + optimizer slots
        + step count + RNG, enough to resume bit-exactly (reference:
        framework/io.py:553 save of model+opt state; SURVEY §5 resume)."""
        import numpy as np

        from ..core.random import default_generator

        def host(tree):
            return jax.tree_util.tree_map(
                lambda a: np.asarray(a) if hasattr(a, "shape") else a, tree)

        return {
            "params": host(self.params),
            "frozen": host(self.frozen),
            "buffers": host(self.buffers),
            "opt_state": host(self.opt_state),
            "step_count": self.step_count,
            "rng_state": default_generator().get_state(),
            "lr": self.optimizer.get_lr(),
        }

    def set_state_dict(self, state):
        """Restore a state_dict; re-applies SPMD layouts when a mesh is
        active so resume preserves shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..core.random import default_generator

        def put(k, a, spec=None):
            if not hasattr(a, "shape"):
                return a
            if self.mesh is not None:
                return jax.device_put(
                    a, NamedSharding(self.mesh, spec or P()))
            return jnp.asarray(a)

        if self.mesh is not None:
            self._specs = self._param_specs()
            self.params = {k: put(k, v, self._specs.get(k))
                           for k, v in state["params"].items()}
            self.opt_state = {
                k: jax.tree_util.tree_map(
                    lambda a, k=k: jax.device_put(
                        a, NamedSharding(self.mesh,
                                         self._slot_spec(k, a.shape)))
                    if hasattr(a, "shape") and getattr(a, "ndim", 0) > 0
                    else a, v)
                for k, v in state["opt_state"].items()}
        else:
            self.params = {k: jnp.asarray(v)
                           for k, v in state["params"].items()}
            self.opt_state = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a) if hasattr(a, "shape") else a,
                state["opt_state"])
        if self.mesh is not None:
            from jax.sharding import NamedSharding as _NS
            frozen_specs = {k: getattr(p, "spec", None) or P()
                            for k, p in self.layer.named_parameters()
                            if k not in self.params}
            self.frozen = {
                k: jax.device_put(v, _NS(self.mesh,
                                         frozen_specs.get(k, P())))
                for k, v in state["frozen"].items()}
            self.buffers = {k: jax.device_put(v, _NS(self.mesh, P()))
                            for k, v in state["buffers"].items()}
        else:
            self.frozen = {k: jnp.asarray(v)
                           for k, v in state["frozen"].items()}
            self.buffers = {k: jnp.asarray(v)
                            for k, v in state["buffers"].items()}
        self.step_count = int(state["step_count"])
        # restore starts a fresh gradient-accumulation window: a partial
        # accumulator from before the restore must never leak in
        self._acc_grads = None
        self._micro_count = 0
        if state.get("rng_state") is not None:
            default_generator().set_state(state["rng_state"])
        if state.get("lr") is not None and hasattr(self.optimizer, "set_lr"):
            try:
                self.optimizer.set_lr(state["lr"])
            except Exception:
                pass
        self.sync_to_layer()

    def save(self, path: str):
        from ..framework.io import save as fsave
        fsave(self.state_dict(), path)

    def load(self, path: str):
        from ..framework.io import load as fload
        self.set_state_dict(fload(path))

    def save_sharded(self, path: str, asynchronous: bool = True):
        """Sharded async checkpoint (each host writes its own shards;
        serialization overlaps training). See distributed.checkpoint."""
        from ..distributed import checkpoint as dckpt
        dckpt.save_train_step(self, path, asynchronous=asynchronous)

    def load_sharded(self, path: str):
        """Restore a sharded checkpoint, resharding to this step's current
        mesh layout (which may differ from the one saved under)."""
        from ..distributed import checkpoint as dckpt
        dckpt.load_train_step(self, path)


def save(layer, path, input_spec=None, **configs):
    """Export: StableHLO text + params pickle (replaces save_inference_model).

    reference: python/paddle/fluid/dygraph/jit.py save / io.py:1246.
    """
    import os
    import pickle

    import numpy as np

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    params = param_arrays(layer)
    buffers = buffer_arrays(layer)
    meta = {"class": type(layer).__name__}

    if input_spec:
        specs = [s if isinstance(s, InputSpec) else InputSpec(s) for s in input_spec]
        example = [jnp.zeros(tuple(d if d and d > 0 else 1 for d in s.shape),
                             s.dtype) for s in specs]

        def pure(p, b, *inputs):
            tensors = [Tensor(i) for i in inputs]
            with bind(layer, p, dict(b)), no_grad(), trace_rng(jax.random.key(0)):
                out = layer(*tensors)
            return unwrap(out)

        was_training = layer.training
        layer.eval()
        try:
            jitted = jax.jit(pure)
            lowered = jitted.lower(params, buffers, *example)
            stablehlo = lowered.as_text(dialect="stablehlo")
            # portable executable blob: params/buffers are BAKED as the
            # first two arguments; load() rebinds the pickled values
            from jax import export as jexport
            exp = jexport.export(jitted)(
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), buffers),
                *[jax.ShapeDtypeStruct(e.shape, e.dtype) for e in example])
            with open(path + ".jaxexport", "wb") as f:
                f.write(exp.serialize())
        finally:
            if was_training:
                layer.train()
        with open(path + ".mlir", "w") as f:
            f.write(stablehlo)
        meta["input_spec"] = [(tuple(s.shape), str(np.dtype(s.dtype))) for s in specs]

    with open(path + ".pdiparams", "wb") as f:
        pickle.dump({k: np.asarray(v) for k, v in {**params, **buffers}.items()}, f)
    with open(path + ".pdmodel.meta", "wb") as f:
        pickle.dump({**meta, "param_names": list(params),
                     "buffer_names": list(buffers)}, f)


class TranslatedLayer:
    """Runnable model restored from a jit.save export (reference:
    fluid/dygraph/io.py TranslatedLayer / jit.py:1162 TracedLayer): holds
    the deserialized executable + parameter arrays and is called like the
    original layer (positional Tensors/arrays in, Tensor out)."""

    def __init__(self, exported, params, buffers, meta):
        self._exported = exported
        self._params = params
        self._buffers = buffers
        self._meta = meta

    @property
    def program(self):   # parity shim: the export object is the "program"
        return self._exported

    def state_dict(self):
        return {**self._params, **self._buffers}

    def __call__(self, *inputs, **feeds):
        if feeds and inputs:
            raise TypeError("pass inputs positionally OR as named feeds, "
                            "not both")
        if feeds:
            # Executor.run feeds by name: exports name inputs 'x0','x1',...
            n_in = len(self._meta.get("input_spec") or []) or len(feeds)

            def idx(n):
                if not (n.startswith("x") and n[1:].isdigit()):
                    raise KeyError(
                        f"unknown feed {n!r}: a jit.save export names its "
                        f"inputs positionally as "
                        f"{['x%d' % i for i in range(n_in)]}")
                return int(n[1:])
            inputs = [feeds[k] for k in sorted(feeds, key=idx)]
        raw = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
               for i in inputs]
        out = self._exported.call(self._params, self._buffers, *raw)
        if isinstance(out, (tuple, list)):
            outs = [Tensor(o) for o in out]
            return outs[0] if len(outs) == 1 else outs
        return Tensor(out)


def load(path, **configs):
    """Restore a jit.save export.

    Returns a runnable :class:`TranslatedLayer` when the executable blob
    exists (saved with input_spec); otherwise the raw params dict
    (weights-only save). reference: fluid/io.py:1246 load_inference_model."""
    import os
    import pickle

    with open(path + ".pdiparams", "rb") as f:
        arrays = pickle.load(f)
    if not os.path.exists(path + ".jaxexport"):
        return arrays
    with open(path + ".pdmodel.meta", "rb") as f:
        meta = pickle.load(f)
    with open(path + ".jaxexport", "rb") as f:
        from jax import export as jexport
        exported = jexport.deserialize(f.read())
    params = {k: jnp.asarray(arrays[k]) for k in meta.get("param_names", [])}
    buffers = {k: jnp.asarray(arrays[k])
               for k in meta.get("buffer_names", [])}
    return TranslatedLayer(exported, params, buffers, meta)
