"""Optimizer base.

Redesign of the reference's optimizer family
(reference: python/paddle/optimizer/optimizer.py:49 + C++ kernels
operators/optimizers/*).

Architecture: each optimizer defines a **pure update rule**
``_init_slot(param) -> slots`` and ``_update(param, grad, slots, lr, t) ->
(new_param, new_slots)``. Two consumers:

- Eager ``step()``: gathers all (param, grad) pairs and applies ONE jitted
  fused multi-tensor update over the whole param dict (the TPU answer to the
  reference's fused `merged_adam`/multi_tensor kernels) with buffer donation.
- Functional training (jit/distributed): ``init_state`` + ``apply_gradients``
  run inside the caller's jitted step, so the update fuses into the step's
  XLA program.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter, Tensor
from .clip import GradClipBase
from .lr import LRScheduler

__all__ = ["Optimizer"]


class L2Decay:
    """Coupled L2 regularizer (reference: fluid/regularizer.py L2Decay)."""

    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class Optimizer:
    # subclasses override
    _hyper_defaults: Dict[str, float] = {}
    #: elementwise update rules fuse over stacked param groups; rules with
    #: per-param reductions (Lamb's trust ratio) must opt out
    _mt_fusable = True

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._name = name
        # weight decay: float => L2Decay (coupled, reference semantics);
        # AdamW overrides with decoupled decay.
        if isinstance(weight_decay, (int, float)):
            self.regularization = L2Decay(weight_decay)
        else:
            self.regularization = weight_decay
        self._accumulators: Dict[int, Any] = {}  # id(param) -> slots pytree
        self._step_count = 0
        self._fused_step_cache: Dict[Any, Callable] = {}
        self._use_multi_tensor = False

    # ------------------------------------------------------------------
    # LR plumbing
    # ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    @property
    def _learning_rate(self):
        return self._lr

    # ------------------------------------------------------------------
    # Pure rule API (overridden by subclasses)
    # ------------------------------------------------------------------
    def _init_slot(self, param: jnp.ndarray):
        """Return the per-param slot pytree (e.g. (m, v) for Adam)."""
        return ()

    def _update(self, param, grad, slots, lr, t):
        """Pure single-param update. Returns (new_param, new_slots)."""
        raise NotImplementedError

    def _coupled_decay(self, param, grad):
        if isinstance(self.regularization, L2Decay) and self.regularization.coeff:
            return grad + self.regularization.coeff * param
        if isinstance(self.regularization, L1Decay) and self.regularization.coeff:
            return grad + self.regularization.coeff * jnp.sign(param)
        return grad

    # ------------------------------------------------------------------
    # Functional API (used by jitted trainers — runs under tracing)
    # ------------------------------------------------------------------
    def init_state(self, params: Dict[str, jnp.ndarray]):
        if self._use_multi_tensor and self._mt_fusable:
            # multi-tensor mode (reference: use_multi_tensor /
            # merged_adam multi-tensor CUDA kernels,
            # operators/optimizers/merged_adam_op.cc): group params by
            # (shape, dtype), keep slots STACKED [N, *shape] per group —
            # the update runs as ~a dozen large fused kernels instead of
            # one tiny fusion per parameter (a ~300-launch, ~30 ms/step
            # overhead on GPT-2 345M; a device trace shows it:
            # python -m benchmark.harness.trace_reduce <dir>)
            groups: Dict[Any, List[str]] = {}
            for k in sorted(params):
                gid = (tuple(params[k].shape), str(params[k].dtype))
                groups.setdefault(gid, []).append(k)
            # the name->group map is DERIVED state (deterministic given the
            # param dict) kept on the instance — jit-traced opt_state must
            # hold only arrays
            self._mt_groups = {f"mt{i}": names for i, (_, names) in
                               enumerate(sorted(groups.items(),
                                                key=lambda kv: repr(kv[0])))}
            slots = {gk: self._init_slot(
                jnp.stack([params[k] for k in names]))
                for gk, names in self._mt_groups.items()}
            return {"__mt__": slots}
        return {k: self._init_slot(p) for k, p in params.items()}

    def _apply_gradients_mt(self, params, grads, state, lr, step):
        """Stacked multi-tensor update (state from the __mt__ layout)."""
        if lr is None:
            lr = self.get_lr()
        if step is None:
            step = self._step_count + 1
        if self._grad_clip is not None:
            grads = self._grad_clip(grads)
        gmap = self._mt_groups
        slots = state["__mt__"]
        grouped = {k for names in gmap.values() for k in names}
        if set(params) != grouped:
            extra = sorted(set(params) - grouped)[:3]
            gone = sorted(grouped - set(params))[:3]
            raise ValueError(
                "use_multi_tensor=True: the parameter dict no longer "
                "matches the groups built at init_state (new: "
                f"{extra}, missing: {gone}); call init_state again after "
                "changing the parameter set")
        new_params, new_slots = {}, {}
        for gk, names in gmap.items():
            missing = [k for k in names if grads.get(k) is None]
            if missing:
                raise ValueError(
                    "use_multi_tensor=True needs a gradient for every "
                    f"parameter (none for {missing[:3]}); construct the "
                    "optimizer with use_multi_tensor=False for partially-"
                    "frozen parameter sets")
            p_s = jnp.stack([params[k] for k in names])
            g_s = jnp.stack([grads[k] for k in names])
            if self._multi_precision:
                g_s = g_s.astype(jnp.float32)
            g_s = self._coupled_decay(p_s, g_s)
            np_s, ns = self._update(p_s, g_s, slots[gk], lr, step)
            np_s = np_s.astype(params[names[0]].dtype)
            new_slots[gk] = ns
            for i, k in enumerate(names):
                new_params[k] = np_s[i]
        return new_params, {"__mt__": new_slots}

    def apply_gradients(self, params: Dict[str, jnp.ndarray],
                        grads: Dict[str, jnp.ndarray], state, lr=None, step=None):
        """Pure fused update over a param dict. Returns (params, state)."""
        if isinstance(state, dict) and "__mt__" in state:
            return self._apply_gradients_mt(params, grads, state, lr, step)
        if lr is None:
            lr = self.get_lr()
        if step is None:
            step = self._step_count + 1
        if self._grad_clip is not None:
            grads = self._grad_clip(grads)
        new_params, new_state = {}, {}
        for k, p in params.items():
            g = grads.get(k)
            if g is None:
                new_params[k] = p
                new_state[k] = state[k]
                continue
            g = self._coupled_decay(p, g.astype(jnp.float32) if
                                    self._multi_precision else g)
            np_, ns = self._update(p, g, state[k], lr, step)
            new_params[k] = np_.astype(p.dtype)
            new_state[k] = ns
        return new_params, new_state

    # ------------------------------------------------------------------
    # Eager API (paddle UX)
    # ------------------------------------------------------------------
    def _ensure_params(self):
        if self._parameter_list is None:
            raise ValueError("optimizer constructed without parameters; "
                             "pass parameters=layer.parameters()")
        return [p for p in self._parameter_list if isinstance(p, Parameter) or
                isinstance(p, Tensor)]

    def step(self):
        params = self._ensure_params()
        live = [(i, p) for i, p in enumerate(params)
                if p.grad is not None and getattr(p, "trainable", True)]
        if not live:
            return
        self._step_count += 1
        keys = [str(i) for i, _ in live]
        param_arrays = {k: p._data for k, (_, p) in zip(keys, live)}
        grad_arrays = {k: p.grad._data for k, (_, p) in zip(keys, live)}

        # slot init (eager, once per param)
        for k, (_, p) in zip(keys, live):
            if id(p) not in self._accumulators:
                self._accumulators[id(p)] = self._init_slot(p._data)
        state = {k: self._accumulators[id(p)] for k, (_, p) in zip(keys, live)}

        lr = jnp.asarray(self.get_lr(), jnp.float32)
        t = jnp.asarray(self._step_count, jnp.int32)

        cache_key = tuple(
            (k, p._data.shape, str(p._data.dtype)) for k, (_, p) in zip(keys, live))
        fused = self._fused_step_cache.get(cache_key)
        if fused is None:
            def _fused(params_d, grads_d, state_d, lr_s, t_s):
                return self.apply_gradients(params_d, grads_d, state_d, lr_s, t_s)
            fused = jax.jit(_fused, donate_argnums=(0, 2))
            self._fused_step_cache[cache_key] = fused

        new_params, new_state = fused(param_arrays, grad_arrays, state, lr, t)
        for k, (_, p) in zip(keys, live):
            p._data = new_params[k]
            self._accumulators[id(p)] = new_state[k]

    # reference's minimize(): compute backward then step; under an active
    # static.program_guard it instead ATTACHES this optimizer to the
    # recording program (the reference appends backward+optimizer ops to
    # the program the same way)
    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..core.tensor import _static_recorders
        if _static_recorders:
            prog = _static_recorders[-1]
            prog._optimizer = self
            prog._loss = loss
            return None, None
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=False):
        for p in self._ensure_params():
            p.clear_grad()

    clear_gradients = clear_grad

    # ------------------------------------------------------------------
    # State persistence
    # ------------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        import numpy as np
        out: Dict[str, Any] = {"_step_count": self._step_count}
        if self._parameter_list is not None:
            for i, p in enumerate(self._parameter_list):
                slots = self._accumulators.get(id(p))
                if slots is None:
                    continue
                flat, _ = jax.tree_util.tree_flatten(slots)
                for j, leaf in enumerate(flat):
                    out[f"param{i}_slot{j}"] = np.asarray(leaf)
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state: Dict[str, Any]):
        self._step_count = int(state.get("_step_count", 0))
        if self._parameter_list is not None:
            for i, p in enumerate(self._parameter_list):
                slots = self._accumulators.get(id(p))
                if slots is None:
                    slots = self._init_slot(p._data)
                flat, treedef = jax.tree_util.tree_flatten(slots)
                loaded = []
                for j, leaf in enumerate(flat):
                    key = f"param{i}_slot{j}"
                    loaded.append(jnp.asarray(state[key]) if key in state else leaf)
                self._accumulators[id(p)] = jax.tree_util.tree_unflatten(treedef, loaded)
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])
