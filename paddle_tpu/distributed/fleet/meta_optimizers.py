"""LocalSGD / AdaptiveLocalSGD training step.

reference parity: fleet/meta_optimizers/localsgd_optimizer.py
(LocalSGDOptimizer:30 — k local steps between parameter broadcasts;
AdaptiveLocalSGDOptimizer:443 — k adapted from the loss ratio, the
AdaComm schedule k_t = ceil(k_0 * sqrt(F(w_t)/F(w_0)))).

TPU-native redesign: the reference mutates the Program to skip grad
allreduces and injects broadcast ops. Here each dp replica owns a
DISTINCT parameter copy — a leading replica axis sharded over ``dp`` —
and the whole local step runs inside ``shard_map`` where no cross-replica
collective exists at all; the sync step is one ``pmean`` over the dp axis
every k steps. XLA compiles both as single donated programs; between
syncs the only ICI traffic is zero.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["LocalSGDTrainStep"]


class LocalSGDTrainStep:
    """Compile (model, loss, optimizer) into a LocalSGD step over the
    ``axis`` mesh dimension.

    Every call runs ONE local step on each replica's own parameters (the
    batch is split over ``axis``); every ``k_steps``-th call additionally
    averages parameters across replicas. ``adaptive=True`` re-derives k
    from the loss ratio at every sync (AdaComm; reference
    localsgd_optimizer.py:443).

    Restriction: parameters must be replicated modulo the replica axis —
    LocalSGD composes with dp/sharding data parallelism, not with tensor
    parallelism inside the same step (matching the reference, whose
    LocalSGD meta-optimizer is dp-only).
    """

    def __init__(self, layer, loss_fn: Callable, optimizer, mesh,
                 k_steps: int = 1, axis: str = "dp",
                 adaptive: bool = False, min_k_steps: int = 1,
                 max_k_steps: int = 16):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ...jit.functional import (bind, buffer_arrays,
                                       trainable_param_arrays)
        from ...core.random import make_rng, trace_rng
        from ...core.tensor import Tensor, no_grad

        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
        self.layer = layer
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.axis = axis
        self.k_steps = max(1, int(k_steps))
        self.adaptive = adaptive
        self.min_k = max(1, int(min_k_steps))
        self.max_k = int(max_k_steps)
        self._k0 = self.k_steps
        self._loss0: Optional[float] = None
        self.step_count = 0
        self._stats = {"localsgd_syncs": 0, "local_steps": 0}
        self._make_rng = make_rng
        D = mesh.shape[axis]
        self.num_replicas = D

        params0 = trainable_param_arrays(layer)
        self.buffers = buffer_arrays(layer)

        def rep(a):
            # per-replica copy: leading replica dim, sharded over `axis`
            return jax.device_put(
                jnp.broadcast_to(a, (D,) + a.shape),
                NamedSharding(mesh, P(axis, *([None] * a.ndim))))

        self.params = {k: rep(v) for k, v in params0.items()}
        slots0 = optimizer.init_state(params0)
        self.opt_state = jax.tree_util.tree_map(
            lambda a: rep(a) if hasattr(a, "shape") and a.ndim > 0 else a,
            slots0)

        # ---- compiled programs -------------------------------------------
        opt = optimizer

        def local_fn(p_rep, bufs, opt_rep, lr, t, key, batch_rep):
            """Runs INSIDE shard_map: leading replica dim of size 1."""
            p = {k: v[0] for k, v in p_rep.items()}
            st = jax.tree_util.tree_map(
                lambda a: a[0] if hasattr(a, "ndim") and a.ndim > 0 else a,
                opt_rep)
            batch = [b[0] for b in batch_rep]
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))

            def compute_loss(pp):
                tensors = [Tensor(b) for b in batch]
                new_bufs = dict(bufs)
                with trace_rng(key), no_grad():
                    with bind(layer, pp, new_bufs):
                        loss = loss_fn(layer, *tensors)
                arr = loss._data if isinstance(loss, Tensor) else loss
                return arr.astype(jnp.float32), new_bufs

            (loss, new_bufs), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(p)
            new_p, new_st = opt.apply_gradients(p, grads, st, lr, t)
            new_p_rep = {k: v[None] for k, v in new_p.items()}
            new_st_rep = jax.tree_util.tree_map(
                lambda a: a[None] if hasattr(a, "ndim") else a, new_st)
            # buffer updates (BN/IN running stats) are averaged across
            # replicas every step — the per-replica batches differ, so the
            # mean is the stats over the union batch (SyncBN-flavoured;
            # the reference's LocalSGD leaves BN stats per-replica and
            # broadcasts rank 0's at the end, which silently discards
            # k-1/k of the statistics)
            new_bufs = {
                k: jax.lax.pmean(v, axis)
                if jnp.issubdtype(v.dtype, jnp.floating) else v
                for k, v in new_bufs.items()}
            # mean replica loss for reporting
            loss = jax.lax.pmean(loss, axis)
            return new_p_rep, new_st_rep, new_bufs, loss[None]

        pspec = {k: P(axis, *([None] * v.ndim))
                 for k, v in params0.items()}
        stspec = jax.tree_util.tree_map(
            lambda a: P(axis, *([None] * getattr(a, "ndim", 0)))
            if hasattr(a, "shape") and a.ndim > 0 else P(), slots0)
        from jax.sharding import PartitionSpec as _P

        def batch_specs(batch):
            return [ _P(axis, *([None] * (b.ndim - 1))) for b in batch ]

        self._local_cache: Dict = {}

        def make_local(bspecs):
            in_specs = (pspec, _P(), stspec, _P(), _P(), _P(),
                        list(bspecs))
            out_specs = (pspec, stspec, _P(), _P(axis))
            sm = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            return jax.jit(sm, donate_argnums=(0, 2))

        self._make_local = make_local

        def sync_fn(p_rep):
            # parameter average over replicas = mean over the leading dim
            return {k: jnp.broadcast_to(jnp.mean(v, axis=0,
                                                 keepdims=True),
                                        v.shape).astype(v.dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v
                    for k, v in p_rep.items()}

        self._sync = jax.jit(sync_fn, donate_argnums=(0,))

    def __call__(self, *batch):
        from ...core.tensor import Tensor
        raw = [b._data if isinstance(b, Tensor) else jnp.asarray(b)
               for b in batch]
        rep = []
        for b in raw:
            if b.shape[0] % self.num_replicas:
                raise ValueError(
                    f"batch dim {b.shape[0]} not divisible by "
                    f"{self.num_replicas} replicas")
            rep.append(b.reshape((self.num_replicas,
                                  b.shape[0] // self.num_replicas)
                                 + b.shape[1:]))
        from jax.sharding import PartitionSpec as P
        bspecs = tuple(P(self.axis, *([None] * (b.ndim - 1)))
                       for b in rep)
        jitted = self._local_cache.get(
            (bspecs, tuple((b.shape, str(b.dtype)) for b in rep)))
        if jitted is None:
            jitted = self._make_local(bspecs)
            self._local_cache[(bspecs, tuple((b.shape, str(b.dtype))
                                             for b in rep))] = jitted
        self.step_count += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        t = jnp.asarray(self.step_count, jnp.int32)
        key = self._make_rng("localsgd")
        self.params, self.opt_state, self.buffers, loss = jitted(
            self.params, self.buffers, self.opt_state, lr, t, key, rep)
        # host-sync the loss ONLY when the AdaComm schedule needs it — a
        # per-step float() would serialize dispatch between local steps
        if self.adaptive and self._loss0 is None:
            self._loss0 = max(float(loss[0]), 1e-12)
        self._stats["local_steps"] += 1
        if self.step_count % self.k_steps == 0:
            # LocalSGD SYNC boundary: replicas average parameters here —
            # surfaced to the monitor registry so the k-step cadence (and
            # AdaComm's adaptation of it) is observable next to the step
            # timings (docs/OBSERVABILITY.md)
            self._stats["localsgd_syncs"] += 1
            from ...core.flags import get_flag
            if get_flag("monitor"):
                from ...monitor import get_registry
                reg = get_registry()
                reg.counter("localsgd_syncs_total",
                            "LocalSGD parameter-averaging boundaries"
                            ).inc(axis=self.axis)
                reg.gauge("localsgd_k_steps",
                          "current LocalSGD sync period (AdaComm adapts "
                          "this)").set(self.k_steps, axis=self.axis)
            self.params = self._sync(self.params)
            if self.adaptive:
                # AdaComm: k_t = ceil(k_0 * sqrt(F(w_t) / F(w_0)))
                import math
                loss_val = float(loss[0])
                k = math.ceil(self._k0
                              * math.sqrt(max(loss_val, 1e-12)
                                          / self._loss0))
                self.k_steps = min(max(k, self.min_k), self.max_k)
        return Tensor(loss[0])

    def stats(self) -> dict:
        """Telemetry snapshot (TrainStep.stats() analogue): local steps,
        parameter-averaging sync boundaries, and the current/initial k."""
        d = dict(self._stats)
        d.update(steps=self.step_count, k_steps=self.k_steps,
                 initial_k_steps=self._k0, num_replicas=self.num_replicas)
        return d

    def sync_to_layer(self):
        """Average replicas and write back into the Layer."""
        synced = self._sync(self.params)
        self.params = synced
        for k, p in self.layer.named_parameters():
            if k in synced:
                p._data = synced[k][0]
