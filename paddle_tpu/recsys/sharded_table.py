"""Mesh-sharded embedding table with dedup lookups and colocated
sparse-optimizer state.

reference parity: fluid/distributed SparseTable shards behind brpc
pull_sparse/push_sparse — rows live where their shard is, gradients
travel to the rows. TPU-native redesign: the shards are MESH shards
(row-sharded over the ``ps`` axis), a lookup is one gather + one psum
inside a ``shard_map`` manual program (the PR 9/10 manual-collectives
recipe), and the sparse optimizer state (adagrad row accumulators)
lives NEXT TO the embedding rows it updates — the update never moves
state across the mesh.

Three dispatch modes, resolved per call (moe/nn.scan convention):

- **manual** — a ps>1 mesh is active, ``FLAGS_recsys_sharded_lookup``
  is on and the backend can compile manual-subgroup collectives
  (``manual_collectives_ok``): each shard gathers the unique rows it
  owns (ownership: ``id % n == shard``, the SparseTable convention),
  one ``psum`` over ``ps`` assembles the full batch on every shard.
- **auto** — same math on the GSPMD path (the row-sharded array keeps
  its ``P('ps', ...)`` placement and XLA inserts the collectives);
  entered via the kill switch or an incapable backend, counted through
  :func:`~paddle_tpu.recsys.note_recsys_fallback`.
- **local** — no mesh / ps absent: single-shard arrays, same code.

Dedup (``FLAGS_recsys_dedup``, default on): sort-unique the batch ids,
fetch each distinct row ONCE, inverse-permute back — duplicate ids (the
power-law hot-id regime: a handful of ids dominate every batch) cost
one row of traffic instead of one per occurrence. Gradients accumulate
over the unique set BEFORE the row update regardless of the flag (that
is SparseTable's push semantics, not an optimization); the flag only
governs gather traffic, so off = the bit-compatible per-id oracle.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.flags import get_flag
from . import RECSYS_STATS, note_recsys_fallback

__all__ = ["PS_AXIS", "ShardedEmbeddingTable"]

PS_AXIS = "ps"


def _pad_len(n: int) -> int:
    """Pow2 bucket ≥ 8 so the manual program compiles once per bucket,
    not once per batch's unique-id count."""
    p = 8
    while p < n:
        p *= 2
    return p


class ShardedEmbeddingTable:
    """Device-resident embedding shards over the mesh ``ps`` axis.

    Protocol-compatible with :class:`~paddle_tpu.distributed.ps.
    SparseTable` (``pull``/``push``/``state_dict``), so
    ``DistributedEmbedding(table=...)`` and the tier manager work
    unchanged; :meth:`lookup` / :meth:`apply_grads` are the device-array
    fast path the DLRM model and the serving engine use."""

    def __init__(self, num_rows: int, dim: int, optimizer: str = "adagrad",
                 lr: float = 0.05, seed: int = 0, axis: str = PS_AXIS,
                 initializer=None):
        if optimizer not in ("adagrad", "sgd"):
            raise ValueError(f"unknown PS optimizer {optimizer!r}")
        from ..distributed import env as dist_env
        self.num_rows = int(num_rows)
        self.dim = int(dim)
        self.optimizer = optimizer
        self.lr = float(lr)
        self.seed = int(seed)
        self.axis = axis
        self._mesh = dist_env.get_mesh()
        if self._mesh is not None and axis in self._mesh.axis_names:
            self.num_shards = int(self._mesh.shape[axis])
        else:
            self._mesh = None
            self.num_shards = 1
        n = self.num_shards
        self._rows_per_shard = (self.num_rows + n - 1) // n
        scale = 1.0 / np.sqrt(self.dim)
        shards = []
        for s in range(n):
            local = (self.num_rows + n - 1 - s) // n
            if initializer is not None:
                block = np.asarray(initializer(local, self.dim),
                                   np.float32)
            else:
                # per-shard rng stream == SparseTable(shard_id=s): a
                # 1-shard table matches SparseTable(seed) bit-for-bit
                rng = np.random.default_rng(self.seed + s)
                block = rng.uniform(-scale, scale,
                                    (local, self.dim)).astype(np.float32)
            if local < self._rows_per_shard:
                block = np.concatenate(
                    [block, np.zeros((self._rows_per_shard - local,
                                      self.dim), np.float32)])
            shards.append(block)
        data = np.stack(shards)                       # [n, R, D]
        g2 = np.zeros((n, self._rows_per_shard), np.float32)
        if self._mesh is not None:
            self.data = jax.device_put(
                data, NamedSharding(self._mesh, P(axis, None, None)))
            self.g2 = jax.device_put(
                g2, NamedSharding(self._mesh, P(axis, None)))
        else:
            self.data = jnp.asarray(data)
            self.g2 = jnp.asarray(g2)
        self._lookup_progs: Dict[tuple, object] = {}
        self._update_progs: Dict[tuple, object] = {}
        self.pull_count = 0
        self.push_count = 0
        self.ids_seen = 0
        self.rows_fetched = 0
        self.bytes_pulled = 0
        self.bytes_pushed = 0

    # -- dispatch-mode resolution ------------------------------------------
    def _mode(self) -> str:
        if self._mesh is None or self.num_shards == 1:
            return "local"
        if not bool(get_flag("recsys_sharded_lookup")):
            note_recsys_fallback("flag_off")
            return "auto"
        from ..distributed.meta_parallel.spmd_pipeline import (
            manual_collectives_ok)
        if not manual_collectives_ok(self._mesh, self.axis):
            note_recsys_fallback(
                "backend_mesh",
                f"backend={jax.default_backend()} "
                f"mesh={dict(self._mesh.shape)}")
            return "auto"
        return "manual"

    def _check_ids(self, ids) -> np.ndarray:
        """Range-validate BOTH surfaces: the manual update program clips
        local indices (a pad-row necessity), so an out-of-range id would
        silently update the wrong row on one dispatch mode and scatter-
        drop on the other — reject it loudly instead, like SparseTable's
        wrong-shard check."""
        ids_np = np.asarray(ids, np.int64).reshape(-1)
        if ids_np.size and (ids_np.min() < 0
                            or ids_np.max() >= self.num_rows):
            raise ValueError(
                f"embedding ids outside [0, {self.num_rows})")
        return ids_np

    def _dedup(self, ids: np.ndarray):
        """(uniq, inv) under the dedup flag; flag off = identity (the
        per-id gather oracle). Accounting feeds the bench's dedup
        ratio: ids_seen / rows_fetched."""
        self.ids_seen += ids.size
        if bool(get_flag("recsys_dedup")):
            uniq, inv = np.unique(ids, return_inverse=True)
        else:
            uniq, inv = ids, np.arange(ids.size)
        self.rows_fetched += uniq.size
        return uniq.astype(np.int64), inv.reshape(-1)

    @property
    def dedup_ratio(self) -> float:
        """Mean ids-per-fetched-row since construction (1.0 = no reuse)."""
        return self.ids_seen / self.rows_fetched if self.rows_fetched \
            else 1.0

    # -- lookup -------------------------------------------------------------
    def lookup(self, ids) -> jnp.ndarray:
        """Rows for ``ids`` as a device array ``[N, dim]`` (any leading
        shape flattens; the caller reshapes). One unique-row gather +
        inverse permute under the dedup flag."""
        ids_np = self._check_ids(ids)
        self.pull_count += 1
        uniq, inv = self._dedup(ids_np)
        self.bytes_pulled += uniq.size * self.dim * 4
        rows = self._gather_unique(uniq)
        return rows[jnp.asarray(inv, jnp.int32)]

    def _gather_unique(self, uniq: np.ndarray) -> jnp.ndarray:
        mode = self._mode()
        n = self.num_shards
        if mode == "manual":
            RECSYS_STATS["manual_lookups"] += 1
            U = _pad_len(max(1, uniq.size))
            pad_val = int(uniq[0]) if uniq.size else 0
            padded = np.full((U,), pad_val, np.int64)
            padded[:uniq.size] = uniq
            prog = self._lookup_prog(U)
            rows = prog(self.data, jnp.asarray(padded, jnp.int32))
            return rows[:uniq.size]
        RECSYS_STATS["auto_lookups"] += 1
        u = jnp.asarray(uniq, jnp.int32)
        return self.data[u % n, u // n]

    def _lookup_prog(self, U: int):
        key = (id(self._mesh), U)
        prog = self._lookup_progs.get(key)
        if prog is not None:
            return prog
        from ..distributed import env as dist_env
        n, axis = self.num_shards, self.axis

        def body(data_s, shard_s, uniq):
            s = shard_s[0]
            own = (uniq % n) == s
            local = jnp.clip(uniq // n, 0, data_s.shape[1] - 1)
            rows = jnp.where(own[:, None], data_s[0, local], 0.0)
            return jax.lax.psum(rows, axis)

        shard_ids = jax.device_put(
            np.arange(n, dtype=np.int32),
            NamedSharding(self._mesh, P(axis)))
        prog = jax.jit(dist_env.shard_map(
            body, mesh=self._mesh,
            in_specs=(P(axis, None, None), P(axis), P()),
            out_specs=P(), axis_names={axis}, check_vma=False))
        wrapped = lambda data, uniq: prog(data, shard_ids, uniq)
        self._lookup_progs[key] = wrapped
        return wrapped

    # -- sparse update ------------------------------------------------------
    def apply_grads(self, ids, grads) -> None:
        """Sparse optimizer step: accumulate duplicate-id gradients over
        the unique set (SparseTable push semantics, np accumulation
        order), then the row-wise adagrad/sgd update runs ON the shard
        that owns each row — optimizer state never crosses the mesh."""
        ids_np = self._check_ids(ids)
        grads_np = np.asarray(grads, np.float32).reshape(
            ids_np.size, self.dim)
        self.push_count += 1
        self.bytes_pushed += grads_np.nbytes
        uniq, inv = np.unique(ids_np, return_inverse=True)
        acc = np.zeros((uniq.size, self.dim), np.float32)
        np.add.at(acc, inv, grads_np)
        self._update_unique(uniq.astype(np.int64), acc)

    def _update_unique(self, uniq: np.ndarray, acc: np.ndarray) -> None:
        mode = self._mode()
        n = self.num_shards
        if mode == "manual":
            RECSYS_STATS["manual_updates"] += 1
            U = _pad_len(max(1, uniq.size))
            pad_val = int(uniq[0]) if uniq.size else 0
            padded_ids = np.full((U,), pad_val, np.int64)
            padded_ids[:uniq.size] = uniq
            padded_acc = np.zeros((U, self.dim), np.float32)
            padded_acc[:uniq.size] = acc
            prog = self._update_prog(U)
            self.data, self.g2 = prog(
                self.data, self.g2, jnp.asarray(padded_ids, jnp.int32),
                jnp.asarray(padded_acc))
            return
        RECSYS_STATS["auto_updates"] += 1
        u = jnp.asarray(uniq, jnp.int32)
        shard, local = u % n, u // n
        a = jnp.asarray(acc)
        if self.optimizer == "adagrad":
            g2 = self.g2.at[shard, local].add((a ** 2).mean(axis=1))
            denom = jnp.sqrt(g2[shard, local])[:, None] + 1e-10
            self.data = self.data.at[shard, local].add(
                -self.lr * a / denom)
            self.g2 = g2
        else:
            self.data = self.data.at[shard, local].add(-self.lr * a)

    def _update_prog(self, U: int):
        key = (id(self._mesh), U)
        prog = self._update_progs.get(key)
        if prog is not None:
            return prog
        from ..distributed import env as dist_env
        n, axis, lr = self.num_shards, self.axis, self.lr
        adagrad = self.optimizer == "adagrad"

        def body(data_s, g2_s, shard_s, uniq, acc):
            s = shard_s[0]
            own = (uniq % n) == s
            local = jnp.clip(uniq // n, 0, data_s.shape[1] - 1)
            if adagrad:
                # pad entries carry zero acc: their .add is a no-op,
                # and pad-vs-real duplicates of the same row read the
                # SAME final g2, so the real entry's denom is exact
                msq = jnp.where(own, (acc ** 2).mean(axis=1), 0.0)
                g2n = g2_s[0].at[local].add(msq)
                denom = jnp.sqrt(g2n[local])[:, None] + 1e-10
                upd = jnp.where(own[:, None], -lr * acc / denom, 0.0)
                return (data_s[0].at[local].add(upd)[None],
                        g2n[None])
            upd = jnp.where(own[:, None], -lr * acc, 0.0)
            return data_s[0].at[local].add(upd)[None], g2_s

        # donation keeps the update at ONE table copy in HBM
        shard_ids = jax.device_put(
            np.arange(n, dtype=np.int32),
            NamedSharding(self._mesh, P(axis)))
        prog = jax.jit(dist_env.shard_map(
            body, mesh=self._mesh,
            in_specs=(P(axis, None, None), P(axis, None), P(axis),
                      P(), P()),
            out_specs=(P(axis, None, None), P(axis, None)),
            axis_names={axis}, check_vma=False),
            donate_argnums=(0, 1))
        wrapped = lambda data, g2, uniq, acc: prog(data, g2, shard_ids,
                                                   uniq, acc)
        self._update_progs[key] = wrapped
        return wrapped

    # -- SparseTable protocol (host arrays) ---------------------------------
    def pull(self, ids) -> np.ndarray:
        return np.asarray(self.lookup(ids))

    def push(self, ids, grads) -> None:
        self.apply_grads(ids, grads)

    # -- accounting / attribution -------------------------------------------
    def device_arrays(self):
        """Live device buffers for the HBM census
        (:func:`paddle_tpu.recsys.publish_table_hbm`)."""
        out = [self.data]
        if self.optimizer == "adagrad":
            out.append(self.g2)
        return out

    def hbm_bytes(self) -> int:
        return sum(int(a.nbytes) for a in self.device_arrays())

    # -- checkpoint ---------------------------------------------------------
    def state_dict(self) -> Dict[str, np.ndarray]:
        """Global-row-order dense arrays (mesh-layout-independent: a
        checkpoint written on ps=8 restores onto ps=2 or ps=1)."""
        arr = np.asarray(self.data)             # [n, R, D]
        g2 = np.asarray(self.g2)
        ids = np.arange(self.num_rows)
        out = {"data": arr[ids % self.num_shards, ids // self.num_shards]}
        if self.optimizer == "adagrad":
            out["g2"] = g2[ids % self.num_shards, ids // self.num_shards]
        return out

    def load_state_dict(self, state) -> None:
        data = np.asarray(state["data"], np.float32)
        if data.shape != (self.num_rows, self.dim):
            raise ValueError(
                f"state_dict shape {data.shape} != table "
                f"{(self.num_rows, self.dim)}")
        n, R = self.num_shards, self._rows_per_shard
        arr = np.zeros((n, R, self.dim), np.float32)
        ids = np.arange(self.num_rows)
        arr[ids % n, ids // n] = data
        g2 = np.zeros((n, R), np.float32)
        if "g2" in state and self.optimizer == "adagrad":
            g2[ids % n, ids // n] = np.asarray(state["g2"], np.float32)
        if self._mesh is not None:
            self.data = jax.device_put(
                arr, NamedSharding(self._mesh, P(self.axis, None, None)))
            self.g2 = jax.device_put(
                g2, NamedSharding(self._mesh, P(self.axis, None)))
        else:
            self.data = jnp.asarray(arr)
            self.g2 = jnp.asarray(g2)
