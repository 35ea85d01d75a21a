"""SPMD plumbing: mesh construction + axis-aware shard_map.

The TPU-native replacement for the reference's multi-process execution
fabric: where the reference launches one process per device and wires NCCL
rings (fleet/launch_utils.py, platform/nccl_helper.h), here a single
controller lays a :class:`jax.sharding.Mesh` over the chips and jit-compiles
SPMD programs; collectives inside are keyed by named mesh axes.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import env

__all__ = ["make_mesh", "shard_map", "named_sharding", "current_mesh",
           "PartitionSpec", "apply_param_shardings", "constrain", "BATCH",
           "data_axes", "degrade_spec", "SERVE_KV_SPEC",
           "shard_serving_cache", "auto_axes", "shard_kernel"]

PartitionSpec = P

# Sentinel for "the batch dimension": expands to every data-style mesh axis
# present (dp and the ZeRO 'sharding' axis), matching the composite
# P(('dp', 'sharding')) batch layout TrainStep uses for its data_spec.
BATCH = "__batch__"
_DATA_AXES = ("dp", "sharding")


def data_axes(mesh: Mesh):
    """The mesh axes the batch dim is sharded over (dp + ZeRO sharding)."""
    return tuple(a for a in _DATA_AXES if a in mesh.axis_names)


def _degrade_entry(s, names):
    """One PartitionSpec entry with axis names absent from ``names``
    degraded to None/dropped (replicated) — the shared rule behind
    :func:`constrain`, :func:`apply_param_shardings` and TrainStep's
    ``_param_specs``: a model annotated for mp/ep composes with any
    sub-mesh that lacks those axes."""
    if isinstance(s, str):
        return s if s in names else None
    if isinstance(s, (tuple, list)):
        kept = tuple(a for a in s if a in names)
        return kept if kept else None
    return s


def degrade_spec(spec, mesh: Mesh) -> P:
    """A full PartitionSpec with absent-axis entries degraded for
    ``mesh`` (no BATCH sentinel handling — that is constrain-only)."""
    names = set(mesh.axis_names)
    return P(*(_degrade_entry(s, names) for s in tuple(spec)))


def constrain(x, *spec):
    """with_sharding_constraint on a Tensor/array against the active mesh.

    Axis names absent from the mesh degrade to None (replicated); the BATCH
    sentinel expands to the composite data axes; trailing dims pad with
    None. No-op without an active mesh — model code can sprinkle layout
    pins unconditionally.
    """
    mesh = env.get_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def clean_one(s):
        if s == BATCH:
            axes = data_axes(mesh)
            return axes if axes else None
        return _degrade_entry(s, names)
    clean = tuple(clean_one(s) for s in spec)
    ndim = len(x.shape)
    clean = clean[:ndim] + (None,) * max(0, ndim - len(clean))
    sh = NamedSharding(mesh, P(*clean))
    from ..core.tensor import Tensor, apply
    if isinstance(x, Tensor):
        return apply(lambda a: jax.lax.with_sharding_constraint(a, sh), x,
                     name="sharding_constraint")
    return jax.lax.with_sharding_constraint(x, sh)


def auto_axes() -> frozenset:
    """The axes of the active mesh that GSPMD partitions at this point
    of a trace: more than one device long and not already manual (inside
    a shard_map region, e.g. the pipeline's ``pp``). Empty off-mesh."""
    mesh = env.get_mesh()
    if mesh is None:
        return frozenset()
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return frozenset(a for a in mesh.axis_names
                     if int(mesh.shape[a]) > 1 and a not in manual)


def shard_kernel(fn, in_specs, out_specs):
    """``fn`` run per device shard over the active mesh — for Mosaic
    kernels, which GSPMD cannot partition (``NotImplementedError: Mosaic
    kernels cannot be automatically partitioned`` at lowering).

    The specs name the layout the kernel's math is independent over
    (BATCH expands to the data axes, as in :func:`constrain`); axes that
    are absent, one device long or already manual drop out of them, and
    every remaining axis goes manual for the call, so an axis no spec
    mentions sees replicated operands and repeats the work. With nothing
    left to partition ``fn`` is returned as it is."""
    axes = auto_axes()
    if not axes:
        return fn
    mesh = env.get_mesh()
    batch = data_axes(mesh)

    def clean(spec):
        return P(*(_degrade_entry(batch if s == BATCH else s, axes)
                   for s in tuple(spec)))

    as_specs = lambda t: jax.tree_util.tree_map(  # noqa: E731
        clean, t, is_leaf=lambda x: isinstance(x, P))
    # a Mosaic kernel lowers only where EVERY mesh axis is manual, the
    # one-device-long ones too; inside another shard_map the context
    # mesh is the one to extend
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    return env.shard_map(fn, in_specs=as_specs(in_specs),
                         out_specs=as_specs(out_specs),
                         axis_names=set(mesh.axis_names) - manual,
                         check_vma=False,
                         **({} if manual else {"mesh": mesh}))


def make_mesh(axis_sizes: Dict[str, int], devices=None) -> Mesh:
    """Build a named mesh. Axis order = dict order; trailing axes are most
    minor (place tp/sp last so their collectives ride adjacent ICI links —
    see SURVEY.md §7 design mapping)."""
    names = tuple(axis_sizes.keys())
    sizes = tuple(int(v) for v in axis_sizes.values())
    n = int(np.prod(sizes))
    devices = list(devices if devices is not None else jax.devices())
    if n > len(devices):
        raise ValueError(
            f"mesh {dict(axis_sizes)} needs {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(sizes)
    mesh = Mesh(arr, names)
    return mesh


def current_mesh() -> Optional[Mesh]:
    return env.get_mesh()


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def apply_param_shardings(layer, mesh: Optional[Mesh] = None):
    """Lay a Layer's parameters out on the mesh per their PartitionSpecs.

    The TPU-native replacement for the reference's parameter broadcast at
    engine setup (fleet/utils/hybrid_parallel_util.py:103): instead of
    broadcasting replicas over NCCL, each Parameter carries a
    ``spec`` (PartitionSpec) and is device_put once; XLA keeps it resident
    in the sharded layout from then on.
    """
    mesh = mesh or env.get_mesh()
    if mesh is None:
        raise ValueError("no active mesh; call fleet.init or pass mesh=")
    for _, p in layer.named_parameters():
        spec = getattr(p, "spec", None) or P()
        p._data = jax.device_put(
            p._data, NamedSharding(mesh, degrade_spec(spec, mesh)))
    for _, b in layer.named_buffers():
        b._data = jax.device_put(b._data, NamedSharding(mesh, P()))
    return layer


_TP_COLUMN = ("q_proj.weight", "k_proj.weight", "v_proj.weight",
              "linear1.weight")          # [in, out]: shard out over mp
_TP_ROW = ("out_proj.weight", "linear2.weight")   # [in, out]: shard in
_TP_COLUMN_BIAS = ("q_proj.bias", "k_proj.bias", "v_proj.bias",
                   "linear1.bias")
_VOCAB = ("word_embeddings.weight",)


def apply_hybrid_specs(layer, mp_axis: str = "mp"):
    """Stamp Megatron-style tensor-parallel PartitionSpecs onto a model
    built from nn.MultiHeadAttention/TransformerEncoder by parameter-name
    pattern (reference: the mp_layers rewrite the reference applies when
    building hybrid models — here layout is declarative so stock layers
    become TP-sharded without rewriting the model).

    Column-parallel (out-dim sharded): q/k/v projections, ffn in-proj.
    Row-parallel (in-dim sharded): attention out-proj, ffn out-proj — XLA
    inserts the psum after it. Vocab embeddings shard over the vocab dim.
    Everything else (norms, biases of row layers) stays replicated.
    """
    for name, p in layer.named_parameters():
        if getattr(p, "spec", None) not in (None, P()):
            continue                          # already placed explicitly
        if name.endswith(_VOCAB):
            p.spec = P(mp_axis, None)
        elif name.endswith(_TP_COLUMN):
            p.spec = P(None, mp_axis)
        elif name.endswith(_TP_ROW):
            p.spec = P(mp_axis, None)
        elif name.endswith(_TP_COLUMN_BIAS):
            p.spec = P(mp_axis)
        else:
            p.spec = P()
    return layer


#: layout of a serving paged K/V pool ``[L, P, G, bs, (H/G)*D]`` under
#: tensor parallelism (ISSUE 16): the head-group axis ``G`` (= the mp
#: size; each group holds ``H/G`` consecutive heads, fused into the minor
#: dim) shards over the mp axis — the same split apply_hybrid_specs gives
#: the q/k/v projections, so the TP decode program reads/writes its local
#: head shard without any gather. Layers, pages and the per-page token
#: dim stay replicated (page tables index them host-side). The
#: ``[L, P, G, bs, H/G]`` scale pools of a quantized cache shard the
#: same axis.
SERVE_KV_SPEC = P(None, None, "mp", None, None)


def shard_serving_cache(cache, mesh: Mesh):
    """Lay a serving PagedKVCache's pools out on the TP mesh (head
    groups over ``mp`` per :data:`SERVE_KV_SPEC`, degraded for meshes
    without an mp axis). Called once at engine init, before the first
    AOT compile, so the serving programs see sharded donors and GSPMD
    keeps the pools resident in the split layout — per-chip HBM then
    holds ``1/mp`` of the KV footprint, which is what lets models beyond
    single-chip HBM serve at all."""
    sh = NamedSharding(mesh, degrade_spec(SERVE_KV_SPEC, mesh))
    # quantized pools (FLAGS_serve_kv_quant) are (pages, scales) tuples
    cache.pools = jax.device_put(cache.pools, sh)
    return cache


def shard_map(body, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """jax.shard_map wrapper that records the mesh's axis names as *bound*
    for the dynamic extent of the body trace, so paddle_tpu.distributed
    collectives called inside dispatch to their lax (traced) lowering."""

    def wrapped(*args):
        with env.axes_bound(*mesh.axis_names):
            return body(*args)

    return env.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
