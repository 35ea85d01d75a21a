"""Process groups and functional collectives over XLA.

TPU-native redesign of the reference's collective stack
(reference: python/paddle/distributed/collective.py:41-1577 — Group/new_group
creating NCCL rings via c_comm_init, functional ops appending c_allreduce_* /
c_broadcast / c_allgather / alltoall / send_v2 graph ops; platform
collective_helper.h:68 NCCLCommContext ring registry).

Design (SURVEY.md §5/§7): a *ring* becomes a **named mesh axis**. A
:class:`Group` is a set of device positions with an axis name and a 1-D
sub-mesh; there is no comm-id bootstrap — XLA owns the ICI/DCN transport.

Every functional collective works in TWO contexts:

1. **Traced (inside jit/shard_map)** — the hot path. When the group's axis
   is bound (we track bound axes in `env`), the op lowers straight to the
   XLA collective: ``psum``/``all_gather``/``ppermute``/``all_to_all``.
   The compiler schedules/overlaps them — this replaces comm streams,
   ``c_sync_comm_stream`` and the Reducer.

2. **Eager (single-controller)** — the per-rank view. In the reference each
   rank is a process holding its own tensor; in single-controller JAX the
   per-rank tensors of a group live stacked along a leading axis of one
   array (shape ``[nranks, ...]`` — exactly the layout the reference's
   multi-process tests compare, test_collective_base.py:206). Eager
   collectives shard that axis over the group's mesh and run the real XLA
   collective via ``shard_map`` — the same lowering multi-chip uses.

In true multi-process mode (``jax.distributed`` initialized) the eager ops
on this-process tensors additionally route through multihost utilities.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.tensor import Tensor
from ..testing import chaos
from . import env

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "destroy_process_group",
    "all_reduce", "all_gather", "all_gather_object", "reduce", "broadcast",
    "scatter", "alltoall", "send", "recv", "barrier", "wait",
    "all_reduce_arrays", "is_initialized", "get_world_size_of_group",
    "CollectiveTimeoutError",
]


class CollectiveTimeoutError(RuntimeError):
    """An eager collective dispatch exceeded ``FLAGS_collective_timeout_s``.

    The reference analogue is an NCCL communicator watchdog abort
    (NCCL_ASYNC_ERROR_HANDLING): a hung ring must become a structured,
    catchable error on the controller instead of a silent stall. Carries
    the op name, group axis and the budget for supervisors that restart
    on comm failure."""

    def __init__(self, op: str, group: "Group", timeout_s: float):
        super().__init__(
            f"collective {op!r} on group {group.axis_name!r} "
            f"(nranks={group.nranks}) did not complete within "
            f"{timeout_s:g}s (FLAGS_collective_timeout_s). The dispatch "
            "thread is abandoned; on a real hang, restart from the last "
            "committed checkpoint (distributed.checkpoint."
            "CheckpointManager).")
        self.op = op
        self.group_axis = group.axis_name
        self.timeout_s = timeout_s


class ReduceOp:
    """reference: collective.py ReduceOp (SUM/MAX/MIN/PROD/AVG)."""
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_LAX_REDUCE = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


class Group:
    """A communicator: device positions + named mesh axis (replaces ring_id).

    reference: collective.py:79 Group, :209 new_group (ring creation via
    c_comm_init); here no bootstrap is needed — the axis name keys XLA
    collectives and the 1-D sub-mesh scopes eager emulation.
    """

    def __init__(self, ranks: Sequence[int], gid: int,
                 axis_name: Optional[str] = None, mesh: Optional[Mesh] = None):
        self.ranks = list(ranks)
        self.id = gid
        self.axis_name = axis_name or f"group_{gid}"
        self._mesh = mesh

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    world_size = nranks

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            devices = np.array([jax.devices()[r] for r in self.ranks])
            self._mesh = Mesh(devices, (self.axis_name,))
        return self._mesh

    def get_group_rank(self, global_rank: int) -> int:
        return self.ranks.index(global_rank) if global_rank in self.ranks else -1

    def is_member(self) -> bool:
        return True

    def __repr__(self):
        return f"Group(id={self.id}, axis={self.axis_name!r}, ranks={self.ranks})"


_lock = threading.Lock()
_groups: dict = {}
_next_gid = [1]  # gid 0 is reserved for the world group


def _default_group() -> Group:
    with _lock:
        if 0 not in _groups:
            n = len(jax.devices())
            _groups[0] = Group(list(range(n)), 0, axis_name="world")
    return _groups[0]


def new_group(ranks: Optional[Sequence[int]] = None, backend: Optional[str] = None,
              timeout=None, axis_name: Optional[str] = None) -> Group:
    """Create a communicator over a subset of device positions.

    reference: collective.py:209 new_group — there: ring_id allocation +
    per-rank c_comm_init; here: allocate an id + axis name, done.
    """
    if ranks is None:
        ranks = list(range(len(jax.devices())))
    with _lock:
        gid = _next_gid[0]
        _next_gid[0] += 1
        g = Group(sorted(ranks), gid, axis_name=axis_name)
        _groups[gid] = g
    return g


def get_group(gid: int = 0) -> Group:
    if gid == 0:
        return _default_group()
    return _groups[gid]


def destroy_process_group(group: Optional[Group] = None):
    with _lock:
        if group is None:
            _groups.clear()
            # gid counter stays monotonic: gid 0 remains reserved for the
            # world group so a later new_group can never be mistaken for it
        else:
            _groups.pop(group.id, None)


def is_initialized() -> bool:
    return True


def get_world_size_of_group(group: Optional[Group] = None) -> int:
    return (group or _default_group()).nranks


# ---------------------------------------------------------------------------
# Traced/eager dispatch plumbing
# ---------------------------------------------------------------------------

def _unwrap(x):
    return x._data if isinstance(x, Tensor) else x


def _rewrap(out, like):
    if isinstance(like, Tensor):
        t = Tensor(out, stop_gradient=like.stop_gradient)
        return t
    return out


def _traced_axes(group: Optional[Group]):
    """Return the axis name(s) to use if we're inside a bound trace context."""
    bound = env.bound_axes()
    if not bound:
        return None
    if group is None or group.id == 0:
        return tuple(bound)  # default group = reduce over every bound axis
    if group.axis_name in bound:
        return (group.axis_name,)
    return None


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


_eager_cache: dict = {}


def _eager_shardmap(group: Group, key, body, n_out_stacked=True):
    """jit(shard_map(body)) over the group's 1-D mesh, cached per (group,key).

    The operand's leading axis (length group.nranks) is the per-rank axis;
    each shard sees a [1, ...] local block with the group axis bound.
    """
    ck = (group.id, group.axis_name, group.nranks, key)
    f = _eager_cache.get(ck)
    if f is None:
        ax = group.axis_name
        f = jax.jit(env.shard_map(
            body, mesh=group.mesh, in_specs=P(ax), out_specs=P(ax),
            check_vma=False))
        _eager_cache[ck] = f
    return f


def _eager_warm(group: Group, key) -> bool:
    """Whether this (group, op-key)'s shard_map wrapper is already built.
    Approximate compile detection: a warm wrapper can still trigger an
    XLA compile on a new operand shape, but the common skew source — the
    first call paying jit+compile — is caught."""
    return (group.id, group.axis_name, group.nranks, key) in _eager_cache


@contextlib.contextmanager
def _comm_trace(op: str, group: Group, x, cache_key=None):
    """Comms observability for every eager collective (tentpole pillar 3;
    reference analogue: the NCCL comm events CUPTI puts on the
    device_tracer timeline). Records op name, group axis/size, operand
    bytes and dispatch latency into the monitor registry, and emits a
    ``comm::<op>`` RecordEvent (a :class:`~paddle_tpu.monitor.trace.span`)
    so collectives show up on host timelines when a profiler window is
    open.

    Latency here is DISPATCH latency (time for the XLA call to return,
    enqueue included, device completion not) — the single-controller
    eager model has no per-collective completion event; use
    ``wait``/``block_until_ready`` timings for on-device time. A COLD
    call (shard_map wrapper not built yet) pays trace+compile, orders of
    magnitude above steady-state dispatch — those land in the separate
    ``comm_cold_dispatch_seconds`` histogram so the latency series stays
    readable. Telemetry must never sink the collective itself, hence the
    broad guards."""
    nbytes = int(getattr(x, "nbytes", 0) or 0)
    warm = cache_key is None or _eager_warm(group, cache_key)
    try:
        # one span: the ring, the profiler's timeline while one is open,
        # and a child of the active train.step trace (FLAGS_trace +
        # TrainStep's activate())
        from ..profiler import RecordEvent
        span = RecordEvent(f"comm::{op}", group=group.axis_name,
                           nranks=group.nranks, bytes=nbytes)
    except Exception:
        span = contextlib.nullcontext()
    t0 = time.perf_counter()
    with span:
        yield
    dt = time.perf_counter() - t0
    try:
        from ..monitor import get_registry
        reg = get_registry()
        labels = {"op": op, "group": group.axis_name,
                  "nranks": group.nranks}
        reg.counter("comm_ops_total",
                    "eager collective dispatches").inc(**labels)
        reg.counter("comm_bytes_total",
                    "operand bytes moved through eager collectives"
                    ).inc(nbytes, **labels)
        reg.histogram("comm_latency_seconds" if warm
                      else "comm_cold_dispatch_seconds",
                      "eager collective dispatch latency (warm wrapper)"
                      if warm else
                      "first-call eager collective dispatch incl. "
                      "trace+compile").observe(dt, **labels)
        # crash forensics: collectives land in the flight-recorder event
        # ring too (a run that dies mid-sync should say so in the dump);
        # gated like the TrainStep records — off = zero recorder writes
        from ..monitor import flight_recorder as _flight
        if _flight.enabled():
            _flight.get_flight_recorder().record_event(
                "collective", op=op, group=group.axis_name,
                nranks=group.nranks, bytes=nbytes, dispatch_ms=dt * 1e3)
    except Exception:
        pass


def _run_collective(op: str, group: Group, fn, *args):
    """Dispatch an eager collective under the watchdog.

    With ``FLAGS_collective_timeout_s`` unset (default) and no chaos
    armed this is a direct call — zero overhead. With a budget, the
    dispatch runs on a daemon worker thread and a wall-clock watchdog
    converts a stall into :class:`CollectiveTimeoutError`, recording a
    ``collective_timeout`` flight-recorder event and a registry counter.
    XLA cannot cancel an in-flight collective from python, so the hung
    thread is abandoned (exactly what the NCCL watchdog does before
    aborting the communicator) — the caller's recovery is a restart from
    the last committed checkpoint. The budget covers the whole dispatch,
    including a first-call trace+compile; set it well above cold-start.

    Chaos site ``collective.hang`` blocks the worker (bounded,
    cancellable) to prove the watchdog path deterministically."""
    from ..core.flags import get_flag
    timeout_s = float(get_flag("collective_timeout_s") or 0.0)
    hang = chaos.active() and chaos.probe("collective.hang")
    if timeout_s <= 0.0 and not hang:
        return fn(*args)
    if hang and timeout_s <= 0.0:
        # a hang with no watchdog budget would block the controller (the
        # faithful simulation) — useless in any harness; fail loudly at
        # the misconfiguration instead
        raise RuntimeError(
            "chaos site 'collective.hang' fired but "
            "FLAGS_collective_timeout_s is unset — set a timeout budget "
            "so the watchdog (the thing this site exists to exercise) "
            "can convert the hang into CollectiveTimeoutError")

    result: dict = {}
    done = threading.Event()

    def worker():
        try:
            if hang:
                chaos.hang_loop(max(timeout_s, 1.0) * 20 + 60.0)
            result["value"] = fn(*args)
        except BaseException as e:     # surfaces on the caller's thread
            result["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=worker, daemon=True,
                         name=f"collective-{op}")
    t.start()
    if not done.wait(timeout_s if timeout_s > 0.0 else None):
        try:
            from ..monitor import get_registry
            get_registry().counter(
                "collective_timeouts_total",
                "eager collective watchdog trips").inc(
                    op=op, group=group.axis_name)
        except Exception:
            pass
        try:
            from ..monitor import flight_recorder as _flight
            if _flight.enabled():
                _flight.get_flight_recorder().record_event(
                    "collective_timeout", op=op, group=group.axis_name,
                    nranks=group.nranks, timeout_s=timeout_s)
        except Exception:
            pass
        raise CollectiveTimeoutError(op, group, timeout_s)
    if "error" in result:
        raise result["error"]
    return result["value"]


def _check_stacked(arr, group: Group, opname: str):
    if arr.ndim == 0 or arr.shape[0] != group.nranks:
        raise ValueError(
            f"{opname}: eager collectives in the single-controller model "
            f"operate on the stacked per-rank view — expected leading axis "
            f"of size {group.nranks} (group ranks), got shape {tuple(arr.shape)}. "
            "Inside jit, call this under a shard_map with the group's axis "
            "bound (see paddle_tpu.distributed.shard_ctx).")


# ---------------------------------------------------------------------------
# Functional collectives
# ---------------------------------------------------------------------------

def all_reduce(tensor, op: int = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True, use_calc_stream: bool = False):
    """reference: collective.py:415 all_reduce → c_allreduce_{sum,max,...}."""
    g = group or _default_group()
    x = _unwrap(tensor)

    axes = _traced_axes(g)
    if axes is not None and _is_traced(x):
        if op == ReduceOp.AVG:
            out = jax.lax.pmean(x, axes if len(axes) > 1 else axes[0])
        elif op == ReduceOp.PROD:
            out = _pprod(x, axes)
        else:
            out = _LAX_REDUCE[op](x, axes if len(axes) > 1 else axes[0])
        return _rewrap(out, tensor)

    if g.nranks == 1:
        return tensor
    _check_stacked(x, g, "all_reduce")
    ax = g.axis_name

    def body(s):
        if op == ReduceOp.AVG:
            return jnp.broadcast_to(jax.lax.pmean(s, ax), s.shape)
        if op == ReduceOp.PROD:
            return jnp.broadcast_to(_pprod(s, (ax,)), s.shape)
        return jnp.broadcast_to(_LAX_REDUCE[op](s, ax), s.shape)

    with _comm_trace("all_reduce", g, x, ("all_reduce", op)):
        out = _run_collective(
            "all_reduce", g, _eager_shardmap(g, ("all_reduce", op), body), x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def _pprod(x, axes):
    """Product reduction via all_gather (no native pprod in lax)."""
    for ax in axes:
        g = jax.lax.all_gather(x, ax)
        x = jnp.prod(g, axis=0)
    return x


def _gather_global_order(x, axes):
    """all_gather over bound axes with the result in GLOBAL RANK order.

    Gathering innermost-axis-first stacks leading dims in (outer, ..., inner)
    order; one flatten then yields row-major global ranks — matching the
    layout every eager collective and the reference guarantee."""
    out = x
    for ax in reversed(axes):
        out = jax.lax.all_gather(out, ax)
    return out.reshape((-1,) + tuple(x.shape))


def _global_axis_index(axes):
    """This shard's global rank across the bound axes (row-major)."""
    idx = None
    for ax in axes:
        i = jax.lax.axis_index(ax)
        n = jax.lax.psum(1, ax)
        idx = i if idx is None else idx * n + i
    return idx


def all_gather(tensor_or_list, tensor=None, group: Optional[Group] = None,
               sync_op: bool = True, axis: int = 0):
    """reference: collective.py:589 all_gather (fills a python list).

    Traced: returns the gathered array (leading axis = group size).
    Eager stacked: every rank slot receives the full stack.
    Called with (tensor_list, tensor) it appends per-rank tensors for parity.
    """
    g = group or _default_group()

    if tensor is None:
        x = _unwrap(tensor_or_list)
        axes = _traced_axes(g)
        if axes is not None and _is_traced(x):
            out = _gather_global_order(x, axes)
            return _rewrap(out, tensor_or_list)
        if g.nranks == 1:
            return _rewrap(jnp.expand_dims(x, 0), tensor_or_list)
        _check_stacked(x, g, "all_gather")
        ax = g.axis_name

        def body(s):
            return jax.lax.all_gather(s[0], ax)[None]

        with _comm_trace("all_gather", g, x, ("all_gather",)):
            out = _run_collective(
                "all_gather", g, _eager_shardmap(g, ("all_gather",), body),
                x)
        return _rewrap(out, tensor_or_list)

    # list-filling parity form
    tensor_list, t = tensor_or_list, tensor
    x = _unwrap(t)
    if g.nranks == 1:
        tensor_list.append(_rewrap(x, t))
        return
    _check_stacked(x, g, "all_gather")
    gathered = all_gather(x, group=g)  # [n, n, ...] per-slot stacks
    for r in range(g.nranks):
        tensor_list.append(_rewrap(gathered[0, r], t))


def all_gather_object(obj_list: List, obj, group: Optional[Group] = None):
    """Host-side object gather (reference: collective.py all_gather_object)."""
    g = group or _default_group()
    if env.get_world_size() > 1:
        from jax.experimental import multihost_utils
        import pickle
        payload = np.frombuffer(pickle.dumps(obj), np.uint8)
        # pad to max length across processes
        n = multihost_utils.process_allgather(np.array([payload.size]))
        m = int(np.max(n))
        buf = np.zeros(m, np.uint8)
        buf[:payload.size] = payload
        out = multihost_utils.process_allgather(buf)
        for i in range(out.shape[0]):
            obj_list.append(pickle.loads(out[i, :int(n[i])].tobytes()))
        return
    for _ in range(g.nranks):
        obj_list.append(obj)


def reduce(tensor, dst: int = 0, op: int = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True):
    """reference: collective.py:495 reduce → c_reduce_*; result lands on dst,
    other ranks keep their input."""
    g = group or _default_group()
    x = _unwrap(tensor)
    dst_local = g.get_group_rank(dst) if dst in g.ranks else dst

    axes = _traced_axes(g)
    if axes is not None and _is_traced(x):
        ax_arg = axes if len(axes) > 1 else axes[0]
        if op == ReduceOp.AVG:
            red = jax.lax.pmean(x, ax_arg)
        elif op == ReduceOp.PROD:
            red = _pprod(x, axes)
        else:
            red = _LAX_REDUCE[op](x, ax_arg)
        idx = _global_axis_index(axes)
        out = jnp.where(idx == dst_local, red, x)
        return _rewrap(out, tensor)

    if g.nranks == 1:
        return tensor
    _check_stacked(x, g, "reduce")
    ax = g.axis_name

    def body(s):
        if op == ReduceOp.AVG:
            red = jax.lax.pmean(s, ax)
        elif op == ReduceOp.PROD:
            red = _pprod(s, (ax,))
        else:
            red = _LAX_REDUCE[op](s, ax)
        idx = jax.lax.axis_index(ax)
        return jnp.where(idx == dst_local, red, s)

    with _comm_trace("reduce", g, x, ("reduce", op, dst_local)):
        out = _run_collective(
            "reduce", g, _eager_shardmap(g, ("reduce", op, dst_local), body),
            x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def _group_size_traced(axes):
    return jax.lax.psum(1, axes if len(axes) > 1 else axes[0])


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True):
    """reference: collective.py:348 broadcast → c_broadcast."""
    g = group or _default_group()
    x = _unwrap(tensor)
    src_local = g.get_group_rank(src) if src in g.ranks else src

    axes = _traced_axes(g)
    if axes is not None and _is_traced(x):
        out = _gather_global_order(x, axes)[src_local]
        return _rewrap(out, tensor)

    if g.nranks == 1:
        return tensor
    _check_stacked(x, g, "broadcast")
    ax = g.axis_name

    def body(s):
        return jax.lax.all_gather(s[0], ax)[src_local][None]

    with _comm_trace("broadcast", g, x, ("broadcast", src_local)):
        out = _run_collective(
            "broadcast", g,
            _eager_shardmap(g, ("broadcast", src_local), body), x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    """reference: collective.py:666 scatter → c_scatter.

    Eager stacked form: operand is the stacked [nranks, ...] source held by
    ``src``; each rank slot receives its slice."""
    g = group or _default_group()
    if tensor_list is not None:
        stacked = jnp.stack([_unwrap(t) for t in tensor_list])
        out = scatter(stacked, src=src, group=g)
        if isinstance(tensor, Tensor):
            tensor._data = out[g.get_group_rank(env.get_rank())] \
                if out.ndim > _unwrap(tensor).ndim else out
            return tensor
        return out
    x = _unwrap(tensor)
    axes = _traced_axes(g)
    if axes is not None and _is_traced(x):
        # x: full stacked source replicated; pick this rank's slice
        idx = _global_axis_index(axes)
        out = jax.lax.dynamic_index_in_dim(x, idx, 0, keepdims=False)
        return _rewrap(out, tensor)
    if g.nranks == 1:
        return tensor
    _check_stacked(x, g, "scatter")
    # scatter of the stacked view is the identity layout-wise; each rank's
    # slot keeps row r — nothing moves (data already lives rank-major).
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group: Optional[Group] = None,
             sync_op: bool = True):
    """reference: collective.py:1395 alltoall → AllToAll; traced form lowers
    to lax.all_to_all (the MoE dispatch primitive, global_scatter_op.cc)."""
    g = group or _default_group()

    if isinstance(in_tensor_list, (list, tuple)):
        stacked = jnp.stack([_unwrap(t) for t in in_tensor_list])
        out = alltoall(stacked, group=g)
        res = [_rewrap(out[i], in_tensor_list[i]) for i in range(out.shape[0])]
        if out_tensor_list is not None:
            out_tensor_list.extend(res)
            return
        return res

    x = _unwrap(in_tensor_list)
    axes = _traced_axes(g)
    if axes is not None and _is_traced(x):
        # x: [nranks, ...] per-destination blocks on each rank
        out = jax.lax.all_to_all(x, axes[0], split_axis=0, concat_axis=0,
                                 tiled=False)
        return _rewrap(out, in_tensor_list)

    if g.nranks == 1:
        return in_tensor_list
    # eager stacked: x[r, d] = block rank r sends to rank d  (shape [n, n, ...])
    if x.ndim < 2 or x.shape[0] != g.nranks or x.shape[1] != g.nranks:
        raise ValueError(
            f"alltoall: expected stacked [nranks, nranks, ...] blocks, got "
            f"{tuple(x.shape)}")
    ax = g.axis_name

    def body(s):  # s: [1, n, ...] — this rank's outgoing blocks
        return jax.lax.all_to_all(s, ax, split_axis=1, concat_axis=0,
                                  tiled=False).swapaxes(0, 1)

    # traced under the canonical lax op name (comm::all_to_all RecordEvent
    # + comm_* registry series) — the MoE dispatch primitive's telemetry,
    # ROADMAP item 5's prerequisite for expert-parallel overlap work
    with _comm_trace("all_to_all", g, x, ("all_to_all",)):
        out = _run_collective(
            "all_to_all", g, _eager_shardmap(g, ("all_to_all",), body), x)
    return _rewrap(out, in_tensor_list)


_pending_sends: dict = {}


def send(tensor, dst: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """reference: collective.py:1472 send → send_v2 (NCCL P2P).

    Point-to-point is a *process*-level op. Single-controller SPMD has no
    second process — traced P2P over a mesh axis is :func:`ppermute_shift`
    (the pipeline-stage channel). Eagerly, send enqueues under
    (group, src=this rank, dst) and only a matching recv on the SAME process
    (i.e. dst == this rank, the self-loop the reference also permits) can
    deliver it; anything else raises instead of silently dropping."""
    g = group or _default_group()
    _pending_sends.setdefault((g.id, env.get_rank(), dst), []).append(
        _unwrap(tensor))
    return tensor


def recv(tensor, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """reference: collective.py:1525 recv → recv_v2."""
    g = group or _default_group()
    me = env.get_rank()
    q = _pending_sends.get((g.id, src, me))
    if q:
        val = q.pop(0)
        if not q:
            _pending_sends.pop((g.id, src, me), None)
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(val)
            return tensor
        return val
    raise RuntimeError(
        f"recv(src={src}): no matching send. Eager P2P only pairs within "
        "one process (send dst == recv rank); for cross-device P2P inside "
        "jit use ppermute_shift over the group's mesh axis.")


def ppermute_shift(x, group: Optional[Group] = None, shift: int = 1):
    """Ring shift: rank r's block moves to rank (r+shift)%n. The TPU-native
    send_v2/recv_v2 for pipeline stages (reference: partial_send/recv ops) —
    traced it lowers to collective-permute on ICI."""
    g = group or _default_group()
    arr = _unwrap(x)
    n = g.nranks
    axes = _traced_axes(g)
    perm = [(i, (i + shift) % n) for i in range(n)]
    if axes is not None and _is_traced(arr):
        return _rewrap(jax.lax.ppermute(arr, axes[0], perm), x)
    if n == 1:
        return x
    _check_stacked(arr, g, "ppermute_shift")
    ax = g.axis_name

    def body(s):
        return jax.lax.ppermute(s, ax, perm)

    with _comm_trace("ppermute_shift", g, arr, ("ppermute", shift)):
        out = _run_collective(
            "ppermute_shift", g,
            _eager_shardmap(g, ("ppermute", shift), body), arr)
    return _rewrap(out, x)


def barrier(group: Optional[Group] = None):
    """reference: collective.py barrier → barrier op / gloo."""
    if env.get_world_size() > 1:
        from jax.experimental import multihost_utils
        # the cross-HOST sync is the likeliest real-world hang (a dead
        # peer process): watchdog applies here too
        _run_collective(
            "barrier", group or _default_group(),
            multihost_utils.sync_global_devices, "paddle_tpu_barrier")
        return
    g = group or _default_group()
    if g.nranks > 1:
        x = jnp.zeros((g.nranks,), jnp.int32)
        out = all_reduce(x, ReduceOp.SUM, g)
        jax.block_until_ready(_unwrap(out))


def wait(tensor, group: Optional[Group] = None, use_calc_stream: bool = True):
    """reference: collective.py wait — XLA async dispatch: block on the value."""
    jax.block_until_ready(_unwrap(tensor))
    return tensor


def all_reduce_arrays(arrays: List, op: int = ReduceOp.SUM,
                      group: Optional[Group] = None) -> List:
    """Multi-process helper used by DataParallel.apply_collective_grads:
    allreduce a list of this-process arrays across processes."""
    if env.get_world_size() <= 1:
        return list(arrays)
    from jax.experimental import multihost_utils

    def gather_sum():
        out = []
        for a in arrays:
            g = multihost_utils.process_allgather(np.asarray(a))
            out.append(jnp.asarray(np.sum(g, axis=0)))
        return out

    # cross-host allgather: a dead peer hangs this forever without the
    # watchdog — the exact production scenario the timeout exists for
    return _run_collective("all_reduce_arrays", group or _default_group(),
                           gather_sum)
