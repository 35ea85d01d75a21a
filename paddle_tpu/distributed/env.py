"""Distributed environment state.

Replaces the reference's env-variable protocol
(PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS, reference:
fleet/launch_utils.py) + NCCL comm registry (platform/collective_helper.h:68)
with a process-global registry of the active `jax.sharding.Mesh`, the rank
(process index) and named-axis groups.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax

# one symbol for every distributed module (call sites use the check_vma /
# axis_names surface)
shard_map = jax.shard_map

_state = threading.local()
_global = {
    "mesh": None,           # active jax.sharding.Mesh
    "initialized": False,
    "data_axis": None,      # axis name used for data parallel inside shard_map
}


def get_rank() -> int:
    if _global["initialized"]:
        return jax.process_index()
    return int(os.environ.get("PADDLE_TRAINER_ID", 0))


def get_world_size() -> int:
    if _global["initialized"]:
        return jax.process_count()
    return int(os.environ.get("PADDLE_TRAINERS_NUM", 1))


def set_mesh(mesh):
    _global["mesh"] = mesh


def get_mesh():
    return _global["mesh"]


def mark_initialized():
    _global["initialized"] = True


def is_initialized() -> bool:
    return _global["initialized"]


def reset():
    """Clear process-global distributed state (tests / re-init)."""
    _global["mesh"] = None
    _global["initialized"] = False
    _global["data_axis"] = None


def set_data_axis(name: Optional[str]):
    """Set while tracing inside shard_map so SyncBatchNorm etc. can pmean."""
    _global["data_axis"] = name


def current_data_axis() -> Optional[str]:
    return _global["data_axis"]


# ---------------------------------------------------------------------------
# Bound-axis tracking: collectives consult this to decide traced vs eager.
# The analogue of the reference's "which ring am I on" (ring_id attr on
# c_* ops) — here, which mesh axes the enclosing shard_map bound.
# ---------------------------------------------------------------------------

import contextlib


def _axis_stack():
    if not hasattr(_state, "axes"):
        _state.axes = []
    return _state.axes


@contextlib.contextmanager
def axes_bound(*names: str):
    """Mark mesh axes as bound for the dynamic extent (used by shard_ctx)."""
    stack = _axis_stack()
    stack.extend(names)
    try:
        yield
    finally:
        del stack[len(stack) - len(names):]


def bound_axes():
    return tuple(_axis_stack())
