"""MoE dispatch/combine: the einsum oracle and the sort-based fast path.

Two implementations of the SAME data movement — tokens to per-expert
capacity slices and back — selected by ``FLAGS_moe_dispatch``:

``einsum`` (the GShard formulation, the parity oracle / kill switch):
    dispatch = einsum('tec,td->ecd') over a one-hot [T, E, C] mask,
    combine = einsum('tec,ecd->td') over the weighted mask. Simple, but
    both einsums materialize/stream O(T·E·C) tensors — the memory-bound
    shape this module exists to eliminate (every token row is multiplied
    against E·C mask entries that are almost all zero).

``sort`` (default): flatten the (token, choice) pairs CHOICE-MAJOR
    (matching the router's capacity priority), argsort by expert id so
    writes group by destination expert, then one static-shape scatter
    into a [E*C + 1, D] buffer (row E*C = the drop bucket) and one gather
    back. Data moved is O(T·k·D) regardless of E and capacity — at E=8,
    k=2, cf=2 that is ~8x less than the einsum's O(T·E·C·D) stream, and
    the gap grows linearly with E.

Both consume one :class:`~paddle_tpu.incubate.moe.routing.Routing`, so
capacity clipping and drop decisions are identical; outputs agree
bitwise in f32 (pinned in tests/test_moe.py — the combine sums the same
two addends, and IEEE addition is commutative).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.flags import get_flag

__all__ = ["resolve_dispatch_mode", "combine_tensor", "einsum_dispatch",
           "einsum_combine", "sort_dispatch", "sort_combine",
           "dispatch_slots"]

DISPATCH_MODES = ("sort", "einsum")


def resolve_dispatch_mode(explicit=None) -> str:
    """``FLAGS_moe_dispatch`` (kill switch) unless an explicit layer-level
    override is given."""
    mode = str(explicit or get_flag("moe_dispatch") or "sort").lower()
    if mode not in DISPATCH_MODES:
        raise ValueError(
            f"unknown MoE dispatch mode {mode!r}; expected one of "
            f"{DISPATCH_MODES} (FLAGS_moe_dispatch)")
    return mode


# ---------------------------------------------------------------------------
# einsum path (oracle)
# ---------------------------------------------------------------------------

def combine_tensor(r, num_experts: int, capacity: int):
    """The GShard combine weights [T, E, C] from routing decisions —
    the original one-hot arithmetic (g_i * keep_i * loc_i summed over
    choices), kept as the oracle the sort path is pinned against."""
    k = r.gates.shape[0]
    out = None
    for i in range(k):
        m = jax.nn.one_hot(r.idx[i].astype(jnp.int32), num_experts,
                           dtype=jnp.float32)
        keep_full = m * r.keep[i][:, None]
        loc = jax.nn.one_hot(r.pos[i].astype(jnp.int32), capacity,
                             dtype=jnp.float32)
        term = (r.gates[i][:, None, None] * keep_full[:, :, None]
                * loc[:, None, :])
        out = term if out is None else out + term
    return out


def einsum_dispatch(x, r, num_experts: int, capacity: int):
    """x [T, D] -> expert inputs [E, C, D] via the one-hot einsum."""
    dispatch = combine_tensor(r, num_experts, capacity) > 0.0
    return jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)


def einsum_combine(expert_out, r, capacity: int):
    """expert outputs [E, C, D] -> y [T, D] via the weighted einsum."""
    combine = combine_tensor(r, expert_out.shape[0], capacity)
    return jnp.einsum("tec,ecd->td", combine.astype(expert_out.dtype),
                      expert_out)


# ---------------------------------------------------------------------------
# sort path
# ---------------------------------------------------------------------------

def dispatch_slots(r, num_experts: int, capacity: int):
    """Flat per-(choice, token) destination slots, choice-major.

    Returns ``(slot [k*T] int32, gate [k*T] f32, tok [k*T] int32)``:
    ``slot = expert * C + position`` for kept pairs, ``E*C`` (the drop
    bucket) otherwise. Kept slots are unique by construction — capacity
    positions are a per-expert running count."""
    k, T = r.idx.shape
    E, C = num_experts, capacity
    idx = r.idx.reshape(k * T).astype(jnp.int32)
    pos = r.pos.reshape(k * T).astype(jnp.int32)
    keep = r.keep.reshape(k * T) > 0.0
    gate = (r.gates.reshape(k * T) * r.keep.reshape(k * T))
    slot = jnp.where(keep, idx * C + pos, E * C).astype(jnp.int32)
    tok = jnp.tile(jnp.arange(T, dtype=jnp.int32), k)
    return slot, gate.astype(jnp.float32), tok


def sort_dispatch(x, r, num_experts: int, capacity: int):
    """x [T, D] -> expert inputs [E, C, D] via argsort-by-expert +
    static-shape scatter. Dropped pairs route to the trailing drop-bucket
    row, which is sliced off."""
    E, C = num_experts, capacity
    slot, _, tok = dispatch_slots(r, E, C)
    # group writes by destination expert (dropped pairs sort last):
    # stable order preserves the router's choice-major token order
    order = jnp.argsort(slot, stable=True)
    buf = jnp.zeros((E * C + 1, x.shape[1]), x.dtype)
    buf = buf.at[slot[order]].set(x[tok[order]])
    return buf[:E * C].reshape(E, C, x.shape[1])


def sort_combine(expert_out, r, capacity: int):
    """expert outputs [E, C, D] -> y [T, D]: one gather per (choice,
    token) pair through the flat slot map, gate-weighted, summed over
    choices. Dropped pairs gather the zero drop-bucket row."""
    E, C, D = expert_out.shape
    k, T = r.idx.shape
    slot, gate, _ = dispatch_slots(r, E, C)
    flat = jnp.concatenate(
        [expert_out.reshape(E * C, D),
         jnp.zeros((1, D), expert_out.dtype)])
    picked = flat[slot] * gate[:, None].astype(expert_out.dtype)
    return picked.reshape(k, T, D).sum(0)
