"""Mixture-of-Experts subsystem (reference: incubate MoE heritage —
global_scatter/global_gather collective ops; SURVEY §2.3).

Promoted from a single-file GShard layer into an expert-parallel
subsystem (ISSUE 10):

- ``routing``  — capacity-disciplined top-k router (f32), aux + z losses,
  routing-health stats;
- ``dispatch`` — the einsum oracle and the sort-based fast path, selected
  by ``FLAGS_moe_dispatch``;
- ``layer``    — :class:`ExpertFFN` / :class:`MoELayer`, the explicit
  shard_map + all_to_all expert-parallel program
  (``FLAGS_moe_expert_parallel``, double-buffered via
  ``FLAGS_moe_a2a_chunks``), router telemetry, and the reference-parity
  ``global_scatter``/``global_gather`` primitives.

- ``held``     — the dropless sigmoid top-k layer of one chip of an
  expert-parallel group (serving): told which experts it holds, routes
  over all, one grouped product over its own.

See docs/MOE.md for the routing math, dispatch modes, ep-axis layout and
overlap knobs.
"""

from .dispatch import (DISPATCH_MODES, combine_tensor, dispatch_slots,
                       einsum_combine, einsum_dispatch,
                       resolve_dispatch_mode, sort_combine, sort_dispatch)
from .held import (SigmoidRouting, gated_ffn, held_experts_ffn,
                   sigmoid_topk_routing)
from .layer import (EP_AXIS, MOE_STATS, ExpertFFN, MoELayer,
                    expert_ffn_apply, global_gather, global_scatter,
                    moe_ep_group, note_moe_fallback, publish_router_stats,
                    reset_moe_stats, resolve_a2a_chunks)
from .routing import (Routing, STATS_FIELDS, moe_capacity, stats_fields,
                      top2_gating, topk_routing)

__all__ = [
    "EP_AXIS", "MOE_STATS", "ExpertFFN", "MoELayer", "Routing",
    "STATS_FIELDS", "DISPATCH_MODES", "combine_tensor", "dispatch_slots",
    "einsum_combine", "einsum_dispatch", "expert_ffn_apply",
    "global_gather", "global_scatter", "moe_capacity", "moe_ep_group",
    "note_moe_fallback", "publish_router_stats", "reset_moe_stats",
    "resolve_a2a_chunks", "resolve_dispatch_mode", "sort_combine",
    "sort_dispatch", "stats_fields", "top2_gating", "topk_routing",
]
