"""The expert layer of one chip of an expert-parallel group: it is TOLD
which experts it holds, routes over all of them, and computes its own
experts' part of the result for the tokens routed to them.

    s   = sigmoid(x W_r)                       float32, all E experts
    T   = top-k of s + b                       b: the selection bias
                                               (``noaux_tc``); weighs by s
    g_e = scale * s_e / (sum_{e' in T} s_e' + eps)   (``norm_topk_prob``;
                                               eps 0 unless a family
                                               states one)
    y   = sum_{e in T, first <= e < first + held} g_e FFN_e(x)
    FFN(x) = W_out (silu(x W_gate) * (x W_up))

Dropless: every (token, choice) pair whose expert lives here is
computed, whatever the load; pairs routed to experts held elsewhere are
skipped, and what those experts would add is NOT in ``y`` — on the
chips of a group the shares sum to the uncut layer's routed part
(tests/test_glm_moe_dsa.py). On one chip the layer runs without its
exchange; nothing stands in for the absent chips.

Data movement: the T*k pairs are sorted by held expert (pairs routed
elsewhere last), the token rows gathered in that order, and ONE grouped
product per weight runs over the experts held (``jax.lax.ragged_dot``:
on a TPU, XLA's own grouped-matmul kernel, which visits only the row
tiles the groups cover). The capacity-factor router and its dispatch
(``routing.py``, ``dispatch.py``) stay what the trainers use.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["SigmoidRouting", "sigmoid_topk_routing", "held_experts_ffn",
           "gated_ffn"]


class SigmoidRouting(NamedTuple):
    """``idx`` ``[T, k]`` int32 expert ids over ALL experts, best first;
    ``gates`` ``[T, k]`` float32; ``scores`` ``[T, E]`` float32 ``s``."""

    idx: object
    gates: object
    scores: object


def sigmoid_topk_routing(x, w_router, bias, top_k: int,
                         scale: float = 1.0,
                         eps: float = 0.0) -> SigmoidRouting:
    """Sigmoid scores in float32 whatever ``x`` is; selection by
    ``s + bias`` (ties: the lower expert id), weights from ``s`` alone,
    renormalized over the chosen (``eps`` added to their sum where a
    family states one; 0 adds nothing to the program) and scaled."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    # in the order the eps-free form traces to: the product, the sum,
    # the quotient
    scaled = scale * chosen
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    gates = scaled / (total + eps if eps else total)
    return SigmoidRouting(idx.astype(jnp.int32), gates, s)


def gated_ffn(x, w_in, w_out):
    """``W_out (silu(x W_gate) * (x W_up))``, ``w_in`` ``[D, 2F]`` holding
    gate then up; products accumulate in float32."""
    h = jnp.dot(x, w_in, preferred_element_type=jnp.float32)
    gate, up = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.dot(h, w_out, preferred_element_type=jnp.float32)


def held_experts_ffn(x, routing: SigmoidRouting, w_in, w_out, first: int):
    """This chip's part of the routed result.

    ``x`` ``[T, D]``; ``w_in`` ``[held, D, 2F]`` (gate then up),
    ``w_out`` ``[held, F, D]``: experts ``first .. first + held - 1``.
    Returns ``(y [T, D] float32, tokens [held] int32, here [T, k] bool)``:
    the pairs each held expert was given, and which pairs were computed
    here (the rest were skipped)."""
    T, D = x.shape
    held = w_in.shape[0]
    k = routing.idx.shape[1]
    local = routing.idx - first                              # [T, k]
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(T * k)        # elsewhere: last
    order = jnp.argsort(key, stable=True)
    tokens = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)                        # [held]
    rows = x[order // k]                                     # [T*k, D]
    h = jax.lax.ragged_dot(rows, w_in, tokens,
                           preferred_element_type=jnp.float32)
    gate, up = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jax.lax.ragged_dot(h, w_out, tokens,
                             preferred_element_type=jnp.float32)
    # back to (token, choice) order; rows past the groups hold nothing
    back = jnp.argsort(order)
    out = out.astype(x.dtype)[back].reshape(T, k, D)
    out = jnp.where(here[..., None], out.astype(jnp.float32), 0.0)
    y = jnp.sum(out * routing.gates[..., None], axis=1)
    return y, tokens, here
