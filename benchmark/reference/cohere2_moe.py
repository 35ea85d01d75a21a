"""`cohere2_moe` (Command A+'s family) forward in plain jax.numpy, float32
— the yardstick of the serving cell and of tests/test_cohere2_moe.py.

One sequence, the whole causal forward at once: no cache, no pages, no
chunks, no kernels, no grouped product, no fused shared experts. Every
matmul under `jax.default_matmul_precision("highest")`. It takes the
system's weights BY NAME (the `state_dict` names of
`paddle_tpu.models.cohere2_moe.Cohere2MoeForCausalLM`) and a dict of the
sizes no weight's shape gives (`benchmark/models/cohere2_moe.py
sizes()`), and nothing else from the program. A layer's weights are read
as float32 when the layer runs (an expert when the expert runs), and
attention runs a K/V head and a block of rows at a time, so that 10k
positions at the published widths fit beside the engine.

`x` is the residual row of width `hidden_size`; a layer:

    h      = LayerNorm(x) * w          mean and variance over the row, eps
                                       layer_norm_eps, no bias
    q,k,v  = h W_q, h W_k, h W_v       num_heads query heads, num_kv_heads
                                       K/V heads, no bias, no q/k norm
    window layer (`sliding_attention`): q, k rotated, pairs (2i, 2i+1)
           interleaved, all head_dim dims, angle t * theta^(-2i/d);
           key j visible to query i iff 0 <= i - j < sliding_window
    full layer (`full_attention`): no positional term at all; j <= i
    a      = W_o softmax(q k^T / sqrt(head_dim)) v     query head n reads
                                                       K/V head n // group
    s      = sigmoid(h W_r)            all routed experts; T = top-k of s,
                                       ties to the lower expert
    g_e    = s_e / sum_{e' in T} s_e'
    routed = sum_{e in T HELD HERE} g_e FFN_e(h)
             FFN(h) = W_down (silu(W_gate h) * (W_up h))
    shared = (1 / n_shared) sum_j FFN_sj(h)
    x'     = x + a + routed + shared   the parallel block: ONE h for both
    logits = logit_scale * LayerNorm(x_L) E^T          the embedding, tied

What the experts held elsewhere would add is left out, here as in the
program (the chip's share of an EP group).

Assumed (the published config leaves each open; the configuration file
says the same): "average" is the mean over the shared experts' outputs,
ADDED to the routed sum; `intermediate_size` is the width of one routed
and of one shared expert; the window holds `sliding_window` keys, the
query's own among them; no selection bias and no routed scaling. The
vision tower is not part of the language model and is not here.

Layout only: W_gate and W_up of an expert are one matrix `w_in`
[D, 2F] (gate first), the held experts stacked `[held, ...]`; the
shared experts lie side by side in `shared.w_in` [D, 2nF] (expert j:
columns jF.. of the gate half and of the up half) and stacked by rows
in `shared.w_out` [nF, D]. Here they are taken apart, run one by one
and averaged.

`forward(..., forced=...)`: the model's one DISCRETE choice, the chosen
experts, turns on scores that lie as close together as rounding moves
them, so a system in a lower precision cannot reproduce float32's
choice in every row, and logits of forwards that chose otherwise cannot
be compared. Given the system's choices for every row of every layer,
this forward (a) judges each against its OWN scores — as many chosen,
the share that is also its own choice, how far below its own cut the
worst of the others scores — and then (b) goes on with the system's, so
that everything after is compared on equal terms
(`benchmark/reference/glm_moe_dsa.py` says the same at more length).
The router's scores, which the family states as float32, are held apart
from the choice: `router_scores_of` computes them from the very rows a
system computed them from.

Two CONTROLS, not yardsticks, which whatever comparison calls a system
correct has to call not correct: `forward(..., dtype=jnp.bfloat16)`,
every weight, product, norm, softmax and score in that dtype (positions
and rotary angles stay float32); and `forward(..., windowed=False)`, the
window taken off all window layers (rotary stays).
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
WINDOW = "sliding_attention"
#: query rows one pass of attention scores against the whole sequence
ROW_BLOCK = 128


def _precise(dt):
    """Float32 runs every product at the highest precision; the control
    runs as its dtype does by default."""
    return jax.default_matmul_precision("highest") if dt == F32 \
        else nullcontext()


def _layer_norm(x, w, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w


def _rotary(x, theta):
    """x [T, H, d]: position = row index."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(T, dtype=F32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape)


def _ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps", "dt"))
def _norm(x, w, *, eps, dt=F32):
    return _layer_norm(x, jnp.asarray(w).astype(dt), eps)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "window", "theta",
                                   "rotate", "dt"))
def _attention(h, rows, wq, wk, wv, wo, *, n_heads, n_kv, window, theta,
               rotate, dt):
    """(attention through `W_o` [T, D], attention before `W_o` at `rows`
    [R, H*d]): a K/V head at a time — its `group` query heads are
    projected, turned, scored and sent through their rows of `W_o`
    together, so that no [T, H*d] array exists — and `ROW_BLOCK` query
    rows at a time against all T keys. `window` 0: every j <= i."""
    with _precise(dt):
        T, D = h.shape
        c = lambda a: jnp.asarray(a).astype(dt)
        k = (h @ c(wk)).reshape(T, n_kv, -1)
        v = (h @ c(wv)).reshape(T, n_kv, -1)
        if rotate:
            k = _rotary(k, theta)
        d, g = k.shape[-1], n_heads // n_kv
        # query head n reads K/V head n // g: W_q's columns and W_o's
        # rows by K/V head, read as `dt` a head at a time
        wq = jnp.moveaxis(jnp.asarray(wq).reshape(D, n_kv, g * d), 1, 0)
        wo = jnp.asarray(wo).reshape(n_kv, g * d, D)
        nb = -(-T // ROW_BLOCK)
        cols = jnp.arange(T)
        scale = 1.0 / math.sqrt(d)

        def kv_head(acc, args):
            wq_h, wo_h, k_h, v_h = args
            q_h = (h @ c(wq_h)).reshape(T, g, d)
            if rotate:
                q_h = _rotary(q_h, theta)
            q_h = jnp.pad(q_h, ((0, nb * ROW_BLOCK - T), (0, 0), (0, 0)))

            def block(args):
                q_b, i0 = args
                i = i0 + jnp.arange(ROW_BLOCK)
                ok = cols[None, :] <= i[:, None]
                if window:
                    ok &= i[:, None] - cols[None, :] < window
                s = jnp.einsum("rgd,td->grt", q_b, k_h) * scale
                p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
                return jnp.einsum("grt,td->rgd", p, v_h)

            o_h = jax.lax.map(block, (q_h.reshape(nb, ROW_BLOCK, g, d),
                                      jnp.arange(nb) * ROW_BLOCK))
            o_h = o_h.reshape(nb * ROW_BLOCK, g * d)[:T]
            return acc + o_h @ c(wo_h), o_h[rows]

        out, o_rows = jax.lax.scan(
            kv_head, jnp.zeros((T, D), dt),
            (wq, wo, jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)))
        # [n_kv, R, g * d] -> [R, n_kv * g * d]
        return out, jnp.moveaxis(o_rows, 0, 1).reshape(rows.shape[0], -1)


@partial(jax.jit, static_argnames=("top_k", "dt"))
def _route(x, w_r, *, top_k, dt=F32):
    """(scores s [T, E], chosen [T, k] best first, gates [T, k])."""
    with _precise(dt):
        s = jax.nn.sigmoid(x @ jnp.asarray(w_r).astype(dt))
    order = jnp.argsort(-s, axis=-1, stable=True)[:, :top_k]
    chosen = jnp.take_along_axis(s, order, -1)
    return s, order, chosen / jnp.sum(chosen, -1, keepdims=True)


@jax.jit
def router_scores_of(x, w_r):
    """sigmoid(x W_r) [R, E] in float32 from a system's own router
    input rows `x` [R, D], read as float32."""
    with _precise(F32):
        return jax.nn.sigmoid(x.astype(F32) @ jnp.asarray(w_r).astype(F32))


@partial(jax.jit, static_argnames=("dt",))
def _expert(x, gate_of_token, w_in, w_out, *, dt=F32):
    with _precise(dt):
        w_in = jnp.asarray(w_in).astype(dt)
        F = w_in.shape[-1] // 2
        return gate_of_token[:, None] * _ffn(
            x, w_in[:, :F], w_in[:, F:], jnp.asarray(w_out).astype(dt))


@partial(jax.jit, static_argnames=("j", "n", "dt"))
def _shared_expert(x, w_in, w_out, *, j, n, dt=F32):
    """Shared expert `j` of `n`, cut out of the side-by-side layout."""
    with _precise(dt):
        F = w_in.shape[-1] // (2 * n)
        c = lambda a: jnp.asarray(a).astype(dt)
        return _ffn(x, c(w_in[:, j * F:(j + 1) * F]),
                    c(w_in[:, (n + j) * F:(n + j + 1) * F]),
                    c(w_out[j * F:(j + 1) * F]))


@partial(jax.jit, static_argnames=("eps", "scale", "dt"))
def _head(x, nw, embed, *, eps, scale, dt=F32):
    with _precise(dt):
        return (scale * (_layer_norm(x, jnp.asarray(nw).astype(dt), eps)
                         @ jnp.asarray(embed).astype(dt).T)).astype(F32)


@jax.jit
def judge(scores, mine, theirs):
    """How a system's choice `theirs` (bool, like `mine`) stands against
    this reference's own choice `mine` of the largest `scores` a row:
    whether every row chose as many, the smallest share of a row's
    choice that is also mine, and the worst miss: how far below my cut
    (my lowest chosen score) a score of theirs lies."""
    cut = jnp.min(jnp.where(mine, scores, jnp.inf), -1, keepdims=True)
    below = jnp.where(theirs & ~mine, cut - scores, 0.0)
    n_mine, n_theirs = jnp.sum(mine, -1), jnp.sum(theirs, -1)
    return {"sizes_equal": jnp.all(n_mine == n_theirs),
            "min_overlap": jnp.min(jnp.sum(mine & theirs, -1)
                                   / jnp.maximum(n_theirs, 1)),
            "worst_miss": jnp.max(below)}


def routed_part(x, weights, prefix, sz, experts=None, chosen=None, dt=F32):
    """The routed experts' part of a layer for tokens `x` [T, D]: the
    sum over the chosen experts in `experts` (default: the
    `(first, count)` of `sz["experts_held"]`) of g_e FFN_e(x), without
    the shared experts. `chosen` [T, k]: experts to go on with in place
    of this router's own (weighed by this router's scores). Returns
    (y, scores, the router's own choice)."""
    first, count = experts if experts is not None else sz["experts_held"]
    s, own, gates = _route(x, weights[prefix + "router.weight"],
                           top_k=sz["num_experts_per_tok"], dt=dt)
    if chosen is None:
        chosen = own
    else:
        picked = jnp.take_along_axis(s, chosen, -1)
        gates = picked / jnp.sum(picked, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(count):
        gate_e = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        y = y + _expert(x, gate_e, weights[prefix + "experts.w_in"][e],
                        weights[prefix + "experts.w_out"][e], dt=dt)
    return y, s, own


def shared_part(x, weights, prefix, sz, dt=F32):
    """The mean of the shared experts' outputs for tokens `x` [T, D]."""
    n = sz["n_shared_experts"]
    y = jnp.zeros_like(x)
    for j in range(n):
        y = y + _shared_expert(x, weights[prefix + "shared.w_in"],
                               weights[prefix + "shared.w_out"], j=j, n=n,
                               dt=dt)
    return y / n


def forward(weights, ids, sz, rows=None, forced=None, dtype=F32,
            windowed=True):
    """`weights`: name -> array (any float dtype; read as `dtype`).
    `ids`: int [T]. `sz`: the family's sizes. `dtype`: float32, the
    yardstick, or a lower one, a control; `windowed=False`: the other
    control (module docstring). Returns a dict: `logits` [len(rows), V]
    float32 at positions `rows` (default: all); a list a layer of
    `routing` ([T, k] int, the chosen experts, in the form `forced`
    takes), `router_probe` (`scores` [R, E], `x` [R, D] at `rows`) and
    `attn_out` ([R, H*d], attention before `W_o` at `rows`). `forced`:
    `{"routing": [...]}`, a system's choices; the dict then also holds
    `routing_judged`, a `judge()` a layer."""
    ids = jnp.asarray(ids)
    T = ids.shape[0]
    rows = jnp.arange(T) if rows is None else jnp.asarray(rows)
    eps, dt = sz["layer_norm_eps"], dtype
    x = jnp.asarray(weights["embed"][ids]).astype(dt)
    out = {"routing": [], "router_probe": [], "attn_out": []}
    if forced is not None:
        out["routing_judged"] = []
    for li, kind in enumerate(sz["layer_types"]):
        p = f"layers.{li}."
        g = lambda n: weights[p + n]
        h = _norm(x, g("norm.weight"), eps=eps, dt=dt)
        window = kind == WINDOW
        a, o_rows = _attention(
            h, rows, g("attn.wq"), g("attn.wk"), g("attn.wv"), g("attn.wo"),
            n_heads=sz["num_heads"], n_kv=sz["num_kv_heads"],
            window=sz["sliding_window"] if window and windowed else 0,
            theta=sz["rope_theta"], rotate=window, dt=dt)
        out["attn_out"].append(o_rows)
        theirs = None if forced is None else jnp.asarray(
            forced["routing"][li])
        y, s, own = routed_part(h, weights, p + "moe.", sz, chosen=theirs,
                                dt=dt)
        out["router_probe"].append(dict(scores=s[rows], x=h[rows]))
        out["routing"].append(own if theirs is None else theirs)
        if theirs is not None:
            experts = jnp.arange(s.shape[-1])
            out["routing_judged"].append(judge(
                s.astype(F32), jnp.any(own[..., None] == experts, 1),
                jnp.any(theirs[..., None] == experts, 1)))
        x = x + a + y + shared_part(h, weights, p + "moe.", sz, dt=dt)
    out["logits"] = _head(x[rows], weights["final_norm.weight"],
                          weights["embed"], eps=eps,
                          scale=float(sz["logit_scale"]), dt=dt)
    return out
