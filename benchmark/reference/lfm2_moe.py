"""`lfm2_moe` (LFM2-24B-A2B's family) forward in plain jax.numpy,
float32 — the yardstick of the serving cell and of tests/test_lfm2_moe.py.

One sequence, the whole causal forward at once: no cache, no pages, no
state, no chunks, no grouped product, no kernels. Every matmul under
`jax.default_matmul_precision("highest")`. It takes the system's weights
BY NAME (the `state_dict` names of
`paddle_tpu.models.lfm2_moe.Lfm2MoeForCausalLM`) and a dict of the sizes
no weight's shape gives (`benchmark/models/lfm2_moe.py sizes()`), and
nothing else from the program. Computed in blocks so that at the
published widths it fits beside a 10.36 GB model: a layer's weights are
read as float32 when the layer runs, an expert when the expert runs,
attention a head at a time and `ROW_BLOCK` query rows at a time, the
head `HEAD_BLOCK` columns at a time.

x is a token's residual row (hidden D = 2,048); a layer is
h = x + op(RMSNorm_op(x)), x' = h + ffn(RMSNorm_ffn(h)); after the last
layer RMSNorm_final and the head E^T (the embedding tied).
RMSNorm(v) = v / sqrt(mean(v^2) + 1e-5) * w.

conv       [B | C | X] = x W_in (W_in [D, 3D], three of D in that order);
           u_t = B_t * X_t; z_t = sum_{j=0..2} k_j * u_{t-2+j}, u = 0
           before position 0 (k [3, D], depthwise); op = (C_t * z_t)
           W_out. No bias (`conv_bias` false). The whole sequence in one
           pass, no state: the published `Lfm2ShortConv.slow_forward`
           with no cache (Conv1d of padding L - 1, the first T outputs).
attention  q = x W_q (32 heads), k = x W_k, v = x W_v (8 heads), head
           dim 64; RMSNorm over each q and each k head's 64 dims (its own
           weights of 64), then the half-split rotary embedding
           (`rotate_half`: dims i and i + 32 turn as a pair by
           t theta^(-2i/64), theta 1e6); query head n reads K/V head
           n // 4; causal softmax(q k^T / 8) v; concat; W_o. No bias.
dense FFN  W_2(silu(x W_1) * x W_3), width 11,776.
experts    s = sigmoid(x W_r) (64); T = top-4 of s + b (`expert_bias`,
           for selection only), ties to the lower expert;
           g_e = 1.0 * s_e / (sum_T s + 1e-6); y = sum_{e in T} g_e E_e(x),
           E SwiGLU of width 1,536; no shared expert. Every expert is
           held (`experts_held` = (0, 64)).

Departures from the published description, all of them noted in the
configuration file under `assumed`: the transformers release here has
the dense LFM2 family and no `lfm2_moe` module, so the expert layer's
routing is read from the config's keys (sigmoid scores, the bias used to
select and not to weigh, the renormalization's + 1e-6); the head is
tied (the family's `tie_embedding`); the dense width is 11,776 as the
config states, with no `block_auto_adjust_ff_dim`.

Layout only: W_1 and W_3 are one matrix `w_in` [D, 2F] (W_1 first); the
experts are stacked `[64, ...]`; the conv kernel is [3, D] (row j meets
the input 2 - j positions back).

`forward(..., forced={"routing": [...]})`: the chosen experts turn on
scores that lie as close together as rounding moves them, so the
comparison takes the choice apart from the arithmetic as the other MoE
references do (`benchmark/reference/glm_moe_dsa.py` says why at length):
this forward (a) JUDGES the system's chosen experts by its own scores
and (b) GOES ON with them. `router_scores_of` computes the router's
scores from the very rows a system computed them from, so a difference
is that arithmetic alone.

Two CONTROLS, not yardsticks, which whatever comparison calls a system
correct has to call not correct: `forward(..., dtype=jnp.bfloat16)`,
every weight, product, norm, softmax and score in that dtype (positions
and rotary angles stay float32); and `forward(..., conv_from=[...])`,
every short convolution started from zero at each of the positions
given (a system's chunk and decode-step starts): a system that carried
no state from one program to the next.
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows one pass of attention scores against the whole sequence
ROW_BLOCK = 128
#: columns of the head one product takes (2,048 x 16,384 float32: 134 MB)
HEAD_BLOCK = 16384


def _precise(dt):
    """Float32 runs every product at the highest precision; the control
    runs as its dtype does by default."""
    return jax.default_matmul_precision("highest") if dt == F32 \
        else nullcontext()


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate_half(x, theta):
    """x [T, H, d]: dims i and i + d/2 turn by t theta^(-2i/d), t the
    row index; angles in float32."""
    T, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _ffn(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


@partial(jax.jit, static_argnames=("dt",))
def _conv(x, w_in, kernel, w_out, start, *, dt):
    """The conv operator [T, D] of normed rows `x` [T, D]; `start` [T]:
    the first position a row's convolution may reach back to (0: the
    whole history)."""
    with _precise(dt):
        c = lambda a: jnp.asarray(a).astype(dt)
        b, g, xx = jnp.split(x @ c(w_in), 3, axis=-1)
        u = b * xx
        k = c(kernel)
        L, T = k.shape[0], x.shape[0]
        t = jnp.arange(T)
        z = jnp.zeros_like(u)
        for j in range(L):
            back = L - 1 - j
            shifted = jnp.pad(u, ((back, 0), (0, 0)))[:T]
            z = z + k[j] * jnp.where((t - back >= start)[:, None], shifted,
                                     0.0)
        return (g * z) @ c(w_out)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta", "eps", "dt"))
def _attention(x, wq, wk, wv, qn, kn, wo, *, n_heads, n_kv, theta, eps, dt):
    """Attention output [T, D]: a head at a time, ROW_BLOCK query rows
    at a time against all T keys."""
    with _precise(dt):
        T = x.shape[0]
        c = lambda a: jnp.asarray(a).astype(dt)
        q = (x @ c(wq)).reshape(T, n_heads, -1)
        dh = q.shape[-1]
        k = (x @ c(wk)).reshape(T, n_kv, dh)
        v = (x @ c(wv)).reshape(T, n_kv, dh)
        q = rotate_half(_rms(q, c(qn), eps), theta)
        k = rotate_half(_rms(k, c(kn), eps), theta)
        group = n_heads // n_kv
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
        pad = -T % ROW_BLOCK
        t_pos = jnp.arange(T + pad).reshape(-1, ROW_BLOCK)
        s_pos = jnp.arange(T)

        def head(args):
            q_h, k_h, v_h = args
            q_b = jnp.pad(q_h, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, dh)

            def block(blk):
                q_r, t = blk
                s = (q_r @ k_h.T) * dh ** -0.5
                s = jnp.where(s_pos[None, :] <= t[:, None], s, -jnp.inf)
                return jax.nn.softmax(s, -1) @ v_h

            return jax.lax.map(block, (q_b, t_pos)).reshape(T + pad, -1)[:T]

        o = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
        return jnp.swapaxes(o, 0, 1).reshape(T, -1) @ c(wo)


@partial(jax.jit, static_argnames=("dt",))
def _dense_ffn(x, w_in, w_out, *, dt=F32):
    with _precise(dt):
        return _ffn(x, jnp.asarray(w_in).astype(dt),
                    jnp.asarray(w_out).astype(dt))


def _gates(chosen, scale, eps):
    return scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + eps)


@partial(jax.jit, static_argnames=("top_k", "scale", "eps", "dt"))
def _route(x, w_r, bias, *, top_k, scale, eps, dt=F32):
    """(scores s [T, E], chosen [T, k] best first, gates [T, k])."""
    with _precise(dt):
        s = jax.nn.sigmoid(x @ jnp.asarray(w_r).astype(dt))
    order = jnp.argsort(-(s + jnp.asarray(bias).astype(dt)), axis=-1,
                        stable=True)[:, :top_k]
    return s, order, _gates(jnp.take_along_axis(s, order, -1), scale, eps)


@partial(jax.jit, static_argnames=("dt",))
def _expert(x, gate_of_token, w_in, w_out, *, dt=F32):
    with _precise(dt):
        return gate_of_token[:, None] * _ffn(
            x, jnp.asarray(w_in).astype(dt), jnp.asarray(w_out).astype(dt))


@partial(jax.jit, static_argnames=("eps", "dt"))
def _norm(x, w, *, eps, dt=F32):
    return _rms(x, jnp.asarray(w).astype(dt), eps)


@partial(jax.jit, static_argnames=("dt",))
def _head_block(x, w, *, dt=F32):
    with _precise(dt):
        return (x @ jnp.asarray(w).astype(dt).T).astype(F32)


@jax.jit
def router_scores_of(x, w_r):
    """sigmoid(x W_r) [R, E] in float32 from a system's own router
    input rows `x` [R, D], read as float32."""
    with _precise(F32):
        return jax.nn.sigmoid(x.astype(F32) @ jnp.asarray(w_r).astype(F32))


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
    """The routed experts' part of an expert layer for tokens `x`
    [T, D]: the sum over the chosen experts in `experts` (default: the
    `(first, count)` of `sz["experts_held"]`) of g_e E_e(x). `chosen`
    [T, k]: experts to go on with in place of this router's own (weighed
    by this router's scores). Returns (y, scores, the router's own
    choice)."""
    first, count = experts if experts is not None else sz["experts_held"]
    scale, eps = sz["routed_scaling_factor"], sz["router_eps"]
    s, own, gates = _route(
        x, weights[prefix + "router.weight"], weights[prefix + "router.bias"],
        top_k=sz["num_experts_per_tok"], scale=scale, eps=eps, dt=dt)
    if chosen is None:
        chosen = own
    else:
        gates = _gates(jnp.take_along_axis(s, chosen, -1), scale, eps)
    y = jnp.zeros_like(x)
    for e in range(count):
        gate_e = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        y = y + _expert(x, gate_e, weights[prefix + "experts.w_in"][e],
                        weights[prefix + "experts.w_out"][e], dt=dt)
    return y, s, own


def forward(weights, ids, sz, rows=None, forced=None, dtype=F32,
            conv_from=None):
    """`weights`: name -> array (any float dtype; read as `dtype`).
    `ids`: int [T]. `sz`: the family's sizes. `dtype`: float32, the
    yardstick, or a lower one, a control; `conv_from`: positions at
    which every short convolution starts again from zero, the other
    control (module docstring). Returns a dict: `logits`
    [len(rows), V] float32 at positions `rows` (default: all);
    `routing`, the chosen experts [T, k] an expert layer, in the form
    `forced` takes; `router_probe`, the scores at `rows` beside the rows
    they were computed from, a dict an expert layer (`scores` [R, E],
    `x` [R, D]). `forced`: `{"routing": [...]}`, a system's chosen
    experts; the dict then also holds `routing_judged`, a `judge()` an
    expert layer."""
    ids = jnp.asarray(ids)
    T = ids.shape[0]
    rows = jnp.arange(T) if rows is None else jnp.asarray(rows)
    eps, dt = sz["rms_norm_eps"], dtype
    starts = jnp.zeros((T,), jnp.int32)
    for at in (conv_from or ()):
        starts = jnp.where(jnp.arange(T) >= at, at, starts)
    x = jnp.asarray(weights["embed"][ids]).astype(dt)
    out = {"routing": [], "router_probe": []}
    if forced is not None:
        out["routing_judged"] = []
    for li, (op, mlp) in enumerate(zip(sz["layer_types"],
                                       sz["mlp_layer_types"])):
        g = lambda name: weights[f"layers.{li}." + name]
        h = _norm(x, g("op_norm.weight"), eps=eps, dt=dt)
        if op == "conv":
            x = x + _conv(h, g("conv.w_in"), g("conv.kernel"),
                          g("conv.w_out"), starts, dt=dt)
        else:
            x = x + _attention(
                h, g("attn.wq"), g("attn.wk"), g("attn.wv"),
                g("attn.q_norm.weight"), g("attn.k_norm.weight"),
                g("attn.wo"), n_heads=sz["num_heads"],
                n_kv=sz["num_kv_heads"], theta=sz["rope_theta"], eps=eps,
                dt=dt)
        h = _norm(x, g("ffn_norm.weight"), eps=eps, dt=dt)
        if mlp == "dense":
            x = x + _dense_ffn(h, g("mlp.w_in"), g("mlp.w_out"), dt=dt)
            continue
        theirs = None if forced is None else jnp.asarray(
            forced["routing"][len(out["routing"])])
        y, s, own = routed_part(h, weights, f"layers.{li}.moe.", sz,
                                chosen=theirs, dt=dt)
        out["router_probe"].append(dict(scores=s[rows], x=h[rows]))
        out["routing"].append(own if theirs is None else theirs)
        if theirs is not None:
            experts = jnp.arange(s.shape[-1])
            out["routing_judged"].append(judge(
                s.astype(F32) + jnp.asarray(
                    g("moe.router.bias")).astype(F32),
                jnp.any(own[..., None] == experts, 1),
                jnp.any(theirs[..., None] == experts, 1)))
        x = x + y
    x = _norm(x[rows], weights["final_norm.weight"], eps=eps, dt=dt)
    embed = weights["embed"]
    out["logits"] = jnp.concatenate(
        [_head_block(x, embed[at:at + HEAD_BLOCK], dt=dt)
         for at in range(0, embed.shape[0], HEAD_BLOCK)], axis=-1)
    return out
