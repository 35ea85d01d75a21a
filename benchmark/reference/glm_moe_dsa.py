"""`glm_moe_dsa` (GLM-5.2's family) forward in plain jax.numpy, float32
— the yardstick of the serving cell and of tests/test_glm_moe_dsa.py.

One sequence, the whole causal forward at once: no cache, no pages, no
chunks, no absorbed products, no grouped product, no radix select.
Every matmul under `jax.default_matmul_precision("highest")`. It takes
the system's weights BY NAME (the `state_dict` names of
`paddle_tpu.models.glm_moe_dsa.GlmMoeDsaForCausalLM`) and a dict of the
sizes no weight's shape gives (`benchmark/models/glm_moe_dsa.py
sizes()`), and nothing else from the program. A layer's weights are
read as float32 when the layer runs (an expert when the expert runs),
so that at the published widths it fits beside the engine.

Pre-norm residual blocks, RMSNorm eps from the config. `x` is the
hidden state after the layer's input norm.

MLA      c_q = RMSNorm(x W_qa); q_h = c_q W_qb = [q_nope (192) ; q_rope
         (64)] a head, rotary on q_rope. [c_kv (512) ; k_r (64)] =
         x W_kva; c_kv = RMSNorm(c_kv); k_r = rotary(k_r), shared by the
         heads. [k_nope_h (192) ; v_h (256)] = c_kv W_kvb.
         a_h(t, s) = softmax over s in S_t of (q_nope_h . k_nope_h(s) +
         q_rope_h . k_r(s)) / sqrt(256); o_h = sum a_h v_h; out =
         concat(o_h) W_o.
indexer  (`full` layers) qI_j = c_q W_Iq (32 heads of 128), kI =
         LayerNorm(x W_Ik) (128), rotary on the first 64 dims of both,
         w = x W_Iw (32). I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)),
         s <= t. S_t = the index_topk positions of largest I(t, .),
         ties to the lower position; every s <= t while t < index_topk.
         A `shared` layer uses S_t of the nearest `full` layer before.
experts  s = sigmoid(x W_r) (256); T = top-8 of s + b, ties to the lower
         expert; g_e = 2.5 s_e / sum_{T} s; y = FFN_shared(x) + sum over
         e in T HELD HERE of g_e FFN_e(x); FFN(x) = W_down(silu(W_gate x)
         * W_up x). What the experts held elsewhere would add is left
         out, here as in the program (the chip's share of an EP group).
rotary   interleaved pairs (x[2i], x[2i+1]), angle t * theta^(-2i/d).

Assumed, after the public DeepSeek-V3.2 sparse-attention release that
`glm_moe_dsa` follows (the published config does not say): the indexer's
key norm is a LayerNorm with bias (eps 1e-6); rotary covers the FIRST
64 of its 128 dims; `w` carries the constants 32^-0.5 * 128^-0.5 (they
scale I and do not change S_t). The release also turns qI and kI by a
Hadamard matrix before an FP8 product; the turn is orthogonal, leaves
every qI . kI as it is, and is left out. The multi-token-prediction
layer does not enter the main model's logits and is not here.

Layout only: W_gate and W_up are one matrix `w_in` [D, 2F] (gate
first); the held experts are stacked `[held, ...]`.

`forward(..., forced=...)`: the two DISCRETE choices of the model, the
selected set and the chosen experts, turn on scores that lie as close
together as rounding moves them (the cut of 2,048 of 8,192 goes through
the dense middle of the index scores), and with seeded random weights
attention is close to uniform over the set, so ONE position in a
hundred swapped at the cut moves a layer's attention output by a
seventh. A system in a lower precision than float32 therefore cannot
reproduce float32's choices, and its logits cannot be compared with
those of a forward that chose otherwise. So the comparison takes the
choices apart from the arithmetic: given the system's choices for every
row of every layer, this forward (a) judges each against its OWN scores
— sizes equal, the share that is also its own choice, and how far below
its own cut the worst of the others scores — and then (b) goes on with
the system's choice, so that everything after it is compared on equal
terms. A choice that is wrong by more than rounding fails (a); arithmetic
that is wrong fails the logits. What the family states as float32 — the
index scores and the router's — is held apart from the choices too:
`index_scores_of` and `router_scores_of` compute them from the very
inputs a system computed them from (its index query, weights and cached
keys; the router's input row), so a difference is the score arithmetic
alone: float32 accumulation reads parts in a million, bfloat16 parts in
a thousand.

`forward(..., dtype=jnp.bfloat16)` is the CONTROL, not a yardstick: the
same equations with every weight, product, norm, softmax and score in
that dtype (positions and rotary angles stay float32: bfloat16 cannot
count to 8,192). Whatever comparison calls a system correct has to call
this forward not correct (harness/glm_serve_runner.py runs it through
the same checks in every run).
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
LN_EPS = 1e-6


def _precise(dt):
    """Float32 runs every product at the highest precision; the control
    runs as its dtype does by default."""
    return jax.default_matmul_precision("highest") if dt == F32 \
        else nullcontext()


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _rotary(x, theta):
    """x [T, (H,) d]: position = row index."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape)


def _ffn(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def _weighted_relu_scores(q, w, k):
    """sum_j w[:, j] ReLU(q[:, j] . k[s]) -> [R, T], a head at a time."""
    def head(acc, qw):
        q_j, w_j = qw                                       # [R, Di], [R]
        return acc + w_j[:, None] * jax.nn.relu(q_j @ k.T), None

    scores, _ = jax.lax.scan(
        head, jnp.zeros((q.shape[0], k.shape[0]), q.dtype),
        (jnp.swapaxes(q, 0, 1), w.T))
    return scores


@partial(jax.jit, static_argnames=("n_heads", "rope", "theta", "dt"))
def _index_scores(x, c_q, wq, wk, nw, nb, ww, *, n_heads, rope, theta, dt):
    """(I [T, T], -inf above the diagonal; the query [T, Hi, Di], the
    weights [T, Hi] and the keys [T, Di] it is the product of)."""
    with _precise(dt):
        T = x.shape[0]
        c = lambda a: jnp.asarray(a).astype(dt)
        q = (c_q @ c(wq)).reshape(T, n_heads, -1)
        k = _layer_norm(x @ c(wk), c(nw), c(nb))
        q = jnp.concatenate([_rotary(q[..., :rope], theta), q[..., rope:]], -1)
        k = jnp.concatenate([_rotary(k[..., :rope], theta), k[..., rope:]], -1)
        w = (x @ c(ww)) * (n_heads ** -0.5 * q.shape[-1] ** -0.5)
        scores = _weighted_relu_scores(q, w, k).astype(F32)
        return jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores,
                         -jnp.inf), q, w, k


@jax.jit
def index_scores_of(q, w, keys):
    """I(t, .) [R, T] in float32 from a system's OWN operands: its index
    queries `q` [R, Hi, Di] and weights `w` [R, Hi] of R rows and the
    keys `keys` [T, Di] it holds, read as float32. No causal mask."""
    with _precise(F32):
        return _weighted_relu_scores(q.astype(F32), w.astype(F32),
                                     keys.astype(F32))


@jax.jit
def router_scores_of(x, w_r):
    """sigmoid(x W_r) [R, E] in float32 from a system's own router
    input rows `x` [R, D], read as float32."""
    with _precise(F32):
        return jax.nn.sigmoid(x.astype(F32) @ jnp.asarray(w_r).astype(F32))


@partial(jax.jit, static_argnames=("k",))
def _members(scores, *, k):
    """[T, T] bool: row t's k largest, ties to the lower position (a
    stable sort of the negated scores), never a masked one."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return (rank < k) & (scores > -jnp.inf)


@partial(jax.jit, static_argnames=("eps", "dt"))
def _c_q(x, wq_a, qn, *, eps, dt):
    with _precise(dt):
        return _rms(x @ jnp.asarray(wq_a).astype(dt),
                    jnp.asarray(qn).astype(dt), eps)


@partial(jax.jit,
         static_argnames=("n_heads", "dn", "dr", "theta", "eps", "dt"))
def _mla(x, member, wq_a, qn, wq_b, wkv_a, kvn, wkv_b, wo, *, n_heads, dn,
         dr, theta, eps, dt):
    """Attention output [T, D]."""
    with _precise(dt):
        T = x.shape[0]
        c = lambda a: jnp.asarray(a).astype(dt)
        c_q = _rms(x @ c(wq_a), c(qn), eps)
        q = (c_q @ c(wq_b)).reshape(T, n_heads, dn + dr)
        q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], theta)
        kv = x @ c(wkv_a)
        r = kv.shape[-1] - dr
        c_kv = _rms(kv[:, :r], c(kvn), eps)
        k_r = _rotary(kv[:, r:], theta)
        kvb = (c_kv @ c(wkv_b)).reshape(T, n_heads, -1)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        scale = 1.0 / math.sqrt(dn + dr)

        def head(args):
            qn_h, qr_h, kn_h, v_h = args
            s = (qn_h @ kn_h.T + qr_h @ k_r.T) * scale
            return jax.nn.softmax(jnp.where(member, s, -jnp.inf), -1) @ v_h

        o = jax.lax.map(head, tuple(jnp.swapaxes(t, 0, 1)
                                    for t in (q_nope, q_rope, k_nope, v)))
        return jnp.swapaxes(o, 0, 1).reshape(T, -1) @ c(wo)


@partial(jax.jit, static_argnames=("dt",))
def _dense_ffn(x, w_in, w_out, *, dt=F32):
    with _precise(dt):
        return _ffn(x, jnp.asarray(w_in).astype(dt),
                    jnp.asarray(w_out).astype(dt))


@partial(jax.jit, static_argnames=("top_k", "scale", "dt"))
def _route(x, w_r, bias, *, top_k, scale, dt=F32):
    """(scores s [T, E], chosen [T, k] best first, gates [T, k])."""
    with _precise(dt):
        s = jax.nn.sigmoid(x @ jnp.asarray(w_r).astype(dt))
    order = jnp.argsort(-(s + jnp.asarray(bias).astype(dt)), axis=-1,
                        stable=True)[:, :top_k]
    chosen = jnp.take_along_axis(s, order, -1)
    return s, order, scale * chosen / jnp.sum(chosen, -1, keepdims=True)


@partial(jax.jit, static_argnames=("dt",))
def _expert(x, gate_of_token, w_in, w_out, *, dt=F32):
    with _precise(dt):
        return gate_of_token[:, None] * _ffn(
            x, jnp.asarray(w_in).astype(dt), jnp.asarray(w_out).astype(dt))


@partial(jax.jit, static_argnames=("eps", "dt"))
def _norm(x, w, *, eps, dt=F32):
    return _rms(x, jnp.asarray(w).astype(dt), eps)


@partial(jax.jit, static_argnames=("eps", "dt"))
def _head(x, nw, w, *, eps, dt=F32):
    with _precise(dt):
        return (_rms(x, jnp.asarray(nw).astype(dt), eps)
                @ jnp.asarray(w).astype(dt)).astype(F32)


@jax.jit
def judge(scores, mine, theirs):
    """How a system's choice `theirs` (bool, like `mine`) stands against
    this reference's own choice `mine` of the largest `scores` a row:
    whether every row chose as many, the smallest share of a row's
    choice that is also mine, and the worst miss: how far below my cut
    (my lowest chosen score) a score of theirs lies, (a) in units of my
    chosen scores' range a row and (b) in the scores' own units."""
    chosen = jnp.where(mine, scores, jnp.inf)
    cut = jnp.min(chosen, -1, keepdims=True)
    top = jnp.max(jnp.where(mine, scores, -jnp.inf), -1, keepdims=True)
    below = jnp.where(theirs & ~mine, cut - scores, 0.0)
    n_mine, n_theirs = jnp.sum(mine, -1), jnp.sum(theirs, -1)
    return {"sizes_equal": jnp.all(n_mine == n_theirs),
            "min_overlap": jnp.min(jnp.sum(mine & theirs, -1)
                                   / jnp.maximum(n_theirs, 1)),
            "worst_miss_of_range": jnp.max(
                below / jnp.maximum(top - cut, 1e-30)),
            "worst_miss": jnp.max(below)}


def routed_part(x, weights, prefix, sz, experts=None, chosen=None, dt=F32):
    """The routed experts' part of an expert layer for tokens `x`
    [T, D]: the sum over the chosen experts in `experts` (default: the
    `(first, count)` of `sz["experts_held"]`) of g_e FFN_e(x), without
    the shared expert. `chosen` [T, k]: experts to go on with in place
    of this router's own (weighed by this router's scores). Returns
    (y, scores, the router's own choice)."""
    first, count = experts if experts is not None else sz["experts_held"]
    s, own, gates = _route(
        x, weights[prefix + "router.weight"], weights[prefix + "router.bias"],
        top_k=sz["num_experts_per_tok"], scale=sz["routed_scaling_factor"],
        dt=dt)
    if chosen is None:
        chosen = own
    else:
        picked = jnp.take_along_axis(s, chosen, -1)
        gates = sz["routed_scaling_factor"] * picked \
            / jnp.sum(picked, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(count):
        gate_e = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        y = y + _expert(x, gate_e, weights[prefix + "experts.w_in"][e],
                        weights[prefix + "experts.w_out"][e], dt=dt)
    return y, s, own


def forward(weights, ids, sz, rows=None, forced=None, dtype=F32):
    """`weights`: name -> array (any float dtype; read as `dtype`).
    `ids`: int [T]. `sz`: the family's sizes. `dtype`: float32, the
    yardstick, or a lower one, the control (module docstring). Returns
    a dict: `logits` [len(rows), V] float32 at positions `rows`
    (default: all); of the LAST `full` layer `index_scores`
    [len(rows), T] and `members` (bool), of the LAST expert layer
    `router_scores` [len(rows), E] and `router_topk` [len(rows), k];
    the choices of EVERY layer in the form `forced` takes, `selection`
    (a [T, T] bool a `full` layer) and `routing` (a [T, k] int an
    expert layer); and the scores at `rows` beside their operands, a
    dict a layer: `index_probe` (`scores` [R, T], `q` [R, Hi, Di], `w`
    [R, Hi], `keys` [T, Di]) and `router_probe` (`scores` [R, E], `x`
    [R, D]). `forced` (module docstring): `{"selection": [...],
    "routing": [...]}`, a system's choices; the dict then also holds
    `selection_judged` and `routing_judged`, a `judge()` a layer."""
    ids = jnp.asarray(ids)
    T = ids.shape[0]
    rows = jnp.arange(T) if rows is None else jnp.asarray(rows)
    eps, theta, dt = sz["rms_norm_eps"], sz["rope_theta"], dtype
    x = jnp.asarray(weights["embed"][ids]).astype(dt)
    out = {"selection": [], "routing": [], "index_probe": [],
           "router_probe": []}
    if forced is not None:
        out["selection_judged"], out["routing_judged"] = [], []
    member = None
    for li, (mlp, ind) in enumerate(zip(sz["mlp_layer_types"],
                                        sz["indexer_types"])):
        p = f"layers.{li}."
        g = lambda n: weights[p + n]
        h = _norm(x, g("attn_norm.weight"), eps=eps, dt=dt)
        if ind == "full":
            c_q = _c_q(h, g("attn.wq_a"), g("attn.q_norm.weight"), eps=eps,
                       dt=dt)
            scores, q_i, w_i, k_i = _index_scores(
                h, c_q, g("indexer.wq"), g("indexer.wk"),
                g("indexer.k_norm.weight"), g("indexer.k_norm.bias"),
                g("indexer.w"), n_heads=sz["index_n_heads"],
                rope=sz["qk_rope_head_dim"], theta=theta, dt=dt)
            own = _members(scores, k=sz["index_topk"])
            out["index_scores"], out["members"] = scores[rows], own[rows]
            out["index_probe"].append(dict(scores=scores[rows], q=q_i[rows],
                                           w=w_i[rows], keys=k_i))
            member = own
            if forced is not None:
                member = jnp.asarray(
                    forced["selection"][len(out["selection"])])
                out["selection_judged"].append(judge(scores, own, member))
            out["selection"].append(member)
        x = x + _mla(
            h, member, wq_a=g("attn.wq_a"), qn=g("attn.q_norm.weight"),
            wq_b=g("attn.wq_b"), wkv_a=g("attn.wkv_a"),
            kvn=g("attn.kv_norm.weight"), wkv_b=g("attn.wkv_b"),
            wo=g("attn.wo"), n_heads=sz["num_heads"],
            dn=sz["qk_nope_head_dim"], dr=sz["qk_rope_head_dim"],
            theta=theta, eps=eps, dt=dt)
        h = _norm(x, g("ffn_norm.weight"), eps=eps, dt=dt)
        if mlp == "dense":
            y = _dense_ffn(h, g("mlp.w_in"), g("mlp.w_out"), dt=dt)
        else:
            theirs = None if forced is None else jnp.asarray(
                forced["routing"][len(out["routing"])])
            y, s, own = routed_part(h, weights, p + "moe.", sz,
                                    chosen=theirs, dt=dt)
            y = y + _dense_ffn(h, g("moe.shared.w_in"),
                               g("moe.shared.w_out"), dt=dt)
            out["router_scores"], out["router_topk"] = s[rows], own[rows]
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
    out["logits"] = _head(x[rows], weights["final_norm.weight"],
                          weights["head"], eps=eps, dt=dt)
    return out
