"""`xing4_0` (Xing4.0-29B-A4B's family) forward in plain jax.numpy,
float32 — the yardstick of the serving cell and of tests/test_xing4.py.

One sequence, the whole causal forward at once: no cache, no pages, no
chunks, no absorbed products, no grouped product, no kernels. Every
matmul under `jax.default_matmul_precision("highest")`. It takes the
system's weights BY NAME (the `state_dict` names of
`paddle_tpu.models.xing4.Xing4ForCausalLM`) and a dict of the sizes no
weight's shape gives (`benchmark/models/xing4.py sizes()`), and nothing
else from the program. Computed in blocks so that at the published
widths it fits beside a 9.59 GB model: a layer's weights are read as
float32 when the layer runs, an expert when the expert runs, attention a
head at a time and `ROW_BLOCK` query rows at a time, the head
`HEAD_BLOCK` columns at a time.

The residual state of a token is X in R^{n x C}, n = `hc_mult` (4)
streams of the hidden width C (3,584); X_0 = the token's embedding in
each of the n rows. Each sublayer F (attention; the dense FFN or the
expert layer) with its own phi, alpha, b:

mHC      xt = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)       in R^{nC}
         Ht_pre  = a_pre  (xt phi_pre)  + b_pre            in R^n
         Ht_post = a_post (xt phi_post) + b_post           in R^n
         Ht_res  = a_res  mat(xt phi_res) + b_res          in R^{n x n}
         H_pre = sigmoid(Ht_pre); H_post = 2 sigmoid(Ht_post)
         M_0 = exp(clamp(Ht_res, -30, 30)); 20 times:
           M <- M / (rowsum(M) + hc_eps); M <- M / (colsum(M) + hc_eps)
         H_res = M_20
         u  = H_pre X  in R^C, through the sublayer's own RMSNorm (gain,
              rms_norm_eps) as in a pre-norm block
         X' = H_res X + H_post^T F(RMSNorm(u))
         After the last layer the rows are summed, then the final
         RMSNorm and the head.
MLA      c_q = RMSNorm(h W_qa); [q_nope (128) ; q_rope (64)] a head =
         c_q W_qb; [c_kv (512) ; k_r (64)] = h W_kva; c_kv =
         RMSNorm(c_kv); [k_nope_h (128) ; v_h (128)] = c_kv W_kvb; one
         rope(k_r) shared by the heads. a_h(t, s) = softmax over s <= t
         of (q_nope_h . k_nope_h(s) + rope(q_rope_h) . rope(k_r(s))) *
         192^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1 (1.416);
         o_h = sum a_h v_h; out = concat(o_h) W_o. NON-absorbed: K and V
         are expanded from the latent.
rotary   interleaved pairs (x[2i], x[2i+1]), angle t * f_i, f = YaRN's
         blend: theta^(-2i/d) where a pair turns more than beta_fast
         (32) times over the original 4,096 positions, 1/factor (1/64)
         of it where fewer than beta_slow (1), linear between (pairs
         10..23 of 32); cos and sin unscaled (mscale = mscale_all_dim).
experts  s = sigmoid(x W_r) (64); T = top-4 of s + b, ties to the lower
         expert; g_e = 2 s_e / sum_T s; y = FFN_shared(x) + sum over e
         in T of g_e FFN_e(x); FFN(x) = W_down(silu(W_gate x) * W_up x).
         Every expert is held (`experts_held` = (0, 64)).

Assumed, where the published config and arXiv:2512.24880 leave it open
(the configuration file lists the same under `assumed`): X_0 repeats the
embedding and the rows are SUMMED at the end (arXiv:2409.19606); xt has
no learned gain; Sinkhorn normalizes rows first and adds hc_eps to both
sums; phi, alpha and b are float32 and one `phi` holds the pre, post and
res columns side by side (layout only); rotary pairs are interleaved
(DeepSeek-V2 permutes to halves first: the same products in another
order of the dims). The multi-token-prediction layer does not enter the
main model's logits and is not here.

Layout only: W_gate and W_up are one matrix `w_in` [D, 2F] (gate
first); the experts are stacked `[64, ...]`; X is one row [n*C], stream
j at columns jC .. jC+C-1.

`forward(..., forced={"routing": [...]})`: the chosen experts turn on
scores that lie as close together as rounding moves them, so the
comparison takes the choice apart from the arithmetic as the two other
MoE references do (`benchmark/reference/glm_moe_dsa.py` says why at
length): this forward (a) JUDGES the system's chosen experts by its own
scores and (b) GOES ON with them. What the family states as float32 is
held apart too: `router_scores_of` and `mhc_mappings_of` compute the
router's scores and a sublayer's mappings from the very rows a system
computed them from, so a difference is that arithmetic alone.

Two CONTROLS, not yardsticks, which whatever comparison calls a system
correct has to call not correct: `forward(..., dtype=jnp.bfloat16)`,
every weight, product, norm, softmax, score and mapping in that dtype
(positions and rotary angles stay float32); and `forward(...,
h_res="identity")`, the residual mixing matrix taken off (H_res = I:
each stream keeps to itself). `forward(..., plain=True)` is what n = 1
with H_pre = H_post = H_res = 1 must equal: x' = x + F(RMSNorm(x)).
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows one pass of attention scores against the whole sequence
ROW_BLOCK = 128
#: columns of the head one product takes (3,584 x 16,384 float32: 235 MB)
HEAD_BLOCK = 16384


def _precise(dt):
    """Float32 runs every product at the highest precision; the control
    runs as its dtype does by default."""
    return jax.default_matmul_precision("highest") if dt == F32 \
        else nullcontext()


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_frequencies(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The `dim/2` rotary frequencies, float32 (module docstring)."""
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if factor <= 1:
        return plain
    pair = lambda turns: dim * math.log(original_max / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotary(x, freq):
    """x [T, (H,) d]: position = row index; angles in float32."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * freq
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape)


def _ffn(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def _mappings(X, phi, alpha, b, *, n, iters, eps, clamp, identity):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of X [T, n*C], in
    X's dtype."""
    xt = X * jax.lax.rsqrt(jnp.mean(X * X, -1, keepdims=True) + eps)
    proj = xt @ phi                                          # [T, 2n + n^2]
    h_pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + b[:n])
    h_post = 2 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + b[n:2 * n])
    ht = (alpha[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    m = jnp.exp(jnp.clip(ht, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)        # rows
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)        # columns
    if identity:
        m = jnp.broadcast_to(jnp.eye(n, dtype=m.dtype), m.shape)
    return h_pre, h_post, m


@partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp", "identity",
                                   "dt"))
def _enter(X, phi, alpha, b, nw, *, n, iters, eps, clamp, identity, norm_eps,
           dt):
    """(the sublayer's normed input h [T, C], H_post, H_res)."""
    with _precise(dt):
        c = lambda a: jnp.asarray(a).astype(dt)
        h_pre, h_post, h_res = _mappings(
            X, c(phi), c(alpha), c(b), n=n, iters=iters, eps=eps,
            clamp=clamp, identity=identity)
        T = X.shape[0]
        u = jnp.einsum("tj,tjc->tc", h_pre, X.reshape(T, n, -1))
        return _rms(u, c(nw), norm_eps), h_post, h_res


@partial(jax.jit, static_argnames=("n", "dt"))
def _leave(X, h_res, h_post, y, *, n, dt):
    """X' = H_res X + H_post^T y, [T, n*C]."""
    with _precise(dt):
        T = X.shape[0]
        out = jnp.einsum("tij,tjc->tic", h_res, X.reshape(T, n, -1)) \
            + h_post[:, :, None] * y[:, None, :]
        return out.reshape(T, -1)


@partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp"))
def mhc_mappings_of(x, phi, alpha, b, *, n, iters, eps, clamp):
    """(H_pre, H_post, H_res) in float32 from a system's OWN residual
    rows `x` [R, n*C], read as float32."""
    with _precise(F32):
        c = lambda a: jnp.asarray(a).astype(F32)
        return _mappings(c(x), c(phi), c(alpha), c(b), n=n, iters=iters,
                         eps=eps, clamp=clamp, identity=False)


@jax.jit
def router_scores_of(x, w_r):
    """sigmoid(x W_r) [R, E] in float32 from a system's own router
    input rows `x` [R, D], read as float32."""
    with _precise(F32):
        return jax.nn.sigmoid(x.astype(F32) @ jnp.asarray(w_r).astype(F32))


@partial(jax.jit, static_argnames=("n_heads", "dn", "dr", "scale", "eps",
                                   "dt"))
def _mla(x, freq, wq_a, qn, wq_b, wkv_a, kvn, wkv_b, wo, *, n_heads, dn, dr,
         scale, eps, dt):
    """Attention output [T, D]: a head at a time, ROW_BLOCK query rows
    at a time against all T keys."""
    with _precise(dt):
        T = x.shape[0]
        c = lambda a: jnp.asarray(a).astype(dt)
        c_q = _rms(x @ c(wq_a), c(qn), eps)
        q = (c_q @ c(wq_b)).reshape(T, n_heads, dn + dr)
        q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], freq)
        kv = x @ c(wkv_a)
        r = kv.shape[-1] - dr
        c_kv = _rms(kv[:, :r], c(kvn), eps)
        k_r = _rotary(kv[:, r:], freq)
        kvb = (c_kv @ c(wkv_b)).reshape(T, n_heads, -1)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        pad = -T % ROW_BLOCK
        t_pos = jnp.arange(T + pad).reshape(-1, ROW_BLOCK)
        s_pos = jnp.arange(T)

        def head(args):
            qn_h, qr_h, kn_h, v_h = args
            qn_b = jnp.pad(qn_h, ((0, pad), (0, 0))).reshape(
                -1, ROW_BLOCK, dn)
            qr_b = jnp.pad(qr_h, ((0, pad), (0, 0))).reshape(
                -1, ROW_BLOCK, dr)

            def block(blk):
                qn_r, qr_r, t = blk
                s = (qn_r @ kn_h.T + qr_r @ k_r.T) * scale
                s = jnp.where(s_pos[None, :] <= t[:, None], s, -jnp.inf)
                return jax.nn.softmax(s, -1) @ v_h

            return jax.lax.map(block, (qn_b, qr_b, t_pos)).reshape(
                T + pad, -1)[:T]

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


@partial(jax.jit, static_argnames=("dt",))
def _head_block(x, w, *, dt=F32):
    with _precise(dt):
        return (x @ jnp.asarray(w).astype(dt)).astype(F32)


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


def forward(weights, ids, sz, rows=None, forced=None, dtype=F32,
            h_res="sinkhorn", plain=False):
    """`weights`: name -> array (any float dtype; read as `dtype`).
    `ids`: int [T]. `sz`: the family's sizes. `dtype`: float32, the
    yardstick, or a lower one, a control; `h_res="identity"`: the other
    control; `plain`: a pre-norm residual model with no mHC at all
    (module docstring). Returns a dict: `logits` [len(rows), V] float32
    at positions `rows` (default: all); `routing`, the chosen experts
    [T, k] an expert layer, in the form `forced` takes; `h_res`
    [T, n, n] a SUBLAYER (attention and FFN of layer 0, then layer 1's,
    ...); and the float32-stated values at `rows` beside what they were
    computed from, a dict a sublayer or expert layer: `mhc_probe`
    (`h_res` [R, n, n], `x` [R, n*C]) and `router_probe` (`scores`
    [R, E], `x` [R, D]). `forced`: `{"routing": [...]}`, a system's
    chosen experts; the dict then also holds `routing_judged`, a
    `judge()` an expert layer."""
    ids = jnp.asarray(ids)
    T = ids.shape[0]
    rows = jnp.arange(T) if rows is None else jnp.asarray(rows)
    eps, dt, n = sz["rms_norm_eps"], dtype, (1 if plain else sz["hc_mult"])
    rope = sz["rope"]
    freq = yarn_frequencies(sz["qk_rope_head_dim"], sz["rope_theta"],
                            rope["factor"], rope["original_max"],
                            rope["beta_fast"], rope["beta_slow"])
    m = 1.0 if rope["factor"] <= 1 else \
        0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
    scale = (sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]) ** -0.5 * m * m
    hc = dict(n=n, iters=sz["hc_sinkhorn_iters"], eps=sz["hc_eps"],
              clamp=tuple(sz["mhc_h_res_clamp"]))
    x = jnp.asarray(weights["embed"][ids]).astype(dt)
    X = jnp.concatenate([x] * n, axis=-1)
    out = {"routing": [], "router_probe": [], "h_res": [], "mhc_probe": []}
    if forced is not None:
        out["routing_judged"] = []

    def sublayer(X, p, which, fn):
        """X' of one sublayer: `fn(h)` its output for the normed input."""
        g = lambda name: weights[p + name]
        if plain:
            return X + fn(_norm(X, g(which + "_norm.weight"), eps=eps, dt=dt))
        h, h_post, m_res = _enter(
            X, g(which + "_hc.phi"), g(which + "_hc.alpha"),
            g(which + "_hc.b"), g(which + "_norm.weight"), norm_eps=eps,
            identity=h_res == "identity", dt=dt, **hc)
        out["h_res"].append(m_res.astype(F32))
        out["mhc_probe"].append(dict(h_res=m_res[rows].astype(F32),
                                     x=X[rows]))
        return _leave(X, m_res, h_post, fn(h), n=n, dt=dt)

    for li, mlp in enumerate(sz["mlp_layer_types"]):
        p = f"layers.{li}."
        g = lambda name: weights[p + name]
        X = sublayer(X, p, "attn", lambda h: _mla(
            h, freq, wq_a=g("attn.wq_a"), qn=g("attn.q_norm.weight"),
            wq_b=g("attn.wq_b"), wkv_a=g("attn.wkv_a"),
            kvn=g("attn.kv_norm.weight"), wkv_b=g("attn.wkv_b"),
            wo=g("attn.wo"), n_heads=sz["num_heads"],
            dn=sz["qk_nope_head_dim"], dr=sz["qk_rope_head_dim"],
            scale=scale, eps=eps, dt=dt))

        def ffn(h):
            if mlp == "dense":
                return _dense_ffn(h, g("mlp.w_in"), g("mlp.w_out"), dt=dt)
            theirs = None if forced is None else jnp.asarray(
                forced["routing"][len(out["routing"])])
            y, s, own = routed_part(h, weights, p + "moe.", sz,
                                    chosen=theirs, dt=dt)
            y = y + _dense_ffn(h, g("moe.shared.w_in"),
                               g("moe.shared.w_out"), dt=dt)
            out["router_probe"].append(dict(scores=s[rows], x=h[rows]))
            out["routing"].append(own if theirs is None else theirs)
            if theirs is not None:
                experts = jnp.arange(s.shape[-1])
                out["routing_judged"].append(judge(
                    s.astype(F32) + jnp.asarray(
                        g("moe.router.bias")).astype(F32),
                    jnp.any(own[..., None] == experts, 1),
                    jnp.any(theirs[..., None] == experts, 1)))
            return y

        X = sublayer(X, p, "ffn", ffn)
    x = jnp.sum(X[rows].reshape(len(rows), n, -1), axis=1)
    x = _norm(x, weights["final_norm.weight"], eps=eps, dt=dt)
    head = weights["head"]
    out["logits"] = jnp.concatenate(
        [_head_block(x, head[:, at:at + HEAD_BLOCK], dt=dt)
         for at in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)
    return out
