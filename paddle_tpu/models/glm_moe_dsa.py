"""Decoder family ``glm_moe_dsa``: multi-head latent attention (MLA)
over a learned sparse selection (DSA), dense and expert FFN layers in a
per-layer pattern — the serving form, over paged state.

Pre-norm residual blocks; ``x`` below is a position's hidden state after
the layer's input RMSNorm. The equations (the plain reference,
``benchmark/reference/glm_moe_dsa.py``, follows the same ones and notes
what the published config leaves open):

MLA        c_q = RMSNorm(x W_qa); q_h = c_q W_qb -> heads of [q_nope ;
           q_rope], interleaved rotary on q_rope. [c_kv ; k_r] = x W_kva,
           c_kv = RMSNorm(c_kv), k_r = rotary(k_r), one for all heads.
           [k_nope_h ; v_h] = c_kv W_kvb. Over the selected set S_t:
           softmax((q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(d_qk)),
           weighted sum of v_h, concat over heads, W_o. The cache holds
           the LATENT [c_kv ; k_r] of a position, no head axis.
indexer    (a ``full`` layer) qI_j = c_q W_Iq, kI = LayerNorm(x W_Ik),
           rotary on the leading rope dims of both, w = x W_Iw;
           I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)) in float32 for
           s <= t; S_t = the ``index_topk`` positions of largest I(t, .)
           (ties: the lower position), all of s <= t while t <
           index_topk. The cache holds kI, in ``full`` layers only. A
           ``shared`` layer has no indexer and no index page: it uses
           S_t of the nearest ``full`` layer before it.
experts    ``incubate.moe.held``: sigmoid scores over all experts in
           float32, top-k by score + bias, renormalized and scaled; this
           chip's held experts' part plus the shared expert.

Two page kinds ride ONE block table (``cfg.page_kinds()``): ``latent``
in every layer, ``index`` in ``full`` layers. How the selection enters
attention, by program:

decode     (S == 1) the indexer scores every cached index key of the
           slot block by block, ``lax.top_k`` gives the exact set, and
           attention gathers ONLY those latent rows through the table
           and runs in absorbed form (W_kvb folded into the query and
           the output);
prefill    (S > 1, plain or context) the set is a membership mask
           ``[B, S, L]`` from an exact radix select of the score's
           k-th largest; attention is a blocked pass over the context
           with an online softmax, K and V expanded from the latent a
           block at a time — no gather of selected rows a query, no
           expanded K/V of the context, no scores against the whole
           context ever exist.

The five-odd layers of a chip's share are a Python loop (the stack is
not homogeneous, and nothing is stacked per step). bf16 weights and
cache with float32 accumulation; norms, softmax, router and index
scores in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..incubate.moe.held import (gated_ffn, held_experts_ffn,
                                 sigmoid_topk_routing)
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer, LayerList

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaForCausalLM", "glm_moe_dsa_tiny"]

F32 = jnp.float32
_NEG = -jnp.inf


@dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    num_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    #: (first expert, how many) this chip holds of ``n_routed_experts``
    experts_held: Tuple[int, int] = (0, 256)
    #: one entry a layer held here: "dense" | "sparse", "full" | "shared"
    mlp_layer_types: Tuple[str, ...] = ("dense",)
    indexer_types: Tuple[str, ...] = ("full",)
    rope_theta: float = 8e6
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    max_position_embeddings: int = 1048576
    dtype: str = "float32"
    #: positions of the context one pass of the indexer and of the
    #: blocked attention takes
    context_block: int = 512

    def __post_init__(self):
        if len(self.mlp_layer_types) != len(self.indexer_types):
            raise ValueError("one mlp and one indexer type a layer")
        if self.indexer_types[0] != "full":
            raise ValueError("the first layer held has to be a `full` "
                             "indexer layer: a `shared` one reads the "
                             "selection of a `full` layer before it")

    @property
    def num_layers(self) -> int:
        return len(self.mlp_layer_types)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def page_kinds(self):
        """``latent`` ([c_kv ; k_r]) in every layer, ``index`` (kI) in
        the ``full`` layers."""
        from ..serving.kv_cache import PageKind
        full = tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "full")
        return (latent_page_kind(self),
                PageKind("index", self.index_head_dim, full))


def latent_page_kind(cfg):
    """The page kind of an MLA family: ``[c_kv ; rope(k_r)]`` of a
    position, no head axis, in every layer, for as long as the slot."""
    from ..serving.kv_cache import PageKind
    return PageKind("latent", cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                    tuple(range(cfg.num_layers)))


def glm_moe_dsa_tiny(**kw) -> GlmMoeDsaConfig:
    """Test-size config: the published pattern of one dense ``full``
    layer then ``shared, shared, shared, full`` expert layers."""
    d = dict(vocab_size=256, hidden_size=64, num_heads=4, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
             v_head_dim=16, index_n_heads=2, index_head_dim=8,
             index_topk=8, intermediate_size=128,
             moe_intermediate_size=32, n_routed_experts=8,
             num_experts_per_tok=2, experts_held=(2, 2),
             mlp_layer_types=("dense",) + ("sparse",) * 4,
             indexer_types=("full", "shared", "shared", "shared", "full"),
             max_position_embeddings=4096, context_block=8)
    d.update(kw)
    return GlmMoeDsaConfig(**d)


# -- pieces, on raw arrays ------------------------------------------------------

def _mm(x, w):
    """``x @ w`` accumulated in float32, back in the stream's dtype."""
    return jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)


def _rms_norm(x, w, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _layer_norm(x, w, b, eps=1e-6):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(F32) + b.astype(F32)).astype(x.dtype)


def _rotary(x, positions, theta, inv=None):
    """Interleaved rotary embedding over the last axis of ``x``
    ``[B, S, (H,) d]``: pairs ``(x[2i], x[2i+1])`` turn by
    ``position * theta^(-2i/d)``, or by ``position * inv[i]`` where a
    family hands its own ``d/2`` frequencies (``xing4.yarn_inv_freq``);
    float32 inside."""
    d = x.shape[-1]
    if inv is None:
        inv = jnp.exp(-math.log(theta) * jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[..., None] * inv                # [B, S, d/2]
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rotary_half(x, positions, theta):
    """Half-split rotary embedding (``rotate_half``) over the last axis
    of ``x`` ``[B, S, (H,) d]``: dims ``i`` and ``i + d/2`` turn as a
    pair by ``position * theta^(-2i/d)``; float32 inside. The same
    rotation as :func:`_rotary` on the dims in another order."""
    d = x.shape[-1]
    inv = jnp.exp(-math.log(theta) * jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[..., None] * inv                # [B, S, d/2]
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def _pages_a_block(max_blocks: int, block_size: int, want: int) -> int:
    """Pages one pass of the context takes: the largest divisor of the
    table's width that covers at most ``want`` positions."""
    pb = max(1, min(max_blocks, want // block_size))
    while max_blocks % pb:
        pb -= 1
    return pb


def _context_rows(pool, table, base, j, pb):
    """Positions ``j*pb*bs ..`` of every slot, ``[B, pb*bs, W]``, read
    through the table from the flat pool ``[N, 1, bs, W]``."""
    pages = jax.lax.dynamic_slice_in_dim(table, j * pb, pb, axis=1) + base
    rows = pool[pages]                                   # [B, pb, 1, bs, W]
    return rows.reshape(rows.shape[0], -1, rows.shape[-1])


def index_scores(q_i, w_i, pool, table, base, pos, want: int):
    """``I(t, s)`` ``[B, S, L]`` float32 over the slot's cached index
    keys, ``-inf`` where ``s > t``. ``q_i`` ``[B, S, Hi, Di]``, ``w_i``
    ``[B, S, Hi]`` float32; products accumulate in float32. The context
    passes in blocks, as far as the furthest slot reaches; one block's
    per-head scores are all that exists at a time."""
    B, S = q_i.shape[:2]
    bs, mb = pool.shape[2], table.shape[1]
    pb = _pages_a_block(mb, bs, want)
    kb, L = pb * bs, mb * bs
    n_blk = (jnp.max(pos) + S + kb - 1) // kb

    def body(j, out):
        k_i = _context_rows(pool, table, base, j, pb)           # [B, kb, Di]
        s = jnp.einsum("bshd,bkd->bshk", q_i, k_i,
                       preferred_element_type=F32)
        s = jnp.sum(jax.nn.relu(s) * w_i[..., None], axis=2)    # [B, S, kb]
        return jax.lax.dynamic_update_slice(out, s, (0, 0, j * kb))

    out = jax.lax.fori_loop(0, jnp.minimum(n_blk, mb // pb), body,
                            jnp.full((B, S, L), _NEG, F32))
    q_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    seen = jnp.arange(L, dtype=jnp.int32)[None, None, :] <= q_pos[..., None]
    return jnp.where(seen, out, _NEG)


def _cumsum_last(x01):
    """Inclusive running count of a 0/1 array over its last axis, as
    int32: inside lanes of 128 by a triangular product (exact: counts
    stay under 2^8), across them by a short scan."""
    L = x01.shape[-1]
    lane = 128 if L % 128 == 0 else L
    blocks = x01.reshape(x01.shape[:-1] + (L // lane, lane))
    tri = jnp.triu(jnp.ones((lane, lane), jnp.bfloat16))
    inner = jnp.dot(blocks.astype(jnp.bfloat16), tri,
                    preferred_element_type=F32).astype(jnp.int32)
    before = jnp.cumsum(inner[..., -1], axis=-1) - inner[..., -1]
    return (inner + before[..., None]).reshape(x01.shape)


def topk_members(scores, k: int):
    """Membership ``[..., L]`` bool of the ``k`` largest of ``scores``
    over the last axis, EXACT: a radix select finds the k-th largest
    bit by bit (32 counting passes over a monotone integer image of the
    float), and among equals at that value the lower positions win.
    Rows with fewer than ``k`` finite scores keep all of them."""
    u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i))
        n = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, prefix)

    kth = jax.lax.fori_loop(
        jnp.uint32(0), jnp.uint32(32), bit,
        jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    equal = key == kth
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)
    return (above | (equal & (_cumsum_last(equal) <= need))) \
        & (scores > _NEG)


def mla_project(at, h, positions, cfg, inv=None):
    """The MLA projections of ``h`` ``[B, S, D]`` through ``at`` (a
    :class:`GlmAttention`): ``(c_q, q_nope [B, S, H, dn], q_rope
    [B, S, H, dr] turned, latent [B, S, r + dr])``, the latent
    ``[RMSNorm(c_kv) ; rope(k_r)]`` being what a position caches.
    ``inv``: the rotary's own frequencies (:func:`_rotary`)."""
    B, S, _ = h.shape
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c_q = _rms_norm(_mm(h, at.wq_a._data), at.q_norm.weight._data,
                    cfg.rms_norm_eps)
    q = _mm(c_q, at.wq_b._data).reshape(B, S, H, dn + dr)
    q_nope = q[..., :dn]
    q_rope = _rotary(q[..., dn:], positions, cfg.rope_theta, inv)
    kv = _mm(h, at.wkv_a._data)
    c_kv = _rms_norm(kv[..., :cfg.kv_lora_rank],
                     at.kv_norm.weight._data, cfg.rms_norm_eps)
    k_r = _rotary(kv[..., cfg.kv_lora_rank:], positions,
                  cfg.rope_theta, inv)
    return c_q, q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def mla_context_attention(q_nope, q_rope, pool, table, base, pos, w_kvb,
                          member, cfg, scale: Optional[float] = None):
    """Prefill attention ``[B, S, H*dv]`` of a chunk at ``pos`` over
    what the latent pages hold (the chunk's own rows included), limited
    to ``member`` ``[B, S, L]`` (None: every position ``<= t``, dense
    attention). ``cfg``: any config with the MLA widths and
    ``context_block``; ``scale``: the scores' factor where it is not
    ``qk_head_dim ** -0.5``. One block of the context at a time: its K and V are expanded from the
    latent and scored in ONE product over ``[nope ; rope]`` (the rope
    key repeated over the heads: two products summed cost a further
    pass over the scores), masked and folded into an online softmax.
    An XLA composition, bound by its passes over a block's
    ``H * S * block`` float32 scores in HBM."""
    B, S, H, dn = q_nope.shape
    dv, r = cfg.v_head_dim, cfg.kv_lora_rank
    bs, mb = pool.shape[2], table.shape[1]
    pb = _pages_a_block(mb, bs, cfg.context_block)
    kb = pb * bs
    n_blk = (jnp.max(pos) + S + kb - 1) // kb
    if scale is None:
        scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    q_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    dt = q_nope.dtype
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    def body(j, carry):
        m, l, acc = carry
        rows = _context_rows(pool, table, base, j, pb)       # [B, kb, r+dr]
        c_kv, k_r = rows[..., :r], rows[..., r:cfg.latent_width]
        kv = _mm(c_kv, w_kvb).reshape(B, kb, H, dn + dv)
        v = kv[..., dn:]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :],
                                            (B, kb, H, k_r.shape[-1]))],
            axis=-1)
        s = jnp.einsum("bshd,bkhd->bhsk", q, k, preferred_element_type=F32)
        k_pos = j * kb + jnp.arange(kb, dtype=jnp.int32)
        ok = k_pos[None, None, :] <= q_pos[..., None]        # [B, S, kb]
        if member is not None:
            ok &= jax.lax.dynamic_slice_in_dim(member, j * kb, kb, axis=2)
        s = jnp.where(ok[:, None], s * scale, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        shift = jnp.where(m_new == _NEG, 0.0, m_new)
        p = jnp.exp(s - shift[..., None])
        fade = jnp.exp(m - shift)
        l = l * fade + jnp.sum(p, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhsk,bkhd->bhsd", p.astype(dt), v, preferred_element_type=F32)
        return m_new, l, acc

    init = (jnp.full((B, H, S), _NEG, F32), jnp.zeros((B, H, S), F32),
            jnp.zeros((B, H, S, dv), F32))
    _, l, acc = jax.lax.fori_loop(0, jnp.minimum(n_blk, mb // pb), body,
                                  init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).reshape(B, S, H * dv).astype(dt)


def mla_sparse_decode(q_nope, q_rope, pool, table, base, idx, valid, w_kvb,
                      cfg, scale: Optional[float] = None):
    """Decode attention ``[B, H*dv]`` over the selected positions
    ``idx`` ``[B, K]`` (``valid`` marks the real ones): only those
    latent rows are read, through the table, and attention runs in
    absorbed form — ``W_kvb``'s key half folded into the query, its
    value half applied to the weighted latent. With ``idx`` every
    position of the table and ``valid`` the causal mask it is the dense
    decode's XLA form (``xing4``, where the kernel is off); ``scale``:
    the scores' factor where it is not ``qk_head_dim ** -0.5``."""
    B, H, dn = q_nope.shape
    dv, r = cfg.v_head_dim, cfg.kv_lora_rank
    bs = pool.shape[2]
    page = jnp.take_along_axis(table, idx // bs, axis=1) + base
    rows = pool.reshape(-1, pool.shape[-1])[page * bs + idx % bs]  # [B,K,W]
    c_kv, k_r = rows[..., :r], rows[..., r:cfg.latent_width]
    w = w_kvb.reshape(r, H, dn + dv)
    dt = q_nope.dtype
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w[..., :dn],
                       preferred_element_type=F32).astype(dt)
    s = jnp.einsum("bhr,bkr->bhk", q_lat, c_kv,
                   preferred_element_type=F32) \
        + jnp.einsum("bhd,bkd->bhk", q_rope, k_r,
                     preferred_element_type=F32)
    s = jnp.where(valid[:, None], s / math.sqrt(cfg.qk_head_dim)
                  if scale is None else s * scale, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhk,bkr->bhr", p.astype(dt), c_kv,
                       preferred_element_type=F32).astype(dt)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w[..., dn:],
                   preferred_element_type=F32)
    return o.reshape(B, H * dv).astype(dt)


def moe_layer(moe, h, stats, cfg, taps=None):
    """An expert layer (a :class:`GlmMoE`) on ``h`` ``[B, S, D]``: the
    held experts' part for the tokens routed to them plus the shared
    expert where the layer has one; in a decode step, a row a slot of
    what it counted into ``stats``. ``cfg``: any config with
    ``num_experts_per_tok``, ``routed_scaling_factor`` and
    ``experts_held``; where it has them, ``router_eps`` (added to the
    chosen scores' sum) and ``count_experts_read`` (a decode step also
    hands the engine this layer's pairs an expert, ``[B, held]``, beside
    the other expert layers' under one key: the experts given a pair are
    the experts whose weights the step reads)."""
    B, S, D = h.shape
    flat = h.reshape(B * S, D)
    # the router's operand, ONE array for its product and for a probe
    flat32 = flat.astype(F32)
    routing = sigmoid_topk_routing(
        flat32, moe.router.weight._data, moe.router.bias._data,
        cfg.num_experts_per_tok, cfg.routed_scaling_factor,
        getattr(cfg, "router_eps", 0.0))
    first, held = cfg.experts_held
    y, _, here = held_experts_ffn(
        flat, routing, moe.experts.w_in._data, moe.experts.w_out._data,
        first)
    shared = getattr(moe, "shared", None)
    if shared is not None:
        y = y + gated_ffn(flat, shared.w_in._data, shared.w_out._data)
    if taps is not None:
        taps.setdefault("router_topk", []).append(
            routing.idx.reshape(B, S, -1))
        taps.setdefault("router_probe", []).append(dict(
            scores=routing.scores.reshape(B, S, -1)[:, -1],
            x=flat32.reshape(B, S, D)[:, -1]))
    if S == 1:
        # a row a slot, for the engine's counters (active slots only)
        given = jnp.sum(
            (routing.idx - first)[..., None] == jnp.arange(held),
            axis=1, dtype=jnp.int32)                        # [B, held]
        skipped = jnp.sum(~here, axis=1, dtype=jnp.int32)
        for key, v in (("serve_moe_routed_tokens_total:expert", given),
                       ("serve_moe_skipped_pairs_total", skipped)):
            stats[key] = stats[key] + v if key in stats else v
        if getattr(cfg, "count_experts_read", False):
            # emits-metrics: serve_moe_experts_read_total
            key = "serve_moe_experts_read_total#nonzero"
            stats[key] = jnp.concatenate([stats[key], given], axis=1) \
                if key in stats else given
    return y.reshape(B, S, D).astype(h.dtype)


# -- layers ---------------------------------------------------------------------

class _Norm(Layer):
    block = "norm"

    def __init__(self, width: int, dtype: str, bias: bool = False):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            (width,), default_initializer=Constant(1.0))
        if bias:
            self.bias = self.create_parameter((width,), is_bias=True)


class GlmAttention(Layer):
    """MLA projections (the attention itself is a function above)."""

    def __init__(self, cfg: GlmMoeDsaConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        D, H = cfg.hidden_size, cfg.num_heads
        init = Normal(0.0, cfg.initializer_range)
        mk = lambda *shape, i=init: self.create_parameter(
            shape, default_initializer=i)
        self.wq_a = mk(D, cfg.q_lora_rank)
        self.q_norm = _Norm(cfg.q_lora_rank, cfg.dtype)
        self.wq_b = mk(cfg.q_lora_rank, H * cfg.qk_head_dim)
        self.wkv_a = mk(D, cfg.latent_width)
        self.kv_norm = _Norm(cfg.kv_lora_rank, cfg.dtype)
        self.wkv_b = mk(cfg.kv_lora_rank,
                        H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.wo = mk(H * cfg.v_head_dim, D,
                     i=Normal(0.0, cfg.initializer_range * depth_scale))


class GlmIndexer(Layer):
    """The learned sparse-attention indexer of a ``full`` layer."""

    def __init__(self, cfg: GlmMoeDsaConfig):
        super().__init__(dtype=cfg.dtype)
        init = Normal(0.0, cfg.initializer_range)
        mk = lambda *shape: self.create_parameter(
            shape, default_initializer=init)
        self.wq = mk(cfg.q_lora_rank, cfg.index_n_heads * cfg.index_head_dim)
        self.wk = mk(cfg.hidden_size, cfg.index_head_dim)
        self.k_norm = _Norm(cfg.index_head_dim, cfg.dtype, bias=True)
        self.w = mk(cfg.hidden_size, cfg.index_n_heads)


class GlmFFN(Layer):
    """Gated SiLU FFN; ``w_in`` holds gate then up."""

    def __init__(self, cfg: GlmMoeDsaConfig, width: int, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        self.w_in = self.create_parameter(
            (cfg.hidden_size, 2 * width),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.w_out = self.create_parameter(
            (width, cfg.hidden_size), default_initializer=Normal(
                0.0, cfg.initializer_range * depth_scale))


class GlmRouter(Layer):
    """Router weight and selection bias, float32 whatever the model's."""

    def __init__(self, cfg: GlmMoeDsaConfig):
        super().__init__(dtype="float32")
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.n_routed_experts),
            default_initializer=Normal(0.0, cfg.initializer_range))
        # `noaux_tc`'s bias is trained to balance load; seeded here so
        # that it does change selections
        self.bias = self.create_parameter(
            (cfg.n_routed_experts,), default_initializer=Normal(0.0, 0.05))


class GlmExperts(Layer):
    """The routed experts HELD here, stacked ``[held, ...]``."""

    def __init__(self, cfg: GlmMoeDsaConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        held, F = cfg.experts_held[1], cfg.moe_intermediate_size
        self.w_in = self.create_parameter(
            (held, cfg.hidden_size, 2 * F),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.w_out = self.create_parameter(
            (held, F, cfg.hidden_size), default_initializer=Normal(
                0.0, cfg.initializer_range * depth_scale))


class GlmMoE(Layer):
    def __init__(self, cfg: GlmMoeDsaConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        self.router = GlmRouter(cfg)
        self.experts = GlmExperts(cfg, depth_scale)
        self.shared = GlmFFN(cfg, cfg.moe_intermediate_size, depth_scale)


class GlmDecoderLayer(Layer):
    def __init__(self, cfg: GlmMoeDsaConfig, mlp: str, indexer: str):
        super().__init__(dtype=cfg.dtype)
        scale = 1.0 / math.sqrt(2 * cfg.num_layers)
        self.attn_norm = _Norm(cfg.hidden_size, cfg.dtype)
        self.attn = GlmAttention(cfg, scale)
        if indexer == "full":
            self.indexer = GlmIndexer(cfg)
        self.ffn_norm = _Norm(cfg.hidden_size, cfg.dtype)
        if mlp == "dense":
            self.mlp = GlmFFN(cfg, cfg.intermediate_size, scale)
        else:
            self.moe = GlmMoE(cfg, scale)


class _Selection(NamedTuple):
    """What a ``full`` layer hands the ``shared`` layers after it:
    ``idx``/``valid`` ``[B, K]`` in a decode step, ``member``
    ``[B, S, L]`` (None: all of ``s <= t``) in a prefill."""

    idx: object = None
    valid: object = None
    member: object = None


class GlmMoeDsaForCausalLM(Layer):
    """Embedding, the layers held here, final norm, untied head; serves
    through ``forward(ids, caches=<PagedPools>, cache_pos=<[B]>)``."""

    def __init__(self, cfg: GlmMoeDsaConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        init = Normal(0.0, cfg.initializer_range)
        self.embed = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), default_initializer=init)
        self.layers = LayerList([
            GlmDecoderLayer(cfg, m, i)
            for m, i in zip(cfg.mlp_layer_types, cfg.indexer_types)])
        self.final_norm = _Norm(cfg.hidden_size, cfg.dtype)
        self.head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), default_initializer=init)
        #: a dict for a probe to fill, or None; a list a key, in layer
        #: order. The discrete choices: ``selection``, a mask
        #: ``[B, S, L]`` a ``full`` layer, ``router_topk`` ``[B, S, k]``
        #: an expert layer. The float32 scores of the LAST row beside
        #: what they were computed from: ``index_probe`` (``scores``
        #: ``[B, L]``, ``q`` ``[B, Hi, Di]``, ``w`` ``[B, Hi]``, ``keys``
        #: ``[B, L, Di]`` as cached, of the leading ``taps["live"]``
        #: slots if the probe put that in) and ``router_probe``
        #: (``scores`` ``[B, E]``, ``x`` ``[B, D]``)
        self.taps: Optional[dict] = None

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None):
        from ..serving.kv_cache import PagedPools
        if not isinstance(caches, PagedPools):
            raise ValueError(
                "glm_moe_dsa serves over paged state: forward needs "
                "caches=<PagedPools>; the cache-free forward is the "
                "reference's (benchmark/reference/glm_moe_dsa.py)")
        cfg = self.cfg
        ids, pos = input_ids._data, cache_pos._data.astype(jnp.int32)
        table = caches.block_table._data
        # the pools as one pool of L_kind * P pages a kind (a bitcast),
        # layer l of a kind at pages l*P + table
        pools, n_pages = {}, {}
        for kd, p in zip(cfg.page_kinds(), caches.pools):
            a = p._data
            n_pages[kd.name] = a.shape[1]
            pools[kd.name] = a.reshape((-1,) + a.shape[2:])
        B, S = ids.shape
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        with jax.named_scope("embed"):
            x = self.embed._data[ids]
        sel, n_full, stats = None, 0, {}
        for li, layer in enumerate(self.layers):
            with jax.named_scope("norm"):
                h = _rms_norm(x, layer.attn_norm.weight._data,
                              cfg.rms_norm_eps)
            base_lat = li * n_pages["latent"]
            full = cfg.indexer_types[li] == "full"
            a, pools, sel = self._attention(
                layer, h, positions, pos, table, pools, base_lat,
                n_full * n_pages["index"] if full else None, sel)
            n_full += full
            x = x + a
            with jax.named_scope("norm"):
                h = _rms_norm(x, layer.ffn_norm.weight._data,
                              cfg.rms_norm_eps)
            if cfg.mlp_layer_types[li] == "dense":
                with jax.named_scope("ffn"):
                    y = gated_ffn(h, layer.mlp.w_in._data,
                                  layer.mlp.w_out._data).astype(x.dtype)
            else:
                with jax.named_scope("moe"):
                    y = moe_layer(layer.moe, h, stats, cfg, self.taps)
            x = x + y
        if S == 1:
            # what a decode step counted, a row a slot: the engine adds
            # the active slots' rows to the counters of these names
            # emits-metrics: serve_dsa_selected_total, serve_dsa_available_total, serve_moe_routed_tokens_total, serve_moe_skipped_pairs_total
            k = min(cfg.index_topk, table.shape[1] * pools["latent"].shape[2])
            stats["serve_dsa_selected_total"] = jnp.minimum(pos + 1, k)
            stats["serve_dsa_available_total"] = pos + 1
        with jax.named_scope("norm"):
            x = _rms_norm(x, self.final_norm.weight._data, cfg.rms_norm_eps)
        logits = jnp.dot(x, self.head._data, preferred_element_type=F32)
        new = tuple(Tensor(pools[kd.name].reshape(p._data.shape))
                    for kd, p in zip(cfg.page_kinds(), caches.pools))
        return Tensor(logits), caches._replace(
            pools=new, stats=stats if S == 1 else None)

    # -- attention ---------------------------------------------------------------
    def _attention(self, layer, h, positions, pos, table, pools, base_lat,
                   base_idx, sel):
        from ..serving.kv_cache import write_pages
        cfg, at = self.cfg, layer.attn
        S = h.shape[1]
        with jax.named_scope("mla"):
            c_q, q_nope, q_rope, latent = mla_project(at, h, positions, cfg)
        with jax.named_scope("kv_write"):
            pools = dict(pools, latent=write_pages(
                pools["latent"], latent[:, :, None, :], table, pos,
                base_lat))
        if base_idx is not None:
            pools, sel = self._select(layer.indexer, h, c_q, positions, pos,
                                      table, pools, base_idx)
        with jax.named_scope("mla"):
            if S == 1:
                o = mla_sparse_decode(
                    q_nope[:, 0], q_rope[:, 0], pools["latent"], table,
                    base_lat, sel.idx, sel.valid, at.wkv_b._data,
                    cfg)[:, None]
            else:
                o = mla_context_attention(
                    q_nope, q_rope, pools["latent"], table, base_lat, pos,
                    at.wkv_b._data, sel.member, cfg)
            return _mm(o, at.wo._data), pools, sel

    def _select(self, ix, h, c_q, positions, pos, table, pools, base_idx):
        """A ``full`` layer's indexer: its key into the index pages, the
        scores over the slot's cached keys, the exact top set."""
        from ..serving.kv_cache import write_pages
        cfg = self.cfg
        B, S, _ = h.shape
        Hi, Di, dr = cfg.index_n_heads, cfg.index_head_dim, \
            cfg.qk_rope_head_dim
        rot = lambda t: jnp.concatenate(
            [_rotary(t[..., :dr], positions, cfg.rope_theta), t[..., dr:]],
            axis=-1)
        with jax.named_scope("indexer"):
            q_i = rot(_mm(c_q, ix.wq._data).reshape(B, S, Hi, Di))
            k_i = rot(_layer_norm(_mm(h, ix.wk._data),
                                  ix.k_norm.weight._data,
                                  ix.k_norm.bias._data))
            # the positive constants on I (they do not change the set)
            w_i = jnp.dot(h, ix.w._data, preferred_element_type=F32) \
                * (Hi ** -0.5 * Di ** -0.5)
        with jax.named_scope("kv_write"):
            pools = dict(pools, index=write_pages(
                pools["index"], k_i[:, :, None, :], table, pos, base_idx))
        with jax.named_scope("indexer"):
            scores = index_scores(q_i, w_i, pools["index"], table, base_idx,
                                  pos, cfg.context_block)
        L = scores.shape[-1]
        with jax.named_scope("select"):
            if S == 1:
                vals, idx = jax.lax.top_k(scores[:, 0],
                                          min(cfg.index_topk, L))
                sel = _Selection(idx=idx.astype(jnp.int32),
                                 valid=vals > _NEG)
            else:
                sel = _Selection(member=topk_members(scores, cfg.index_topk)
                                 if cfg.index_topk < L else None)
        if self.taps is not None:
            # the set as a mask [B, S, L], whichever form attention takes
            if S == 1:
                mask = jnp.zeros((B, L), bool).at[
                    jnp.arange(B)[:, None], sel.idx].max(sel.valid)[:, None]
            else:
                mask = sel.member if sel.member is not None \
                    else scores > _NEG
            self.taps.setdefault("selection", []).append(mask)
            self.taps.setdefault("index_probe", []).append(dict(
                scores=scores[:, -1], q=q_i[:, -1], w=w_i[:, -1],
                keys=_context_rows(
                    pools["index"], table[:self.taps.get("live", B)],
                    base_idx, 0, table.shape[1])))
        return pools, sel
