"""Decoder family ``xing4_0`` (Xing4.0-29B-A4B): a FOUR-STREAM residual
path (manifold-constrained hyper-connections, mHC) around every
sublayer, multi-head latent attention read DENSELY over the latent
cache, dense and expert FFN layers with every expert held — the serving
form, over paged state.

The residual state of a token is ``X`` in ``R^{n x C}`` (``n`` =
``hc_mult`` streams of the hidden width ``C``), carried as one row
``[.., n*C]``: stream ``j`` is lanes ``jC .. jC+C-1``, so ``vec(X)`` is
the row itself and a stream is a lane-aligned slice. ``X_0`` is the
token's embedding in each stream. Each sublayer ``F`` (attention; the
dense FFN or the expert layer) has its own ``phi``, ``alpha``, ``b``
(the plain reference, ``benchmark/reference/xing4.py``, follows the same
equations and notes what the published config leaves open):

mHC        xt = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)   float32, no gain
           Ht_pre  = a_pre  (xt phi_pre)  + b_pre         [n]
           Ht_post = a_post (xt phi_post) + b_post        [n]
           Ht_res  = a_res  mat(xt phi_res) + b_res       [n, n]
           H_pre = sigmoid(Ht_pre); H_post = 2 sigmoid(Ht_post)
           M_0 = exp(clamp(Ht_res, lo, hi)); ``hc_sinkhorn_iters`` times:
           M <- M / (rowsum(M) + hc_eps); M <- M / (colsum(M) + hc_eps)
           H_res = M_last (doubly stochastic to the iteration's reach)
           u  = H_pre X                        the sublayer's input row
           X' = H_res X + H_post^T F(RMSNorm(u))
           The mappings are float32 whatever the stream's dtype, both
           mixes accumulate in float32, the stream is stored in the
           model's dtype. After the last layer the streams are summed.
MLA        ``glm_moe_dsa``'s projections (``mla_project``) with YaRN's
           blended rotary frequencies (:func:`yarn_inv_freq`); scores
           ``(q_nope . k_nope + rope(q_rope) . rope(k_r)) * d_qk^-0.5 *
           m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``, causal over
           ALL cached positions: no selection. The cache holds
           ``[c_kv ; rope(k_r)]``.
experts    ``glm_moe_dsa.moe_layer``: ``incubate.moe.held`` with every
           expert held (``experts_held`` = (0, all)): no pair is
           skipped; plus the shared expert.

ONE page kind, ``latent``, in every layer, one lifetime. How attention
reads, by program:

decode     (S == 1) the Pallas ``paged_mla_decode`` kernel in absorbed
           form: ``W_kvb``'s key half folded into the query (``[q_nope
           W_uk ; rope(q_rope)]``, one row a head over the pool's row),
           the sweep over the slot's live pages copies a page ONCE and
           reads it as keys and, its first ``kv_lora_rank`` lanes, as
           values; ``W_kvb``'s value half and ``W_o`` outside. Where the
           kernel is off: ``mla_sparse_decode`` over every position of
           the table (the gathered XLA form).
prefill    (S > 1, plain or context) ``mla_context_attention`` with no
           membership mask: the blocked XLA pass over the context with
           an online softmax that ``glm_moe_dsa`` has.

The six-odd layers a chip holds are a Python loop. bf16 weights and
cache with float32 accumulation; norms, softmax, router scores and the
mHC mappings in float32.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..incubate.moe.held import gated_ffn
from ..nn.initializer import Assign, Normal
from ..nn.layer import Layer, LayerList
# what the two MLA families compute alike: the projections, the blocked
# context read, the gathered decode, the latent page kind, the expert
# layer and its modules
from .glm_moe_dsa import (GlmAttention, GlmFFN, GlmMoE, _Norm, _mm, _rms_norm,
                          latent_page_kind, mla_context_attention,
                          mla_project, mla_sparse_decode, moe_layer)

__all__ = ["Xing4Config", "Xing4ForCausalLM", "xing4_tiny", "yarn_inv_freq",
           "mhc_mappings", "mhc_pre", "mhc_mix"]

F32 = jnp.float32


@dataclass
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    #: (first expert, how many) this chip holds of ``n_routed_experts``
    experts_held: Tuple[int, int] = (0, 64)
    #: one entry a layer held here: "dense" | "sparse"
    mlp_layer_types: Tuple[str, ...] = ("dense",)
    #: residual streams, Sinkhorn-Knopp iterations, the eps of the
    #: mappings' norm and of both normalizations, the clamp on Ht_res
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    rope_theta: float = 10000.0
    #: YaRN (``rope_scaling``); factor 1 is the plain rotary embedding
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    max_position_embeddings: int = 262144
    dtype: str = "float32"
    #: positions of the context one pass of the blocked attention takes
    context_block: int = 512

    def __post_init__(self):
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise NotImplementedError(
                "YaRN with mscale != mscale_all_dim scales cos and sin; "
                "the published config has them equal")

    @property
    def num_layers(self) -> int:
        return len(self.mlp_layer_types)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        """``d_qk^-0.5 * m^2``: YaRN's attention factor on both sides of
        the score (DeepSeek-V2's ``mscale_all_dim``)."""
        m = 1.0 if self.rope_factor <= 1 else \
            0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    def page_kinds(self):
        """ONE kind: ``latent`` ([c_kv ; rope(k_r)]) in every layer."""
        return (latent_page_kind(self),)


def xing4_tiny(**kw) -> Xing4Config:
    """Test-size config: four streams, a dense then an expert layer, 8
    experts top-2 all held, YaRN over 32 original positions."""
    d = dict(vocab_size=256, hidden_size=64, num_heads=4, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
             v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
             n_routed_experts=8, num_experts_per_tok=2, experts_held=(0, 8),
             mlp_layer_types=("dense", "sparse"), rope_factor=4.0,
             rope_original_max_position=32, max_position_embeddings=4096,
             context_block=8)
    d.update(kw)
    return Xing4Config(**d)


# -- pieces, on raw arrays ------------------------------------------------------

def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's ``dim/2`` rotary frequencies: pair ``i`` keeps
    ``theta^(-2i/dim)`` where it turns more than ``beta_fast`` times
    over the original context, takes ``1/factor`` of it where it turns
    fewer than ``beta_slow`` times, and a linear blend between (the
    bounds are the floor and the ceiling of the pairs' fractional
    indices, as DeepSeek-V2 computes them)."""
    extra = jnp.exp(-math.log(theta) * jnp.arange(0, dim, 2, dtype=F32) / dim)
    if factor <= 1:
        return extra

    def pair_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _streams(X, n: int):
    """The ``n`` streams of ``X`` ``[.., n*C]``, lane-aligned slices."""
    C = X.shape[-1] // n
    return [X[..., j * C:(j + 1) * C] for j in range(n)]


def mhc_mappings(X, hc, cfg: Xing4Config):
    """``(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n])`` of
    ``X`` ``[B, S, n*C]`` (any float dtype) under a sublayer's ``hc`` (``phi`` ``[nC, 2n +
    n^2]``: the pre, post and res columns side by side, ``alpha`` ``[3]``,
    ``b`` ``[2n + n^2]``), all float32. After the one product everything
    is written entry by entry on ``[T]`` vectors, the tokens in the
    lanes and the sums of ``n`` entries as explicit additions: an
    elementwise graph, one fusion a trip of the loop. (With ``jnp.sum``
    over ``[n, n, T]`` every half-iteration was two fusions, 83 a
    sublayer and a thousand a decode step, each a few microseconds of
    launch for 16 values a token.) The loop is a ``fori_loop`` unrolled
    five iterations a trip: all twenty in one fusion read 0.36% of a
    decode step on the chip (my chip run, PR 35) but cost minutes of
    compile a program, here and there."""
    n = cfg.hc_mult
    B, S, W = X.shape
    T = B * S
    xf = X.astype(F32).reshape(T, W)
    # (xt phi) as (vec(X) phi) / rms: the product's left operand is then
    # the stored stream itself, exact in bf16 whatever the compiler feeds
    # the MXU from a fused producer (with xt = vec(X) / rms computed
    # inside the product's fusion a 2,048-row chunk read `highest` at
    # bf16's grade on the chip: 2e-3-6e-3 off in the product, 3e-4 in
    # H_res; my chip runs, PR 35)
    proj = jnp.dot(xf, hc.phi._data.astype(F32),
                   precision=jax.lax.Precision.HIGHEST)       # [T, 2n + n^2]
    proj = proj * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + cfg.hc_eps)
    # a gate a group of columns: pre, post, res
    gain = jnp.repeat(hc.alpha._data.astype(F32), jnp.array([n, n, n * n]),
                      total_repeat_length=2 * n + n * n)
    ht = (proj * gain + hc.b._data.astype(F32)).T             # [2n + n^2, T]
    h_pre = jax.nn.sigmoid(ht[:n])
    h_post = 2.0 * jax.nn.sigmoid(ht[n:2 * n])
    lo, hi = cfg.mhc_h_res_clamp
    m = [[jnp.exp(jnp.clip(ht[2 * n + i * n + j], lo, hi))
          for j in range(n)] for i in range(n)]
    total = lambda vs: functools.reduce(jnp.add, vs)

    def sinkhorn(_, m):
        rows = [total(m[i]) + cfg.hc_eps for i in range(n)]
        m = [[m[i][j] / rows[i] for j in range(n)] for i in range(n)]
        cols = [total([m[i][j] for i in range(n)]) + cfg.hc_eps
                for j in range(n)]
        return [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]

    m = jax.lax.fori_loop(0, cfg.hc_sinkhorn_iters, sinkhorn, m, unroll=5)
    h_res = jnp.stack([jnp.stack(row, axis=-1) for row in m], axis=-2)
    return (h_pre.T.reshape(B, S, n), h_post.T.reshape(B, S, n),
            h_res.reshape(B, S, n, n))


def mhc_pre(X, h_pre):
    """``u = H_pre X`` ``[B, S, C]`` float32: the sublayer's input."""
    n = h_pre.shape[-1]
    xs = _streams(X, n)
    u = h_pre[..., 0, None] * xs[0].astype(F32)
    for j in range(1, n):
        u = u + h_pre[..., j, None] * xs[j].astype(F32)
    return u


def mhc_mix(X, h_res, h_post, y):
    """``X' = H_res X + H_post^T y`` ``[B, S, n*C]`` in ``X``'s dtype,
    ``y`` ``[B, S, C]`` the sublayer's output: one elementwise pass
    over the streams, accumulated in float32. The new state is
    MATERIALIZED in the stream's dtype behind an optimization barrier:
    without it XLA:TPU fuses these sums into the next sublayer's
    consumers UNROUNDED (a cast, and a `reduce_precision` too, it skips
    for a consumer it fuses into), and the mappings then read another
    stream than the one that is stored and handed on (`H_res` moved by
    up to 1e-3 on the chip, a fourth of what bf16 arithmetic moves it
    by; my chip runs, PR 35). The stream is written once either way."""
    n = h_post.shape[-1]
    xs = [s.astype(F32) for s in _streams(X, n)]
    yf = y.astype(F32)
    rows = []
    for i in range(n):
        acc = h_post[..., i, None] * yf
        for j in range(n):
            acc = acc + h_res[..., i, j, None] * xs[j]
        rows.append(acc.astype(X.dtype))
    return jax.lax.optimization_barrier(jnp.concatenate(rows, axis=-1))


# -- layers ---------------------------------------------------------------------

class XingHyperConnection(Layer):
    """A sublayer's mHC parameters, float32 whatever the model's:
    ``2n + n^2`` columns of ``phi`` (pre, post, res), a gate ``alpha``
    for each of the three, and the static part ``b``."""

    block = "mhc"

    def __init__(self, cfg: Xing4Config):
        super().__init__(dtype="float32")
        n, width = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
        cols = 2 * n + n * n
        # seeded so that the DYNAMIC part decides: xt has unit mean
        # square over nC values, so xt . phi_col is N(0, 1). With a_res
        # 1/2 and b_res the identity an entry of H_res moves by 0.2-0.6
        # from token to token, so a missing projection cannot pass a
        # comparison (the paper's alpha of 0.01 would let it), and 20
        # iterations still reach row sums of 1 to 2e-5 (200,000 draws;
        # at a_res 1, b_res 2 I they stop at 2e-2). a_pre = a_post = 1,
        # b_pre = b_post = 0: H_pre about 1/2 a stream, H_post about 1
        self.phi = self.create_parameter(
            (width, cols), default_initializer=Normal(0.0, width ** -0.5))
        self.alpha = self.create_parameter(
            (3,), default_initializer=Assign([1.0, 1.0, 0.5]))
        self.b = self.create_parameter(
            (cols,), is_bias=True, default_initializer=Assign(
                [0.0] * (2 * n) + [float(i == j) for i in range(n)
                                   for j in range(n)]))


class XingDecoderLayer(Layer):
    def __init__(self, cfg: Xing4Config, mlp: str):
        super().__init__(dtype=cfg.dtype)
        scale = 1.0 / math.sqrt(2 * cfg.num_layers)
        self.attn_hc = XingHyperConnection(cfg)
        self.attn_norm = _Norm(cfg.hidden_size, cfg.dtype)
        self.attn = GlmAttention(cfg, scale)
        self.ffn_hc = XingHyperConnection(cfg)
        self.ffn_norm = _Norm(cfg.hidden_size, cfg.dtype)
        if mlp == "dense":
            self.mlp = GlmFFN(cfg, cfg.intermediate_size, scale)
        else:
            self.moe = GlmMoE(cfg, scale)


class Xing4ForCausalLM(Layer):
    """Embedding, the layers held here, the streams' sum, final norm,
    untied head; serves through ``forward(ids, caches=<PagedPools>,
    cache_pos=<[B]>)``."""

    def __init__(self, cfg: Xing4Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        init = Normal(0.0, cfg.initializer_range)
        self.embed = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), default_initializer=init)
        self.layers = LayerList([XingDecoderLayer(cfg, m)
                                 for m in cfg.mlp_layer_types])
        self.final_norm = _Norm(cfg.hidden_size, cfg.dtype)
        self.head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), default_initializer=init)
        #: a dict for a probe to fill, or None; a list a key, in sublayer
        #: or layer order: ``h_res`` ``[B, S, n, n]`` a sublayer;
        #: ``mhc_probe`` a sublayer, the LAST row's ``h_res`` ``[B, n,
        #: n]`` beside the float32 residual row ``x`` ``[B, n*C]`` it
        #: was computed from; ``router_topk`` ``[B, S, k]`` and
        #: ``router_probe`` (``scores`` ``[B, E]``, ``x`` ``[B, D]``) an
        #: expert layer
        self.taps: Optional[dict] = None

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None):
        from ..serving.kv_cache import PagedPools
        if not isinstance(caches, PagedPools):
            raise ValueError(
                "xing4 serves over paged state: forward needs "
                "caches=<PagedPools>; the cache-free forward is the "
                "reference's (benchmark/reference/xing4.py)")
        if caches.scales is not None:
            raise ValueError("xing4 does not read int8 pages")
        cfg = self.cfg
        ids, pos = input_ids._data, cache_pos._data.astype(jnp.int32)
        table = caches.block_table._data
        # the pool as one pool of L * P pages (a bitcast), layer l at
        # pages l*P + table
        pool = caches.pools[0]._data
        n_pages = pool.shape[1]
        flat = pool.reshape((-1,) + pool.shape[2:])
        B, S = ids.shape
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                            cfg.rope_factor, cfg.rope_original_max_position,
                            cfg.rope_beta_fast, cfg.rope_beta_slow)
        with jax.named_scope("embed"):
            x = self.embed._data[ids]
        with jax.named_scope("mhc"):
            X = jnp.concatenate([x] * cfg.hc_mult, axis=-1)
        stats = {}
        for li, layer in enumerate(self.layers):
            h, mix = self._enter(X, layer.attn_hc, layer.attn_norm)
            a, flat = self._attention(layer.attn, h, positions, pos, table,
                                      flat, li * n_pages, inv)
            X = mix(a)
            h, mix = self._enter(X, layer.ffn_hc, layer.ffn_norm)
            if cfg.mlp_layer_types[li] == "dense":
                with jax.named_scope("ffn"):
                    y = gated_ffn(h, layer.mlp.w_in._data,
                                  layer.mlp.w_out._data).astype(h.dtype)
            else:
                with jax.named_scope("moe"):
                    # emits-metrics: serve_moe_routed_tokens_total, serve_moe_skipped_pairs_total
                    y = moe_layer(layer.moe, h, stats, cfg, self.taps)
            X = mix(y)
        with jax.named_scope("mhc"):
            xs = _streams(X, cfg.hc_mult)
            x = xs[0].astype(F32)
            for s in xs[1:]:
                x = x + s.astype(F32)
            x = x.astype(X.dtype)
        with jax.named_scope("norm"):
            x = _rms_norm(x, self.final_norm.weight._data, cfg.rms_norm_eps)
        logits = jnp.dot(x, self.head._data, preferred_element_type=F32)
        new = (Tensor(flat.reshape(pool.shape)),)
        return Tensor(logits), caches._replace(
            pools=new, stats=stats if S == 1 else None)

    def _enter(self, X, hc, norm):
        """A sublayer's way in and out: ``(h, mix)``, ``h`` its normed
        input in the model's dtype and ``mix(y)`` the new state."""
        cfg = self.cfg
        with jax.named_scope("mhc"):
            # the mappings' operand, ONE array for their product and for
            # a probe: XLA may hand a consumer the float32 sums behind a
            # bf16 stream unrounded, and the rounded row is then not
            # what the mappings were computed from
            xf = X.astype(F32)
            h_pre, h_post, h_res = mhc_mappings(xf, hc, cfg)
            u = mhc_pre(X, h_pre)
        if self.taps is not None:
            self.taps.setdefault("h_res", []).append(h_res)
            self.taps.setdefault("mhc_probe", []).append(
                dict(h_res=h_res[:, -1], x=xf[:, -1]))
        with jax.named_scope("norm"):
            h = _rms_norm(u, norm.weight._data,
                          cfg.rms_norm_eps).astype(X.dtype)

        def mix(y):
            with jax.named_scope("mhc"):
                return mhc_mix(X, h_res, h_post, y)

        return h, mix

    # -- attention ---------------------------------------------------------------
    def _attention(self, at, h, positions, pos, table, pool, base, inv):
        from ..ops import pallas as pallas_ops
        from ..serving.kv_cache import write_pages
        cfg = self.cfg
        S = h.shape[1]
        with jax.named_scope("mla"):
            _, q_nope, q_rope, latent = mla_project(at, h, positions, cfg,
                                                    inv)
        with jax.named_scope("kv_write"):
            pool = write_pages(pool, latent[:, :, None, :], table, pos, base)
        with jax.named_scope("mla"):
            if S == 1 and pallas_ops.kernel_enabled("paged_decode"):
                o = self._absorbed_decode(at, q_nope[:, 0], q_rope[:, 0],
                                          pool, table + base, pos)[:, None]
            elif S == 1:
                # the gathered XLA form: every position of the table
                L = table.shape[1] * pool.shape[2]
                idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                       (h.shape[0], L))
                o = mla_sparse_decode(
                    q_nope[:, 0], q_rope[:, 0], pool, table, base, idx,
                    idx <= pos[:, None], at.wkv_b._data, cfg,
                    cfg.attn_scale)[:, None]
            else:
                o = mla_context_attention(
                    q_nope, q_rope, pool, table, base, pos, at.wkv_b._data,
                    None, cfg, cfg.attn_scale)
            return _mm(o, at.wo._data), pool

    def _absorbed_decode(self, at, q_nope, q_rope, pool, table, pos):
        """``[B, H*dv]``: the kernel over the latent pool, ``W_kvb``'s key
        half folded into the query and its value half applied to the
        weighted latent."""
        from ..ops.pallas.paged_decode import paged_mla_decode
        cfg = self.cfg
        B, H, dn = q_nope.shape
        r, dv, dt = cfg.kv_lora_rank, cfg.v_head_dim, q_nope.dtype
        w = at.wkv_b._data.reshape(r, H, dn + dv)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w[..., :dn],
                           preferred_element_type=F32).astype(dt)
        pad = pool.shape[-1] - cfg.latent_width
        q = jnp.concatenate(
            [q_lat, q_rope] + ([jnp.zeros((B, H, pad), dt)] if pad else []),
            axis=-1)
        o_lat = paged_mla_decode(q, pool, table, pos, scale=cfg.attn_scale,
                                 value_width=r)
        o = jnp.einsum("bhr,rhd->bhd", o_lat, w[..., dn:],
                       preferred_element_type=F32)
        return o.reshape(B, H * dv).astype(dt)
