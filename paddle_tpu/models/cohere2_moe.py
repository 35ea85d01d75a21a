"""Decoder family ``cohere2_moe`` (Command A+): window and full attention
layers in a period, fewer K/V heads than query heads, and a PARALLEL
block — one norm a layer, whose output both the attention and the expert
half read — the serving form, over paged state.

``x`` is a position's residual row; the equations (the plain reference,
``benchmark/reference/cohere2_moe.py``, follows the same ones and notes
what the published config leaves open):

block      h = LayerNorm(x) * w (no bias; float32 inside);
           x' = x + attention(h) + routed(h) + shared(h).
attention  q, k, v = h W_q, h W_k, h W_v: ``num_heads`` query heads on
           ``num_kv_heads`` K/V heads (query head n reads K/V head
           n // group), no bias, no q/k norm; softmax(q k^T / sqrt(d)) v,
           concat over heads, W_o.
           ``sliding_attention``: q and k turned by an interleaved rotary
           embedding over all of d; key j visible to query i iff
           0 <= i - j < sliding_window.
           ``full_attention``: no positional term at all; j <= i.
experts    ``incubate.moe.held``: sigmoid scores over all experts in
           float32, top-k by score (no selection bias, no scaling),
           renormalized; this chip's held experts' part. The shared
           experts' outputs are AVERAGED: they run as one gated product
           of ``n_shared * width`` whose result is scaled by 1/n_shared.
head       logit_scale * LayerNorm(x_L) E^T, the embedding tied.

Pages. ``cfg.page_kinds()`` declares K and V of the full layers under
the ``"slot"`` lifetime and K and V of the window layers under a window
lifetime of ``sliding_window`` positions: two block tables reach
``forward`` (``PagedPools.block_table`` a tuple), and entries of the
window table whose positions have left every later query's reach point
at the scratch page (``serving.kv_cache.WindowPages``). K and V are
cached at ``num_kv_heads``, never repeated. How attention reads, by
program:

decode     (S == 1) the Pallas ``paged_decode`` kernel: the query
           block-diagonal over the K/V heads' lanes, the sweep from the
           window's first page to the slot's last;
prefill    (S > 1 at position 0, the chunk inside the window) causal
           attention over the chunk's own rows: flash attention with the
           K/V blocks' index maps dividing the head index by the group;
context    (S > 1 at pos > 0; any chunk longer than the window) a blocked
           pass over the pages in reach with an online softmax: from the
           block of the chunk's first visible position to the block of
           its last row, so a window layer's work follows the window and
           not the context. An XLA composition; it is also what a decode
           step falls back to where the kernel is off.

The layers a chip holds are a Python loop (two kinds of layer, each with
a table of its own). bf16 weights and cache with float32 accumulation;
norms, softmax and router scores in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..incubate.moe.held import (gated_ffn, held_experts_ffn,
                                 sigmoid_topk_routing)
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer, LayerList
# what the two serving-form families compute alike: the float32-
# accumulated product, the interleaved rotary embedding, the pages a
# pass of the context takes
from .glm_moe_dsa import _mm, _pages_a_block, _rotary

__all__ = ["Cohere2MoeConfig", "Cohere2MoeForCausalLM", "cohere2_moe_tiny"]

F32 = jnp.float32
_NEG = -jnp.inf
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    #: width of ONE routed and of ONE shared expert
    intermediate_size: int = 4096
    n_routed_experts: int = 128
    num_experts_per_tok: int = 8
    n_shared_experts: int = 4
    #: (first expert, how many) this chip holds of ``n_routed_experts``
    experts_held: Tuple[int, int] = (0, 128)
    #: one entry a layer held here: ``sliding_attention`` | ``full_attention``
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    #: keys a window layer's query sees, its own among them
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    initializer_range: float = 0.02
    max_position_embeddings: int = 131072
    dtype: str = "float32"
    #: positions of the context one pass of the blocked attention takes
    context_block: int = 256

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads has to divide num_heads")
        if set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types {self.layer_types}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_group(self) -> int:
        return self.num_heads // self.num_kv_heads

    def page_kinds(self):
        """``k``/``v`` of the full layers for as long as the slot lives,
        ``k_window``/``v_window`` of the window layers for as long as a
        query can reach them."""
        from ..serving.kv_cache import PageKind
        width = self.num_kv_heads * self.head_dim
        kinds = []
        for names, kind, life in ((("k", "v"), FULL, "slot"),
                                  (("k_window", "v_window"), WINDOW,
                                   int(self.sliding_window))):
            layers = tuple(i for i, t in enumerate(self.layer_types)
                           if t == kind)
            if layers:
                kinds += [PageKind(n, width, layers, self.num_kv_heads, life)
                          for n in names]
        return tuple(kinds)


def cohere2_moe_tiny(**kw) -> Cohere2MoeConfig:
    """Test-size config: the published period of three window layers and
    a full one, a window of 8, 8 query heads on 2 K/V heads, 2 of 8
    experts held, 2 shared."""
    d = dict(vocab_size=256, hidden_size=64, num_heads=8, num_kv_heads=2,
             head_dim=16, intermediate_size=32, n_routed_experts=8,
             num_experts_per_tok=2, n_shared_experts=2, experts_held=(2, 2),
             sliding_window=8, max_position_embeddings=4096,
             context_block=8)
    d.update(kw)
    return Cohere2MoeConfig(**d)


# -- pieces, on raw arrays ------------------------------------------------------

def _layer_norm(x, w, eps):
    """Bias-free LayerNorm, mean and variance in float32."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)
            * w.astype(F32)).astype(x.dtype)


def paged_context_attention(q, k_pool, v_pool, table, base, pos, window,
                            block: int):
    """Attention ``[B, S, H*D]`` of ``S`` query rows at positions
    ``pos[b] + 0..S-1`` over what the pages hold, their own rows
    included: key ``j`` visible to query ``i`` iff ``j <= i`` and, under
    a ``window``, ``i - j < window``. ``q`` ``[B, S, H, D]``; the pools
    ``[N, 1, bs, Hkv*D]`` read through ``table`` ``[B, MB]`` at physical
    page ``base + entry``. One block of ``block`` positions at a time,
    folded into an online softmax, FROM the block of the batch's first
    visible position TO the block of its last row: the trip count follows
    what the queries can reach, entries before it are never read. K and V
    are not repeated over the query heads of a group."""
    B, S, H, D = q.shape
    bs, mb = k_pool.shape[2], table.shape[1]
    n_kv = k_pool.shape[-1] // D
    g = H // n_kv
    pb = _pages_a_block(mb, bs, block)
    kb = pb * bs
    q_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    first = jnp.maximum(pos - (window - 1), 0) if window \
        else jnp.zeros_like(pos)
    j0 = jnp.min(first) // kb
    j1 = jnp.minimum((jnp.max(pos) + S + kb - 1) // kb, mb // pb)
    scale = 1.0 / math.sqrt(D)
    dt = q.dtype
    qg = q.reshape(B, S, n_kv, g, D)

    def rows(pool, j):
        pages = jax.lax.dynamic_slice_in_dim(table, j * pb, pb, axis=1) + base
        return pool[pages].reshape(B, kb, n_kv, D)

    def body(j, carry):
        m, l, acc = carry
        s = jnp.einsum("bsngd,bknd->bngsk", qg, rows(k_pool, j),
                       preferred_element_type=F32)
        k_pos = j * kb + jnp.arange(kb, dtype=jnp.int32)
        ok = k_pos[None, None, :] <= q_pos[..., None]            # [B, S, kb]
        if window:
            ok &= q_pos[..., None] - k_pos[None, None, :] < window
        s = jnp.where(ok[:, None, None], s * scale, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        shift = jnp.where(m_new == _NEG, 0.0, m_new)
        p = jnp.exp(s - shift[..., None])
        fade = jnp.exp(m - shift)
        l = l * fade + jnp.sum(p, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bngsk,bknd->bngsd", p.astype(dt), rows(v_pool, j),
            preferred_element_type=F32)
        return m_new, l, acc

    init = (jnp.full((B, n_kv, g, S), _NEG, F32),
            jnp.zeros((B, n_kv, g, S), F32),
            jnp.zeros((B, n_kv, g, S, D), F32))
    _, l, acc = jax.lax.fori_loop(j0, j1, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # [B, n, g, S, D]
    return jnp.moveaxis(out, 3, 1).reshape(B, S, H * D).astype(dt)


# -- layers ---------------------------------------------------------------------

class _Norm(Layer):
    block = "norm"

    def __init__(self, width: int, dtype: str):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            (width,), default_initializer=Constant(1.0))


class CohereAttention(Layer):
    """The projections (attention itself is a function above)."""

    def __init__(self, cfg: Cohere2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        D, dh = cfg.hidden_size, cfg.head_dim
        init = Normal(0.0, cfg.initializer_range)
        mk = lambda *shape, i=init: self.create_parameter(
            shape, default_initializer=i)
        self.wq = mk(D, cfg.num_heads * dh)
        self.wk = mk(D, cfg.num_kv_heads * dh)
        self.wv = mk(D, cfg.num_kv_heads * dh)
        self.wo = mk(cfg.num_heads * dh, D,
                     i=Normal(0.0, cfg.initializer_range * depth_scale))


class CohereRouter(Layer):
    """The router's weight, float32 whatever the model's."""

    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__(dtype="float32")
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.n_routed_experts),
            default_initializer=Normal(0.0, cfg.initializer_range))


class CohereExperts(Layer):
    """The routed experts HELD here, stacked ``[held, ...]``; ``w_in``
    holds gate then up."""

    def __init__(self, cfg: Cohere2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        held, F = cfg.experts_held[1], cfg.intermediate_size
        self.w_in = self.create_parameter(
            (held, cfg.hidden_size, 2 * F),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.w_out = self.create_parameter(
            (held, F, cfg.hidden_size), default_initializer=Normal(
                0.0, cfg.initializer_range * depth_scale))


class CohereSharedExperts(Layer):
    """The ``n`` shared experts as ONE gated FFN of width ``n * F``:
    ``w_in`` ``[D, 2nF]`` holds the experts' gates side by side, then
    their ups (expert ``j``: columns ``jF .. jF+F-1`` of each half),
    ``w_out`` ``[nF, D]`` their down projections stacked by rows. The
    product is the SUM of the experts' outputs; the caller averages."""

    def __init__(self, cfg: Cohere2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        width = cfg.n_shared_experts * cfg.intermediate_size
        self.w_in = self.create_parameter(
            (cfg.hidden_size, 2 * width),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.w_out = self.create_parameter(
            (width, cfg.hidden_size), default_initializer=Normal(
                0.0, cfg.initializer_range * depth_scale))


class CohereMoE(Layer):
    def __init__(self, cfg: Cohere2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        self.router = CohereRouter(cfg)
        self.experts = CohereExperts(cfg, depth_scale)
        self.shared = CohereSharedExperts(cfg, depth_scale)


class CohereDecoderLayer(Layer):
    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        scale = 1.0 / math.sqrt(2 * cfg.num_layers)
        self.norm = _Norm(cfg.hidden_size, cfg.dtype)
        self.attn = CohereAttention(cfg, scale)
        self.moe = CohereMoE(cfg, scale)


class Cohere2MoeForCausalLM(Layer):
    """Embedding, the layers held here, final norm, tied head; serves
    through ``forward(ids, caches=<PagedPools>, cache_pos=<[B]>)``."""

    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([CohereDecoderLayer(cfg)
                                 for _ in cfg.layer_types])
        self.final_norm = _Norm(cfg.hidden_size, cfg.dtype)
        #: a dict for a probe to fill, or None; a list a key, in layer
        #: order: ``router_topk`` ``[B, S, k]``, the chosen experts;
        #: ``router_probe``, the float32 scores of the LAST row beside
        #: the operand they were computed from (``scores`` ``[B, E]``,
        #: ``x`` ``[B, D]``); ``attn_out`` ``[B, H*D]``, the last row's
        #: attention before ``W_o``
        self.taps: Optional[dict] = None

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None):
        from ..serving.kv_cache import ContextPagedPools, PagedPools
        if not isinstance(caches, PagedPools):
            raise ValueError(
                "cohere2_moe serves over paged state: forward needs "
                "caches=<PagedPools>; the cache-free forward is the "
                "reference's (benchmark/reference/cohere2_moe.py)")
        if caches.scales is not None:
            raise ValueError("cohere2_moe does not read int8 pages")
        cfg = self.cfg
        ids, pos = input_ids._data, cache_pos._data.astype(jnp.int32)
        kinds = cfg.page_kinds()
        # a table a lifetime, the slot lifetime's first (it is there even
        # when no layer held here is a full one)
        tables = caches.block_table
        tables = [t._data for t in (tables if isinstance(tables, tuple)
                                    else (tables,))]
        life = {"slot": tables[0]}
        life.update(zip(dict.fromkeys(kd.lifetime for kd in kinds
                                      if kd.lifetime != "slot"), tables[1:]))
        # the pools as one pool of L_kind * P pages a kind (a bitcast),
        # layer l of a kind at pages l*P + table
        pools, n_pages = {}, {}
        for kd, p in zip(kinds, caches.pools):
            a = p._data
            n_pages[kd.name] = a.shape[1]
            pools[kd.name] = a.reshape((-1,) + a.shape[2:])
        B, S = ids.shape
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        ctx = isinstance(caches, ContextPagedPools)
        with jax.named_scope("embed"):
            x = self.embed._data[ids]
        stats, seen = {}, {WINDOW: 0, FULL: 0}
        for li, layer in enumerate(self.layers):
            kind = cfg.layer_types[li]
            names = ("k", "v") if kind == FULL else ("k_window", "v_window")
            with jax.named_scope("norm"):
                h = _layer_norm(x, layer.norm.weight._data,
                                cfg.layer_norm_eps)
            a, pools = self._attention(
                layer.attn, h, positions, pos, pools, names,
                life["slot" if kind == FULL else cfg.sliding_window],
                seen[kind] * n_pages[names[0]],
                cfg.sliding_window if kind == WINDOW else 0, ctx)
            seen[kind] += 1
            with jax.named_scope("moe"):
                y = self._moe(layer.moe, h, stats, li)
            # the parallel block: both halves read the SAME h
            x = x + a + y
        with jax.named_scope("norm"):
            x = _layer_norm(x, self.final_norm.weight._data,
                            cfg.layer_norm_eps)
        logits = jnp.dot(x, self.embed._data.T, preferred_element_type=F32)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        new = tuple(Tensor(pools[kd.name].reshape(p._data.shape))
                    for kd, p in zip(kinds, caches.pools))
        return Tensor(logits), caches._replace(
            pools=new, stats=stats if S == 1 else None)

    # -- attention ---------------------------------------------------------------
    def _attention(self, at, h, positions, pos, pools, names, table, base,
                   window: int, ctx: bool):
        from ..ops import pallas as pallas_ops
        from ..serving.kv_cache import write_pages
        cfg = self.cfg
        B, S, _ = h.shape
        H, n_kv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        with jax.named_scope("attn"):
            q = _mm(h, at.wq._data).reshape(B, S, H, D)
            k = _mm(h, at.wk._data).reshape(B, S, n_kv, D)
            v = _mm(h, at.wv._data).reshape(B, S, n_kv, D)
            if window:
                q = _rotary(q, positions, cfg.rope_theta)
                k = _rotary(k, positions, cfg.rope_theta)
        with jax.named_scope("kv_write"):
            pools = dict(pools)
            pools[names[0]] = write_pages(pools[names[0]], k, table, pos, base)
            pools[names[1]] = write_pages(pools[names[1]], v, table, pos, base)
        kp, vp = pools[names[0]], pools[names[1]]
        with jax.named_scope("attn"):
            if S == 1 and pallas_ops.kernel_enabled("paged_decode"):
                from ..ops.pallas.paged_decode import paged_decode_attention
                o = paged_decode_attention(
                    q[:, 0], kp, vp, table + base, pos,
                    scale=1.0 / math.sqrt(D),
                    first=jnp.maximum(pos - (window - 1), 0) if window
                    else None).reshape(B, 1, H * D)
            elif S > 1 and not ctx and (not window or S <= window):
                # a fresh slot's chunk inside the window: causal over its
                # own rows, K and V at their own head count
                from ..ops.attention import sdpa_array
                o = sdpa_array(q, k, v, is_causal=True).reshape(B, S, H * D)
            else:
                o = paged_context_attention(q, kp, vp, table, base, pos,
                                            window, cfg.context_block)
            if self.taps is not None:
                self.taps.setdefault("attn_out", []).append(o[:, -1])
            return _mm(o, at.wo._data), pools

    # -- experts -------------------------------------------------------------------
    def _moe(self, moe, h, stats, li):
        cfg = self.cfg
        B, S, D = h.shape
        flat = h.reshape(B * S, D)
        # the router's operand, ONE array for its product and for a probe
        flat32 = flat.astype(F32)
        routing = sigmoid_topk_routing(
            flat32, moe.router.weight._data,
            jnp.zeros((cfg.n_routed_experts,), F32),
            cfg.num_experts_per_tok)
        if self.taps is not None:
            self.taps.setdefault("router_topk", []).append(
                routing.idx.reshape(B, S, -1))
            self.taps.setdefault("router_probe", []).append(dict(
                scores=routing.scores.reshape(B, S, -1)[:, -1],
                x=flat32.reshape(B, S, D)[:, -1]))
        first, held = cfg.experts_held
        y, _, here = held_experts_ffn(
            flat, routing, moe.experts.w_in._data, moe.experts.w_out._data,
            first)
        with jax.named_scope("ffn"):
            # the shared experts, averaged: 1/n is a power of two here
            # and exact in every dtype
            y = y + gated_ffn(flat, moe.shared.w_in._data,
                              moe.shared.w_out._data) / cfg.n_shared_experts
        if S == 1:
            # a row a slot, for the engine's counters (active slots only)
            # emits-metrics: serve_moe_routed_tokens_total, serve_moe_skipped_pairs_total
            given = jnp.sum(
                (routing.idx - first)[..., None] == jnp.arange(held),
                axis=1, dtype=jnp.int32)                        # [B, held]
            skipped = jnp.sum(~here, axis=1, dtype=jnp.int32)
            for key, v in (("serve_moe_routed_tokens_total:expert", given),
                           ("serve_moe_skipped_pairs_total", skipped)):
                stats[key] = stats[key] + v if key in stats else v
        return y.reshape(B, S, D).astype(h.dtype)
