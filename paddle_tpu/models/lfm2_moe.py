"""Decoder family ``lfm2_moe`` (LFM2-24B-A2B): gated short-convolution
layers and GQA attention layers in a period, a leading dense FFN, then
expert layers with every expert held — the serving form, over paged
state beside a state a slot.

``x`` is a position's residual row; the equations (the plain reference,
``benchmark/reference/lfm2_moe.py``, follows the same ones and notes
what the published config leaves open):

layer      h = x + op(RMSNorm_op(x)); x' = h + ffn(RMSNorm_ffn(h)); after
           the last layer RMSNorm_final and the head, the embedding
           tied. RMSNorm(v) = v rsqrt(mean(v^2) + eps) w, float32 inside.
conv       [B | C | X] = h W_in (three of ``hidden`` in that order);
           u_t = B_t * X_t; z_t = sum_{j<L} k_j * u_{t-L+1+j} with u = 0
           before position 0 (a depthwise causal kernel ``k`` [L, D],
           ``L`` = ``conv_L_cache``); op = (C_t * z_t) W_out. No bias.
attention  q = h W_q, k = h W_k, v = h W_v: ``num_heads`` query heads on
           ``num_kv_heads`` K/V heads of ``head_dim``; each q and k head
           RMS-normed over its dims, then a half-split rotary embedding
           (``rotate_half``); causal softmax(q k^T / sqrt(d)) v, W_o.
dense FFN  W_2 (silu(h W_1) * h W_3).
experts    ``incubate.moe.held`` with every expert held and no shared
           expert: s = sigmoid(h W_r) in float32, top-k of s + b,
           g = scale * s / (sum of the chosen s + ``router_eps``).

What a slot keeps, by kind (``cfg.page_kinds()``, ``cfg.state_kinds()``):
``k`` and ``v`` of the attention layers in pages, as long as the slot
lives; ``conv``, the last ``L - 1`` inputs ``u`` of each conv layer, as
one row of the state array (``serving.kv_cache.StateKind``: no position
axis). A program's row starts from its slot's state, or from zeros where
its first position is 0, and writes back ``u`` at its last ``L - 1``
REAL positions (``PagedPools.rows``): a chunk of one real row keeps the
older state's last entry, a padded tail and a padded or inactive row
(the scratch row) move no slot's state.

How attention reads, by program: decode (S == 1) the Pallas
``paged_decode`` kernel over the K/V heads' lanes; a chunk at position
0 causal attention over its own rows (flash attention with grouped K/V
heads); a chunk past 0 the blocked pass over the pages of
``cohere2_moe.paged_context_attention``. The layers a chip holds are a
Python loop. bf16 weights and cache with float32 accumulation; norms,
softmax and router scores in float32; the convolution's input is
rounded to the cache's dtype before it is used or kept, so a row reads
the same ``u`` whether it came from this program or from the state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..incubate.moe.held import gated_ffn
from ..nn.initializer import Normal
from ..nn.layer import Layer, LayerList
from .cohere2_moe import paged_context_attention
# what the serving-form families compute alike: the product accumulated
# in float32, the norm, the rotary embedding's half-split form, the
# expert layer and its modules
from .glm_moe_dsa import (GlmExperts, GlmFFN, GlmRouter, _Norm, _mm,
                          _rms_norm, _rotary_half, moe_layer)

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_moe_tiny",
           "short_conv"]

F32 = jnp.float32
CONV, ATTN = "conv", "full_attention"


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    #: added to the chosen scores' sum before the weights are divided by it
    router_eps: float = 1e-6
    #: (first expert, how many) this chip holds of ``n_routed_experts``
    experts_held: Tuple[int, int] = (0, 64)
    #: one entry a layer held here: ``conv`` | ``full_attention``
    layer_types: Tuple[str, ...] = (CONV,)
    #: one entry a layer held here: ``dense`` | ``sparse``
    mlp_layer_types: Tuple[str, ...] = ("dense",)
    #: the convolution's length, the current input among them
    conv_L_cache: int = 3
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    max_position_embeddings: int = 128000
    dtype: str = "float32"
    #: positions of the context one pass of the blocked attention takes
    context_block: int = 256

    #: a decode step counts the experts each expert layer reads
    #: (``glm_moe_dsa.moe_layer``)
    count_experts_read = True

    def __post_init__(self):
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("one operator and one mlp type a layer")
        if set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(f"layer_types {self.layer_types}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads has to divide num_heads")
        if self.conv_L_cache < 2:
            raise ValueError("a short convolution of length < 2 keeps no "
                             "state")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def page_kinds(self):
        """``k``/``v`` of the attention layers, for as long as the slot
        lives, at ``num_kv_heads`` heads (never repeated)."""
        from ..serving.kv_cache import PageKind
        width = self.num_kv_heads * self.head_dim
        return tuple(PageKind(n, width, self.layers_of(ATTN),
                              self.num_kv_heads) for n in ("k", "v"))

    def state_kinds(self):
        """``conv``: the last ``conv_L_cache - 1`` inputs of each conv
        layer, a slot."""
        from ..serving.kv_cache import StateKind
        return (StateKind("conv", (self.conv_L_cache - 1, self.hidden_size),
                          self.layers_of(CONV)),)


def lfm2_moe_tiny(**kw) -> Lfm2MoeConfig:
    """Test-size config: a dense conv layer, then one whole period of
    the published pattern (attention, conv, conv, conv) as expert
    layers; 8 query heads on 2 K/V heads, 8 experts top-4 all held."""
    d = dict(vocab_size=256, hidden_size=64, num_heads=8, num_kv_heads=2,
             head_dim=16, intermediate_size=128, moe_intermediate_size=32,
             n_routed_experts=8, num_experts_per_tok=4, experts_held=(0, 8),
             layer_types=(CONV, ATTN, CONV, CONV, CONV),
             mlp_layer_types=("dense",) + ("sparse",) * 4,
             max_position_embeddings=4096, context_block=8)
    d.update(kw)
    return Lfm2MoeConfig(**d)


# -- pieces, on raw arrays ------------------------------------------------------

def short_conv(h, w_in, kernel, w_out, state, layer, slots, lens, pos):
    """A gated short convolution over ``h`` ``[B, S, D]`` whose rows
    begin at positions ``pos`` ``[B]`` with ``lens`` ``[B]`` real
    positions. ``kernel`` ``[L, D]``; ``state`` ``[L_conv, slots + 1,
    L - 1, D]``, of which ``state[layer, slots[b]]`` is row ``b``'s.
    Returns ``(op [B, S, D], state)``: a row at position 0 reads zeros,
    not its slot's row, and every row writes back its last ``L - 1``
    real inputs (a row of fewer keeps the older entries)."""
    B, S, D = h.shape
    L = kernel.shape[0]
    bcx = _mm(h, w_in)
    b, c, x = jnp.split(bcx, 3, axis=-1)
    # the input as a later program reads it from the state
    u = (b.astype(F32) * x.astype(F32)).astype(state.dtype)
    held = state[layer, slots]                                 # [B, L-1, D]
    held = jnp.where((pos == 0)[:, None, None], jnp.zeros_like(held), held)
    ext = jnp.concatenate([held, u], axis=1)                   # [B, S+L-1, D]
    k = kernel.astype(F32)
    z = ext[:, :S].astype(F32) * k[0]
    for j in range(1, L):
        z = z + ext[:, j:j + S].astype(F32) * k[j]
    op = _mm((c.astype(F32) * z).astype(h.dtype), w_out)
    # the inputs at the row's positions n - L + 1 .. n - 1: ext rows
    # n .. n + L - 2
    keep = lens[:, None] + jnp.arange(L - 1, dtype=jnp.int32)[None, :]
    new = jnp.take_along_axis(ext, keep[..., None], axis=1)
    return op, state.at[layer, slots].set(new)


# -- layers ---------------------------------------------------------------------

class Lfm2ShortConv(Layer):
    """The conv operator: ``w_in`` ``[D, 3D]`` (B, C, X side by side),
    the depthwise kernel ``[L, D]`` (``kernel[j]`` meets the input
    ``L - 1 - j`` positions back), ``w_out`` ``[D, D]``."""

    block = "conv"

    def __init__(self, cfg: Lfm2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        D = cfg.hidden_size
        init = Normal(0.0, cfg.initializer_range)
        self.w_in = self.create_parameter((D, 3 * D), default_initializer=init)
        # a depthwise kernel of L taps is seeded at 1/sqrt(L) a tap, so a
        # tap moves the output as much as a product's input column does
        self.kernel = self.create_parameter(
            (cfg.conv_L_cache, D),
            default_initializer=Normal(0.0, cfg.conv_L_cache ** -0.5))
        self.w_out = self.create_parameter(
            (D, D), default_initializer=Normal(
                0.0, cfg.initializer_range * depth_scale))


class Lfm2Attention(Layer):
    """The projections and the per-head q and k norms."""

    def __init__(self, cfg: Lfm2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        D, dh = cfg.hidden_size, cfg.head_dim
        init = Normal(0.0, cfg.initializer_range)
        mk = lambda *shape, i=init: self.create_parameter(
            shape, default_initializer=i)
        self.wq = mk(D, cfg.num_heads * dh)
        self.wk = mk(D, cfg.num_kv_heads * dh)
        self.wv = mk(D, cfg.num_kv_heads * dh)
        self.q_norm = _Norm(dh, cfg.dtype)
        self.k_norm = _Norm(dh, cfg.dtype)
        self.wo = mk(cfg.num_heads * dh, D,
                     i=Normal(0.0, cfg.initializer_range * depth_scale))


class Lfm2MoE(Layer):
    """Router (with its selection bias) and the experts held; no shared
    expert."""

    def __init__(self, cfg: Lfm2MoeConfig, depth_scale: float):
        super().__init__(dtype=cfg.dtype)
        self.router = GlmRouter(cfg)
        self.experts = GlmExperts(cfg, depth_scale)


class Lfm2DecoderLayer(Layer):
    def __init__(self, cfg: Lfm2MoeConfig, op: str, mlp: str):
        super().__init__(dtype=cfg.dtype)
        scale = 1.0 / math.sqrt(2 * cfg.num_layers)
        self.op_norm = _Norm(cfg.hidden_size, cfg.dtype)
        if op == CONV:
            self.conv = Lfm2ShortConv(cfg, scale)
        else:
            self.attn = Lfm2Attention(cfg, scale)
        self.ffn_norm = _Norm(cfg.hidden_size, cfg.dtype)
        if mlp == "dense":
            self.mlp = GlmFFN(cfg, cfg.intermediate_size, scale)
        else:
            self.moe = Lfm2MoE(cfg, scale)


class Lfm2MoeForCausalLM(Layer):
    """Embedding, the layers held here, final norm, tied head; serves
    through ``forward(ids, caches=<PagedPools>, cache_pos=<[B]>)`` with
    the conv state in ``caches.state`` and the rows' slots and real
    lengths in ``caches.rows``."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([
            Lfm2DecoderLayer(cfg, op, mlp)
            for op, mlp in zip(cfg.layer_types, cfg.mlp_layer_types)])
        self.final_norm = _Norm(cfg.hidden_size, cfg.dtype)
        #: a dict for a probe to fill, or None; a list a key, in expert
        #: layer order: ``router_topk`` ``[B, S, k]``, the chosen
        #: experts; ``router_probe``, the float32 scores of the LAST row
        #: beside the operand they were computed from (``scores``
        #: ``[B, E]``, ``x`` ``[B, D]``)
        self.taps: Optional[dict] = None

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None):
        from ..serving.kv_cache import ContextPagedPools, PagedPools
        if not isinstance(caches, PagedPools) or caches.state is None:
            raise ValueError(
                "lfm2_moe serves over paged state and a conv state a slot: "
                "forward needs caches=<PagedPools> with state and rows; "
                "the cache-free forward is the reference's "
                "(benchmark/reference/lfm2_moe.py)")
        if caches.scales is not None:
            raise ValueError("lfm2_moe does not read int8 pages")
        cfg = self.cfg
        ids, pos = input_ids._data, cache_pos._data.astype(jnp.int32)
        table = caches.block_table._data
        slots, lens = (t._data for t in caches.rows)
        # the pools as one pool of L_kind * P pages a kind (a bitcast),
        # attention layer l at pages l*P + table
        pools = {kd.name: p._data.reshape((-1,) + p._data.shape[2:])
                 for kd, p in zip(cfg.page_kinds(), caches.pools)}
        n_pages = caches.pools[0]._data.shape[1]
        state = caches.state[0]._data
        B, S = ids.shape
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        ctx = isinstance(caches, ContextPagedPools)
        with jax.named_scope("embed"):
            x = self.embed._data[ids]
        stats, n_attn, n_conv = {}, 0, 0
        for li, layer in enumerate(self.layers):
            with jax.named_scope("norm"):
                h = _rms_norm(x, layer.op_norm.weight._data,
                              cfg.rms_norm_eps)
            if cfg.layer_types[li] == CONV:
                cv = layer.conv
                with jax.named_scope("conv"):
                    a, state = short_conv(h, cv.w_in._data, cv.kernel._data,
                                          cv.w_out._data, state, n_conv,
                                          slots, lens, pos)
                n_conv += 1
            else:
                a, pools = self._attention(layer.attn, h, positions, pos,
                                           table, pools, n_attn * n_pages,
                                           ctx)
                n_attn += 1
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
                    # emits-metrics: serve_moe_routed_tokens_total, serve_moe_skipped_pairs_total, serve_moe_experts_read_total
                    y = moe_layer(layer.moe, h, stats, cfg, self.taps)
            x = x + y
        with jax.named_scope("norm"):
            x = _rms_norm(x, self.final_norm.weight._data, cfg.rms_norm_eps)
        logits = jnp.dot(x, self.embed._data.T, preferred_element_type=F32)
        new = tuple(Tensor(pools[kd.name].reshape(p._data.shape))
                    for kd, p in zip(cfg.page_kinds(), caches.pools))
        return Tensor(logits), caches._replace(
            pools=new, state=(Tensor(state),),
            stats=stats if S == 1 else None)

    # -- attention ---------------------------------------------------------------
    def _attention(self, at, h, positions, pos, table, pools, base, ctx):
        from ..ops import pallas as pallas_ops
        from ..serving.kv_cache import write_pages
        cfg = self.cfg
        B, S, _ = h.shape
        H, n_kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        eps = cfg.rms_norm_eps
        with jax.named_scope("attn"):
            q = _mm(h, at.wq._data).reshape(B, S, H, dh)
            k = _mm(h, at.wk._data).reshape(B, S, n_kv, dh)
            v = _mm(h, at.wv._data).reshape(B, S, n_kv, dh)
            q = _rotary_half(_rms_norm(q, at.q_norm.weight._data, eps),
                             positions, cfg.rope_theta)
            k = _rotary_half(_rms_norm(k, at.k_norm.weight._data, eps),
                             positions, cfg.rope_theta)
        with jax.named_scope("kv_write"):
            pools = dict(pools, k=write_pages(pools["k"], k, table, pos, base),
                         v=write_pages(pools["v"], v, table, pos, base))
        kp, vp = pools["k"], pools["v"]
        with jax.named_scope("attn"):
            if S == 1 and pallas_ops.kernel_enabled("paged_decode"):
                from ..ops.pallas.paged_decode import paged_decode_attention
                o = paged_decode_attention(
                    q[:, 0], kp, vp, table + base, pos,
                    scale=dh ** -0.5).reshape(B, 1, H * dh)
            elif S > 1 and not ctx:
                # a fresh slot's chunk: causal over its own rows, K and V
                # at their own head count
                from ..ops.attention import sdpa_array
                o = sdpa_array(q, k, v, is_causal=True).reshape(B, S, H * dh)
            else:
                o = paged_context_attention(q, kp, vp, table, base, pos, 0,
                                            cfg.context_block)
            return _mm(o, at.wo._data), pools
