"""GPT: decoder-only language model — the flagship of the parallel stack.

reference parity: the reference trains GPT through
fleet/meta_parallel/parallel_layers/mp_layers.py (VocabParallelEmbedding:30,
ColumnParallelLinear:97, RowParallelLinear:170, ParallelCrossEntropy:249)
plus the fused attention kernels (paddle/fluid/operators/fused/
fused_attention_op.cu, fused_feedforward_op.cu), wiring NCCL allreduces by
hand between the sharded matmuls.

TPU-native design (GSPMD, single logical program):
- Every parameter is the FULL logical array annotated with a PartitionSpec
  on the ``mp`` mesh axis (QKV/MLP-in column-sharded, attn-out/MLP-out
  row-sharded, vocab embedding row-sharded). Under jit over a mesh, XLA's
  SPMD partitioner lays the weights out and inserts the same psums the
  reference's c_allreduce_sum ops perform — no hand-written collectives.
- QKV is ONE fused matmul ([E] x [E, 3·H·D]) for MXU utilisation; the
  weight is stored [E, 3, H, D] so the mp sharding rides the head axis and
  the reshape to per-head layout is communication-free.
- Attention routes through ops.attention (Pallas flash kernel when
  eligible, fused XLA softmax otherwise), causal.
- The LM head ties the vocab-parallel embedding weight; logits stay
  vocab-sharded into ParallelCrossEntropy (the c_softmax_with_cross_entropy
  pattern) so the [B, S, V] logits tensor is never materialised replicated.
- ``use_recompute`` wraps each block in jax.checkpoint (reference:
  fleet/utils/recompute.py) to trade FLOPs for HBM. By default a block
  keeps what only a kernel, a product or a collective would rebuild (the
  flash kernel's output and log-sum-exp, the QKV and FFN-in products,
  the attention branch after its dropout), so its recomputed forward
  runs no product.
- ``sequence_parallel`` pins the residual stream's seq axis to the ``sp``
  mesh axis so LayerNorm/dropout activations are sequence-sharded
  (reference: sequence_parallel_utils.py scatter/gather pattern).
"""

from __future__ import annotations

import collections
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.flags import matmul_precision
from ..core.tensor import apply
from ..distributed import env as dist_env
from ..distributed.fleet.utils.recompute import (LAYER_RESIDUAL_NAMES,
                                                  recompute)
from ..distributed.meta_parallel.parallel_layers.mp_layers import (
    VocabParallelEmbedding, ParallelCrossEntropy)
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Dropout, Embedding
from ..nn.layers.norm import LayerNorm
from ..nn.scan import (can_scan_layers, note_scan_fallback, scan_layers,
                       scan_layers_with_cache)

__all__ = ["GPTConfig", "GPTModel", "GPTForPretraining", "GPTForPretrainingPipe",
           "GPTPretrainingCriterion", "GPTMoEDecoderLayer",
           "gpt_tiny", "gpt2_small", "gpt2_medium", "gpt2_large", "gpt2_xl"]

MP = "mp"
SP = "sp"


@dataclass
class GPTConfig:
    vocab_size: int = 50304           # padded to a multiple of 128 for the MXU
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: Optional[int] = None   # default 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    use_recompute: bool = False
    #: remat policy name for use_recompute (see
    #: fleet.utils.recompute.resolve_checkpoint_policy). None keeps what
    #: a recomputed block would rebuild with a kernel, a product or a
    #: collective: the flash kernel's output and log-sum-exp, the fused
    #: QKV product, the FFN's first product before GELU and the attention
    #: branch (the out-projection after its tensor-parallel all-reduce and
    #: dropout, which the mid-layer residual add reads): 144.5 MiB a layer
    #: at B=8, S=1024, E=1024 under AMP O1. The block then runs its two
    #: norms, that add and GELU again and no product; its loss and
    #: gradients are what 'full' gives (bit for bit on the CPU; on a TPU
    #: the gradients' last bits follow each program's order of summation,
    #: as 'full' and no recompute differ from each other there).
    #: 'dots_with_no_batch_dims_saveable' keeps every MXU output
    #: besides. 'full' keeps nothing, every product and the flash forward
    #: run a second time: for a stack that does not fit memory otherwise.
    recompute_policy: Optional[str] = None
    #: run the decoder stack as one jax.lax.scan over layer-stacked params
    #: (nn.scan): O(1) trace+compile in num_layers, per-layer state_dict
    #: names and LayerList API unchanged. Falls back to the Python loop for
    #: KV-cache decoding or heterogeneous stacks.
    scan_layers: bool = True
    sequence_parallel: bool = False
    #: Mixture-of-Experts (ISSUE 10, docs/MOE.md): moe_experts > 0 swaps
    #: the FFN of every ``moe_every``-th decoder layer (layer i is MoE
    #: iff (i+1) % moe_every == 0; moe_every=1 = every layer, the
    #: homogeneous stack that scans as ONE lax.scan) for an
    #: incubate.moe.MoELayer with ``moe_experts`` stacked ExpertFFN
    #: experts (hidden = ffn_size), top-``moe_top_k`` routing at
    #: ``moe_capacity_factor``. The router aux/z losses are weighted by
    #: moe_aux_weight/moe_z_weight into ``GPTModel.moe_loss()``; add it
    #: to the CE in the training loss_fn. Dense layer state_dict names
    #: are unchanged; MoE layers add ``layers.<i>.moe.*`` leaves.
    moe_experts: int = 0
    moe_every: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 1e-2
    moe_z_weight: float = 1e-3

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def page_kinds(self):
        """What a serving engine keeps in pages for this model
        (``serving.kv_cache.PageKind``): K and V of every head in every
        layer."""
        from ..serving.kv_cache import kv_page_kinds
        return kv_page_kinds(self.num_layers, self.num_heads, self.head_dim)

    def moe_layer_indices(self):
        """Decoder-layer indices that carry an MoE FFN."""
        if not self.moe_experts:
            return []
        k = max(1, int(self.moe_every))
        return [i for i in range(self.num_layers) if (i + 1) % k == 0]


def _mesh():
    return dist_env.get_mesh()


_ATTN_BRANCH, _FFN_IN, _QKV = LAYER_RESIDUAL_NAMES


def _name_value(a, tag):
    return checkpoint_name(a, tag)


def _keep(t, tag):
    """``t`` named ``tag`` for the remat policy, which keeps it
    (``resolve_checkpoint_policy``). Only a training forward without a
    cache names anything: a serving program holds no ``name`` equation."""
    return apply(_name_value, t, name="checkpoint_name", tag=tag)


# shared layout-pin helper; BATCH expands to the composite data axes
# (('dp', 'sharding')) so activation pins agree with TrainStep's data_spec
from ..distributed.spmd import BATCH, constrain as _constrain  # noqa: E402


def _seq_spec(cfg) -> Optional[str]:
    """Mesh axis for the sequence dim of the residual stream (or None)."""
    if not cfg.sequence_parallel:
        return None
    mesh = _mesh()
    if mesh is not None and SP in mesh.axis_names:
        return SP
    return None


class GPTAttention(Layer):
    """Causal self-attention with ONE fused QKV matmul, head-sharded over mp.

    reference: fused_attention_op.cu computes qkv in one gemm then runs the
    fmha kernel; mp_layers.py shards qkv column-wise + out row-wise. Here the
    qkv weight is [E, 3, H, D] with spec P(None, None, 'mp', None): one
    logical gemm, head axis sharded, zero-copy reshape to [B, S, H, D].
    """

    block = "attn"

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        E, H, D = cfg.hidden_size, cfg.num_heads, cfg.head_dim
        self.cfg = cfg
        self.num_heads, self.head_dim = H, D
        init = Normal(0.0, cfg.initializer_range)
        # scaled init for the residual-out projection (GPT-2 paper)
        out_init = Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers))
        self.qkv_weight = self.create_parameter((E, 3, H, D),
                                                default_initializer=init)
        self.qkv_weight.spec = P(None, None, MP, None)
        self.qkv_bias = self.create_parameter((3, H, D), is_bias=True)
        self.qkv_bias.spec = P(None, MP, None)
        self.out_weight = self.create_parameter((H, D, E),
                                                default_initializer=out_init)
        self.out_weight.spec = P(MP, None, None)
        self.out_bias = self.create_parameter((E,), is_bias=True)
        self.out_bias.spec = P()

    #: fixed-size KV buffers [B, L_max, H, D] for jit-compatible decoding
    #: (reference generation uses growing concat caches; on TPU a static
    #: buffer + dynamic_update_slice keeps every decode step the same
    #: compiled program)
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def forward(self, x, cache=None, pos=None):
        cfg = self.cfg
        prec = matmul_precision()

        def qkv_fn(h, w, b):
            y = jnp.einsum("bse,ethd->bsthd", h, w, precision=prec) + b
            return y

        qkv = apply(qkv_fn, x, self.qkv_weight, self.qkv_bias, name="fused_qkv")
        # the serving layer is only imported once a paged cache actually
        # arrives — training forwards (cache=None) never touch it
        is_paged = False
        if cache is not None and \
                not isinstance(cache, GPTAttention.StaticCache):
            from ..serving.kv_cache import PagedLayerCache
            is_paged = isinstance(cache, PagedLayerCache)
        if is_paged and cache.lora_a is not None:
            # multi-tenant LoRA (serving.lora): per-slot adapter deltas
            # on the fused QKV projection, batched over adapters via
            # bgmv. Absent pools (the default) add nothing to the graph.
            qkv = qkv + self._lora_delta(x, cache)
        qkv = _constrain(qkv, BATCH, None, None, MP, None)
        if self.training and cache is None:
            qkv = _keep(qkv, _QKV)
        from ..tensor.manipulation import split as tsplit, squeeze
        q, k, v = (squeeze(t, 2) for t in tsplit(qkv, 3, axis=2))

        if is_paged:
            out, cache = self._paged_attention(x, q, k, v, cache, pos)
        elif isinstance(cache, GPTAttention.StaticCache):
            # write this chunk's K/V into the preallocated buffers at pos
            def upd(buf, new, p):
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype),
                    (0, p.astype(jnp.int32), 0, 0))

            kb = apply(upd, cache.k, k, pos, name="kv_cache_update")
            vb = apply(upd, cache.v, v, pos, name="kv_cache_update")
            cache = GPTAttention.StaticCache(kb, vb)
            S = x.shape[1]
            L = kb.shape[1]

            # row i of the chunk sees cache slots j <= pos + i
            def mk_mask(p):
                rows = p + jnp.arange(S, dtype=jnp.int32)[:, None]
                cols = jnp.arange(L, dtype=jnp.int32)[None, :]
                return jnp.where(cols <= rows, 0.0, -1e30)[None, None]

            mask = apply(mk_mask, pos, name="kv_cache_mask")
            from ..ops.attention import scaled_dot_product_attention
            out = scaled_dot_product_attention(
                q, kb, vb, attn_mask=mask, dropout_p=0.0, is_causal=False,
                training=False)
        else:
            if cache is not None:
                from ..tensor.manipulation import concat
                k = concat([cache[0], k], axis=1)
                v = concat([cache[1], v], axis=1)
                cache = (k, v)

            from ..ops.attention import scaled_dot_product_attention
            out = scaled_dot_product_attention(
                q, k, v, dropout_p=cfg.attention_dropout_prob,
                is_causal=True, training=self.training)   # [B, S, H, D]
        out = _constrain(out, BATCH, None, MP, None)

        def out_fn(o, w, b):
            return jnp.einsum("bshd,hde->bse", o, w, precision=prec) + b

        y = apply(out_fn, out, self.out_weight, self.out_bias, name="attn_out")
        return (y, cache) if cache is not None else y

    def _lora_delta(self, x, cache):
        """Batched-LoRA delta for the fused QKV projection
        (serving.lora, ISSUE 17): each slot's adapter row of the stacked
        ``[A, r, E]`` / ``[A, r, 3*H*D]`` pools is gathered + applied by
        the bgmv kernel (``FLAGS_pallas_bgmv``; off = the bit-compatible
        XLA gather+einsum oracle). Returns ``[B, S, 3, H, D]`` in x's
        dtype — row-0 (zero-adapter) slots contribute exactly 0.0."""
        from ..ops import pallas as pallas_ops
        # dispatch resolved OUTSIDE the traced fn, like paged_decode
        use_kernel = pallas_ops.kernel_enabled("bgmv")
        H, D = self.num_heads, self.head_dim

        def delta_fn(h, a, b, ids):
            if use_kernel:
                from ..ops.pallas.bgmv import bgmv as _bgmv
            else:
                from ..ops.pallas.bgmv import bgmv_xla as _bgmv
            d = _bgmv(h, a, b, ids.astype(jnp.int32))     # [B, S, 3*H*D]
            return d.reshape(d.shape[0], d.shape[1], 3, H, D)

        return apply(delta_fn, x, cache.lora_a, cache.lora_b,
                     cache.lora_ids, name="lora_qkv_delta")

    def _paged_attention(self, x, q, k, v, cache, pos):
        """Block-table K/V path (paddle_tpu.serving, ISSUE 6).

        ``cache``: :class:`~paddle_tpu.serving.kv_cache.PagedLayerCache`
        (the WHOLE lane-dense pools ``[L*P, G, bs, (H/G)*D]``, the
        ``[B, MB]`` block table and this layer's first page
        ``page_base``); ``pos``: per-slot write positions ``[B]``. The
        chunk's K/V rows scatter into the pool at logical positions
        ``pos + 0..S-1`` of pages ``page_base + table`` (a bucketed
        prefill's padded tail routes to the layer's scratch page);
        nothing else of the pool moves. Prefill (S > 1, fresh slots)
        attends causally over its own K/V — the exact math of the
        full-context forward; decode (S == 1) reads the slot's pages
        through the Pallas kernel or, as the fallback, gathers them and
        masks columns past ``pos``, i.e. PagedAttention as one XLA
        gather + masked SDPA; context prefill (S > 1 at ``pos > 0``)
        is that same gather with one mask row per chunk row.

        A quantized cache (``cache.k_scale is not None``,
        ``FLAGS_serve_kv_quant=int8``) quantizes at write time and
        dequantizes at every page read — both the Pallas decode kernel
        and the XLA gather fallback — so the two dispatch paths stay
        token-exact against each other.
        """
        from ..serving.kv_cache import (ContextPagedLayerCache,
                                        gather_pages, gather_pages_quant,
                                        write_pages, write_pages_quant)

        quant = cache.k_scale is not None
        table, base = cache.block_table, cache.page_base
        with jax.named_scope("kv_write"):
            if quant:
                kp, ksc = apply(write_pages_quant, cache.k_pages,
                                cache.k_scale, k, table, pos, base,
                                name="paged_kv_write_quant")
                vp, vsc = apply(write_pages_quant, cache.v_pages,
                                cache.v_scale, v, table, pos, base,
                                name="paged_kv_write_quant")
            else:
                kp = apply(write_pages, cache.k_pages, k, table, pos,
                           base, name="paged_kv_write")
                vp = apply(write_pages, cache.v_pages, v, table, pos,
                           base, name="paged_kv_write")
                ksc = vsc = None
        new_cache = cache._replace(k_pages=kp, v_pages=vp, k_scale=ksc,
                                   v_scale=vsc)
        S, D = x.shape[1], q.shape[-1]
        if S > 1 and not isinstance(cache, ContextPagedLayerCache):
            from ..ops.attention import scaled_dot_product_attention
            out = scaled_dot_product_attention(
                q, k, v, dropout_p=0.0, is_causal=True, training=False)
            return out, new_cache

        # decode kernel dispatch resolved OUTSIDE the traced fn so the
        # path choice is stable for any cached trace (kill switch:
        # FLAGS_pallas_paged_decode -> the gather+SDPA composition)
        use_kernel = False
        if S == 1:
            from ..ops import pallas as pallas_ops
            use_kernel = pallas_ops.kernel_enabled("paged_decode")
        pools = (kp, ksc, vp, vsc) if quant else (kp, vp)

        def attend(q_, tbl, p, first, *pool):
            p = p.astype(jnp.int32)
            if use_kernel:
                # pages read in place via the block table: the gathered
                # [B, MB*bs, H, D] context never materializes in HBM
                from ..ops.pallas import paged_decode as pd
                kernel = (pd.paged_decode_attention_quant if quant
                          else pd.paged_decode_attention)
                return kernel(q_[:, 0], *pool, tbl + first, p,
                              scale=1.0 / math.sqrt(D))[:, None]
            from ..ops.attention import sdpa_array
            if quant:
                gk = gather_pages_quant(pool[0], pool[1], tbl, D, first)
                gv = gather_pages_quant(pool[2], pool[3], tbl, D, first)
            else:
                gk = gather_pages(pool[0], tbl, D, first)
                gv = gather_pages(pool[1], tbl, D, first)
            # additive key mask [B, 1, S, Lk]: row i of slot b sees the
            # page-resident positions 0..p[b]+i, its own included. S == 1
            # is the decode step; S > 1 a CONTEXT prefill (ISSUE 15): a
            # chunked-prefill continuation, a prefix-cache-hit tail or a
            # speculative verify window starting at pos > 0
            cols = jnp.arange(gk.shape[1], dtype=jnp.int32)
            rows = p[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
            mask = jnp.where(cols[None, None, :] <= rows[:, :, None],
                             0.0, -1e30)[:, None]
            return sdpa_array(q_, gk, gv, mask=mask, dropout_p=0.0,
                              is_causal=False)

        out = apply(attend, q, table, pos, base, *pools,
                    name=("paged_attention" if S == 1
                          else "paged_context_attention")
                    + ("_quant" if quant else ""))
        return out, new_cache


class GPTMLP(Layer):
    """FFN: column-sharded in-proj, gelu, row-sharded out-proj.

    reference: fused_feedforward_op.cu; mp_layers.py Column+RowParallelLinear
    pair. Full logical weights, specs on the ffn axis; XLA inserts the psum
    after the second matmul."""

    block = "ffn"

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        E, FF = cfg.hidden_size, cfg.ffn_size
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers))
        self.w_in = self.create_parameter((E, FF), default_initializer=init)
        self.w_in.spec = P(None, MP)
        self.b_in = self.create_parameter((FF,), is_bias=True)
        self.b_in.spec = P(MP)
        self.w_out = self.create_parameter((FF, E), default_initializer=out_init)
        self.w_out.spec = P(MP, None)
        self.b_out = self.create_parameter((E,), is_bias=True)
        self.b_out.spec = P()

    def forward(self, x):
        h = F.linear(x, self.w_in, self.b_in)
        h = _constrain(h, BATCH, None, MP)
        if self.training:
            h = _keep(h, _FFN_IN)
        h = F.gelu(h, approximate=True)
        y = F.linear(h, self.w_out, None)
        y = _constrain(y, BATCH, None, None)
        return y + self.b_out


class GPTDecoderLayer(Layer):
    """Pre-LN block: x + attn(ln1(x)); x + mlp(ln2(x))."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.ln1 = LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size)
        self.ln1.block = self.ln2.block = "norm"
        self._build_ffn(cfg)
        self.dropout1 = Dropout(cfg.hidden_dropout_prob)
        self.dropout2 = Dropout(cfg.hidden_dropout_prob)

    _ffn_block = "ffn"

    def _build_ffn(self, cfg: GPTConfig):
        self.mlp = GPTMLP(cfg)

    def _ffn(self, h):
        """The block's feed-forward half (GPTMoEDecoderLayer swaps in
        the expert mixture)."""
        return self.mlp(h)

    def forward(self, x, cache=None, pos=None):
        sp = _seq_spec(self.cfg)
        if cache is None:
            a = self.attn(self.ln1(x))
        else:
            a, cache = self.attn(self.ln1(x), cache, pos=pos)
        # each half's dropout and residual add count with the half
        with jax.named_scope("attn"):
            d = self.dropout1(a)
            if self.training and cache is None:
                # the add's operand, not its sum: a kept sum is stored in
                # the stream's dtype where an unkept one may be fused on at
                # float32 (XLA's excess precision), and the bits would part
                d = _keep(d, _ATTN_BRANCH)
            x = x + d
        if sp:
            x = _constrain(x, BATCH, sp, None)
        h = self._ffn(self.ln2(x))
        with jax.named_scope(self._ffn_block):
            x = x + self.dropout2(h)
        if sp:
            x = _constrain(x, BATCH, sp, None)
        return x if cache is None else (x, cache)


class GPTMoEDecoderLayer(GPTDecoderLayer):
    """Pre-LN block whose FFN is a mixture of experts (incubate.moe).

    Forward contract: without a cache it returns ``(x, moe_vec)`` where
    ``moe_vec`` is the layer's [aux, z, drop, entropy, balance,
    load_0..E-1] f32 vector — GPTModel collects these (as scan side
    outputs for homogeneous stacks) into ``moe_loss()`` and the router
    telemetry; with a cache it returns ``(x, cache)`` exactly like the
    dense layer, so every decode path is unchanged."""

    _ffn_block = "moe"

    def _build_ffn(self, cfg: GPTConfig):
        from ..incubate.moe import MoELayer
        self.moe = MoELayer(
            cfg.hidden_size, num_experts=cfg.moe_experts,
            d_hidden=cfg.ffn_size, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor)
        self.moe.block = "moe"

    def _ffn(self, h):
        return self.moe(h)

    def forward(self, x, cache=None, pos=None):
        out = super().forward(x, cache, pos=pos)
        if cache is not None:
            return out                    # (x, cache) — decode unchanged
        return out, self.moe.moe_vec


def _paged_body(cls, template, x, pools, extras, scan_in):
    """Shared core of the paged scan bodies: build one layer's window on
    the carried pools and run the block.

    ``pools`` is ``(k, v)`` or — quantized cache
    (``FLAGS_serve_kv_quant``) — ``(k, v, k_scale, v_scale)``, each the
    WHOLE pool of ``L*P`` pages; ``scan_in`` is this layer's first page
    ``(page_base,)`` plus, on a LoRA engine, its ``(lora_a, lora_b)``
    slices; ``extras`` is ``(block_table, pos)`` plus the broadcast
    ``lora_ids`` then. Layout changes key distinct traces via the scan
    token's ``(n_cache, n_scan_in, len(extra))`` components."""
    ksc, vsc = pools[2:] if len(pools) == 4 else (None, None)
    base, la, lb = scan_in if len(scan_in) == 3 else (scan_in[0], None, None)
    ids = extras[2] if la is not None else None
    x, c = template(x, cls(pools[0], pools[1], extras[0], ksc, vsc,
                           la, lb, ids, base), pos=extras[1])
    if ksc is not None:
        return x, (c.k_pages, c.v_pages, c.k_scale, c.v_scale)
    return x, (c.k_pages, c.v_pages)


def _paged_scan_body(template, x, pools, extras, scan_in):
    """scan_layers_with_cache adapter for GPT blocks: the carried page
    pools in, the same pools with this layer's rows written out
    (module-level so its identity is stable in the eager jit-cache
    token)."""
    from ..serving.kv_cache import PagedLayerCache
    return _paged_body(PagedLayerCache, template, x, pools, extras,
                       scan_in)


def _paged_scan_body_ctx(template, x, pools, extras, scan_in):
    """Context-prefill twin of :func:`_paged_scan_body` (ISSUE 15): the
    layer cache is the :class:`ContextPagedLayerCache` marker, so S>1
    chunks attend over prior pages. A distinct module-level function —
    its identity keys the scan cache token, so the two attention paths
    can never share a trace."""
    from ..serving.kv_cache import ContextPagedLayerCache
    return _paged_body(ContextPagedLayerCache, template, x, pools, extras,
                       scan_in)


class GPTModel(Layer):
    """Embeddings + N decoder blocks + final LN. Returns hidden states."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size)
        # re-init with the model's initializer_range
        self.word_embeddings.weight._data = Normal(0.0, cfg.initializer_range)(
            (cfg.vocab_size, cfg.hidden_size), "float32")
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size)
        self.position_embeddings.weight._data = Normal(
            0.0, cfg.initializer_range)(
            (cfg.max_position_embeddings, cfg.hidden_size), "float32")
        self.embedding_dropout = Dropout(cfg.hidden_dropout_prob)
        moe_idx = set(cfg.moe_layer_indices())
        if cfg.moe_experts and not moe_idx:
            raise ValueError(
                f"moe_experts={cfg.moe_experts} but moe_every="
                f"{cfg.moe_every} places no MoE layer in a "
                f"{cfg.num_layers}-layer stack (layer i is MoE iff "
                "(i+1) % moe_every == 0)")
        self.layers = LayerList([
            GPTMoEDecoderLayer(cfg) if i in moe_idx else
            GPTDecoderLayer(cfg) for i in range(cfg.num_layers)])
        for i in sorted(moe_idx):
            self.layers[i].moe._label = f"layer{i}"
        self.final_norm = LayerNorm(cfg.hidden_size)
        self.final_norm.block = "norm"

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None):
        paged = False
        if caches is not None:
            # deferred so training runs never import the serving layer
            from ..serving.kv_cache import PagedCacheView, PagedPools
            if isinstance(caches, PagedPools):
                return self._forward_pools(input_ids, position_ids, caches,
                                           cache_pos)
            paged = isinstance(caches, PagedCacheView)
        B, S = input_ids.shape
        if position_ids is None:
            from ..tensor.creation import arange
            if paged:
                # per-slot positions: slot b's chunk occupies
                # cache_pos[b] .. cache_pos[b]+S-1
                def pos_ids(p):
                    return (p[:, None].astype(jnp.int32)
                            + jnp.arange(S, dtype=jnp.int32)[None, :])

                position_ids = apply(pos_ids, cache_pos,
                                     name="paged_position_ids")
            elif cache_pos is not None:
                position_ids = cache_pos + arange(0, S, dtype="int32")
            else:
                start = 0 if caches is None else caches[0][0].shape[1]
                position_ids = arange(start, start + S, dtype="int32")
        with jax.named_scope("embed"):
            x = self.word_embeddings(input_ids) + \
                self.position_embeddings(position_ids)
            x = self.embedding_dropout(x)
        sp = _seq_spec(self.cfg)
        if sp:
            x = _constrain(x, BATCH, sp, None)

        if paged:
            return self._forward_paged(x, caches, cache_pos)
        if caches is not None and cache_pos is None and \
                isinstance(caches[0], GPTAttention.StaticCache):
            raise ValueError(
                "StaticCache decoding needs cache_pos (the write offset "
                "into the fixed-size KV buffers); models/generation.py "
                "threads it automatically")
        new_caches = [] if caches is not None else None
        if caches is None:
            self.__dict__["_moe_vecs"] = None
        moe_stack = bool(self.cfg.moe_experts) and caches is None
        if caches is None and self.cfg.scan_layers \
                and can_scan_layers(self.layers):
            # one lax.scan over the layer-stacked params: the block body
            # traces/compiles once regardless of depth; selective remat
            # composes inside the scanned body. A homogeneous MoE stack
            # (moe_every=1) threads its per-layer router vectors out of
            # the scan as side outputs (nn.scan num_aux).
            all_moe = isinstance(self.layers[0], GPTMoEDecoderLayer)
            if all_moe:
                from ..core.flags import get_flag as _gf
                x, vecs = scan_layers(
                    self.layers, x,
                    use_recompute=self.cfg.use_recompute and self.training,
                    policy=self.cfg.recompute_policy, num_aux=1,
                    token_extra=(str(_gf("moe_dispatch")),
                                 bool(_gf("moe_expert_parallel")),
                                 int(_gf("moe_a2a_chunks"))),
                    name="gpt_moe_scan_layers")
                self.__dict__["_moe_vecs"] = vecs          # [L, 5+E]
            else:
                x = scan_layers(
                    self.layers, x,
                    use_recompute=self.cfg.use_recompute and self.training,
                    policy=self.cfg.recompute_policy,
                    name="gpt_scan_layers")
        else:
            if caches is not None and self.cfg.scan_layers \
                    and can_scan_layers(self.layers):
                # legacy per-layer StaticCache/tuple decode cannot ride
                # the scan (per-layer python cache objects); the paged
                # layout (paddle_tpu.serving) can — make the silent
                # degradation loud (ISSUE 6 satellite)
                note_scan_fallback("legacy_static_cache", "gpt")
            vecs = []
            for i, blk in enumerate(self.layers):
                is_moe = isinstance(blk, GPTMoEDecoderLayer)
                if caches is not None:
                    x, c = blk(x, caches[i], pos=cache_pos)
                    new_caches.append(c)
                    continue
                if self.cfg.use_recompute and self.training:
                    out = recompute(blk, x, policy=self.cfg.recompute_policy)
                else:
                    out = blk(x)
                if is_moe:
                    x, vec = out
                    vecs.append(vec)
                else:
                    x = out
            if moe_stack and vecs:
                from ..tensor.manipulation import stack as tstack
                self.__dict__["_moe_vecs"] = tstack(vecs, axis=0)
        if moe_stack:
            self._reduce_moe_loss()
        x = self.final_norm(x)
        return x if caches is None else (x, new_caches)

    # -- MoE side channel --------------------------------------------------
    def _reduce_moe_loss(self):
        """Weighted router losses of the last no-cache forward: aux (load
        balance) + z (logit magnitude), summed over MoE layers. Same-trace
        value — consume it in the SAME loss computation that ran the
        forward (TrainStep loss_fns do)."""
        vecs = self.__dict__.get("_moe_vecs")
        if vecs is None:
            self.__dict__["_moe_loss"] = None
            return
        w_a = float(self.cfg.moe_aux_weight)
        w_z = float(self.cfg.moe_z_weight)
        self.__dict__["_moe_loss"] = apply(
            lambda v: (w_a * v[:, 0].sum()
                       + w_z * v[:, 1].sum()).astype(jnp.float32),
            vecs, name="gpt_moe_loss")

    def moe_loss(self):
        """Weighted MoE router loss (aux + z) of the last forward, or
        None for dense configs. Add it to the CE in the loss_fn:
        ``crit(logits, labels) + model.gpt.moe_loss()``."""
        return self.__dict__.get("_moe_loss")

    def moe_layer_stats(self):
        """Per-MoE-layer router vectors [L_moe, 5+E] of the last no-cache
        forward (Tensor), or None. Rows follow
        ``cfg.moe_layer_indices()`` order; columns are [aux, z, drop,
        entropy, balance, load_0..E-1]."""
        return self.__dict__.get("_moe_vecs")

    def publish_moe_telemetry(self, registry=None) -> int:
        """Publish per-layer router gauges (balance/drop/entropy/loads)
        from the last EAGER forward into the monitor registry; returns
        the number of layers published (0 when the last forward was
        traced — run one eager forward to harvest).
        tools/monitor_report.py --moe renders the result."""
        import jax as _jax
        import numpy as np
        vecs = self.__dict__.get("_moe_vecs")
        if vecs is None or isinstance(vecs._data, _jax.core.Tracer):
            from ..incubate.moe import publish_router_stats
            return publish_router_stats(self, registry)
        from ..incubate.moe.layer import _publish_row
        arr = np.asarray(vecs._data)
        E = self.cfg.moe_experts
        for row, i in zip(arr, self.cfg.moe_layer_indices()):
            _publish_row(row[2:], f"layer{i}", E, registry)
        return arr.shape[0]

    def _forward_pools(self, input_ids, position_ids, caches, cache_pos):
        """The engine's door: its :class:`PagedPools` (the ``k`` and
        ``v`` pools this model declared, scales and LoRA beside them)
        as the K/V view the stack reads, and the view it returns as
        pools again."""
        from ..serving.kv_cache import (ContextPagedCacheView,
                                        ContextPagedPools, PagedCacheView)
        cls = ContextPagedCacheView \
            if isinstance(caches, ContextPagedPools) else PagedCacheView
        x, new = self.forward(
            input_ids, position_ids,
            cls(*caches.pools, caches.block_table,
                *(caches.scales or (None, None)),
                *(caches.lora or ())), cache_pos)
        scales = None if caches.scales is None \
            else (new.k_scale, new.v_scale)
        return x, caches._replace(pools=(new.k, new.v), scales=scales)

    def _forward_paged(self, x, caches, cache_pos):
        """Run the stack over a paged KV view. The pools are viewed as
        ONE pool of ``L*P`` pages (leading dims merged: a bitcast) that
        every layer shares; layer ``l`` reads and writes pages
        ``l*P + block_table`` of it, so no layer's pool is ever sliced
        out or stacked back. Under scan (``FLAGS_scan_decode``, default)
        the pools ride the one ``lax.scan``'s carry — decode keeps the
        O(1)-in-depth trace/compile cost of training; the loop layout
        (kill switch / heterogeneous stacks) walks the same pools with
        the same addressing, layer by layer."""
        from ..core.flags import get_flag
        from ..serving.kv_cache import (ContextPagedCacheView,
                                        ContextPagedLayerCache,
                                        PagedLayerCache)
        # the view CLASS carries the attention-path choice: a
        # ContextPagedCacheView (chunked prefill / prefix-hit tails /
        # speculative verify) selects the gather-over-prior-pages S>1
        # path at trace time (ISSUE 15)
        is_ctx = isinstance(caches, ContextPagedCacheView)
        lora = caches.lora_a is not None
        L, P = caches.k.shape[:2]
        names = ("k", "v") + (("k_scale", "v_scale")
                              if caches.k_scale is not None else ())

        def view(pool, shape, *spec):
            # [L, P, G, ...] <-> [L*P, G, ...]; the head-group axis a
            # serving mesh shards stays an axis of its own
            return _constrain(apply(lambda a: a.reshape(shape), pool,
                                    name="paged_pool_view"), *spec)

        pools = tuple(
            view(p, (L * P,) + tuple(p.shape[2:]), None, MP)
            for p in (getattr(caches, n) for n in names))
        extras = (caches.block_table, cache_pos)
        if lora:
            extras += (caches.lora_ids,)
        eligible = self.cfg.scan_layers and can_scan_layers(self.layers)
        if eligible and get_flag("scan_decode"):
            # scanned-over INPUTS: each layer's first page, and the LoRA
            # pools' [L, ...] per-layer state the step reads, never writes
            scan_in = (jnp.arange(L, dtype=jnp.int32) * P,)
            if lora:
                scan_in += (caches.lora_a, caches.lora_b)
            x, pools = scan_layers_with_cache(
                self.layers, x, pools, *extras,
                body_call=(_paged_scan_body_ctx if is_ctx
                           else _paged_scan_body),
                scan_in=scan_in, name="gpt_paged_scan")
        else:
            if eligible:
                note_scan_fallback("scan_decode_disabled", "gpt")
            layer_cls = ContextPagedLayerCache if is_ctx else PagedLayerCache
            for i, blk in enumerate(self.layers):
                x, pools = _paged_body(
                    layer_cls, blk, x, pools, extras,
                    (i * P,) + ((caches.lora_a[i], caches.lora_b[i])
                                if lora else ()))
        x = self.final_norm(x)
        return x, caches._replace(**{
            n: view(p, getattr(caches, n).shape, None, None, MP)
            for n, p in zip(names, pools)})


def parallel_logits(hidden, embedding_weight):
    """LM head: hidden @ W_vocab.T with the vocab axis kept mp-sharded.

    reference: parallel_matmul in the reference GPT impls — a column-parallel
    matmul against the tied embedding table followed by NO gather; the
    vocab-sharded logits feed ParallelCrossEntropy."""
    prec = matmul_precision()

    def fn(h, w):
        return jnp.einsum("bse,ve->bsv", h, w, precision=prec)

    with jax.named_scope("loss"):
        logits = apply(fn, hidden, embedding_weight, name="lm_logits")
        return _constrain(logits, BATCH, None, MP)


class GPTPretrainingCriterion(Layer):
    """Mean vocab-parallel CE over non-masked positions.

    reference: c_softmax_with_cross_entropy_op.cu + the loss-mask mean."""

    block = "loss"

    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        losses = self.ce(logits, labels)          # [B, S, 1]
        from ..tensor.manipulation import squeeze
        losses = squeeze(losses, -1)

        def reduce_fn(ls, *mm):
            ls = ls.astype(jnp.float32)
            if mm:
                m = mm[0].astype(jnp.float32)
                return jnp.sum(ls * m) / jnp.maximum(jnp.sum(m), 1.0)
            return jnp.mean(ls)

        args = [losses] + ([loss_mask] if loss_mask is not None else [])
        return apply(reduce_fn, *args, name="masked_lm_mean")


class GPTForPretraining(Layer):
    """GPT with the tied vocab-parallel LM head."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_pos=None):
        out = self.gpt(input_ids, position_ids, caches, cache_pos=cache_pos)
        if caches is not None:
            hidden, new_caches = out
            return parallel_logits(hidden, self.gpt.word_embeddings.weight), \
                new_caches
        return parallel_logits(out, self.gpt.word_embeddings.weight)

    def moe_loss(self):
        """Weighted MoE router loss of the last forward (see
        GPTModel.moe_loss), or None for dense configs."""
        return self.gpt.moe_loss()

    def generate(self, input_ids, max_new_tokens=32, **kwargs):
        """Autoregressive decoding with a static KV cache (see
        models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        **kwargs)


def gpt_tiny(**kw) -> GPTConfig:
    """Test-size config (runs on CPU meshes in seconds)."""
    d = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
             max_position_embeddings=128, hidden_dropout_prob=0.0,
             attention_dropout_prob=0.0)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_small(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
             max_position_embeddings=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_large(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=1280, num_layers=36,
             num_heads=20, max_position_embeddings=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_xl(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=1600, num_layers=48,
             num_heads=25, max_position_embeddings=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_medium(**kw) -> GPTConfig:
    """GPT-2 345M — BASELINE.md config 4."""
    d = dict(vocab_size=50304, hidden_size=1024, num_layers=24, num_heads=16,
             max_position_embeddings=1024)
    d.update(kw)
    return GPTConfig(**d)


# ---------------------------------------------------------------------------
# Pipeline-parallel GPT (BASELINE config 4: GPT-2 345M PP + TP)
# ---------------------------------------------------------------------------


class GPTForPretrainingPipe(Layer):
    """GPT with the decoder stack as an SPMD pipeline over the ``pp`` mesh
    axis (BASELINE config 4: PP + TP).

    reference: the model-zoo GPTForPretrainingPipe over
    fleet/meta_parallel/pipeline_parallel.py. TPU-native: the N decoder
    blocks live in a :class:`PipelineStageStack` — layer-stacked params
    sharded over ``pp``, one scan+ppermute program (see spmd_pipeline.py);
    embeddings/final-norm/tied head stay outside the pipeline, replicated
    over ``pp`` and sharded over ``mp``/data axes by GSPMD exactly as in
    GPTForPretraining. TP composes *inside* each stage because the
    pipeline's shard_map is manual only over ``pp``.

    Degrades to sequential execution (same params, same math) when no mesh
    or pp degree 1 is active.
    """

    def __init__(self, cfg: GPTConfig,
                 num_microbatches: Optional[int] = None,
                 schedule: Optional[str] = None):
        super().__init__()
        from ..distributed.meta_parallel.spmd_pipeline import (
            PipelineStageStack)
        if cfg.moe_experts:
            raise NotImplementedError(
                "GPTForPretrainingPipe does not support MoE configs yet "
                "(the pipeline stage stack builds dense decoder layers); "
                "use GPTForPretraining — MoE composes with DP/EP/TP, the "
                "pp schedule is an open item (docs/MOE.md)")
        self.cfg = cfg
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size)
        self.word_embeddings.weight._data = Normal(
            0.0, cfg.initializer_range)(
            (cfg.vocab_size, cfg.hidden_size), "float32")
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size)
        self.position_embeddings.weight._data = Normal(
            0.0, cfg.initializer_range)(
            (cfg.max_position_embeddings, cfg.hidden_size), "float32")
        self.embedding_dropout = Dropout(cfg.hidden_dropout_prob)
        self.blocks = PipelineStageStack(
            lambda: GPTDecoderLayer(cfg), cfg.num_layers,
            num_microbatches=num_microbatches, schedule=schedule)
        self.final_norm = LayerNorm(cfg.hidden_size)

    def _embed(self, input_ids, position_ids=None):
        S = input_ids.shape[1]
        if position_ids is None:
            from ..tensor.creation import arange
            position_ids = arange(0, S, dtype="int32")
        x = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        x = self.embedding_dropout(x)
        sp = _seq_spec(self.cfg)
        if sp:
            x = _constrain(x, BATCH, sp, None)
        return x

    def forward(self, input_ids, position_ids=None):
        x = self.blocks(self._embed(input_ids, position_ids))
        x = self.final_norm(x)
        return parallel_logits(x, self.word_embeddings.weight)

    def _head_apply(self):
        """The pipeline loss head as a raw-array function over explicit
        leaves — final LayerNorm -> tied vocab-parallel logits -> masked
        CE (loss_sum, mask_sum). The SAME math as
        forward()+GPTPretrainingCriterion, packaged so the 1F1B schedule
        can run it per microbatch on the last stage (and the fill-drain
        path on the full batch) — schedule parity by construction."""
        cached = self.__dict__.get("_head_apply_fn")
        if cached is not None:
            return cached
        from ..core.tensor import Tensor
        from ..jit.functional import bind
        norm = self.final_norm
        norm_names = [n for n, _ in norm.named_parameters()]
        ce = ParallelCrossEntropy()

        def head_apply(leaves, y, lab, msk):
            with bind(norm, dict(zip(norm_names, leaves))):
                h = norm(Tensor(y))
            logits = parallel_logits(h, Tensor(leaves[len(norm_names)]))
            losses = ce(logits, Tensor(lab))
            ls = losses._data if isinstance(losses, Tensor) else losses
            ls = jnp.squeeze(ls, -1).astype(jnp.float32)
            m = msk.astype(jnp.float32)
            return jnp.sum(ls * m), jnp.sum(m)

        self.__dict__["_head_apply_fn"] = head_apply
        return head_apply

    def pretraining_loss(self, input_ids, labels, loss_mask=None,
                         position_ids=None):
        """Schedule-aware pretraining loss: embeddings ->
        ``PipelineStageStack.train_loss`` (1F1B combined program on
        capable pp meshes, fill-drain otherwise) -> masked-mean CE.
        Numerically equivalent to
        ``GPTPretrainingCriterion()(self(ids), labels, loss_mask)`` up to
        the per-microbatch summation order (pinned at 1e-6)."""
        from ..core.tensor import Tensor
        x = self._embed(input_ids, position_ids)
        if loss_mask is None:
            ones = jnp.ones(tuple(labels.shape), jnp.float32)
            loss_mask = Tensor(ones)
        head_leaves = [p for _, p in self.final_norm.named_parameters()]
        head_leaves.append(self.word_embeddings.weight)
        return self.blocks.train_loss(
            x, self._head_apply(), head_leaves, [labels, loss_mask],
            head_token=("gpt_pipe_head", id(self)))


class _GPTEmbeddingStage(Layer):
    """Embedding front of the pipeline: ids -> hidden states."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size)
        self.word_embeddings.weight._data = Normal(
            0.0, cfg.initializer_range)(
            (cfg.vocab_size, cfg.hidden_size), "float32")
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size)
        self.position_embeddings.weight._data = Normal(
            0.0, cfg.initializer_range)(
            (cfg.max_position_embeddings, cfg.hidden_size), "float32")
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids):
        from ..tensor.creation import arange
        S = input_ids.shape[1]
        pos = arange(0, S, dtype="int32")
        x = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        return self.dropout(x)


def gpt_pipeline_descs(cfg: GPTConfig):
    """LayerDesc list for PipelineLayer: embedding | N blocks | tied head
    (reference: the model-zoo GPTForPretrainingPipe built on
    fleet/meta_parallel/parallel_layers/pp_layers.py LayerDesc/
    SharedLayerDesc with shared embedding between first/last stage)."""
    from ..distributed.meta_parallel.parallel_layers.pp_layers import (
        LayerDesc, SharedLayerDesc)

    def embed_fwd(shared, ids):
        return shared(ids)

    def head_fwd(shared, hidden):
        # tied LM head: project onto the stage-0 embedding table (the
        # final LayerNorm is its own desc just before this one)
        return parallel_logits(hidden, shared.word_embeddings.weight)

    descs = [
        SharedLayerDesc("gpt_embed", _GPTEmbeddingStage,
                        forward_func=embed_fwd, cfg=cfg),
    ]
    descs += [LayerDesc(GPTDecoderLayer, cfg) for _ in range(cfg.num_layers)]
    descs.append(LayerDesc(LayerNorm, cfg.hidden_size))
    descs.append(SharedLayerDesc("gpt_embed", _GPTEmbeddingStage,
                                 forward_func=head_fwd, cfg=cfg))
    return descs


def build_gpt_pipe(cfg: GPTConfig, num_stages: int, accumulate_steps: int = 1,
                   seg_method: str = "uniform"):
    """GPT as a PipelineParallel engine (PP outer, TP inner via the
    vocab/column/row-parallel layers inside each desc)."""
    from ..distributed.meta_parallel.parallel_layers.pp_layers import (
        PipelineLayer)
    from ..distributed.meta_parallel.pipeline_parallel import (
        PipelineParallel)

    crit = GPTPretrainingCriterion()

    def loss_fn(logits, labels):
        return crit(logits, labels)

    pl_layer = PipelineLayer(gpt_pipeline_descs(cfg), num_stages=num_stages,
                             loss_fn=loss_fn, seg_method=seg_method)
    return PipelineParallel(pl_layer, accumulate_steps=accumulate_steps)
