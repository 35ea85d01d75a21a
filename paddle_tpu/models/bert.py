"""BERT: bidirectional encoder + MLM head (BASELINE.md config 3).

reference parity: the reference's BERT family is built on
nn/layer/transformer.py TransformerEncoder(:~900) with fused attention
(fused_attention_op.cu) underneath; MLM pretraining mirrors
model_zoo/bert semantics (masked positions gathered, CE over vocab).

TPU-native: the encoder reuses nn.TransformerEncoder (whose attention
dispatches to the Pallas flash kernel when eligible); the MLM loss gathers
masked positions with a static-shape `take_along_axis` so the whole step
stays jit-compilable (no dynamic boolean indexing).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.flags import matmul_precision
from ..core.tensor import Tensor, apply
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.layers.common import Dropout, Embedding
from ..nn.layers.norm import LayerNorm
from ..nn.layers.transformer import TransformerEncoder, TransformerEncoderLayer

__all__ = ["BertConfig", "BertModel", "BertForMaskedLM", "bert_tiny",
           "bert_base", "bert_large"]


@dataclass
class BertConfig:
    vocab_size: int = 30528          # padded to a multiple of 64
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    #: run the encoder stack as one jax.lax.scan over layer-stacked params
    #: (nn.scan; O(1) trace/compile in num_layers, state_dict unchanged)
    scan_layers: bool = True
    use_recompute: bool = False
    #: remat policy name (fleet.utils.recompute.
    #: resolve_checkpoint_policy); None = everything recomputed but the
    #: flash kernel's output and log-sum-exp, 'full' = nothing kept
    recompute_policy: Optional[str] = None


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.word_embeddings.weight._data = init(
            (cfg.vocab_size, cfg.hidden_size), "float32")
        self.position_embeddings = Embedding(cfg.max_position_embeddings,
                                             cfg.hidden_size)
        self.token_type_embeddings = Embedding(cfg.type_vocab_size,
                                               cfg.hidden_size)
        self.layer_norm = LayerNorm(cfg.hidden_size)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        S = input_ids.shape[1]
        if position_ids is None:
            from ..tensor.creation import arange
            position_ids = arange(0, S, dtype="int32")
        x = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        if token_type_ids is not None:
            x = x + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertModel(Layer):
    """Embeddings + post-LN transformer encoder + tanh pooler."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation="gelu",
            attn_dropout=cfg.attention_dropout_prob,
            act_dropout=0.0, normalize_before=False)
        self.encoder = TransformerEncoder(enc_layer, cfg.num_layers)
        self.encoder.enable_scan = cfg.scan_layers
        self.encoder.use_recompute = cfg.use_recompute
        self.encoder.recompute_policy = cfg.recompute_policy
        from ..nn.layers.common import Linear
        self.pooler = Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                position_ids=None):
        x = self.embeddings(input_ids, token_type_ids, position_ids)
        if attention_mask is not None and attention_mask.ndim == 2:
            # [B, S] 1/0 mask -> additive [B, 1, 1, S]
            def to_additive(m):
                return ((1.0 - m.astype(jnp.float32))
                        * -1e30)[:, None, None, :]
            attention_mask = apply(to_additive, attention_mask,
                                   name="bert_attn_mask")
        seq = self.encoder(x, attention_mask)
        pooled = F.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class BertForMaskedLM(Layer):
    """BERT + transform head + tied decoder over the vocab."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        from ..nn.layers.common import Linear
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size)
        self.transform_norm = LayerNorm(cfg.hidden_size)
        self.decoder_bias = self.create_parameter((cfg.vocab_size,),
                                                  is_bias=True)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        seq, _ = self.bert(input_ids, token_type_ids, attention_mask)
        h = self.transform_norm(F.gelu(self.transform(seq), approximate=True))
        w = self.bert.embeddings.word_embeddings.weight
        prec = matmul_precision()

        def head(hh, ww, bb, *mp):
            if mp:
                # gather masked positions (static count) before the big gemm
                idx = mp[0].astype(jnp.int32)               # [B, M]
                hh = jnp.take_along_axis(hh, idx[..., None], axis=1)
            return jnp.einsum("bme,ve->bmv", hh, ww, precision=prec) + bb

        args = [h, w, self.decoder_bias] + (
            [masked_positions] if masked_positions is not None else [])
        return apply(head, *args, name="mlm_head")

    def loss(self, prediction_scores, masked_lm_labels, masked_lm_weights=None):
        """Mean CE over masked positions; labels [B, M], weights [B, M].

        Above the chunked-CE vocab threshold the logsumexp streams over
        vocab chunks (nn/chunked_ce.py — online f32 accumulation, no
        full-vocab f32 log-probs); below it the dense composition runs."""
        from ..nn import chunked_ce as _cce
        chunked = _cce.enabled_for(prediction_scores.shape[-1])

        def ce(lg, lab, *ww):
            return _cce.masked_lm_loss(lg, lab, *ww, chunked=chunked)

        args = [prediction_scores, masked_lm_labels] + (
            [masked_lm_weights] if masked_lm_weights is not None else [])
        return apply(ce, *args, name="mlm_loss")


def bert_tiny(**kw) -> BertConfig:
    d = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
             intermediate_size=128, max_position_embeddings=128,
             hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    d.update(kw)
    return BertConfig(**d)


def bert_large(**kw) -> BertConfig:
    d = dict(hidden_size=1024, num_layers=24, num_heads=16,
             intermediate_size=4096)
    d.update(kw)
    return BertConfig(**d)


def bert_base(**kw) -> BertConfig:
    d = dict()
    d.update(kw)
    return BertConfig(**d)
