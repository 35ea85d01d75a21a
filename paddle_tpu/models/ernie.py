"""ERNIE: enhanced-representation encoder + pretraining heads
(BASELINE.md config 5: ERNIE-3.0 1.5B hybrid-parallel pretraining).

reference parity: the reference repo carries ERNIE as a model-zoo family
(README model lineup; the in-tree building blocks are the same
TransformerEncoder + fused attention as BERT). Architecturally ERNIE-style
pretraining = BERT encoder + task-type embeddings + MLM with
knowledge-span masking + sentence-order prediction (SOP) head.

TPU-native: built on nn.TransformerEncoder (flash-attention dispatch
inside), task embeddings folded into the input sum, and hybrid-parallel
ready — `apply_hybrid_specs` stamps TP PartitionSpecs by name, ZeRO via
TrainStep(zero_axis=...), so the 1.5B config shards over a dp x mp mesh
without model rewrites.
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
from ..nn.layers.common import Dropout, Embedding, Linear
from ..nn.layers.norm import LayerNorm
from ..nn.layers.transformer import (TransformerEncoder,
                                     TransformerEncoderLayer)

__all__ = ["ErnieConfig", "ErnieModel", "ErnieForPretraining",
           "ernie_tiny", "ernie_base", "ernie_3_1p5b"]


@dataclass
class ErnieConfig:
    vocab_size: int = 18000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 513
    type_vocab_size: int = 2
    task_type_vocab_size: int = 3
    use_task_id: bool = True
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


class ErnieEmbeddings(Layer):
    """word + position + token-type (+ task-type) embeddings."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.word_embeddings.weight._data = init(
            (cfg.vocab_size, cfg.hidden_size), "float32")
        self.position_embeddings = Embedding(cfg.max_position_embeddings,
                                             cfg.hidden_size)
        self.token_type_embeddings = Embedding(cfg.type_vocab_size,
                                               cfg.hidden_size)
        if cfg.use_task_id:
            self.task_type_embeddings = Embedding(cfg.task_type_vocab_size,
                                                  cfg.hidden_size)
        self.layer_norm = LayerNorm(cfg.hidden_size)
        self.dropout = Dropout(cfg.hidden_dropout_prob)
        self.cfg = cfg

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                task_type_ids=None):
        S = input_ids.shape[1]
        if position_ids is None:
            from ..tensor.creation import arange
            position_ids = arange(0, S, dtype="int32")
        x = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        if token_type_ids is not None:
            x = x + self.token_type_embeddings(token_type_ids)
        if self.cfg.use_task_id and task_type_ids is not None:
            x = x + self.task_type_embeddings(task_type_ids)
        return self.dropout(self.layer_norm(x))


class ErnieModel(Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = ErnieEmbeddings(cfg)
        enc_layer = TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation="gelu",
            attn_dropout=cfg.attention_dropout_prob, act_dropout=0.0,
            normalize_before=False)
        self.encoder = TransformerEncoder(enc_layer, cfg.num_layers)
        self.encoder.enable_scan = cfg.scan_layers
        self.encoder.use_recompute = cfg.use_recompute
        self.encoder.recompute_policy = cfg.recompute_policy
        self.pooler = Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                position_ids=None, task_type_ids=None):
        x = self.embeddings(input_ids, token_type_ids, position_ids,
                            task_type_ids)
        if attention_mask is not None and attention_mask.ndim == 2:
            def to_additive(m):
                return ((1.0 - m.astype(jnp.float32))
                        * -1e30)[:, None, None, :]
            attention_mask = apply(to_additive, attention_mask,
                                   name="ernie_attn_mask")
        seq = self.encoder(x, attention_mask)
        pooled = F.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class ErnieForPretraining(Layer):
    """MLM head (tied decoder) + sentence-order prediction head."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.cfg = cfg
        self.ernie = ErnieModel(cfg)
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size)
        self.transform_norm = LayerNorm(cfg.hidden_size)
        self.decoder_bias = self.create_parameter((cfg.vocab_size,),
                                                  is_bias=True)
        self.sop_head = Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None, task_type_ids=None):
        seq, pooled = self.ernie(input_ids, token_type_ids, attention_mask,
                                 task_type_ids=task_type_ids)
        h = self.transform_norm(F.gelu(self.transform(seq),
                                       approximate=True))
        w = self.ernie.embeddings.word_embeddings.weight
        prec = matmul_precision()

        def head(hh, ww, bb, *mp):
            if mp:
                idx = mp[0].astype(jnp.int32)
                hh = jnp.take_along_axis(hh, idx[..., None], axis=1)
            return jnp.einsum("bme,ve->bmv", hh, ww, precision=prec) + bb

        args = [h, w, self.decoder_bias] + (
            [masked_positions] if masked_positions is not None else [])
        mlm_scores = apply(head, *args, name="ernie_mlm_head")
        sop_scores = self.sop_head(pooled)
        return mlm_scores, sop_scores

    def loss(self, mlm_scores, sop_scores, masked_lm_labels, sop_labels,
             masked_lm_weights=None):
        from ..nn import chunked_ce as _cce
        chunked = _cce.enabled_for(mlm_scores.shape[-1])

        def mlm_ce(lg, lab, *ww):
            # streamed-vocab CE above the threshold (nn/chunked_ce.py),
            # dense logsumexp below — one shared epilogue with BERT
            return _cce.masked_lm_loss(lg, lab, *ww, chunked=chunked)

        args = [mlm_scores, masked_lm_labels] + (
            [masked_lm_weights] if masked_lm_weights is not None else [])
        mlm_loss = apply(mlm_ce, *args, name="ernie_mlm_loss")
        sop_loss = F.cross_entropy(sop_scores, sop_labels)
        return mlm_loss + sop_loss


def ernie_tiny(**kw) -> ErnieConfig:
    d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
             intermediate_size=128, max_position_embeddings=128,
             hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    d.update(kw)
    return ErnieConfig(**d)


def ernie_base(**kw) -> ErnieConfig:
    return ErnieConfig(**kw)


def ernie_3_1p5b(**kw) -> ErnieConfig:
    """ERNIE-3.0 1.5B-class config (BASELINE config 5)."""
    d = dict(vocab_size=40000, hidden_size=2048, num_layers=24,
             num_heads=16, intermediate_size=8192,
             max_position_embeddings=2048)
    d.update(kw)
    return ErnieConfig(**d)
