"""BERT family (reference: the Keras-zoo BERT layer — Scala
pipeline/api/keras/layers self-attention area — and TFPark's BERT
estimators: pyzoo/zoo/tfpark/text/estimator/bert_*.py — BERTClassifier,
BERTNER, BERTSQuAD).

TPU-native: the encoder is a stack of TransformerLayers (pre-LN, bf16-ready,
optional flash attention / ring attention for long sequences), learned
positional + segment embeddings, [CLS] pooler.  BERTClassifier and BERTSQuAD
put the reference's task heads on top.  This is the BASELINE BERT-SQuAD
fine-tune config's model.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn.module import Module, Scope
from .common import ZooModel


class BERT(Module):
    """Encoder trunk: ids [B, T] (+ optional segment ids) → [B, T, H]."""

    def __init__(self, vocab_size: int = 30522, hidden_size: int = 768,
                 n_layers: int = 12, n_heads: int = 12,
                 intermediate_mult: int = 4, max_position: int = 512,
                 type_vocab: int = 2, dropout: float = 0.1,
                 use_flash: bool = False, use_ring: bool = False,
                 remat: bool = False, remat_attention: bool = False,
                 dtype: Any = None, name: Optional[str] = None):
        """``remat``: gradient-checkpoint each WHOLE encoder block
        (nn.Remat) — activation memory drops to O(layers * [B,T,H]) at
        ~1.3x compute, the long-sequence training recipe.

        ``remat_attention``: checkpoint only the attention core
        (logits/softmax recomputed in backward) — what the benchmark's
        BERT cells train with at seq 512; exact, and much cheaper
        recompute than ``remat``."""
        super().__init__(name)
        self.remat = remat
        self.remat_attention = remat_attention
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.intermediate_mult = intermediate_mult
        self.max_position = max_position
        self.type_vocab = type_vocab
        self.dropout = dropout
        self.use_flash = use_flash
        self.use_ring = use_ring
        self.dtype = dtype

    def forward(self, scope: Scope, ids: jax.Array,
                segment_ids: Optional[jax.Array] = None,
                mask: Optional[jax.Array] = None) -> jax.Array:
        t = ids.shape[1]
        x = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        ids, name="tok_embed")
        pos = scope.param("pos_embed", nn.initializers.get("normal"),
                          (1, self.max_position, self.hidden_size))
        x = x + pos[:, :t]
        if segment_ids is not None:
            x = x + scope.child(
                nn.Embedding(self.type_vocab, self.hidden_size),
                segment_ids, name="seg_embed")
        x = scope.child(nn.LayerNormalization(), x, name="embed_ln")
        x = scope.child(nn.Dropout(self.dropout), x, name="embed_drop")
        if self.dtype is not None:
            x = x.astype(self.dtype)
        for i in range(self.n_layers):
            block = nn.TransformerLayer(self.n_heads,
                                        hidden_mult=self.intermediate_mult,
                                        dropout=self.dropout, pre_ln=True,
                                        use_flash=self.use_flash,
                                        use_ring=self.use_ring,
                                        remat_attention=(
                                            self.remat_attention
                                            and not self.remat),
                                        name=f"layer_{i}")
            if self.remat:
                x = scope.child(nn.Remat(block), x, mask=mask,
                                name=f"remat_{i}")
            else:
                x = scope.child(block, x, mask=mask, name=f"layer_{i}")
        return x.astype(jnp.float32)


class BERTClassifier(ZooModel):
    """[CLS] pooler + linear head (reference: tfpark BERTClassifier)."""

    def __init__(self, class_num: int, **bert_kwargs: Any):
        super().__init__()
        self._config = dict(class_num=class_num, **bert_kwargs)
        self.class_num = class_num
        self.bert = BERT(**bert_kwargs)

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        h = scope.child(self.bert, ids, name="bert")
        pooled = scope.child(nn.Dense(self.bert.hidden_size,
                                      activation="tanh"),
                             h[:, 0], name="pooler")
        return scope.child(nn.Dense(self.class_num), pooled, name="head")


class BERTSQuAD(ZooModel):
    """Span head: per-token (start, end) logits (reference: tfpark
    BERTSQuAD).  Output [B, T, 2]; train with the sum of start/end sparse
    cross-entropies (losses.squad_span_loss)."""

    def __init__(self, **bert_kwargs: Any):
        super().__init__()
        self._config = dict(**bert_kwargs)
        self.bert = BERT(**bert_kwargs)

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        h = scope.child(self.bert, ids, name="bert")
        return scope.child(nn.Dense(2), h, name="span_head")


class BERTNER(ZooModel):
    """Token-classification head: per-token entity logits (reference:
    tfpark text/estimator BERTNER — the named-entity-recognition
    estimator).  Output [B, T, num_entities]; train with sparse
    cross-entropy over tokens."""

    def __init__(self, entity_num: int, **bert_kwargs: Any):
        super().__init__()
        self._config = dict(entity_num=entity_num, **bert_kwargs)
        self.entity_num = entity_num
        self.bert = BERT(**bert_kwargs)

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        h = scope.child(self.bert, ids, name="bert")
        return scope.child(nn.Dense(self.entity_num), h, name="ner_head")


def squad_span_loss(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    """y_pred [B, T, 2]; y_true int [B, 2] = (start_idx, end_idx)."""
    start_logits = y_pred[..., 0]
    end_logits = y_pred[..., 1]
    y_true = y_true.astype(jnp.int32)

    def nll(logits, idx):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, idx[:, None], axis=1)[:, 0]

    return (nll(start_logits, y_true[:, 0]) +
            nll(end_logits, y_true[:, 1])).mean() / 2.0
