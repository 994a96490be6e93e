"""Sliding-window / full-attention sparse-expert causal decoder, the block
structure of the ``afmoe`` family (Arcee Trinity, 2025-12;
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json).

Absent from the reference, whose language models end at BERT (tfpark).
Built from layers the zoo shares with its other models.  ``layer_types``
says which blocks attend to a sliding window of ``window`` keys, with rotary
embedding, and which to the whole causal row, with no positional encoding
at all; both are ``nn.MultiHeadAttention`` with kv heads, a plain q/k
RMSNorm and an output gate, through its dense / flash dispatch (the flash
path visits a windowed layer's band alone).  The first ``num_dense_layers``
blocks feed forward through a dense SwiGLU, the others through a dropless
top-k expert layer with an ungated shared expert
(``parallel.DroplessMoE``): a sigmoid router that is balanced by a bias on
its selection, moved every training step from the step's loads, and by no
auxiliary loss; it may hold a share of the experts only.  Four RMSNorms a
block, two of them on the sublayers' outputs (the sandwich form of
``models.decoder.DecoderBlock``); the embedding scaled by
``sqrt(hidden_size)``; a final RMSNorm and an untied vocabulary head:
logits at every position, trained with ``sparse_categorical_crossentropy``
against the ids shifted by one.

Not built: a cache or a decode path (``Estimator.predict`` recomputes the
sequence), packed documents, and the expert exchange across chips (a share
computes its own experts' part and nothing else).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn.module import Scope
from analytics_zoo_tpu.parallel.moe import DroplessMoE
from .common import ZooModel
from .decoder import DecoderBlock, stack

SLIDING, FULL = "sliding_attention", "full_attention"


class AFMoE(ZooModel):
    """ids ``[B, T]`` -> logits ``[B, T, vocab_size]`` (causal).

    The defaults are Trinity-Mini's published widths; ``n_layers`` /
    ``num_dense_layers`` / ``layer_types``, ``experts_held`` /
    ``first_expert`` and ``vocab_size`` are what a deployment divides over
    its chips.  ``layer_types`` names each block's attention
    (``"sliding_attention"`` | ``"full_attention"``); None is the published
    pattern, every ``full_attention_interval``-th block full.  ``remat``
    recomputes each block in the backward pass (``nn.Remat``) and keeps the
    flash kernel's outputs.
    """

    def __init__(self, vocab_size: int = 200192, hidden_size: int = 2048,
                 n_layers: int = 32, num_dense_layers: int = 2,
                 layer_types: Optional[Sequence[str]] = None,
                 full_attention_interval: int = 4, window: int = 2048,
                 num_heads: int = 32, num_kv_heads: int = 4,
                 head_dim: int = 128, rope_theta: float = 10000.0,
                 dense_units: int = 6144, num_experts: int = 128,
                 top_k: int = 8, moe_units: int = 1024,
                 shared_units: int = 1024, route_scale: float = 2.826,
                 balance_coeff: float = 0.001,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 rms_eps: float = 1e-5,
                 use_flash: Union[bool, str] = "auto", remat: bool = True,
                 dtype: Any = "bfloat16"):
        super().__init__()
        if layer_types is None:
            layer_types = [FULL if (i + 1) % full_attention_interval == 0
                           else SLIDING for i in range(n_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != n_layers or \
                set(layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {n_layers} layers as "
                             f"{SLIDING!r} or {FULL!r}; got {layer_types}")
        self._config = {k: v for k, v in locals().items()
                        if k not in ("self", "__class__")}
        self.__dict__.update(self._config)
        self.dtype = jnp.dtype(dtype)
        self._norm = nn.RMSNorm(rms_eps)
        self._blocks = stack([self._block(i) for i in range(n_layers)], remat)

    def _block(self, i: int) -> DecoderBlock:
        sliding = self.layer_types[i] == SLIDING
        # a full layer carries no position signal of its own
        attn = nn.MultiHeadAttention(
            self.num_heads, head_dim=self.head_dim, causal=True,
            use_flash=self.use_flash, num_kv_heads=self.num_kv_heads,
            qk_norm=True, qk_norm_zero_centered=False, gate=True,
            norm_epsilon=self.rms_eps, rope_theta=self.rope_theta,
            rotary_dim=self.head_dim if sliding else 0,
            window=self.window if sliding else None, name="attn")
        if i < self.num_dense_layers:
            ff = nn.SwiGLU(self.dense_units, name="mlp")
        else:
            ff = DroplessMoE(
                self.num_experts, self.top_k, self.moe_units,
                experts_held=self.experts_held,
                first_expert=self.first_expert,
                shared_units=self.shared_units, shared_gate=False,
                score_func="sigmoid", route_scale=self.route_scale,
                norm_epsilon=1e-20, balance_coeff=self.balance_coeff)
        return DecoderBlock(attn, ff, ("input_norm", "post_attn_norm",
                                       "pre_ff_norm", "post_ff_norm"),
                            self._norm, name=f"layer_{i}")

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        x = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        ids, name="embed")
        x = (x * self.hidden_size ** 0.5).astype(self.dtype)
        for block in self._blocks:
            x = scope.child(block, x, name=block.name)
        x = scope.child(self._norm, x, name="final_norm")
        return scope.child(nn.Dense(self.vocab_size, use_bias=False), x,
                           name="head")
