"""Hybrid linear-attention / sparse-expert causal decoder, the block
structure of Qwen3-Next (Qwen team, 2025-09;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).

Absent from the reference, whose language models end at BERT (tfpark).
Built from layers the zoo shares with its other models: of every
``full_attention_interval`` blocks the last mixes tokens with gated
grouped-query softmax attention (``nn.MultiHeadAttention`` with kv heads,
q/k RMSNorm, partial rotary embedding and an output gate, through its dense
/ flash dispatch), the others with a Gated DeltaNet linear-attention layer
(``nn.GatedDeltaNet``); every block then passes a dropless top-k expert
layer with a gated shared expert (``parallel.DroplessMoE``), which may hold
a share of the experts only.  Pre-norm residual blocks
(``models.decoder.DecoderBlock``) with zero-centred RMSNorm, a final
RMSNorm and an untied vocabulary head: logits at every position, trained
with ``sparse_categorical_crossentropy`` against the ids shifted by one.

Not used here: the multi-token-prediction module the model card describes
(the release's config has no key for it; the module exists as
``models.glm_moe_lite.MultiTokenPredictor`` with its loss
``nn.losses.multi_token_crossentropy``, and the Qwen configuration does
not build it).  Not built: a cache or a decode path (``Estimator.predict``
recomputes the sequence), packed documents with state resets, and the
expert exchange across chips (a share computes its own experts' part and
nothing else).
"""

from __future__ import annotations

from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn.module import Scope
from analytics_zoo_tpu.parallel.moe import DroplessMoE
from .common import ZooModel
from .decoder import DecoderBlock, stack


class Qwen3Next(ZooModel):
    """ids ``[B, T]`` -> logits ``[B, T, vocab_size]`` (causal).

    The defaults are the published widths; ``n_layers``, ``experts_held`` /
    ``first_expert`` and ``vocab_size`` are what a deployment divides over
    its chips.  ``remat`` recomputes each block in the backward pass
    (``nn.Remat``): at 8k tokens a block's saved activations are gigabytes.
    """

    def __init__(self, vocab_size: int = 151936, hidden_size: int = 2048,
                 n_layers: int = 48, full_attention_interval: int = 4,
                 num_heads: int = 16, num_kv_heads: int = 2,
                 head_dim: int = 256, partial_rotary_factor: float = 0.25,
                 rope_theta: float = 1e7, linear_num_k_heads: int = 16,
                 linear_num_v_heads: int = 32, linear_k_head_dim: int = 128,
                 linear_v_head_dim: int = 128, linear_conv_kernel: int = 4,
                 chunk: int = 64, num_experts: int = 512, top_k: int = 10,
                 moe_units: int = 512, shared_units: int = 512,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 norm_topk_prob: bool = True, rms_eps: float = 1e-6,
                 use_flash: Union[bool, str] = "auto", remat: bool = True,
                 dtype: Any = "bfloat16"):
        super().__init__()
        self._config = {k: v for k, v in locals().items()
                        if k not in ("self", "__class__")}
        self.__dict__.update(self._config)
        self.dtype = jnp.dtype(dtype)
        self._norm = nn.RMSNorm(rms_eps, zero_centered=True)
        self._blocks = stack([self._block(i) for i in range(n_layers)], remat)

    def _block(self, i: int) -> DecoderBlock:
        if (i + 1) % self.full_attention_interval == 0:
            mixer = nn.MultiHeadAttention(
                self.num_heads, head_dim=self.head_dim, causal=True,
                use_flash=self.use_flash, num_kv_heads=self.num_kv_heads,
                qk_norm=True, gate=True, rope_theta=self.rope_theta,
                rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                norm_epsilon=self.rms_eps, name="attn")
        else:
            mixer = nn.GatedDeltaNet(
                self.linear_num_k_heads, self.linear_num_v_heads,
                self.linear_k_head_dim, self.linear_v_head_dim,
                conv_kernel=self.linear_conv_kernel, chunk=self.chunk,
                epsilon=self.rms_eps, name="gdn")
        moe = DroplessMoE(self.num_experts, self.top_k, self.moe_units,
                          experts_held=self.experts_held,
                          first_expert=self.first_expert,
                          shared_units=self.shared_units,
                          norm_topk_prob=self.norm_topk_prob)
        return DecoderBlock(mixer, moe, ("input_norm", "post_norm"),
                            self._norm, name=f"layer_{i}")

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        x = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        ids, name="embed").astype(self.dtype)
        for block in self._blocks:
            x = scope.child(block, x, name=block.name)
        x = scope.child(self._norm, x, name="final_norm")
        return scope.child(nn.Dense(self.vocab_size, use_bias=False), x,
                           name="head")
