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
a share of the experts only.  Pre-norm residual blocks with zero-centred
RMSNorm, a final RMSNorm and an untied vocabulary head: logits at every
position, trained with ``sparse_categorical_crossentropy`` against the ids
shifted by one.

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
from analytics_zoo_tpu.nn.module import Module, Scope
from analytics_zoo_tpu.parallel.moe import DroplessMoE
from .common import ZooModel


class Qwen3NextBlock(Module):
    """``x += mixer(norm(x)); x += moe(norm(x))``: ``mixer`` is the child
    ``attn`` or ``gdn``, the expert layer the child ``moe``."""

    def __init__(self, mixer: Module, mixer_name: str, moe: Module,
                 epsilon: float, name: Optional[str] = None):
        super().__init__(name)
        self.mixer, self.mixer_name, self.moe = mixer, mixer_name, moe
        self.epsilon = epsilon

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        def norm():
            return nn.RMSNorm(self.epsilon, zero_centered=True)
        h = scope.child(norm(), x, name="input_norm")
        x = x + scope.child(self.mixer, h, name=self.mixer_name)
        h = scope.child(norm(), x, name="post_norm")
        return x + scope.child(self.moe, h, name="moe")


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

    def _block(self, i: int) -> Qwen3NextBlock:
        if (i + 1) % self.full_attention_interval == 0:
            mixer_name, mixer = "attn", nn.MultiHeadAttention(
                self.num_heads, head_dim=self.head_dim, causal=True,
                use_flash=self.use_flash, num_kv_heads=self.num_kv_heads,
                qk_norm=True, gate=True, rope_theta=self.rope_theta,
                rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                norm_epsilon=self.rms_eps)
        else:
            mixer_name, mixer = "gdn", nn.GatedDeltaNet(
                self.linear_num_k_heads, self.linear_num_v_heads,
                self.linear_k_head_dim, self.linear_v_head_dim,
                conv_kernel=self.linear_conv_kernel, chunk=self.chunk,
                epsilon=self.rms_eps)
        moe = DroplessMoE(self.num_experts, self.top_k, self.moe_units,
                          experts_held=self.experts_held,
                          first_expert=self.first_expert,
                          shared_units=self.shared_units,
                          norm_topk_prob=self.norm_topk_prob)
        return Qwen3NextBlock(mixer, mixer_name, moe, self.rms_eps,
                              name=f"layer_{i}")

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        x = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        ids, name="embed").astype(self.dtype)
        for i in range(self.n_layers):
            block = self._block(i)
            if self.remat:
                # what the kernels' backward passes read is kept, so a
                # block's recomputation runs neither again: the flash
                # kernel's output (0.13 GB a layer at 8k x 2 rows), the
                # delta rule's output, chunk-start states and inverses
                # (0.13 + 0.27 + 0.13 GB)
                x = scope.child(
                    nn.Remat(block, save_names=(
                        "flash_attention_out", "flash_attention_lse",
                        "gated_delta_rule_out", "gated_delta_rule_states",
                        "gated_delta_rule_inverse")),
                    x, name=f"remat_{i}")
            else:
                x = scope.child(block, x, name=f"layer_{i}")
        x = scope.child(nn.RMSNorm(self.rms_eps, zero_centered=True), x,
                        name="final_norm")
        return scope.child(nn.Dense(self.vocab_size, use_bias=False), x,
                           name="head")
