"""Hybrid state-space / attention causal decoder, the block structure of the
``granitemoehybrid`` family (IBM Granite 4.0-H, 2025-10;
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).

Absent from the reference, whose language models end at BERT (tfpark).
Built from layers the zoo shares with its other models.  ``layer_types``
says which blocks mix positions through a Mamba-2 state-space recurrence
(``nn.Mamba2``: nine of every ten as published) and which through
grouped-query softmax attention with no positional encoding at all
(``nn.MultiHeadAttention`` through its dense / flash dispatch, at the
published softmax multiplier and not ``1/sqrt(head_dim)``).  Every block
feeds forward through one dense SwiGLU; it is the pre-norm form of
``models.decoder.DecoderBlock``.  Four multipliers shape the residual
stream: the embedding is scaled up, both sublayers' outputs are scaled down
before they join it, and the logits are divided.  The head is TIED: it
reads the embedding's table, one parameter leaf whose gradient is the sum
of both uses.  Logits at every position, trained with
``sparse_categorical_crossentropy`` against the ids shifted by one.

Not built: a cache or a decode path with the convolution's and the
recurrence's state (``Estimator.predict`` recomputes the sequence), packed
documents with a state reset at each boundary, the family's sparse-expert
variants (``num_local_experts`` > 0).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn.module import Scope
from .common import ZooModel
from .decoder import DecoderBlock, stack

MAMBA, ATTENTION = "mamba", "attention"


class GraniteHybrid(ZooModel):
    """ids ``[B, T]`` -> logits ``[B, T, vocab_size]`` (causal).

    The defaults are granite-4.0-h-micro's published widths; ``n_layers`` /
    ``layer_types`` and ``vocab_size`` are what a deployment divides over
    its chips.  ``layer_types`` names each block's mixer (``"mamba"`` |
    ``"attention"``); None is the published pattern of 40.  ``remat``
    recomputes each block in the backward pass (``nn.Remat``) and keeps
    what ``remat_save`` names (values tagged with
    ``jax.ad_checkpoint.checkpoint_name``: the flash kernel's output and
    log-sum-exp by default; ``"mamba2_ssd_out"`` and
    ``"mamba2_ssd_states"`` are the recurrence's).  ``tie_embeddings=False``
    gives the head a ``[hidden, vocab]`` kernel of its own.
    """

    def __init__(self, vocab_size: int = 100352, hidden_size: int = 2048,
                 n_layers: int = 40,
                 layer_types: Optional[Sequence[str]] = None,
                 mamba_heads: int = 64, mamba_head_dim: int = 64,
                 mamba_state: int = 128, mamba_groups: int = 1,
                 mamba_conv_kernel: int = 4, mamba_conv_bias: bool = True,
                 chunk: int = 256, num_heads: int = 32,
                 num_kv_heads: int = 8, head_dim: int = 64,
                 ff_units: int = 8192, embedding_multiplier: float = 12.0,
                 attention_multiplier: float = 0.015625,
                 residual_multiplier: float = 0.22,
                 logits_scaling: float = 8.0, tie_embeddings: bool = True,
                 rms_eps: float = 1e-5,
                 use_flash: Union[bool, str] = "auto", remat: bool = True,
                 remat_save: Sequence[str] = ("flash_attention_out",
                                              "flash_attention_lse"),
                 dtype: Any = "bfloat16"):
        super().__init__()
        if layer_types is None:
            # attention at published layers 5, 15, 25, 35 (counted from 0)
            layer_types = [ATTENTION if i % 10 == 5 else MAMBA
                           for i in range(n_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != n_layers or \
                set(layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types must name {n_layers} layers as "
                             f"{MAMBA!r} or {ATTENTION!r}; got {layer_types}")
        remat_save = tuple(remat_save)
        self._config = {k: v for k, v in locals().items()
                        if k not in ("self", "__class__")}
        self.__dict__.update(self._config)
        self.dtype = jnp.dtype(dtype)
        self._norm = nn.RMSNorm(rms_eps)
        self._blocks = stack([self._block(i) for i in range(n_layers)], remat,
                             keep=remat_save)

    def _block(self, i: int) -> DecoderBlock:
        if self.layer_types[i] == ATTENTION:
            # no rotary embedding, no q/k norm, no gate: position reaches
            # this layer through the state-space blocks before it
            mixer = nn.MultiHeadAttention(
                self.num_heads, head_dim=self.head_dim, causal=True,
                use_flash=self.use_flash, num_kv_heads=self.num_kv_heads,
                scale=self.attention_multiplier, name="attn")
        else:
            mixer = nn.Mamba2(
                self.mamba_heads, self.mamba_head_dim, self.mamba_state,
                n_groups=self.mamba_groups,
                conv_kernel=self.mamba_conv_kernel, chunk=self.chunk,
                conv_bias=self.mamba_conv_bias, epsilon=self.rms_eps,
                name="mamba")
        return DecoderBlock(mixer, nn.SwiGLU(self.ff_units, name="mlp"),
                            ("input_norm", "post_mixer_norm"), self._norm,
                            self.residual_multiplier, name=f"layer_{i}")

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        x = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        ids, name="embed")
        x = (x * self.embedding_multiplier).astype(self.dtype)
        for block in self._blocks:
            x = scope.child(block, x, name=block.name)
        x = scope.child(self._norm, x, name="final_norm")
        # the logits' divisor, applied to [T, hidden] and not [T, vocab]
        x = x / self.logits_scaling
        if self.tie_embeddings:
            # the embedding's own leaf, read a second time: autodiff adds
            # the head's gradient to the gather's
            table = scope.params["embed"]["embeddings"]
            with jax.named_scope("head"):  # as the untied head's child is
                return jnp.einsum("btd,vd->btv", x, table.astype(x.dtype))
        return scope.child(nn.Dense(self.vocab_size, use_bias=False), x,
                           name="head")
