"""Latent-attention sparse-expert causal decoder with a multi-token
prediction module, the block structure of the ``glm4_moe_lite`` family
(GLM-4.7-Flash; https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/
config.json), which is DeepSeek-V3's (arXiv:2412.19437, sections 2.1.1,
2.1.2, 2.2).

Absent from the reference, whose language models end at BERT (tfpark).
Built from layers the zoo shares with its other models.  Every block
attends through ``nn.LatentAttention`` (queries through a low-rank
bottleneck, keys and values rebuilt from one low-rank latent, the rotary
part of the key one head for all) and its dense / flash dispatch.  The
first ``num_dense_layers`` blocks feed forward through a dense SwiGLU, the
others through a dropless top-k expert layer with an ungated shared expert
(``parallel.DroplessMoE``): a sigmoid router balanced by a bias on its
selection and by no auxiliary loss; it may hold a share of the experts
only.  Two RMSNorms a block (the pre-norm ``models.decoder.DecoderBlock``),
a final RMSNorm and an untied vocabulary head.

``mtp_layers=1`` adds the multi-token prediction module: from the last
block's output ``h_i`` (before the final norm) and the embedding of the
NEXT token, ``eh_proj [RMSNorm(E[t_{i+1}]) ; RMSNorm(h_i)]`` goes through
one more expert block and a norm of its own to the main model's head,
which then predicts the token after the next.  Embedding and head are the
main model's leaves, read twice.  The model's output is one array, a row of
logits a prediction depth, ``[B, 1 + mtp_layers, T, vocab]``: depth 0 at
position i is scored against ``t_{i+1}``, depth 1 against ``t_{i+2}``
(``nn.losses.multi_token_crossentropy``).  The model sees ``t_0 ..
t_{T-1}``: at the row's last position the module is given ``t_{T-1}``
again for the token it cannot see, and that position is in no loss.

Not built: a cache or a decode path (the absorbed form of latent attention
that decodes from cached latents; ``Estimator.predict`` recomputes the
sequence), drafting from the prediction module, prediction depths past 1
(the report chains them), grouped expert selection (``n_group > 1``),
packed documents, and the expert exchange across chips (a share computes
its own experts' part and nothing else).
"""

from __future__ import annotations

from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn.module import Module, Scope
from analytics_zoo_tpu.parallel.moe import DroplessMoE
from .common import ZooModel
from .decoder import DecoderBlock, stack

#: device-side counters of the prediction module, kept in the model's state
#: under ``counters`` and published by the Estimator once an epoch as the
#: registry series ``mtp.<key>`` (docs/observability.md): positions of depth
#: 1 whose target the model holds (``t_{i+2}`` is among its ids: T - 2 a
#: row, one fewer than the loss scores), and those of them where depth 1's
#: arg-max is the target (what a decoder drafting from the module would
#: accept)
MTP_COUNTER_KEYS = ("positions", "top1_hits")

#: a level beside them (a float32 leaf: observed as it stands, in the
#: histogram ``mtp.<key>``): depth 1's mean cross-entropy over the same
#: positions, unweighted, in the last step
MTP_LEVEL_KEYS = ("loss",)


class MultiTokenPredictor(Module):
    """One prediction depth (DeepSeek-V3 section 2.2): the hidden states
    ``h`` of the depth before and the embeddings ``e`` of the tokens one
    further on, both ``[B, T, D]`` -> the normalised hidden states the
    shared head reads."""

    def __init__(self, block: DecoderBlock, epsilon: float, remat: bool,
                 name: Optional[str] = None):
        super().__init__(name)
        self.block = (nn.Remat(block, name="remat",
                               save_names=block.remat_keep)
                      if remat else block)
        self.norm = nn.RMSNorm(epsilon)

    def forward(self, scope: Scope, h: jax.Array, e: jax.Array) -> jax.Array:
        def norm(name: str, a: jax.Array) -> jax.Array:
            return scope.child(self.norm, a, name=name)
        g = jnp.concatenate([norm("enorm", e), norm("hnorm", h)], axis=-1)
        g = scope.child(nn.Dense(h.shape[-1], use_bias=False), g,
                        name="eh_proj")
        g = scope.child(self.block, g, name=self.block.name)
        return norm("head_norm", g)


class GlmMoeLite(ZooModel):
    """ids ``[B, T]`` -> logits ``[B, 1 + mtp_layers, T, vocab_size]``
    (causal; one row of logits a prediction depth).

    The defaults are GLM-4.7-Flash's published widths; ``n_layers`` /
    ``num_dense_layers``, ``experts_held`` / ``first_expert`` and
    ``vocab_size`` are what a deployment divides over its chips.
    ``mtp_layers`` is 0 (a plain decoder, ``[B, 1, T, vocab]``) or 1.
    ``remat`` recomputes each block in the backward pass (``nn.Remat``) and
    keeps the flash kernel's outputs.
    """

    def __init__(self, vocab_size: int = 154880, hidden_size: int = 2048,
                 n_layers: int = 47, num_dense_layers: int = 1,
                 num_heads: int = 20, q_rank: int = 768, kv_rank: int = 512,
                 nope_dim: int = 192, rope_dim: int = 64, v_dim: int = 256,
                 rope_theta: float = 1000000.0, dense_units: int = 10240,
                 num_experts: int = 64, top_k: int = 4,
                 moe_units: int = 1536, shared_units: int = 1536,
                 route_scale: float = 1.8, balance_coeff: float = 0.001,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 mtp_layers: int = 1, rms_eps: float = 1e-5,
                 use_flash: Union[bool, str] = "auto", remat: bool = True,
                 dtype: Any = "bfloat16"):
        super().__init__()
        if mtp_layers not in (0, 1):
            raise ValueError("mtp_layers is 0 or 1 (deeper prediction "
                             f"modules are not built); got {mtp_layers}")
        self._config = {k: v for k, v in locals().items()
                        if k not in ("self", "__class__")}
        self.__dict__.update(self._config)
        self.dtype = jnp.dtype(dtype)
        self._norm = nn.RMSNorm(rms_eps)
        self._blocks = stack([self._block(i < num_dense_layers, f"layer_{i}")
                              for i in range(n_layers)], remat)
        self._mtp = (MultiTokenPredictor(self._block(False, "block"), rms_eps,
                                         remat, name="mtp")
                     if mtp_layers else None)

    def _block(self, dense: bool, name: str) -> DecoderBlock:
        attn = nn.LatentAttention(
            self.num_heads, self.q_rank, self.kv_rank, self.nope_dim,
            self.rope_dim, self.v_dim, rope_theta=self.rope_theta,
            norm_epsilon=self.rms_eps, causal=True, use_flash=self.use_flash,
            name="attn")
        if dense:
            ff = nn.SwiGLU(self.dense_units, name="mlp")
        else:
            ff = DroplessMoE(
                self.num_experts, self.top_k, self.moe_units,
                experts_held=self.experts_held,
                first_expert=self.first_expert,
                shared_units=self.shared_units, shared_gate=False,
                score_func="sigmoid", route_scale=self.route_scale,
                norm_epsilon=1e-20, balance_coeff=self.balance_coeff)
        return DecoderBlock(attn, ff, ("input_norm", "post_attn_norm"),
                            self._norm, name=name)

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        depths = [ids]
        if self.mtp_layers:
            # the tokens one further on; the row's last stands in for the
            # one past its end
            depths.append(jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1))
        # one gather of the one table for every depth: [B, K, T, D]
        e = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        jnp.stack(depths, axis=1),
                        name="embed").astype(self.dtype)
        x = e[:, 0]
        for block in self._blocks:
            x = scope.child(block, x, name=block.name)
        hidden = [scope.child(self._norm, x, name="final_norm")]
        if self.mtp_layers:
            hidden.append(scope.child(self._mtp, x, e[:, 1], name="mtp"))
        # the one head over every depth's rows at once
        logits = scope.child(nn.Dense(self.vocab_size, use_bias=False),
                             jnp.stack(hidden, axis=1), name="head")
        if self.mtp_layers:
            self._count(scope, jax.lax.stop_gradient(logits[:, 1]), ids)
        return logits

    def _count(self, scope: Scope, logits: jax.Array, ids: jax.Array) -> None:
        """Depth 1 against the targets the model holds: position i predicts
        ``ids[i + 2]``."""
        with jax.named_scope("mtp_counters"):
            logits, target = logits[:, :-2], ids[:, 2:]
            level = nn.losses.sparse_categorical_crossentropy(logits, target)
            grew = dict(zip(MTP_COUNTER_KEYS, (
                jnp.asarray(target.size, jnp.int32),
                jnp.sum(jnp.argmax(logits, axis=-1) == target,
                        dtype=jnp.int32))))
        seen = scope.variable("counters", lambda: {
            **{"mtp." + k: jnp.zeros((), jnp.int32)
               for k in MTP_COUNTER_KEYS},
            **{"mtp." + k: jnp.zeros((), jnp.float32)
               for k in MTP_LEVEL_KEYS}})
        scope.put_variable("counters", {
            **{"mtp." + k: seen["mtp." + k] + v for k, v in grew.items()},
            "mtp." + MTP_LEVEL_KEYS[0]: level})
