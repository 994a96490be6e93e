"""Looped language model: one stack of decoder blocks run several times
with the same weights, a per-pass exit gate and an exit-weighted loss over
every pass's head, the structure of the ``ouro`` family (Ouro 1.4B / 2.6B,
ByteDance Seed, "Scaling Latent Reasoning via Looped Language Models",
2025; https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json and
the release's ``modeling_ouro.py``).

Absent from the reference, whose language models end at BERT (tfpark).
Built from layers the zoo shares with its other models.  A block is
``models.decoder.DecoderBlock`` in its sandwich form with a dense SwiGLU:
``h += N(attn(N(h))); h += N(ff(N(h)))``, four RMSNorms, causal
``nn.MultiHeadAttention`` with rotary embedding on the whole head and its
dense / flash dispatch.  ``h = E[ids]``; then ``total_ut_steps`` passes,
each through every block (the SAME module objects every pass, so the
``Scope`` hands each the one set of weights and their gradient sums the
passes) and then the final RMSNorm, whose output is the state the next pass
starts from.  After pass ``t`` the untied head
gives ``logits_t`` and the exit gate ``lambda_t = sigmoid(h_t w_g + b_g)``;
the exit distribution a position is ``p_t = lambda_t prod_{j<t} (1 -
lambda_j)`` and, on the last pass, ``p_T = prod_{j<T} (1 - lambda_j)``.

Without labels the output is every pass's logits, ``[B, passes, T,
vocab]``.  With ``labels`` (``losses.model_loss``: the Estimator passes the
batch's ``y``) the output is the report's entropy-regularised loss, ``mean_i
[sum_t p_t(i) CE_t(i) - beta H(p(i))]``: each pass's cross-entropy a token
comes from ``ops.fused_softmax_xent_tokens`` in chunks of ``HEAD_CHUNK``
tokens, so no ``[tokens, vocab]`` array of any pass is held, and the
exit weights and the entropy are taken in log space.  Ops carry the scopes
``loop`` (every pass's blocks) and ``exit`` (each pass's final norm, the
head's chunks, the gate and, under ``exit/loss``, the weighting); each
training step leaves float32 levels in the model's state under
``counters`` (``level_keys``), which ``fit()`` publishes once an epoch
as the registry series ``ouro.<key>``.

Not built: the report's stage-II training of the gate alone and the
inference exit rule (``early_exit_threshold``: 1 runs every pass, as here),
a cache or decode path (one key / value cache a pass), packed documents.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn import initializers
from analytics_zoo_tpu.nn.module import Module, Scope
from analytics_zoo_tpu.ops.fused_xent import fused_softmax_xent_tokens
from .common import ZooModel
from .decoder import DecoderBlock, stack

#: tokens a chunk of the heads' cross-entropy: a chunk's float32 logits at
#: the published vocabulary are 201 MB, and its product 206 GFLOP
HEAD_CHUNK = 1024


def level_keys(passes: int) -> Tuple[str, ...]:
    """The levels a training step leaves (``ouro.<key>``): each pass's mean
    cross-entropy, each pass's mean exit mass ``p_t``, the expected number
    of passes ``sum_t t p_t`` and the exit distribution's entropy, means
    over the step's positions."""
    return (tuple(f"loss_pass{t}" for t in range(1, passes + 1))
            + tuple(f"exit_pass{t}" for t in range(1, passes + 1))
            + ("expected_passes", "entropy"))


#: ``level_keys`` at the published four passes, as the registry's catalog
#: (docs/observability.md) lists them
OURO_LEVEL_KEYS = ("loss_pass1", "loss_pass2", "loss_pass3", "loss_pass4",
                   "exit_pass1", "exit_pass2", "exit_pass3", "exit_pass4",
                   "expected_passes", "entropy")


class ExitHead(Module):
    """The untied vocabulary head: ``h [..., D]`` -> logits ``[..., V]``;
    given ``labels [...]``, each position's cross-entropy ``[...]`` in
    float32, ``chunk`` tokens at a time (the logits of a chunk at most)."""

    def __init__(self, vocab_size: int, chunk: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.vocab_size, self.chunk = vocab_size, chunk

    def forward(self, scope: Scope, h: jax.Array,
                labels: Optional[jax.Array] = None) -> jax.Array:
        w = scope.param("kernel", initializers.get("glorot_uniform"),
                        (h.shape[-1], self.vocab_size))
        if labels is None:
            return jnp.dot(h, w.astype(h.dtype))
        return fused_softmax_xent_tokens(h, w, labels, self.chunk)


class Ouro(ZooModel):
    """ids ``[B, T]`` -> logits ``[B, total_ut_steps, T, vocab_size]``
    (causal; one row a pass), or with ``labels [B, T]`` the scalar loss.

    The defaults are Ouro-2.6B's published widths; ``n_layers`` is what a
    pipeline stage holds.  ``entropy_beta`` weighs the exit distribution's
    entropy in the loss.
    ``remat`` recomputes each block application in the backward pass
    (``nn.Remat``, one wrapper a block for every pass) and keeps the flash
    kernel's outputs.
    """

    def __init__(self, vocab_size: int = 49152, hidden_size: int = 2048,
                 n_layers: int = 48, total_ut_steps: int = 4,
                 num_heads: int = 16, num_kv_heads: int = 16,
                 head_dim: int = 128, rope_theta: float = 1000000.0,
                 ffn_units: int = 5632, rms_eps: float = 1e-6,
                 entropy_beta: float = 0.05,
                 use_flash: Union[bool, str] = "auto", remat: bool = True,
                 dtype: Any = "bfloat16"):
        super().__init__()
        if total_ut_steps < 1:
            raise ValueError(f"total_ut_steps must be >= 1; got "
                             f"{total_ut_steps}")
        self._config = {k: v for k, v in locals().items()
                        if k not in ("self", "__class__")}
        self.__dict__.update(self._config)
        self.dtype = jnp.dtype(dtype)
        # built once: every pass runs these same objects
        self._norm = nn.RMSNorm(rms_eps)
        self._blocks = stack([self._block(i) for i in range(n_layers)], remat)
        self._head = ExitHead(vocab_size, HEAD_CHUNK)
        self._gate = nn.Dense(1, dtype=jnp.float32)

    def _block(self, i: int) -> DecoderBlock:
        attn = nn.MultiHeadAttention(
            self.num_heads, head_dim=self.head_dim, causal=True,
            use_flash=self.use_flash, num_kv_heads=self.num_kv_heads,
            rotary_dim=self.head_dim, rope_theta=self.rope_theta, name="attn")
        return DecoderBlock(attn, nn.SwiGLU(self.ffn_units, name="mlp"),
                            ("input_norm", "post_attn_norm", "pre_ff_norm",
                             "post_ff_norm"), self._norm,
                            name=f"layer_{i}")

    def forward(self, scope: Scope, ids: jax.Array,
                labels: Optional[jax.Array] = None) -> jax.Array:
        scope.variable("counters", lambda: {
            "ouro." + k: jnp.zeros((), jnp.float32)
            for k in level_keys(self.total_ut_steps)})
        x = scope.child(nn.Embedding(self.vocab_size, self.hidden_size),
                        ids, name="embed").astype(self.dtype)
        states = []
        for _ in range(self.total_ut_steps):
            with jax.named_scope("loop"):
                for block in self._blocks:
                    x = scope.child(block, x, name=block.name)
            with jax.named_scope("exit"):
                # the normed state is the one the next pass starts from
                x = scope.child(self._norm, x, name="final_norm")
            states.append(x)
        h = jnp.stack(states, axis=1)                       # [B, P, T, D]
        with jax.named_scope("exit"):
            # the last pass's gate is never read: p_T is what is left
            gate = scope.child(self._gate, h[:, :-1],
                               name="exit_gate")[..., 0]     # [B, P-1, T]
            if labels is None:
                return scope.child(self._head, h, name="head")
            nll = scope.child(self._head, h, jnp.broadcast_to(
                labels[:, None], h.shape[:-1]), name="head")   # [B, P, T]
            with jax.named_scope("loss"):
                loss, levels = exit_weighted_loss(nll, gate,
                                                  self.entropy_beta)
        scope.put_variable("counters", {"ouro." + k: jax.lax.stop_gradient(v)
                                        for k, v in levels.items()})
        return loss


def exit_distribution(gate: jax.Array) -> jax.Array:
    """``log p [B, P, T]`` from the gate's logits ``[B, P-1, T]``: ``log p_t
    = log lambda_t + sum_{j<t} log(1 - lambda_j)``, the last pass without
    its own ``lambda``; in float32 log space, so no product underflows."""
    gate = gate.astype(jnp.float32)
    zero = jnp.zeros(gate.shape[:1] + (1,) + gate.shape[2:], jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=1)
    return (jnp.concatenate([zero, stay], axis=1)
            + jnp.concatenate([jax.nn.log_sigmoid(gate), zero], axis=1))


def exit_weighted_loss(nll: jax.Array, gate: jax.Array, beta: float):
    """The report's entropy-regularised objective from each pass's
    cross-entropy ``nll [B, P, T]`` and the gate's logits ``[B, P-1, T]``:
    ``mean_i [sum_t p_t CE_t - beta H(p)]``, and the levels of
    ``level_keys``."""
    log_p = exit_distribution(gate)
    p = jnp.exp(log_p)
    entropy = -(p * log_p).sum(axis=1)                        # [B, T]
    loss = ((p * nll).sum(axis=1) - beta * entropy).mean()
    passes = nll.shape[1]
    levels = {f"loss_pass{t + 1}": nll[:, t].mean() for t in range(passes)}
    levels.update({f"exit_pass{t + 1}": p[:, t].mean()
                   for t in range(passes)})
    levels["expected_passes"] = (
        p * jnp.arange(1, passes + 1, dtype=jnp.float32)[:, None]
    ).sum(axis=1).mean()
    levels["entropy"] = entropy.mean()
    return loss, levels
