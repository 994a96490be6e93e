"""Attention layers.

``MultiHeadAttention``: the plain layer of the BERT family (reference,
SURVEY.md §2.3, §5.7: the Scala Keras zoo's TransformerLayer / BERT
self-attention, replicated per worker at seq <= 512 on the CPU) and, by its
options, the attention of today's decoder blocks: grouped-query heads, a
q/k RMSNorm, rotary embedding on a part of each head, an output gate, a
sliding window, a published softmax scale.  ``LatentAttention``: multi-head
latent attention (DeepSeek-V2/V3's block): queries through a low-rank
bottleneck, keys and values rebuilt from one low-rank latent, the rotary
part of the key one head shared by all.  ``TransformerLayer``: the
reference's pre/post-LN encoder block.

Both attention classes hand q, k, v ``[B, T, H, D]`` to ``attention_core``,
the one dense / flash / ring dispatch: batched einsum attention that XLA
puts on the MXU (under ``jax.checkpoint`` with ``remat``), the Pallas flash
kernels of ``analytics_zoo_tpu.ops.flash_attention`` (forward and backward;
a window visits its band's tiles alone), or ring attention over a ``seq``
mesh axis (``analytics_zoo_tpu.parallel.ring_attention``).
``rotary_embedding``, ``causal_mask`` and ``dot_product_attention`` are the
pieces, usable alone.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from . import initializers
from .layers import Dense, Dropout, LayerNormalization, RMSNorm
from .module import Module, Scope


# use_flash="auto" switches to the Pallas flash kernel at this kv length.
# The crossover timings that stood here (BERT-base, seq 512-4096) came through
# a route to the device that is gone (PERF.md); none of them survives.  What
# this constant has been read against since, on a directly attached v5e
# (PR 27, one reading each, the whole layer with its projections): 16 query /
# 2 kv heads of 256, causal, one row of 8192 tokens, forward 14.8 ms with the
# kernel against 453.1 ms dense, forward + backward 49.2 ms against 938.7 ms
# dense + remat; at two rows the dense path's [2, 16, 8192, 8192] float32
# logits do not fit the chip.  At 32 query heads of 128 (kv heads repeated
# to 32), causal, one row, the attention core alone (PR 32, with the backward
# a Pallas kernel too; one reading each, forward / forward + backward, dense
# under jax.checkpoint; PR 31's readings with the blocked jax.numpy backward
# in brackets): at 2048 tokens, the shortest length read, the kernels 1.59 /
# 2.44 ms (1.57 / 3.66) against 4.26 / 7.82 dense, and with a window of 1024
# 1.39 / 2.14 (1.38 / 4.31) against 4.23 / 7.84; at 4096 tokens 6.03 / 8.71
# (6.06 / 13.69) against 16.04 / 29.93, and with a window of 2048 4.32 / 6.91
# (4.31 / 11.58) against 16.04 / 29.93; at 16,384 tokens 88.1 / 119.6
# (88.1 / 252.8) full and 20.5 / 32.1 (20.4 / 56.2) with a window of 2048
# (dense does not fit).  So at these heads the kernels lead from 2048 on, by
# 3.2-4.3x forward + backward; BERT's 12 x 64 heads and batches of rows have
# not been read there, and the constant stays.
FLASH_AUTO_MIN_SEQ = 2048


def causal_mask(tq: int, tk: Optional[int] = None,
                window: Optional[int] = None) -> jax.Array:
    """[1, 1, Tq, Tk] lower-triangular attend-mask (shared by the dense path
    and ring_attention's no-seq-axis fallback); handles Tq != Tk
    (cross-attention) by comparing absolute positions.  With a ``window``
    the band: position i sees the ``window`` keys ``i - window + 1 .. i``."""
    tk = tq if tk is None else tk
    seen = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
    mask = seen >= 0 if window is None else (seen >= 0) & (seen < window)
    return mask[None, None]


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None,
                          scale: Optional[float] = None) -> jax.Array:
    """Plain attention: q,k,v [B, T, H, D] → [B, T, H, D].

    mask: broadcastable to [B, H, Tq, Tk]; 1 = attend, 0 = masked.
    scale: the logits' multiplier, 1/sqrt(D) unless given.
    """
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    if scale is None:
        logits = logits / jnp.sqrt(jnp.asarray(d, logits.dtype))
    else:
        logits = logits * scale
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def rotary_embedding(x: jax.Array, rotary_dim: int,
                     theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding on the first ``rotary_dim`` dims of every
    head of ``x`` ([B, T, H, D]; positions 0..T-1), the rest passed through.
    Half-split pairing: dim ``i`` turns with dim ``i + rotary_dim/2`` by the
    angle ``t * theta^(-2i/rotary_dim)``.  Angles and the rotation in
    float32 (at theta 1e7 and T 8192 a bf16 angle is off by whole turns)."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rotary_dim)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = False, window: Optional[int] = None,
                   scale: Optional[float] = None,
                   mask: Optional[jax.Array] = None,
                   use_flash: Union[bool, str] = False,
                   use_ring: bool = False, remat: bool = False) -> jax.Array:
    """The dense / flash / ring dispatch of every attention layer: q
    ``[B, Tq, H, D]``, k ``[B, Tk, H, D]``, v ``[B, Tk, H, Dv]`` (the heads
    already repeated to H) -> ``[B, Tq, H, Dv]``.  ``use_flash="auto"``
    takes the flash kernels from ``FLASH_AUTO_MIN_SEQ`` keys on; an explicit
    ``mask`` takes the dense path, where ``causal`` and ``window`` still
    apply; ``remat`` recomputes the dense core in the backward pass.  The
    flash kernels want value heads as wide as the key's (``Dv == D``); the
    dense path takes any."""
    tq, tk = q.shape[1], k.shape[1]
    if use_flash == "auto":
        use_flash = (mask is None
                     and tk >= FLASH_AUTO_MIN_SEQ)
    if use_ring and mask is None:
        from analytics_zoo_tpu.parallel import ring_self_attention
        return ring_self_attention(q, k, v, causal=causal)
    if use_flash and mask is None:
        if v.shape[-1] != q.shape[-1]:
            raise ValueError(
                f"the flash kernels take value heads as wide as the key's; "
                f"got {v.shape[-1]} against {q.shape[-1]} at {tk} keys "
                "(use_flash=False is the dense path, which takes any)")
        from analytics_zoo_tpu.ops import flash_attention
        return flash_attention(q, k, v, causal=causal,
                               window=window, scale=scale)
    # explicit mask: dense path (flash/ring kernels take no mask);
    # causal still applies — combine, never silently drop it
    if window is not None and mask is not None \
            and tk >= FLASH_AUTO_MIN_SEQ:
        # the band exists to spare the [T, T] maps: a mask that
        # forces them at such a length is refused, not obeyed
        raise ValueError(
            f"a window with an explicit mask takes the dense path; "
            f"at {tk} keys (>= {FLASH_AUTO_MIN_SEQ}) that "
            "is [T, T] maps the window was meant to spare")
    if causal:
        cm = causal_mask(tq, tk, window)
        mask = cm if mask is None else (mask.astype(bool) & cm)
    attn = functools.partial(dot_product_attention, scale=scale)
    return (jax.checkpoint(attn) if remat else attn)(q, k, v, mask)


class MultiHeadAttention(Module):
    """Multi-head attention, ``[B, T, D] -> [B, T, D]``.  The defaults are
    the plain layer of the BERT family.  A decoder block of today's kind
    sets, in any combination: ``num_kv_heads`` (grouped-query attention:
    each key/value head serves ``num_heads / num_kv_heads`` query heads),
    ``qk_norm`` (an RMSNorm over the head dim on q and on k, before the
    rotation; zero-centred unless ``qk_norm_zero_centered=False``, which
    stores the scale itself, initialised 1), ``rotary_dim`` / ``rope_theta``
    (rotary embedding on the first ``rotary_dim`` dims of each head, 0 for
    none; self-attention from position 0), ``gate`` (``wq`` is twice as
    wide, split a head into query and gate; the context is multiplied by
    ``sigmoid(gate)`` before ``wo``), ``window`` (sliding-window attention,
    causal only: position i sees the ``window`` keys ``i - window + 1 ..
    i``; the flash path visits the band's blocks alone, the dense path
    builds the band mask), ``scale`` (the softmax's multiplier where a
    model publishes one other than ``1/sqrt(head_dim)``; dense and flash
    paths).  All of them go through the one dense / flash / ring dispatch
    above (``attention_core``)."""

    # what the flash backward reads: small beside running the kernel again
    remat_keep = ("flash_attention_out", "flash_attention_lse")

    def __init__(self, num_heads: int, head_dim: Optional[int] = None,
                 dropout: float = 0.0,
                 use_flash: Union[bool, str] = False,
                 use_ring: bool = False, causal: bool = False,
                 remat: bool = False, dtype: Optional[Any] = None,
                 num_kv_heads: Optional[int] = None, qk_norm: bool = False,
                 rotary_dim: int = 0, rope_theta: float = 10000.0,
                 gate: bool = False, norm_epsilon: float = 1e-6,
                 window: Optional[int] = None,
                 qk_norm_zero_centered: bool = True,
                 scale: Optional[float] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        if scale is not None and use_ring:
            raise ValueError("scale is taken by the dense and flash paths; "
                             "ring attention scales by 1/sqrt(head_dim)")
        self.scale = scale
        if window is not None and (not causal or use_ring or window < 1):
            raise ValueError("window is sliding-window causal attention on "
                             "the dense or flash path: it needs causal=True, "
                             f"no ring and window >= 1; got {window}")
        self.window = window
        self.qk_norm_zero_centered = qk_norm_zero_centered
        if num_kv_heads is not None and num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} is not a multiple of "
                             f"num_kv_heads {num_kv_heads}")
        if rotary_dim % 2:
            raise ValueError(f"rotary_dim must be even; got {rotary_dim}")
        self.num_kv_heads = num_kv_heads or num_heads
        self.qk_norm = qk_norm
        self.rotary_dim = rotary_dim
        self.rope_theta = rope_theta
        self.gate = gate
        self.norm_epsilon = norm_epsilon
        if use_flash not in (True, False, "auto"):
            raise ValueError(
                f"use_flash must be True, False, or 'auto'; got "
                f"{use_flash!r}")
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.dropout = dropout
        self.use_flash = use_flash
        self.use_ring = use_ring  # sequence-parallel ring attention (seq axis)
        self.causal = causal
        # remat: rematerialize the attention core (logits/softmax) in the
        # backward pass instead of saving residuals — trades ~2*T^2*d
        # recompute FLOPs per head for the T x T probability maps' HBM
        # round-trip, which XLA would otherwise keep for every layer's
        # backward.  Exact: same math, recomputed.
        self.remat = remat
        # use_flash: True | False | "auto" — "auto" picks the flash
        # kernel when the kv length reaches FLASH_AUTO_MIN_SEQ (the
        # measured crossover) and there is no explicit mask; below it,
        # the dense path (+ remat if set) wins.  Same math either way.
        if remat and (use_flash is True or use_ring):
            # the flash/ring kernels already avoid materializing the
            # T x T maps — remat would silently be a no-op there; make
            # the conflicting config an error, not a wrong measurement.
            # ("auto" composes: remat applies when auto picks dense.)
            raise ValueError(
                "remat=True applies to the dense attention path only; "
                "use_flash/use_ring kernels already rematerialize — "
                "pick one (use_flash='auto' composes with remat)")
        self.dtype = dtype

    def forward(self, scope: Scope, x: jax.Array,
                kv: Optional[jax.Array] = None,
                mask: Optional[jax.Array] = None) -> jax.Array:
        kv = x if kv is None else kv
        d_model = x.shape[-1]
        h = self.num_heads
        d_head = self.head_dim or d_model // h
        init = initializers.get("glorot_uniform")

        def proj(name: str, src: jax.Array, heads: int = h,
                 width: int = d_head) -> jax.Array:
            w = scope.param(name, init, (src.shape[-1], heads * width))
            # same-dtype dot: an f32-preferred output downcast right after
            # would make both vjp matmuls mixed f32 x bf16 (see Dense)
            y = jnp.dot(src, w.astype(src.dtype))
            return y.reshape(src.shape[:-1] + (heads, width))

        kv_h = self.num_kv_heads
        gate = None
        if self.gate:
            q = proj("wq", x, width=2 * d_head)
            q, gate = q[..., :d_head], q[..., d_head:]
        else:
            q = proj("wq", x)
        k = proj("wk", kv, kv_h)
        v = proj("wv", kv, kv_h)
        if self.qk_norm:
            norm = RMSNorm(self.norm_epsilon,
                           zero_centered=self.qk_norm_zero_centered)
            q = scope.child(norm, q, name="q_norm")
            k = scope.child(norm, k, name="k_norm")
        if self.rotary_dim:
            q = rotary_embedding(q, self.rotary_dim, self.rope_theta)
            k = rotary_embedding(k, self.rotary_dim, self.rope_theta)
        if kv_h != h:  # query head i reads key/value head i // (h / kv_h)
            k = jnp.repeat(k, h // kv_h, axis=2)
            v = jnp.repeat(v, h // kv_h, axis=2)

        ctx = attention_core(q, k, v, causal=self.causal, window=self.window,
                             scale=self.scale, mask=mask,
                             use_flash=self.use_flash,
                             use_ring=self.use_ring, remat=self.remat)

        if gate is not None:
            ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)
                                       ).astype(ctx.dtype)
        wo = scope.param("wo", init, (h * d_head, d_model))
        out = jnp.dot(ctx.reshape(x.shape[:-1] + (h * d_head,)),
                      wo.astype(x.dtype))
        return scope.child(Dropout(self.dropout), out, name="drop")


#: level (a float32 leaf beside the counters; the Estimator observes it as
#: it stands, in the histogram ``mla.<key>``, docs/observability.md) of
#: LatentAttention: the largest |c_kv| after its norm in the layer's last
#: step, what a latent cache in a lower precision would have to hold
LATENT_LEVEL_KEYS = ("kv_latent_abs_max",)


class LatentAttention(Module):
    """Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 section
    2.1.1), self-attention from position 0, ``[B, T, D] -> [B, T, D]``, in
    its training form: keys and values are rebuilt from the latent at every
    position (the absorbed form that decodes from a cache of latents is not
    built).

    ``c_q = RMSNorm(x wq_a)`` (``q_rank`` wide) and ``c_q wq_b`` gives each
    head ``rope_dim + nope_dim`` query dims.  ``x wkv_a`` gives ``[c_kv |
    k_r]``: the latent (``kv_rank`` wide, then RMS-normalised) and ONE key
    head of ``rope_dim`` that every query head shares; ``c_kv wkv_b`` gives
    each head ``[k_n | v]``, ``nope_dim`` key dims and ``v_dim`` value dims.
    The rotary embedding turns the first ``rope_dim`` dims of a query head
    and all of ``k_r`` (:func:`rotary_embedding`: half-split pairs); a key
    head is ``[k_r | k_n]``.  Scores are scaled by ``1 / sqrt(rope_dim +
    nope_dim)``, and the ``num_heads * v_dim`` values go through ``wo``.  No
    bias anywhere; matmul operands in the input's dtype, norms and rotation
    in float32.

    The core goes through ``attention_core``; the flash kernels take it
    when ``rope_dim + nope_dim == v_dim`` and refuse it otherwise.

    State: ``counters`` — the level ``mla.kv_latent_abs_max``
    (``LATENT_LEVEL_KEYS``), read by the Estimator once an epoch.
    """

    remat_keep = MultiHeadAttention.remat_keep

    def __init__(self, num_heads: int, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 10000.0, norm_epsilon: float = 1e-6,
                 causal: bool = True, use_flash: Union[bool, str] = False,
                 name: Optional[str] = None):
        super().__init__(name)
        if rope_dim % 2:
            raise ValueError(f"rope_dim must be even; got {rope_dim}")
        if use_flash not in (True, False, "auto"):
            raise ValueError(
                f"use_flash must be True, False, or 'auto'; got "
                f"{use_flash!r}")
        self.num_heads = num_heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope_theta = rope_theta
        self.norm_epsilon = norm_epsilon
        self.causal = causal
        self.use_flash = use_flash

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        b, t, d_model = x.shape
        h, rope, nope = self.num_heads, self.rope_dim, self.nope_dim
        init = initializers.get("glorot_uniform")

        def proj(name: str, src: jax.Array, width: int) -> jax.Array:
            w = scope.param(name, init, (src.shape[-1], width))
            with jax.named_scope(name):  # a profile tells the five apart
                return jnp.dot(src, w.astype(src.dtype))  # same-dtype dot

        norm = RMSNorm(self.norm_epsilon)
        c_q = scope.child(norm, proj("wq_a", x, self.q_rank), name="q_norm")
        q = proj("wq_b", c_q, h * (rope + nope)).reshape(b, t, h, rope + nope)
        kv_a = proj("wkv_a", x, self.kv_rank + rope)
        c_kv = scope.child(norm, kv_a[..., :self.kv_rank], name="kv_norm")
        k_r = kv_a[..., None, self.kv_rank:]                 # one head
        kv = proj("wkv_b", c_kv, h * (nope + self.v_dim)).reshape(
            b, t, h, nope + self.v_dim)
        q = rotary_embedding(q, rope, self.rope_theta)
        k_r = rotary_embedding(k_r, rope, self.rope_theta)
        k = jnp.concatenate([jnp.broadcast_to(k_r, (b, t, h, rope)),
                             kv[..., :nope]], axis=-1)
        ctx = attention_core(q, k, kv[..., nope:], causal=self.causal,
                             use_flash=self.use_flash)

        if scope.init_mode:  # a level: nothing of the last step is read
            scope.variable("counters", lambda: {
                "mla." + LATENT_LEVEL_KEYS[0]: jnp.zeros((), jnp.float32)})
        scope.put_variable("counters", {
            "mla." + LATENT_LEVEL_KEYS[0]: jax.lax.stop_gradient(
                jnp.abs(c_kv).max().astype(jnp.float32))})
        return proj("wo", ctx.reshape(b, t, h * self.v_dim), d_model)


class TransformerLayer(Module):
    """Pre/post-LN transformer encoder block (reference: keras/layers
    TransformerLayer)."""

    def __init__(self, num_heads: int, hidden_mult: int = 4,
                 dropout: float = 0.0, pre_ln: bool = False,
                 use_flash: Union[bool, str] = False,
                 use_ring: bool = False,
                 causal: bool = False, remat_attention: bool = False,
                 name: Optional[str] = None):
        super().__init__(name)
        self.mha = MultiHeadAttention(num_heads, dropout=dropout,
                                      use_flash=use_flash, use_ring=use_ring,
                                      causal=causal, remat=remat_attention)
        self.hidden_mult = hidden_mult
        self.dropout = dropout
        self.pre_ln = pre_ln

    def forward(self, scope: Scope, x: jax.Array,
                mask: Optional[jax.Array] = None) -> jax.Array:
        d_model = x.shape[-1]
        ln1 = LayerNormalization(name="ln1")
        ln2 = LayerNormalization(name="ln2")
        ffn1 = Dense(d_model * self.hidden_mult, activation="gelu", name="ffn1")
        ffn2 = Dense(d_model, name="ffn2")
        drop = Dropout(self.dropout, name="drop")

        if self.pre_ln:
            a = scope.child(self.mha, scope.child(ln1, x, name="ln1"),
                            mask=mask, name="mha")
            x = x + scope.child(drop, a, name="drop1")
            f = scope.child(ln2, x, name="ln2")
            f = scope.child(ffn2, scope.child(ffn1, f, name="ffn1"),
                            name="ffn2")
            return x + scope.child(drop, f, name="drop2")
        a = scope.child(self.mha, x, mask=mask, name="mha")
        x = scope.child(ln1, x + scope.child(drop, a, name="drop1"),
                        name="ln1")
        f = scope.child(ffn2, scope.child(ffn1, x, name="ffn1"), name="ffn2")
        return scope.child(ln2, x + scope.child(drop, f, name="drop2"),
                           name="ln2")
