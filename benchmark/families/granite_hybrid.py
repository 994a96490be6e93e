"""The ``granitemoehybrid`` causal decoder (``models.GraniteHybrid``;
granite-4.0-h-micro): Mamba-2 state-space blocks in nine of every ten
layers and grouped-query softmax attention with no positional encoding in
the tenth, a dense SwiGLU in every block, four multipliers on the residual
stream and a tied, scaled head; logits at every position of a causal-LM row.

``reference`` is the published forward in plain float32, written from the
model's ``config.json`` and the ``granitemoehybrid`` port in
``transformers`` (each equation the config does not give is listed under
``assumed`` in the configuration file), on the system's own parameter tree:
the Mamba block as the RECURRENCE ITSELF (a ``lax.scan`` over positions, one
state update a token: no chunks, no [Q, Q] term, no cumulative sum),
softmax attention in blocks of queries behind an explicit mask at the
published multiplier (no kernel), the tied head as ``h @ E.T / 8``.  It is
given the same slice of the vocabulary as the system (model-configs guide,
section 4).

Departures from the published model, in the system and here alike:
``in_proj``'s columns are z | x B C | dt as the release has them (no
permutation); ``A_log`` and ``dt_bias`` are initialised as the Mamba-2
reference code does (``A`` uniform in (0, 16), ``dt`` log-uniform in
[0.001, 0.1]; the release's port starts ``A`` at 1..64 and ``dt_bias`` at 1,
which at ``A`` = 64 forgets the state within a position and would leave
the chunk-to-chunk carry untested); no cache, no packed documents.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import GraniteHybrid
from analytics_zoo_tpu.models.granite_hybrid import ATTENTION, MAMBA
from analytics_zoo_tpu.nn.module import Module

from benchmark.families import qwen3_next

#: Largest |system - reference| over the reference's largest magnitude, at
#: the logits of one timed row (8,192 tokens at the published widths).  Read
#: on the chip (PERF.md section 6, PR 33): the system 0.0055-0.0060 over the
#: builder's 22 runs; the reference itself with every matmul's operands (and
#: the recurrence's write and read) rounded to bf16 and float32 sums, the
#: least a bf16 system can differ by, 0.0010-0.0011; with operands rounded
#: to fp8 (e4m3) 0.0223-0.0244, which must fail; with the recurrence's
#: DECAYS in bf16 0.037-0.074, which must fail too (the system keeps them in
#: float32).  The system sits five times over the bf16-operand reading
#: because its RESIDUAL STREAM is bf16 as well: the embedding is scaled by
#: 12, so the stream's entries reach a few units at a relative 2^-8, through
#: twenty additions of 0.22 x a sublayer, where the reference rounds
#: operands only.  0.011 leaves the system's worst reading a factor of 1.8
#: and sits a factor of 2.0 under fp8's least.
TOLERANCE = 0.011

#: per-leaf limit of the gradient comparison (``reference_loss_and_grads``):
#: ||g_system - g_reference|| / ||g_reference|| of every parameter leaf, on
#: one row of 8,192 tokens at the published widths.  Read on the chip
#: (PERF.md section 6, PR 33; six seeds): loss 9.517965 against 9.517186
#: (the others alike); the system's matrices at most 0.0133, its median leaf
#: 0.011-0.013, its worst 0.023-0.062, always a ``dt_bias`` or ``A_log`` (64
#: numbers, each summed over 8,192 positions of one head); the reference
#: with bf16 operands reads a worst of 0.012-0.021 on the same leaves; with
#: fp8 operands every one of the 128 leaves reads over 0.8 (cotangents
#: rounded to e4m3 underflow); with bf16 decays 102-124 read over 0.15
#: (median 0.17-0.33).  One limit for every leaf: 2.4 times the system's
#: worst.
GRAD_TOLERANCE = 0.15

_ATTN_QUERY_BLOCK = 512   # reference attention: [H, 512, T] scores at once
_SCAN_SEGMENT = 64        # reference recurrence: checkpoint every 64 steps


def build(config: dict) -> Module:
    return GraniteHybrid(**config["model"])


#: causal-LM rows from the vocabulary slice: the Qwen family's loader
loader = qwen3_next.loader
inputs = qwen3_next.inputs
batch_spec = qwen3_next.batch_spec


def _model(config: dict) -> dict:
    """``config["model"]`` with the constructor's defaults filled in."""
    return GraniteHybrid(**config["model"])._config


# -- the work the mathematics requires ----------------------------------------

def causal_pairs(t: int) -> int:
    """(query, key) pairs of one row and head under a causal mask."""
    return t * (t + 1) // 2


def matmul_params_per_token(m: dict) -> int:
    """Parameters a token meets in a matmul (or, for the depthwise
    convolution, a multiply-add); the tied table once, as the head (the
    embedding's gather is no matmul)."""
    d = m["hidden_size"]
    inner = m["mamba_heads"] * m["mamba_head_dim"]
    conv_dim = inner + 2 * m["mamba_groups"] * m["mamba_state"]
    mamba = d * (inner + conv_dim + m["mamba_heads"]) \
        + conv_dim * m["mamba_conv_kernel"] + inner * d
    heads, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    n_mamba = m["layer_types"].count(MAMBA)
    return n_mamba * mamba + (m["n_layers"] - n_mamba) * attn \
        + m["n_layers"] * 3 * d * m["ff_units"] + m["vocab_size"] * d


def recurrence_flops_per_token(m: dict) -> int:
    """Forward FLOPs of the recurrence itself a token, all Mamba layers:
    the state's write (``dt x B^T``) and read (``S C``), a multiply-add
    each over ``[H, P, N]``."""
    return 2 * 2 * m["mamba_head_dim"] * m["mamba_state"] \
        * m["mamba_heads"] * m["layer_types"].count(MAMBA)


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Training FLOPs a row: 6 x the matmul parameters a token, the two
    products (q k^T, p v) over the causal triangle of each attention layer
    and the recurrence's write and read, forward and backward (3 x).  No
    recomputation, no padding, no masked block; the chunked form's [Q, Q]
    products are not the mathematics' and are not counted."""
    m, t = _model(config), traffic["seq_len"]
    attn = 3 * 2 * 2 * m["head_dim"] * m["num_heads"] * causal_pairs(t) \
        * m["layer_types"].count(ATTENTION)
    return 6.0 * matmul_params_per_token(m) * t + attn \
        + 3.0 * recurrence_flops_per_token(m) * t


def ssd_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the state-space recurrence needs a step,
    forward and backward, whatever computes it, over the Mamba layers: the
    recurrence's term of :func:`flops_per_sample`; forward x and y (``H x
    P`` in the model's dtype), B and C (``G x N``) and dt (``H`` float32)
    moved once a token; backward those, dy and the five gradients (dx, dB,
    dC, ddt; dy counted with y's bytes), each once."""
    m = _model(config)
    tokens = traffic["global_batch"] * traffic["seq_len"]
    item = jnp.dtype(m["dtype"]).itemsize
    inner = m["mamba_heads"] * m["mamba_head_dim"]
    bc = 2 * m["mamba_groups"] * m["mamba_state"]
    forward = (2 * inner + bc) * item + m["mamba_heads"] * 4
    backward = forward + inner * item + (inner + bc) * item \
        + m["mamba_heads"] * 4
    return {"flops": float(3 * recurrence_flops_per_token(m) * tokens),
            "bytes": float((forward + backward) * tokens
                           * m["layer_types"].count(MAMBA))}


def flash_fwd_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes ONE forward pass of the attention layers'
    causal softmax attention needs a step, whatever computes it: q k^T and
    p v over the triangle's pairs, and q, the kv heads' k and v, and the
    output moved once."""
    m = _model(config)
    b, t = traffic["global_batch"], traffic["seq_len"]
    layers = m["layer_types"].count(ATTENTION)
    item = jnp.dtype(m["dtype"]).itemsize
    rows = b * t * m["head_dim"] * item * layers
    return {"flops": float(2 * 2 * m["head_dim"] * m["num_heads"] * b
                           * causal_pairs(t) * layers),
            "bytes": float(rows * (2 * m["num_heads"]
                                   + 2 * m["num_kv_heads"]))}


def flash_bwd_work(config: dict, traffic: dict) -> dict:
    """The backward pass of the same: five matmuls over the pairs where the
    forward has two (2.5 x its FLOPs); q, out, g read and dq written at the
    query heads, k, v read and dk, dv written at the kv heads (2 x its
    bytes), as PR 32's files count."""
    fwd = flash_fwd_work(config, traffic)
    return {"flops": 2.5 * fwd["flops"], "bytes": 2.0 * fwd["bytes"]}


# -- the plain float32 reference ------------------------------------------------

#: set by ``rounded_operands``: every matmul of the reference (and the
#: recurrence's write and read) rounds its operands to this dtype first
#: (None: plain float32)
_OPERAND_DTYPE = None
#: set by ``rounded_decays``: the recurrence's log decay a position and its
#: ``exp`` are rounded to this dtype (None: float32, as the system has them)
_DECAY_DTYPE = None


@contextlib.contextmanager
def rounded_operands(dtype):
    """The reference with the operands of every matmul rounded to ``dtype``
    (float32 accumulation): what a system computing in that precision would
    give at best.  For showing that TOLERANCE fails the precision below the
    one the configuration states."""
    global _OPERAND_DTYPE
    _OPERAND_DTYPE, was = dtype, _OPERAND_DTYPE
    try:
        yield
    finally:
        _OPERAND_DTYPE = was


@contextlib.contextmanager
def rounded_decays(dtype):
    """The reference with the recurrence's decays in ``dtype``: what a
    system that kept its decay exponents in that precision would give."""
    global _DECAY_DTYPE
    _DECAY_DTYPE, was = dtype, _DECAY_DTYPE
    try:
        yield
    finally:
        _DECAY_DTYPE = was


def _round(a, dtype):
    """``a`` rounded to ``dtype`` and back.  The barrier keeps the pair of
    casts: XLA:TPU removes a float32 -> bf16 -> float32 round trip that
    feeds an elementwise op (``xla_allow_excess_precision``), and the
    reading would be 0."""
    if dtype is None:
        return a
    return jax.lax.optimization_barrier(a.astype(dtype)).astype(jnp.float32)


def _mm(a, b):
    return _round(a, _OPERAND_DTYPE) @ _round(b, _OPERAND_DTYPE)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def recurrence_reference(x, dt, a, b, c, d_skip, s0=None):
    """The state-space recurrence position by position.  x ``[B, T, H, P]``,
    dt ``[B, T, H]``, a (< 0) and d_skip ``[H]``, b, c ``[B, T, G, N]``;
    returns ``(y [B, T, H, P], final state [B, H, P, N])``.  ``S_t =
    exp(dt_t a) S_{t-1} + dt_t x_t b_t^T; y_t = S_t c_t + D x_t``.  The
    positions are walked in segments under ``jax.checkpoint`` so that a
    gradient through 8,192 of them keeps T/64 states and not T."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    seg = qwen3_next._divisor(t, _SCAN_SEGMENT)
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = _round(jnp.exp(_round(dt_t * a, _DECAY_DTYPE)), _DECAY_DTYPE)
        write = _round(dt_t[..., None] * x_t, _OPERAND_DTYPE)[..., None] \
            * _round(b_t, _OPERAND_DTYPE)[:, :, None, :]
        s = decay[..., None, None] * s + write
        y_t = jnp.einsum("bhpn,bhn->bhp", s, _round(c_t, _OPERAND_DTYPE))
        return s, y_t + d_skip[:, None] * x_t

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(step, s, xs)

    def split(v):  # [B, T, ...] -> [T/seg, seg, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // seg, seg) + v.shape[1:])

    if s0 is None:
        s0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    s, y = jax.lax.scan(segment, s0, tuple(map(split, (x, dt, b, c))))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1), s


def mamba_reference(p, x, m):
    """The Mamba-2 mixer: ``[z | xBC | dt] = x W_in``; xBC through the
    depthwise causal convolution with its bias and a SiLU; the recurrence;
    ``RMSNorm_w(y * silu(z))`` over all channels; ``W_out``."""
    bsz, t, _ = x.shape
    h, hp, n, g = (m["mamba_heads"], m["mamba_head_dim"], m["mamba_state"],
                   m["mamba_groups"])
    inner = h * hp
    zxbcdt = _mm(x, p["in_proj"]["kernel"])
    z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * g * n]
    dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * g * n:] + p["dt_bias"])
    w = p["conv"]["kernel"]
    width = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + t] * w[j] for j in range(width))
    if m["mamba_conv_bias"]:
        xbc = xbc + p["conv"]["bias"]
    xbc = jax.nn.silu(xbc)
    y, _ = recurrence_reference(
        xbc[..., :inner].reshape(bsz, t, h, hp), dt, -jnp.exp(p["A_log"]),
        xbc[..., inner:inner + g * n].reshape(bsz, t, g, n),
        xbc[..., inner + g * n:].reshape(bsz, t, g, n), p["D"])
    y = y.reshape(bsz, t, inner) * jax.nn.silu(z)
    return _mm(_rms(y, p["norm"]["weight"], m["rms_eps"]),
               p["out_proj"]["kernel"])


def attention_reference(p, x, m):
    """Grouped-query attention, causal, no positional encoding:
    ``softmax(q k^T x attention_multiplier) v``."""
    bsz, t, _ = x.shape
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _mm(x, p["wq"]).reshape(bsz, t, h, hd)
    k = _mm(x, p["wk"]).reshape(bsz, t, kv, hd)
    v = _mm(x, p["wv"]).reshape(bsz, t, kv, hd)
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    block = qwen3_next._divisor(t, _ATTN_QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", _round(qb, _OPERAND_DTYPE),
                       _round(k, _OPERAND_DTYPE)) * m["attention_multiplier"]
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(w, _OPERAND_DTYPE),
                          _round(v, _OPERAND_DTYPE))

    ctx = jax.lax.map(rows, jnp.arange(0, t, block))    # [T/blk,B,blk,H,D]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(bsz, t, h * hd)
    return _mm(ctx, p["wo"])


def block_reference(p, x, m):
    """One block: ``x += r * mixer(norm(x)); x += r * ff(norm(x))``."""
    eps, r = m["rms_eps"], m["residual_multiplier"]
    h = _rms(x, p["input_norm"]["weight"], eps)
    x = x + r * (attention_reference(p["attn"], h, m) if "attn" in p
                 else mamba_reference(p["mamba"], h, m))
    h = _rms(x, p["post_mixer_norm"]["weight"], eps)
    ff = p["mlp"]
    h = _mm(jax.nn.silu(_mm(h, ff["gate"]["kernel"]))
            * _mm(h, ff["up"]["kernel"]), ff["down"]["kernel"])
    return x + r * h


def forward_reference(params, ids, m):
    """Logits.  Each block sits under ``jax.checkpoint`` (as each stretch
    of the recurrence and each block of queries does): the same arithmetic,
    and a gradient through one row of 8,192 tokens at the published widths
    fits a 16 GB chip."""
    table = params["embed"]["embeddings"]
    x = table[ids] * m["embedding_multiplier"]
    block = jax.checkpoint(functools.partial(block_reference, m=m))
    for _, p in qwen3_next._blocks(params, m):
        x = block(p, x)
    x = _rms(x, params["final_norm"]["weight"], m["rms_eps"])
    head = table.T if m["tie_embeddings"] else params["head"]["kernel"]
    return _mm(x, head) / m["logits_scaling"]


def reference(config: dict, variables: dict, ids: np.ndarray) -> np.ndarray:
    """Plain float32 forward on the system's parameter tree: logits."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(forward_reference, m=m))
        return np.asarray(fwd(qwen3_next._float32(variables["params"]),
                              jnp.asarray(ids)))


def loss_reference(params, ids, labels, m):
    """The cell's training loss: mean cross-entropy over every position."""
    logits = forward_reference(params, ids, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def reference_loss_and_grads(config: dict, variables: dict, ids, labels):
    """``(loss, gradients)`` of :func:`loss_reference`, by ``jax.grad``
    through the reference, in float32."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.value_and_grad(
            functools.partial(loss_reference, m=m)))
        return fn(qwen3_next._float32(variables["params"]), jnp.asarray(ids),
                  jnp.asarray(labels, jnp.int32))
