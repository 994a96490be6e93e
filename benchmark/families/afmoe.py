"""The ``afmoe`` causal decoder (``models.AFMoE``; Trinity-Mini): sliding-
window attention in three of every four blocks and full causal attention in
the fourth, a leading dense block, then top-k expert layers with a sigmoid
router balanced by a bias and one ungated shared expert; logits at every
position of a causal-LM row.

``reference`` is the published forward in plain float32, written from the
model's ``config.json`` and the ``afmoe`` port in ``transformers`` (each
equation the config does not give is listed under ``assumed`` in the
configuration file), on the system's own parameter tree: softmax attention
in blocks of queries behind an explicit band mask (no kernel, no skipped
block), the experts one by one behind a mask (no sort, no grouped matmul).
It is given the same share of the experts and the same slice of the
vocabulary as the system (model-configs guide, section 4): what absent
experts would add is left out of both.  ``expert_bias`` is taken from the
variables' state: it is no parameter and the reference does not move it.

Departures from the published model, in the system and here alike: the
attention gate's projection is the second half of every head's columns of
``wq`` (the release has a ``gate_proj`` of its own: a permutation of
columns, which random weights do not see); the bias update sees this
chip's tokens only (a deployment sums the picks over its data-parallel
chips before the sign); no cache, no packed documents, no exchange.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import AFMoE
from analytics_zoo_tpu.models.afmoe import SLIDING
from analytics_zoo_tpu.nn.module import Module

from benchmark.families import qwen3_next

#: Largest |system - reference| over the reference's largest magnitude, at
#: the logits of one timed row (16,384 tokens at the published widths).
#: Read on the chip (PERF.md section 6, PR 31): the system 0.0078-0.0092
#: over the builder's 22 runs; the reference itself with every matmul's
#: operands rounded to bf16 and float32 sums, the least a bf16 system can
#: differ by, 0.0051; with operands rounded to fp8 (e4m3) 0.066-0.067, which
#: must fail.  Smaller than the Qwen family's readings (0.042-0.055 there): the
#: output norms of a block (N2, N4) renormalise what each sublayer gives,
#: so a flipped pick of the top-8 moves a token by a bounded step and not
#: by an expert's raw output.  0.025 leaves the system's worst reading a
#: factor of 2.7 and sits a factor of 2.7 under fp8.
TOLERANCE = 0.025

#: per-leaf limit of the gradient comparison (``reference_loss_and_grads``):
#: ||g_system - g_reference|| / ||g_reference|| of every parameter leaf, on
#: one row of 16,384 tokens at the published widths.  Read on the chip
#: (PERF.md section 6, PR 31; two seeds): loss 10.180010 against 10.179948
#: and 10.235228 against 10.235386; the system's leaves 0.0046 (head,
#: embedding) to 0.05 (the routed experts' weights) and 0.052-0.105 for the
#: four routers' kernels (a flipped pick changes which scores a token's
#: gradient reaches); the reference with bf16 operands reads 0.083-0.089 on
#: a router and 0.04 on the experts; with fp8 operands 78 of the 80 leaves
#: read over 0.25, the worst 1.02-1.03.  One limit for every leaf: 2.4 times
#: the system's worst, a quarter of fp8's.
GRAD_TOLERANCE = 0.25

_ATTN_QUERY_BLOCK = 512   # reference attention: [H, 512, T] scores at once


def build(config: dict) -> Module:
    return AFMoE(**config["model"])


#: causal-LM rows from the vocabulary slice: the Qwen family's loader
loader = qwen3_next.loader
inputs = qwen3_next.inputs
batch_spec = qwen3_next.batch_spec


def _model(config: dict) -> dict:
    """``config["model"]`` with the constructor's defaults filled in."""
    return AFMoE(**config["model"])._config


# -- the work the mathematics requires ----------------------------------------

def band_pairs(t: int, window: int) -> int:
    """(query, key) pairs of one row under a sliding window: ``window`` keys
    a query, fewer for the first ``window - 1``."""
    w = min(window, t)
    return t * w - w * (w - 1) // 2


def attention_pairs(m: dict, t: int) -> dict:
    """Pairs a row and head, summed over the layers of each kind."""
    sliding = m["layer_types"].count(SLIDING)
    return {"sliding": sliding * band_pairs(t, m["window"]),
            "full": (m["n_layers"] - sliding) * band_pairs(t, t)}


def matmul_params_per_token(m: dict) -> float:
    """Parameters a token meets in a matmul, the routed experts at the
    expected ``top_k * experts_held / num_experts`` picks; the embedding's
    gather is none."""
    d = m["hidden_size"]
    heads, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = d * heads * 2 * hd + 2 * d * kv * hd + heads * hd * d
    held = m["experts_held"] or m["num_experts"]
    moe = d * m["num_experts"] + 3 * d * m["shared_units"] \
        + m["top_k"] * held / m["num_experts"] * 3 * d * m["moe_units"]
    dense = m["num_dense_layers"]
    return m["n_layers"] * attn + dense * 3 * d * m["dense_units"] \
        + (m["n_layers"] - dense) * moe + m["vocab_size"] * d


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Training FLOPs a row: 6 x the matmul parameters a token, and the two
    products (q k^T, p v) of every (query, key) pair a layer attends to,
    forward and backward (3 x): over the BAND on sliding layers, over the
    causal triangle on full ones.  No recomputation, no padding, no masked
    block."""
    m, t = _model(config), traffic["seq_len"]
    pairs = attention_pairs(m, t)
    attn = 3 * 2 * 2 * m["head_dim"] * m["num_heads"] \
        * (pairs["sliding"] + pairs["full"])
    return 6.0 * matmul_params_per_token(m) * t + attn


def _flash_work(m: dict, traffic: dict, pairs: int, layers: int) -> dict:
    b, t = traffic["global_batch"], traffic["seq_len"]
    item = jnp.dtype(m["dtype"]).itemsize
    rows = b * t * m["head_dim"] * item * layers
    return {"flops": float(2 * 2 * m["head_dim"] * m["num_heads"] * b
                           * pairs),
            "bytes": float(rows * (2 * m["num_heads"]
                                   + 2 * m["num_kv_heads"]))}


def window_flash_fwd_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes ONE forward pass of the sliding layers' attention
    needs a step, whatever computes it: q k^T and p v over the band's
    pairs, and q, the kv heads' k and v, and the output moved once."""
    m = _model(config)
    return _flash_work(m, traffic,
                       attention_pairs(m, traffic["seq_len"])["sliding"],
                       m["layer_types"].count(SLIDING))


def full_flash_fwd_work(config: dict, traffic: dict) -> dict:
    """The same for the full-attention layers: the causal triangle."""
    m = _model(config)
    return _flash_work(m, traffic,
                       attention_pairs(m, traffic["seq_len"])["full"],
                       m["n_layers"] - m["layer_types"].count(SLIDING))


def ragged_dot_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the routed experts' grouped matmuls need a step,
    forward and backward, at the expected load (the Qwen family's count:
    per pair the three ``hidden x moe_units`` products forward and twice
    that backward; each held expert's weights read twice and its gradient
    written; every pair's rows once each way), over the expert layers."""
    m = _model(config)
    return qwen3_next.ragged_dot_work(
        {"model": dict(m, n_layers=m["n_layers"] - m["num_dense_layers"])},
        traffic)


# -- the plain float32 reference ------------------------------------------------

#: set by ``rounded_operands``: every matmul of the reference rounds both
#: operands to this dtype first (None: plain float32)
_OPERAND_DTYPE = None


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


def _mm(a, b):
    if _OPERAND_DTYPE is not None:
        a, b = (v.astype(_OPERAND_DTYPE).astype(jnp.float32) for v in (a, b))
    return a @ b


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"]["kernel"]))
               * _mm(x, p["up"]["kernel"]), p["down"]["kernel"])


def attention_reference(p, x, m, sliding: bool):
    """Gated grouped-query attention: position i sees j <= i, and on a
    sliding layer also j > i - window; rotary embedding on sliding layers
    only."""
    b, t, _ = x.shape
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _mm(x, p["wq"]).reshape(b, t, h, 2 * hd)
    q, gate = q[..., :hd], q[..., hd:]
    k = _mm(x, p["wk"]).reshape(b, t, kv, hd)
    v = _mm(x, p["wv"]).reshape(b, t, kv, hd)
    q = _rms(q, p["q_norm"]["weight"], m["rms_eps"])
    k = _rms(k, p["k_norm"]["weight"], m["rms_eps"])
    if sliding:
        inv_freq = m["rope_theta"] ** (-jnp.arange(hd // 2) * 2.0 / hd)
        ang = jnp.arange(t)[:, None] * inv_freq
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rope(a):
            a1, a2 = a[..., :hd // 2], a[..., hd // 2:]
            return jnp.concatenate([a1 * cos - a2 * sin,
                                    a2 * cos + a1 * sin], axis=-1)
        q, k = rope(q), rope(k)
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    block = qwen3_next._divisor(t, _ATTN_QUERY_BLOCK)
    window = m["window"] if sliding else t

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(hd * 1.0)
        back = (start + jnp.arange(block))[:, None] - jnp.arange(t)
        seen = (back >= 0) & (back < window)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    ctx = jax.lax.map(rows, jnp.arange(0, t, block))    # [T/blk,B,blk,H,D]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h, hd)
    ctx = ctx * jax.nn.sigmoid(gate)
    return _mm(ctx.reshape(b, t, h * hd), p["wo"])


def moe_reference(p, x, m, bias, first=None, held=None, shared=True):
    """The expert layer's output.  ``s = sigmoid(x Wr)``; the ``top_k``
    largest of ``s + bias`` are picked; ``w = s[picked] / (sum + 1e-20) *
    route_scale``.  The experts ``first .. first + held - 1`` are computed
    one by one, each for every token, and weighted by the token's weight
    for that expert or by 0; the shared expert is added ungated.  ``p``
    holds those experts' weights only.  The defaults are the model's share;
    ``first=0, held=num_experts`` with every expert's weights is the uncut
    layer; ``shared=False`` leaves the shared expert out (what the shares
    of a layer add up without counting it once each)."""
    first = m["first_expert"] if first is None else first
    held = (m["experts_held"] or m["num_experts"]) if held is None else held
    b, t, d = x.shape
    xs = x.reshape(b * t, d)
    scores = jax.nn.sigmoid(xs @ p["router"]["kernel"])
    _, top_e = jax.lax.top_k(scores + bias, m["top_k"])
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20) * m["route_scale"]
    units = m["moe_units"]

    @jax.checkpoint
    def expert(acc, ew):
        e, w_in, w_out = ew
        weight = jnp.where(top_e == e, top_w, 0.0).sum(-1)
        hidden = _mm(xs, w_in)
        hidden = jax.nn.silu(hidden[:, :units]) * hidden[:, units:]
        return acc + weight[:, None] * _mm(hidden, w_out), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(xs), (
        first + jnp.arange(held), p["w_gate_up"], p["w_down"]))
    if shared:
        out = out + _swiglu(p["shared_expert"], xs)
    return out.reshape(b, t, d), top_e


def _blocks(tree, m):
    for i in range(m["n_layers"]):
        yield i, (tree[f"remat_{i}"][f"layer_{i}"] if f"remat_{i}" in tree
                  else tree[f"layer_{i}"])


def block_reference(p, bias, x, m, sliding: bool):
    """One block: ``x += N2(attn(N1(x))); x += N4(ff(N3(x)))``."""
    eps = m["rms_eps"]
    h = attention_reference(p["attn"], _rms(x, p["input_norm"]["weight"],
                                            eps), m, sliding)
    x = x + _rms(h, p["post_attn_norm"]["weight"], eps)
    h = _rms(x, p["pre_ff_norm"]["weight"], eps)
    if "mlp" in p:
        h = _swiglu(p["mlp"], h)
    else:
        h, _ = moe_reference(p["moe"], h, m, bias)
    return x + _rms(h, p["post_ff_norm"]["weight"], eps)


def expert_biases(state, m) -> dict:
    """``{layer: expert_bias}`` of the expert layers, from a state tree."""
    return {i: s["moe"]["expert_bias"] for i, s in _blocks(state, m)
            if "moe" in s}


def forward_reference(params, biases, ids, m):
    """Logits.  Each block sits under ``jax.checkpoint`` (as each expert and
    each block of queries does): the same arithmetic, and a gradient
    through one row of 16,384 tokens at the published widths fits a 16 GB
    chip."""
    x = params["embed"]["embeddings"][ids] * jnp.sqrt(m["hidden_size"] * 1.0)
    for i, p in _blocks(params, m):
        block = jax.checkpoint(functools.partial(
            block_reference, m=m, sliding=m["layer_types"][i] == SLIDING))
        x = block(p, biases.get(i), x)
    x = _rms(x, params["final_norm"]["weight"], m["rms_eps"])
    return _mm(x, params["head"]["kernel"])


def _float32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def reference(config: dict, variables: dict, ids: np.ndarray) -> np.ndarray:
    """Plain float32 forward on the system's parameter tree: logits."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(forward_reference, m=m))
        return np.asarray(fwd(
            _float32(variables["params"]),
            _float32(expert_biases(variables["state"], m)),
            jnp.asarray(ids)))


def loss_reference(params, biases, ids, labels, m):
    """The cell's training loss: mean cross-entropy over every position.
    No auxiliary loss: the router is balanced by its bias."""
    logits = forward_reference(params, biases, ids, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def reference_loss_and_grads(config: dict, variables: dict, ids, labels):
    """``(loss, gradients)`` of :func:`loss_reference`, by ``jax.grad``
    through the reference, in float32."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.value_and_grad(
            functools.partial(loss_reference, m=m)))
        return fn(_float32(variables["params"]),
                  _float32(expert_biases(variables["state"], m)),
                  jnp.asarray(ids), jnp.asarray(labels, jnp.int32))
