"""The ``glm4_moe_lite`` causal decoder (``models.GlmMoeLite``;
GLM-4.7-Flash): latent attention in every block, a leading dense block,
then top-k expert layers with a sigmoid router balanced by a bias and one
ungated shared expert, and a multi-token prediction module of depth 1;
logits of both prediction depths at every position of a causal-LM row,
``[B, 2, T, vocab]``.

``reference`` is the published forward in plain float32, written from the
model's ``config.json``, the DeepSeek-V3 report whose block this is
(arXiv:2412.19437, sections 2.1.1, 2.1.2, 2.2) and the ``glm4_moe_lite`` /
``deepseek_v3`` ports in ``transformers`` (each equation the config does
not give is listed under ``assumed`` in the configuration file), on the
system's own parameter tree.  Latent attention is written head by head:
the key of a head built by an explicit repeat of the one rotary key head,
softmax in blocks of queries behind an explicit causal mask (no kernel, no
skipped block), and the rotation in the RELEASE's layout: a head is
``[nope | rope]`` with the rotary dims in interleaved pairs, reached from
the system's columns (``[rope | nope]``, half-split pairs) through the
permutation ``release_head_order`` / ``release_latent_order``; a system
layout that is no permutation of the release's gives other scores.  The
experts are the afmoe family's reference (one by one behind a mask: no
sort, no grouped matmul; the same router equations).  It is given the same
share of the experts and the same slice of the vocabulary as the system
(model-configs guide, section 4): what absent experts would add is left out
of both.  ``expert_bias`` is taken from the variables' state: it is no
parameter and the reference does not move it.

Departures from the published model, in the system and here alike: the
columns of ``wq_b`` and ``wkv_a`` are permuted as above (random weights do
not see it, and the scores are equal); the module is given the row's last
id again for the id past the row's end, which the row does not hold (that
position is in no loss and is compared like any other logit); the bias
update sees this chip's tokens only; no cache, no absorbed form, no packed
documents, no exchange; one prediction depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import GlmMoeLite
from analytics_zoo_tpu.nn.module import Module

from benchmark.families import afmoe, qwen3_next
from benchmark.families.afmoe import (_float32, _mm, _rms, _swiglu,
                                      rounded_operands)  # noqa: F401

#: Largest |system - reference| over the reference's largest magnitude, at
#: the logits of one timed row, both depths (8,192 tokens at the published
#: widths).  Read on the chip (PERF.md section 6, PR 37): the system
#: 0.0199-0.0236 over the builder's runs (depth 1 alone, in its own range,
#: 0.018-0.019; the position that runs on the stand-in id 0.011-0.013); the
#: reference itself with every matmul's operands rounded to bf16 and float32
#: sums, the least a bf16 system can differ by, 0.0189; with operands
#: rounded to fp8 (e4m3) 1.35, which must fail.  Between Trinity's readings
#: (0.008-0.009) and Qwen's (0.042-0.055): no norm on a sublayer's output
#: bounds what a flipped pick of the top-4 adds, the logits are small (the
#: largest 2.6) and six blocks' roundings add up; fp8 lands far out because
#: it flips most picks.  0.06 leaves the system's worst reading a factor of
#: 2.5 and sits a factor of 22 under fp8.
TOLERANCE = 0.06

#: per-leaf limit of the gradient comparison (``reference_loss_and_grads``):
#: ||g_system - g_reference|| / ||g_reference|| of every parameter leaf, on
#: one row of 8,192 tokens at the published widths.  Read on the chip
#: (PERF.md section 6, PR 37; three seeds): loss 12.97706 against 12.97753,
#: 12.92883 against 12.92883, 13.00568 against 13.00588; the system's 94
#: leaves median 0.016 (embedding 0.0145, head 0.0126), the routed experts'
#: weights up to 0.13-0.16 and the five routers' kernels 0.13-0.31 (worst
#: 0.2946, 0.2948, 0.3075: a flipped pick changes which scores a token's
#: gradient reaches); the reference with bf16 operands reads 0.12-0.24 on
#: the same routers, median 0.014; with fp8 operands all 94 leaves read
#: over 0.3, the median 1.00, the least 0.84.  One limit for every leaf:
#: twice the system's worst, 0.7 of fp8's least.
GRAD_TOLERANCE = 0.6

#: weight of depth 1's cross-entropy in the training loss
#: (``nn.losses.multi_token_crossentropy``'s default; ``assumed``)
DEPTH_WEIGHT = 0.3

_ATTN_QUERY_BLOCK = 1024  # reference attention: one head's [1024, T] scores


def build(config: dict) -> Module:
    return GlmMoeLite(**config["model"])


#: causal-LM rows from the vocabulary slice: the Qwen family's loader
loader = qwen3_next.loader
inputs = qwen3_next.inputs
batch_spec = qwen3_next.batch_spec


def _model(config: dict) -> dict:
    """``config["model"]`` with the constructor's defaults filled in."""
    return GlmMoeLite(**config["model"])._config


# -- the work the mathematics requires ----------------------------------------

def causal_pairs(t: int) -> int:
    """(query, key) pairs of one row and head under the causal mask."""
    return t * (t + 1) // 2


def attention_layers(m: dict) -> int:
    """Latent-attention layers: every block, and the prediction module's."""
    return m["n_layers"] + m["mtp_layers"]


def expert_layers(m: dict) -> int:
    return m["n_layers"] - m["num_dense_layers"] + m["mtp_layers"]


def attention_params(m: dict) -> int:
    """Matmul parameters of one latent-attention layer: the two low-rank
    query products, the latent's and the shared rotary key's, the keys' and
    values' rebuild, the output."""
    d, h = m["hidden_size"], m["num_heads"]
    qk = m["nope_dim"] + m["rope_dim"]
    return d * m["q_rank"] + m["q_rank"] * h * qk \
        + d * (m["kv_rank"] + m["rope_dim"]) \
        + m["kv_rank"] * h * (m["nope_dim"] + m["v_dim"]) \
        + h * m["v_dim"] * d


def matmul_params_per_token(m: dict) -> float:
    """Parameters a token meets in a matmul, the routed experts at the
    expected ``top_k * experts_held / num_experts`` picks, the head once a
    prediction depth; the embedding's gather is none."""
    d = m["hidden_size"]
    held = m["experts_held"] or m["num_experts"]
    moe = d * m["num_experts"] + 3 * d * m["shared_units"] \
        + m["top_k"] * held / m["num_experts"] * 3 * d * m["moe_units"]
    return attention_layers(m) * attention_params(m) \
        + m["num_dense_layers"] * 3 * d * m["dense_units"] \
        + expert_layers(m) * moe \
        + m["mtp_layers"] * 2 * d * d \
        + (1 + m["mtp_layers"]) * m["vocab_size"] * d


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Training FLOPs a row: 6 x the matmul parameters a token, and the two
    products of every (query, key) pair of the causal triangle in every
    attention layer (q k^T over ``nope + rope`` dims, p v over ``v_dim``),
    forward and backward (3 x).  No recomputation, no padding, no masked
    block."""
    m, t = _model(config), traffic["seq_len"]
    attn = 3 * 2 * (m["nope_dim"] + m["rope_dim"] + m["v_dim"]) \
        * m["num_heads"] * causal_pairs(t) * attention_layers(m)
    return 6.0 * matmul_params_per_token(m) * t + attn


def flash_fwd_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes ONE forward pass of every attention layer's
    core needs a step, whatever computes it: q k^T and p v over the causal
    triangle's pairs, and q, k, v and the output (each ``num_heads`` heads
    wide: the key is already repeated when the core sees it) moved once."""
    m = _model(config)
    b, t = traffic["global_batch"], traffic["seq_len"]
    qk, v = m["nope_dim"] + m["rope_dim"], m["v_dim"]
    item = jnp.dtype(m["dtype"]).itemsize
    layers = attention_layers(m)
    return {"flops": float(2 * (qk + v) * m["num_heads"] * b
                           * causal_pairs(t) * layers),
            "bytes": float(b * t * m["num_heads"] * 2 * (qk + v) * item
                           * layers)}


def flash_bwd_work(config: dict, traffic: dict) -> dict:
    """The backward pass, as PR 32's files count it: five matmuls over the
    pairs where the forward has two (2.5 x the FLOPs), q, k, v, the output
    and its gradient read and dq, dk, dv written (2 x the bytes)."""
    fwd = flash_fwd_work(config, traffic)
    return {"flops": 2.5 * fwd["flops"], "bytes": 2.0 * fwd["bytes"]}


def ragged_dot_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the routed experts' grouped matmuls need a step,
    forward and backward, at the expected load (the Qwen family's count),
    over the expert layers, the prediction module's among them."""
    m = _model(config)
    return qwen3_next.ragged_dot_work(
        {"model": dict(m, n_layers=expert_layers(m))}, traffic)


# -- the plain float32 reference ------------------------------------------------

def _interleaved(half: int) -> np.ndarray:
    """Release position ``2i`` of a rotary slice holds the system's dim
    ``i``, position ``2i + 1`` its dim ``half + i`` (the system pairs dim
    ``i`` with ``half + i``, the release ``2i`` with ``2i + 1``)."""
    return np.stack([np.arange(half), half + np.arange(half)], 1).reshape(-1)


def release_head_order(m: dict) -> np.ndarray:
    """For each dim of a query head in the release's layout (``[nope |
    rope]``, interleaved pairs), the system's dim that holds it (``[rope |
    nope]``, half-split pairs)."""
    rope, nope = m["rope_dim"], m["nope_dim"]
    order = np.concatenate([rope + np.arange(nope), _interleaved(rope // 2)])
    assert sorted(order) == list(range(rope + nope)), "not a permutation"
    return order


def release_latent_order(m: dict) -> np.ndarray:
    """The same for ``wkv_a``'s columns: the latent as it stands, then the
    shared rotary key in interleaved pairs."""
    rank = m["kv_rank"]
    order = np.concatenate([np.arange(rank),
                            rank + _interleaved(m["rope_dim"] // 2)])
    assert sorted(order) == list(range(rank + m["rope_dim"]))
    return order


def _rotate_interleaved(x, theta: float):
    """Rotary embedding as the release lays it out: x ``[B, T, ..., R]``,
    dims ``2i`` and ``2i + 1`` turn together by ``t * theta^(-2i / R)``."""
    r, t = x.shape[-1], x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      odd * jnp.cos(ang) + even * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def attention_reference(p, x, m):
    """Latent attention (section 2.1.1), a head at a time."""
    b, t, _ = x.shape
    h, rope, nope, dv = (m[k] for k in ("num_heads", "rope_dim", "nope_dim",
                                        "v_dim"))
    rank, eps = m["kv_rank"], m["rms_eps"]
    wq_b = p["wq_b"].reshape(-1, h, rope + nope)[:, :, release_head_order(m)]
    wkv_a = p["wkv_a"][:, release_latent_order(m)]

    c_q = _rms(_mm(x, p["wq_a"]), p["q_norm"]["weight"], eps)
    q = _mm(c_q, wq_b.reshape(-1, h * (rope + nope))).reshape(
        b, t, h, rope + nope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = _mm(x, wkv_a)
    c_kv = _rms(kv_a[..., :rank], p["kv_norm"]["weight"], eps)
    k_pe = kv_a[..., rank:]                                     # one head
    kv = _mm(c_kv, p["wkv_b"]).reshape(b, t, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    q_pe = _rotate_interleaved(q_pe, m["rope_theta"])
    k_pe = _rotate_interleaved(k_pe, m["rope_theta"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.repeat(k_pe[:, :, None, :], h, axis=2)],
                        axis=-1)
    block = qwen3_next._divisor(t, _ATTN_QUERY_BLOCK)
    starts = jnp.arange(0, t, block)

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                   # [B, T, width]

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, axis=1)
            s = _mm(qb, jnp.swapaxes(kh, 1, 2)) / jnp.sqrt(rope + nope + 0.0)
            seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return _mm(w, vh)                              # [B, blk, dv]
        out = jax.lax.map(jax.checkpoint(rows), starts)    # [T/blk, B, ..]
        return jnp.moveaxis(out, 0, 1).reshape(b, t, dv)

    ctx = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, t, h * dv)    # [B, T, H * dv]
    return _mm(ctx, p["wo"])


def block_reference(p, bias, x, m):
    """One block: ``x += attn(N1(x)); x += ff(N2(x))``."""
    eps = m["rms_eps"]
    x = x + attention_reference(
        p["attn"], _rms(x, p["input_norm"]["weight"], eps), m)
    h = _rms(x, p["post_attn_norm"]["weight"], eps)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], h)
    return x + afmoe.moe_reference(p["moe"], h, m, bias)[0]


def _blocks(tree, m):
    """``(key, subtree)`` of every block: ``"layer_<i>"``, then the
    prediction module's under ``"mtp"``."""
    for i in range(m["n_layers"]):
        yield f"layer_{i}", (tree[f"remat_{i}"][f"layer_{i}"] if f"remat_{i}" in tree
                  else tree[f"layer_{i}"])
    if m["mtp_layers"]:
        mtp = tree["mtp"]
        yield "mtp", (mtp["remat"]["block"] if "remat" in mtp
                      else mtp["block"])


def expert_biases(state, m) -> dict:
    """``{block: expert_bias}`` of the expert layers, from a state tree."""
    return {i: s["moe"]["expert_bias"] for i, s in _blocks(state, m)
            if "moe" in s}


def forward_reference(params, biases, ids, m, twin=None):
    """Logits ``[B, 1 + mtp_layers, T, V]``.  ``twin`` gives the prediction
    module an embedding table and a head of its own (``{"embed", "head"}``:
    the model's twin with separate copies); None shares the main model's.
    Each block sits under ``jax.checkpoint`` (as each head and each block
    of queries does): the same arithmetic, and a gradient through one row
    of 8,192 tokens at the published widths fits a 16 GB chip."""
    eps = m["rms_eps"]
    blocks = dict(_blocks(params, m))
    block = jax.checkpoint(functools.partial(block_reference, m=m))
    table, head = params["embed"]["embeddings"], params["head"]["kernel"]
    h = table[ids]
    for i in range(m["n_layers"]):
        h = block(blocks[f"layer_{i}"], biases.get(f"layer_{i}"), h)
    logits = [_mm(_rms(h, params["final_norm"]["weight"], eps), head)]
    if m["mtp_layers"]:
        p = params["mtp"]
        if twin is not None:
            table, head = twin["embed"], twin["head"]
        # t_{i+1}; the row's last id stands in for the one past its end
        nxt = jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1)
        g = jnp.concatenate([_rms(table[nxt], p["enorm"]["weight"], eps),
                             _rms(h, p["hnorm"]["weight"], eps)], axis=-1)
        g = block(blocks["mtp"], biases.get("mtp"),
                  _mm(g, p["eh_proj"]["kernel"]))
        logits.append(_mm(_rms(g, p["head_norm"]["weight"], eps), head))
    return jnp.stack(logits, axis=1)


def reference(config: dict, variables: dict, ids: np.ndarray) -> np.ndarray:
    """Plain float32 forward on the system's parameter tree: the logits of
    every depth."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(forward_reference, m=m))
        return np.asarray(fwd(
            _float32(variables["params"]),
            _float32(expert_biases(variables["state"], m)),
            jnp.asarray(ids)))


def loss_reference(params, biases, ids, labels, m, twin=None):
    """The cell's training loss (section 2.2): with ``labels[i] = t_{i+1}``,
    ``mean_i CE(logits_0[i], t_{i+1}) + DEPTH_WEIGHT * mean_{i <= T-2}
    CE(logits_1[i], t_{i+2})``.  No auxiliary loss: the router is balanced
    by its bias."""
    logits = forward_reference(params, biases, ids, m, twin)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp[:, 0], labels[..., None], axis=-1).mean()
    if m["mtp_layers"]:
        loss = loss - DEPTH_WEIGHT * jnp.take_along_axis(
            logp[:, 1, :-1], labels[:, 1:, None], axis=-1).mean()
    return loss


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
