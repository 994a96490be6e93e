"""Qwen3-Next causal decoder (``models.Qwen3Next``): Gated DeltaNet linear
attention in three of every four blocks, gated grouped-query softmax
attention in the fourth, a top-k expert layer with a gated shared expert in
every block; logits at every position of a causal-LM row.

``reference`` is the published forward in plain float32, written from the
model's ``config.json`` and the Gated DeltaNet paper (arXiv:2412.06464), on
the system's own parameter tree: the delta rule position by position (a
``lax.scan`` over positions, no chunks), softmax attention in blocks of
queries (no kernel), the experts one by one behind a mask (no sort, no
grouped matmul).  It is given the same share of the experts and the same
slice of the vocabulary as the system (model-configs guide, section 4):
what absent experts would add is left out of both.

Departures from the published model, in the system and here alike: the
multi-token-prediction module is not built; ``in_proj_qkvz`` lays its
columns out as q | k | v | z (the release interleaves them by key head: a
permutation of columns, which random weights do not see); ``dt_bias`` is
initialised as the Gated DeltaNet reference code does (softplus^-1 of a
log-uniform step in [0.001, 0.1]; the release's port starts it at 1, which
with ``A`` up to 16 forgets the state within a position or two and would
leave the chunk-to-chunk carry untested); the load-balancing loss counts
``f_e`` as picks per token (the release's ``load_balancing_loss_func``).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import Qwen3Next
from analytics_zoo_tpu.nn.module import Module

#: Largest |system - reference| over the reference's largest magnitude, at
#: the logits of one timed row (8192 tokens at the published widths).  Read
#: on the chip (PERF.md section 6, PR 27): the system 0.042-0.051 over the
#: builder's seeds; the reference itself with every matmul's operands rounded
#: to bf16 and float32 sums — the least a bf16 system can differ by — 0.033;
#: with operands rounded to fp8 (e4m3) 0.32, which must fail.  Two sources
#: make bf16 cost more here than in bert_mlm (2e-2).  The hidden state is
#: small (max 3-5) and every block adds about a hundredth of its range
#: (0.014, 0.023, 0.032, 0.041 after the four blocks).  And the top-10 of
#: 512 is discrete: bf16 activations flip a pick in 12-34% of the tokens a
#: layer, in 1.4-4.6% of them among the 32 experts held here, each moving
#: that token by about a tenth of an expert's output.  0.1 leaves the
#: system's worst reading a factor of two and sits a factor of three under
#: fp8; a router computed in bf16 flips several times as many picks.
TOLERANCE = 0.1

#: per-leaf limit of the gradient comparison (``reference_loss_and_grads``):
#: ||g_system - g_reference|| / ||g_reference|| of every parameter leaf, on
#: one row of 8192 tokens at the published widths.  Read on the chip (PERF.md
#: section 6, PR 27): loss 10.36265 against 10.36263; the system's leaves
#: 0.009 (attention) to 0.06, and 0.09-0.16 for the routed experts' weights
#: (a flipped pick moves a whole row from one expert's gradient to
#: another's); the reference with bf16 operands reads 0.12 on those same
#: leaves; with fp8 operands 60 of the 66 leaves read over 0.3, the worst
#: 1.7.  One limit for every leaf: twice the system's worst.
GRAD_TOLERANCE = 0.3

#: weight of the summed ``aux_loss`` in the training loss: the Estimator's
#: default (``aux_loss_weight``), which jobs/train_fit.py leaves alone
AUX_LOSS_WEIGHT = 0.01

_ATTN_QUERY_BLOCK = 512   # reference attention: [H, 512, T] scores at once
_SCAN_SEGMENT = 64        # reference recurrence: checkpoint every 64 steps


def build(config: dict) -> Module:
    return Qwen3Next(**config["model"])


def loader(config: dict, traffic: dict, seed: int):
    """Causal-LM rows: ``seq_len + 1`` Zipfian ids from the vocabulary
    slice, ``x`` the first ``seq_len`` and ``y`` the same shifted by one."""
    vocab, seq = config["model"]["vocab_size"], traffic["seq_len"]
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()

    def load_sample(i: int, rng=None) -> dict:
        r = np.random.default_rng([seed, i])
        ids = r.choice(vocab, seq + 1, p=p).astype(np.int32)
        return {"x": ids[:-1], "y": ids[1:]}

    return load_sample


def inputs(config: dict, traffic: dict, seed: int, n: int) -> np.ndarray:
    load = loader(config, traffic, seed)
    return np.stack([load(i)["x"] for i in range(n)])


def batch_spec(config: dict, traffic: dict):
    return (traffic["global_batch"], traffic["seq_len"]), np.int32


# -- the work the mathematics requires ----------------------------------------

def matmul_params_per_token(m: dict) -> float:
    """Parameters a token meets in a matmul (or, for the depthwise
    convolution, a multiply-add), the routed experts at the expected
    ``top_k * experts_held / num_experts`` picks; the embedding's gather is
    none."""
    d = m["hidden_size"]
    key = m["linear_num_k_heads"] * m["linear_k_head_dim"]
    value = m["linear_num_v_heads"] * m["linear_v_head_dim"]
    gdn = d * (2 * key + 2 * value) + d * 2 * m["linear_num_v_heads"] \
        + (2 * key + value) * m["linear_conv_kernel"] + value * d
    heads, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = d * heads * 2 * hd + 2 * d * kv * hd + heads * hd * d
    held = m["experts_held"] or m["num_experts"]
    moe = d * m["num_experts"] + 3 * d * m["shared_units"] + d \
        + m["top_k"] * held / m["num_experts"] * 3 * d * m["moe_units"]
    full = m["n_layers"] // m["full_attention_interval"]
    return (m["n_layers"] - full) * gdn + full * attn \
        + m["n_layers"] * moe + m["vocab_size"] * d


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Training FLOPs a row: 6 x the matmul parameters a token, the causal
    half of the two T x T products of each full-attention layer (forward
    and backward: 3 x), and the recurrence's four ``d_k x d_v`` products a
    value head and position (forget, recall, write, read; 3 x).  No
    recomputation, no padding, no masked block."""
    m, t = config["model"], traffic["seq_len"]
    full = m["n_layers"] // m["full_attention_interval"]
    attn = 3 * 2 * t * t * m["head_dim"] * m["num_heads"] * full
    state = m["linear_k_head_dim"] * m["linear_v_head_dim"]
    rule = 3 * 4 * 2 * state * m["linear_num_v_heads"] * t \
        * (m["n_layers"] - full)
    return 6.0 * matmul_params_per_token(m) * t + attn + rule


def flash_fwd_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes ONE forward pass of causal softmax attention
    needs a step, whatever computes it: the causal half of q k^T and p v,
    and q, the kv heads' k and v, and the output moved once."""
    m, t, b = config["model"], traffic["seq_len"], traffic["global_batch"]
    full = m["n_layers"] // m["full_attention_interval"]
    item = jnp.dtype(m["dtype"]).itemsize
    flops = 2 * t * t * m["head_dim"] * m["num_heads"] * b * full
    rows = b * t * m["head_dim"] * item * full
    return {"flops": float(flops),
            "bytes": float(rows * (2 * m["num_heads"]
                                   + 2 * m["num_kv_heads"]))}


def ragged_dot_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the routed experts' grouped matmuls need a step,
    forward and backward, at the expected load: per (row, expert) pair the
    three ``hidden x moe_units`` products forward and twice that backward;
    each held expert's weights read for the forward and for the input
    gradient and its weight gradient written, in the model's dtype; every
    pair's rows (input, gate/up, product, output) once each way."""
    m, t, b = config["model"], traffic["seq_len"], traffic["global_batch"]
    d, u = m["hidden_size"], m["moe_units"]
    held = m["experts_held"] or m["num_experts"]
    pairs = b * t * m["top_k"] * held / m["num_experts"]
    item = jnp.dtype(m["dtype"]).itemsize
    flops = 3 * pairs * 2 * 3 * d * u
    weights = 3 * held * 3 * d * u * item
    rows = 2 * pairs * (2 * d + 3 * u) * item
    return {"flops": float(flops * m["n_layers"]),
            "bytes": float((weights + rows) * m["n_layers"])}


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


def _rms(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def _divisor(t: int, most: int) -> int:
    return next(s for s in range(min(most, t), 0, -1) if t % s == 0)


def delta_rule_reference(q, k, v, g, beta):
    """The gated delta rule position by position.  q, k ``[B, T, H, d_k]``,
    v ``[B, T, H, d_v]``, g, beta ``[B, T, H]``; returns ``[B, T, H, d_v]``.
    The positions are walked in segments under ``jax.checkpoint`` so that a
    gradient through 8192 of them keeps T/64 states and not T."""
    b, t, h, dk = k.shape
    seg = _divisor(t, _SCAN_SEGMENT)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkd,bhk->bhd", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkd,bhk->bhd", s, q_t)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(step, s, xs)

    def split(a):  # [B, T, ...] -> [T/seg, seg, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // seg, seg) + a.shape[1:])

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(segment, s0, tuple(map(split, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def gdn_reference(p, x, m):
    b, t, _ = x.shape
    hk, hv = m["linear_num_k_heads"], m["linear_num_v_heads"]
    dk, dv = m["linear_k_head_dim"], m["linear_v_head_dim"]
    key, value = hk * dk, hv * dv
    qkvz = _mm(x, p["in_proj_qkvz"]["kernel"])
    ba = _mm(x, p["in_proj_ba"]["kernel"])
    qkv, z = qkvz[..., :2 * key + value], qkvz[..., 2 * key + value:]
    w = p["conv"]["kernel"]
    width = w.shape[0]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + t] * w[j] for j in range(width)))
    q = qkv[..., :key].reshape(b, t, hk, dk)
    k = qkv[..., key:2 * key].reshape(b, t, hk, dk)
    v = qkv[..., 2 * key:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])

    def l2norm(a):
        return a * jax.lax.rsqrt(jnp.square(a).sum(-1, keepdims=True)
                                 + m["rms_eps"])
    q = jnp.repeat(l2norm(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(l2norm(k), hv // hk, axis=2)
    o = delta_rule_reference(q, k, v, g, beta)
    o = _rms(o, p["norm"]["weight"], m["rms_eps"], zero_centered=False)
    o = o * jax.nn.silu(z.reshape(b, t, hv, dv))
    return _mm(o.reshape(b, t, value), p["out_proj"]["kernel"])


def attention_reference(p, x, m):
    b, t, _ = x.shape
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    rot = int(hd * m["partial_rotary_factor"])
    q = _mm(x, p["wq"]).reshape(b, t, h, 2 * hd)
    q, gate = q[..., :hd], q[..., hd:]
    k = _mm(x, p["wk"]).reshape(b, t, kv, hd)
    v = _mm(x, p["wv"]).reshape(b, t, kv, hd)
    q = _rms(q, p["q_norm"]["weight"], m["rms_eps"])
    k = _rms(k, p["k_norm"]["weight"], m["rms_eps"])

    inv_freq = m["rope_theta"] ** (-jnp.arange(rot // 2) * 2.0 / rot)
    ang = jnp.arange(t)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(a):
        a1, a2 = a[..., :rot // 2], a[..., rot // 2:rot]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin,
                                a[..., rot:]], axis=-1)
    q, k = rope(q), rope(k)
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    block = _divisor(t, _ATTN_QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(hd * 1.0)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    ctx = jax.lax.map(rows, jnp.arange(0, t, block))    # [T/blk,B,blk,H,D]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h, hd)
    ctx = ctx * jax.nn.sigmoid(gate)
    return _mm(ctx.reshape(b, t, h * hd), p["wo"])


def moe_reference(p, x, m, first=None, held=None):
    """The expert layer's output and its load-balancing loss.  The experts
    ``first .. first + held - 1`` are computed one by one, each for every
    token, and weighted by the token's renormalised router weight for that
    expert or by 0: no sort, no grouped matmul, no dropped token.  ``p``
    holds those experts' weights only.  The defaults are the model's
    share; ``first=0, held=num_experts`` with every expert's weights is the
    uncut layer."""
    first = m["first_expert"] if first is None else first
    held = (m["experts_held"] or m["num_experts"]) if held is None else held
    b, t, d = x.shape
    xs = x.reshape(b * t, d)
    probs = jax.nn.softmax(xs @ p["router"]["kernel"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m["top_k"])
    if m["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
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
    if m["shared_units"]:
        s = p["shared_expert"]
        shared = _mm(jax.nn.silu(_mm(xs, s["gate"]["kernel"]))
                     * _mm(xs, s["up"]["kernel"]), s["down"]["kernel"])
        out = out + jax.nn.sigmoid(xs @ p["shared_gate"]["kernel"]) * shared
    picks = (top_e[..., None] == jnp.arange(m["num_experts"])).sum((0, 1))
    aux = m["num_experts"] * jnp.sum(picks / (b * t) * probs.mean(0))
    return out.reshape(b, t, d), aux, (top_e, probs)


def _blocks(params, m):
    for i in range(m["n_layers"]):
        yield i, (params[f"remat_{i}"][f"layer_{i}"] if f"remat_{i}" in params
                  else params[f"layer_{i}"])


def block_reference(p, x, m):
    """One block: ``x += mixer(norm(x)); x += experts(norm(x))``; returns the
    new ``x`` and the expert layer's load-balancing loss."""
    h = _rms(x, p["input_norm"]["weight"], m["rms_eps"])
    x = x + (attention_reference(p["attn"], h, m) if "attn" in p
             else gdn_reference(p["gdn"], h, m))
    h = _rms(x, p["post_norm"]["weight"], m["rms_eps"])
    out, aux, _ = moe_reference(p["moe"], h, m)
    return x + out, aux


def forward_reference(params, ids, m):
    """Logits and the summed load-balancing loss.  Each block sits under
    ``jax.checkpoint`` (as each expert and each stretch of the recurrence
    does): the same arithmetic, and a gradient through one row of 8192
    tokens at the published widths fits a 16 GB chip."""
    x = params["embed"]["embeddings"][ids]
    aux = 0.0
    block = jax.checkpoint(functools.partial(block_reference, m=m))
    for _, p in _blocks(params, m):
        x, layer_aux = block(p, x)
        aux = aux + layer_aux
    x = _rms(x, params["final_norm"]["weight"], m["rms_eps"])
    return _mm(x, params["head"]["kernel"]), aux


def _model(config: dict) -> dict:
    """``config["model"]`` with the constructor's defaults filled in."""
    return Qwen3Next(**config["model"])._config


def _float32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def reference(config: dict, variables: dict, ids: np.ndarray) -> np.ndarray:
    """Plain float32 forward on the system's parameter tree: logits."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(forward_reference, m=m))
        return np.asarray(fwd(_float32(variables["params"]),
                              jnp.asarray(ids))[0])


def loss_reference(params, ids, labels, m):
    """The cell's training loss: mean cross-entropy over every position
    plus ``AUX_LOSS_WEIGHT`` x the layers' load-balancing losses."""
    logits, aux = forward_reference(params, ids, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean() + AUX_LOSS_WEIGHT * aux


def reference_loss_and_grads(config: dict, variables: dict, ids, labels):
    """``(loss, gradients)`` of :func:`loss_reference`, by ``jax.grad``
    through the reference, in float32."""
    m = _model(config)
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.value_and_grad(
            functools.partial(loss_reference, m=m)))
        return fn(_float32(variables["params"]), jnp.asarray(ids),
                  jnp.asarray(labels, jnp.int32))
