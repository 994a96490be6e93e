"""Mixture-of-Experts layer with expert parallelism.

Absent from the reference (SURVEY.md §2.9: 'Expert parallel — ❌ absent').
TPU-native design: GShard/Switch-style capacity-based dense dispatch — the
token→expert routing is expressed as einsums against one-hot dispatch/combine
tensors, so the whole layer is static-shaped and XLA turns the expert-sharded
einsums into ``all_to_all`` collectives over the ``expert`` mesh axis (via the
sharding rules in parallel/sharding.py: wi/wo lead with the expert dim).

The load-balancing auxiliary loss is recorded in the state collection under
``aux_loss`` (pure-function discipline: apply() returns it in new_state).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.nn import activations, initializers
from analytics_zoo_tpu.nn.layers import Dense, SwiGLU
from analytics_zoo_tpu.nn.module import Module, Scope


class MoE(Module):
    """Token-choice MoE FFN: [B, T, D] → [B, T, D].

    num_experts experts, each a 2-layer FFN (D → D*hidden_mult → D); top_k
    routing with capacity ``capacity_factor * T*B*top_k / num_experts``.
    Overflowing tokens are dropped (standard Switch behavior) — the residual
    connection around the layer carries them through unchanged.
    """

    def __init__(self, num_experts: int, hidden_mult: int = 4,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: Any = "gelu", name: Optional[str] = None):
        super().__init__(name or "moe")
        self.num_experts = num_experts
        self.hidden_mult = hidden_mult
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.act = activations.get(activation)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        e = self.num_experts
        s = b * t
        cap = max(1, int(self.capacity_factor * s * self.top_k / e))
        init = initializers.get("glorot_uniform")

        wg = scope.param("gate", init, (d, e))
        wi = scope.param("wi", init, (e, d, d * self.hidden_mult))
        wo = scope.param("wo", init, (e, d * self.hidden_mult, d))

        xs = x.reshape(s, d)
        logits = jnp.dot(xs.astype(jnp.float32), wg.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                  # [S, E]

        # top-k sequential assignment: k=0 choices get capacity priority
        assign = []
        masked = probs
        for _ in range(self.top_k):
            idx = jnp.argmax(masked, axis=-1)                    # [S]
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
            assign.append(onehot)
            masked = masked * (1.0 - onehot)
        assign = jnp.stack(assign)                               # [K, S, E]

        # positions: cumulative count in (k-major, then token) order
        flat = assign.reshape(self.top_k * s, e)
        pos = jnp.cumsum(flat, axis=0) - flat                    # [K*S, E]
        pos = pos.reshape(self.top_k, s, e)
        keep = (pos < cap) * assign                              # [K, S, E]

        gates = jnp.einsum("se,kse->ks", probs, keep)            # [K, S]
        if self.top_k > 1:
            # renormalize among the chosen experts (GShard top-2 behavior)
            denom = jnp.maximum(gates.sum(0, keepdims=True), 1e-9)
            gates = gates / denom
        # top-1 (Switch): keep the raw softmax prob — renormalizing to 1.0
        # would sever the router's gradient from the task loss

        # dispatch/combine [S, E, C]
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                dtype=jnp.float32)               # [K,S,E,C]
        dispatch = jnp.einsum("kse,ksec->sec", keep, pos_oh)
        combine = jnp.einsum("ks,kse,ksec->sec", gates, keep, pos_oh)

        xf = xs.astype(jnp.float32)
        expert_in = jnp.einsum("sec,sd->ecd", dispatch, xf)      # [E, C, D]
        h = self.act(jnp.einsum("ecd,edh->ech", expert_in,
                                wi.astype(jnp.float32)))
        expert_out = jnp.einsum("ech,ehd->ecd", h, wo.astype(jnp.float32))
        out = jnp.einsum("sec,ecd->sd", combine, expert_out)     # [S, D]

        # Switch load-balancing loss: E * Σ_e (token_frac_e · prob_frac_e).
        # Declare at init (zeros) so the state pytree structure is stable
        # across init/apply — lax.scan carries require it.
        scope.variable("aux_loss", lambda: jnp.zeros((), jnp.float32))
        frac_tokens = assign[0].mean(axis=0)                     # [E]
        frac_probs = probs.mean(axis=0)
        aux = e * jnp.sum(frac_tokens * frac_probs)
        scope.put_variable("aux_loss", aux)

        return out.reshape(b, t, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dropless expert layer that holds a share of the experts
# ---------------------------------------------------------------------------

#: device-side counters of DroplessMoE, kept in its state under
#: ``counters`` and published by the Estimator once an epoch as the registry
#: series ``moe.<key>`` (docs/observability.md): pairs routed, pairs that
#: landed on held experts, held pairs that found no row (stays 0), and the
#: rows per held expert (a vector: published as the histogram of its
#: largest slot over the mean)
COUNTER_KEYS = ("pairs_total", "pairs_local", "pairs_dropped",
                "load_max_over_mean")

#: a level, not a count, kept beside the counters by a layer that balances
#: its router by a bias (``balance_coeff``): the largest |bias| of the
#: layer, published as the histogram ``moe.<key>`` at the same read-back
LEVEL_KEYS = ("expert_bias_abs_max",)

#: rows of one window of DroplessMoE's buffer, over the rows a balanced
#: router sends to the held experts: a balanced step fits one window
FAST_BUFFER = 2.0


def _window(start, xs, w_in, w_out, pair_w, order, load, n: int, k: int):
    """Rows ``start .. start + n`` of the sorted (token, pick) pairs through
    the held experts: their weighted outputs added up by token ([S, D]
    float32), and how many of the rows are held pairs.  The others belong to
    no group: the grouped matmuls leave them undefined, forward and
    backward, so they are masked on the way in and on the way out."""
    s, m = xs.shape[0], w_out.shape[1]
    pairs = jax.lax.dynamic_slice(order, (start,), (n,))
    token = pairs // k
    ends = jnp.cumsum(load)
    sizes = jnp.clip(ends, start, start + n) \
        - jnp.clip(ends - load, start, start + n)
    live = (start + jnp.arange(n) < ends[-1])[:, None]
    x_rows = jnp.where(live, xs[token], 0)                       # [n, D]
    h = jax.lax.ragged_dot(x_rows, w_in, sizes)
    h = jax.nn.silu(h[:, :m]) * h[:, m:]
    y = jax.lax.ragged_dot(h, w_out, sizes)
    y = jnp.where(live, y, 0).astype(jnp.float32) * pair_w[pairs][:, None]
    out = jnp.zeros((s, xs.shape[1]), jnp.float32).at[token].add(y)
    return out, live.sum(dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _routed(xs, w_in, w_out, pair_w, order, load, n: int, k: int):
    """Every window that holds a held pair, one after another: a loop whose
    length is the data's (``ceil(held pairs / n)``), so a balanced step pays
    for one window and the worst imbalance for all of them, in one window's
    memory.  Such a loop has no automatic transpose; the backward pass is
    the same walk with each window's own vjp."""
    def body(i, carry):
        out, rows = _window(i * n, xs, w_in, w_out, pair_w, order, load,
                            n, k)
        return carry[0] + out, carry[1] + rows
    return jax.lax.fori_loop(
        0, -(-load.sum() // n), body,
        (jnp.zeros(xs.shape, jnp.float32), jnp.zeros((), jnp.int32)))


def _routed_fwd(xs, w_in, w_out, pair_w, order, load, n, k):
    return (_routed(xs, w_in, w_out, pair_w, order, load, n, k),
            (xs, w_in, w_out, pair_w, order, load))


def _routed_bwd(n, k, res, g):
    xs, w_in, w_out, pair_w, order, load = res

    def body(i, grads):
        _, vjp = jax.vjp(
            lambda *a: _window(i * n, *a, order, load, n, k)[0],
            xs, w_in, w_out, pair_w)
        return tuple(a + b for a, b in zip(grads, vjp(g[0])))
    grads = jax.lax.fori_loop(
        0, -(-load.sum() // n), body,
        tuple(jnp.zeros_like(a) for a in (xs, w_in, w_out, pair_w)))
    return (*grads, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def _top_k(x: jax.Array, k: int):
    """``jax.lax.top_k`` over the last axis for small k: k passes of argmax
    and mask (ties to the lower index, as there).  On a TPU ``top_k`` of a
    [tokens, experts] array is a full sort of every row."""
    if k > 16:
        return jax.lax.top_k(x, k)
    picked, rest = [], x
    for _ in range(k):
        i = jnp.argmax(rest, axis=-1)
        picked.append(i)
        rest = jnp.where(jnp.arange(x.shape[-1]) == i[..., None], -jnp.inf,
                         rest)
    idx = jnp.stack(picked, axis=-1).astype(jnp.int32)
    return jnp.take_along_axis(x, idx, axis=-1), idx


class _Router(Module):
    """``softmax(x W)`` (or ``sigmoid(x W)``: ``score_func``) over every
    expert, matmul and scores in float32 at full precision (on a TPU a
    float32 product takes bf16 passes unless told otherwise, and the top-k
    is decided by the last bits)."""

    def __init__(self, num_experts: int, kernel_init: Any,
                 score_func: str = "softmax"):
        super().__init__("router")
        if score_func not in ("softmax", "sigmoid"):
            raise ValueError("score_func must be 'softmax' or 'sigmoid'; "
                             f"got {score_func!r}")
        self.num_experts = num_experts
        self.kernel_init = initializers.get(kernel_init)
        self.score_func = score_func

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        w = scope.param("kernel", self.kernel_init,
                        (x.shape[-1], self.num_experts))
        logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if self.score_func == "sigmoid":
            return jax.nn.sigmoid(logits)
        return jax.nn.softmax(logits, axis=-1)


def balance_bias(bias: jax.Array, picks: jax.Array,
                 coeff: float) -> jax.Array:
    """One step of balancing without an auxiliary loss (arXiv:2408.15664):
    from the step's picks of each expert, ``d = coeff * sign(mean(picks) -
    picks)`` raises the bias of the experts picked less than the mean and
    lowers the others', and ``bias + d - mean(d)`` keeps the bias centred."""
    c = picks.astype(jnp.float32)
    d = coeff * jnp.sign(c.mean() - c)
    return bias + d - d.mean()


class DroplessMoE(Module):
    """Token-choice expert layer that is told which experts it holds:
    ``[B, T, D] -> [B, T, D]``.

    The router scores all ``num_experts`` and keeps its ``top_k`` (weights
    renormalised to sum 1 when ``norm_topk_prob``).  Of those picks this
    layer computes the ones among its own experts, ``first_expert ..
    first_expert + experts_held - 1``; what absent experts would add is left
    out, which is one chip's part of an expert-parallel layer before the
    exchange (held = num_experts is the whole layer).  Each expert is a
    SwiGLU of width ``expert_units``.  ``shared_units > 0`` adds a shared
    expert every token passes, behind a sigmoid gate unless
    ``shared_gate=False``; it is computed whole on every share.

    The router's scores are a softmax over the experts or, with
    ``score_func="sigmoid"``, a sigmoid of each; the kept scores are
    renormalised to ``w / (sum(w) + norm_epsilon)`` and multiplied by
    ``route_scale``.  ``balance_coeff`` balances the router by a bias and
    not by a loss: the top-k is taken of ``score + expert_bias``, a state
    variable over all experts (zeros at first, no gradient) that every
    training forward moves by :func:`balance_bias` from the step's picks
    and inference leaves alone; the kept weights are the scores without
    it.  Such a layer publishes no ``aux_loss``.  A share sees its own
    tokens only; a deployment sums the picks over its data-parallel chips
    before the sign.

    No pick of a held expert is ever dropped.  The (token, pick) pairs are
    sorted by expert, the held ones first, and that order is walked in
    windows of ``FAST_BUFFER`` times the balanced share of rows (``tokens *
    top_k * experts_held / num_experts``): a window's rows are gathered,
    pass two grouped matmuls (``jax.lax.ragged_dot``) whose group sizes are
    the experts' loads inside the window, and are added back to their
    tokens, for as many windows as hold a held pair.  So the work follows
    the rows that are routed here, whatever the imbalance (a balanced step
    is one window; all ``tokens * min(top_k, experts_held)`` rows landing
    here is every window), no shape depends on the data, and the memory is
    one window's.  Expert matmuls in the input's dtype; router, top-k and
    the weighted sum in float32.

    State: ``aux_loss`` — the load-balancing loss ``num_experts * sum_e f_e
    P_e`` over ALL experts (f_e: picks of expert e per token, P_e: mean
    router probability; ``top_k`` when balanced), summed into the training
    loss by the Estimator's ``aux_loss_weight``; ``counters`` — device-side
    counters the Estimator reads once an epoch (``COUNTER_KEYS``,
    docs/observability.md): ``moe.pairs_total``, ``moe.pairs_local``,
    ``moe.pairs_dropped`` (picks of held experts that found no row in the
    buffer: stays 0) and ``moe.load_max_over_mean`` (rows per held expert),
    and under ``balance_coeff`` the level ``moe.expert_bias_abs_max``;
    ``expert_bias`` — the selection bias, under ``balance_coeff`` only.
    """

    def __init__(self, num_experts: int, top_k: int, expert_units: int,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 shared_units: int = 0, norm_topk_prob: bool = True,
                 kernel_init: Any = "glorot_uniform",
                 score_func: str = "softmax", route_scale: float = 1.0,
                 norm_epsilon: float = 0.0, shared_gate: bool = True,
                 balance_coeff: Optional[float] = None,
                 name: Optional[str] = None):
        super().__init__(name or "moe")
        held = num_experts if experts_held is None else experts_held
        if not 0 <= first_expert <= first_expert + held <= num_experts:
            raise ValueError(
                f"experts {first_expert}..{first_expert + held - 1} are not "
                f"among {num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self.expert_units = expert_units
        self.experts_held, self.first_expert = held, first_expert
        self.shared_units = shared_units
        self.norm_topk_prob = norm_topk_prob
        self.kernel_init = kernel_init
        self.score_func, self.route_scale = score_func, route_scale
        self.norm_epsilon, self.shared_gate = norm_epsilon, shared_gate
        self.balance_coeff = balance_coeff

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        s, k, e, held = b * t, self.top_k, self.num_experts, self.experts_held
        m = self.expert_units
        init = initializers.get(self.kernel_init)
        xs = x.reshape(s, d)

        probs = scope.child(_Router(e, self.kernel_init, self.score_func),
                            xs, name="router")
        if self.balance_coeff is None:
            top_w, top_e = _top_k(probs, k)                      # [S, K]
        else:  # picked with the bias, weighted without it
            bias = scope.variable("expert_bias",
                                  lambda: jnp.zeros((e,), jnp.float32))
            _, top_e = _top_k(probs + bias, k)
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        if self.norm_topk_prob:
            total = top_w.sum(axis=-1, keepdims=True)
            top_w = top_w / (total + self.norm_epsilon
                             if self.norm_epsilon else total)
        if self.route_scale != 1.0:
            top_w = top_w * self.route_scale

        # sort the pairs by expert, held experts first (absent ones share
        # the key ``held``): the first n_local rows of that order are ours
        first = self.first_expert
        local = (top_e >= first) & (top_e < first + held)
        key = jnp.where(local, top_e - first, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)  # [S*K]
        picks = jnp.sum(top_e.reshape(-1, 1) == jnp.arange(e), axis=0,
                        dtype=jnp.int32)                         # [E]
        load = picks[first:first + held]
        n_local = load.sum()
        w_in = scope.param("w_gate_up", init,
                           (held, d, 2 * m)).astype(x.dtype)
        w_out = scope.param("w_down", init, (held, m, d)).astype(x.dtype)

        n_most = s * min(k, held)
        n = min(n_most, -(-int(FAST_BUFFER * s * k * held / e) // 8) * 8)
        order = jnp.pad(order, (0, max(0, -(-n_most // n) * n - s * k)))
        out, placed = _routed(xs, w_in, w_out, top_w.reshape(-1), order,
                              load, n, k)

        if self.shared_units:
            shared = scope.child(SwiGLU(self.shared_units,
                                        kernel_init=self.kernel_init),
                                 xs, name="shared_expert")
            if self.shared_gate:
                g = scope.child(Dense(1, use_bias=False,
                                      kernel_init=self.kernel_init),
                                xs, name="shared_gate")
                shared = jax.nn.sigmoid(g.astype(jnp.float32)) * shared
            out = out + shared

        levels = {}
        if self.balance_coeff is None:
            scope.variable("aux_loss", lambda: jnp.zeros((), jnp.float32))
            scope.put_variable("aux_loss", e * jnp.sum(
                picks.astype(jnp.float32) / s * probs.mean(axis=0)))
        else:
            if scope.training:
                bias = balance_bias(bias, picks, self.balance_coeff)
                scope.put_variable("expert_bias", bias)
            levels = {"moe." + LEVEL_KEYS[0]: jnp.abs(bias).max()}
        grew = dict(zip(COUNTER_KEYS, (s * k, n_local, n_local - placed, load)))
        seen = scope.variable("counters", lambda: {
            **{"moe." + key: jnp.zeros_like(v, jnp.int32)
               for key, v in grew.items()},
            **{key: jnp.zeros((), jnp.float32) for key in levels}})
        scope.put_variable("counters", {
            **{"moe." + key: seen["moe." + key] + v
               for key, v in grew.items()}, **levels})
        return out.reshape(b, t, d).astype(x.dtype)
