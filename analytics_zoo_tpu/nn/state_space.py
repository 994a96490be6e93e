"""State-space mixing with a scalar decay a head: the Mamba-2 recurrence
(state-space duality; Dao & Gu 2024, arXiv:2405.21060) in chunked form, and
the mixer layer built on it.

Per head the state ``S`` (``[P, N]``, zero at the sequence's start unless
given) follows, position by position::

    S <- exp(dt_t * A) * S + dt_t * x_t B_t^T     # forget (A < 0), write
    y_t = S C_t + D * x_t                         # read, skip

``B_t`` and ``C_t`` (``[N]``) are shared by the heads of a group.  Absent
from the reference (its recurrent layers are LSTM/GRU, ``nn/recurrent.py``)
and a different recurrence from ``nn/linear_attention.py``'s gated delta
rule: no delta correction, so no triangular system and no inverse; what is
left inside a chunk of ``Q`` positions is one masked ``[Q, Q]`` product.
With ``c_t`` the running sum of ``dt * A`` inside a chunk::

    Y     = ((C B^T) * L) (dt * X) + exp(c_t) C_t S_in
    L[t, s] = exp(c_t - c_s) for s <= t, else 0
    S_out = exp(c_Q) S_in + sum_s exp(c_Q - c_s) dt_s X_s B_s^T

and only the chunk-to-chunk carry of ``S`` is sequential (``T / Q`` steps of
a multiply-add over ``[H, P, N]``).  Every exponent is a difference of
float32 sums taken BEFORE the ``exp`` (never ``exp(c_t) / exp(c_s)``: at a
strong decay ``exp(c_s)`` underflows inside one chunk of 256); matmul
operands keep the inputs' dtype (bf16 on the MXU), sums are float32.

One algorithm, two implementations, chosen from what a call can see
(``ops.mamba2_ssd.dispatch``: the backend and the shapes; no option and no
model's name).  On backend ``tpu``, for heads, a state width and a chunk
that are whole 128-lane tiles, the two Pallas kernels of
``ops/mamba2_ssd.py`` (``mamba2_ssd_fwd``, ``mamba2_ssd_bwd``) keep a
chunk's ``[Q, Q]`` terms and the carried state in VMEM; the running sums
``c_t`` stay ``jax.numpy`` around them (``[B, T, H]`` float32), so autodiff
takes ``dt`` and ``A_log`` through the cumulative sum as before.  Everywhere
else (every other backend, a float32 model at chunk 24, a row shorter than
128) the ``jax.numpy`` form below, which is also the oracle of the kernels'
tests (they set ``ops.mamba2_ssd.INTERPRET``).  Which of the two a trace of
``ssd`` took is counted at trace time: ``ssm.scan_traces{path="kernel" |
"jnp"}`` (docs/observability.md).

What is kept for the backward pass: by the kernels, their inputs and the
float32 state at every chunk's start (the ``[Q, Q]`` terms are rebuilt in
VMEM); by the ``jax.numpy`` form what autodiff keeps (``lax.scan``'s
transpose walks the chunks in reverse), less the ``[chunks, H, Q, Q]``
float32 terms, which are recomputed (``jax.checkpoint``).  Both tag the
output and the chunk-start states (``mamba2_ssd_out``,
``mamba2_ssd_states``) for an enclosing ``nn.Remat(save_names=)``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..core.metrics import get_registry
from ..ops import mamba2_ssd as kernels
from . import initializers
from .layers import CausalConv1D, Dense, RMSNorm
from .module import Module, Scope

#: device-side counters of Mamba2, kept in its state under ``counters`` and
#: published by the Estimator once an epoch as the registry series
#: ``ssm.<key>`` (docs/observability.md): positions scanned, and positions
#: added to fill a row's last chunk (the packing waste once rows vary)
COUNTER_KEYS = ("tokens", "tokens_padded")

#: levels (float32 leaves beside the counters; the Estimator observes a
#: floating-point leaf as it stands, in the histogram ``ssm.<key>``): the
#: largest ``-c_Q`` of any chunk and head of the layer's last step (above
#: about 87, ``exp(c_Q - c_s)`` is 0 in float32 and a chunk forgets its own
#: start), and the largest |S| carried between chunks
LEVEL_KEYS = ("chunk_decay_exponent_max", "state_abs_max")


def ssd(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
        c: jax.Array, d_skip: jax.Array, s0: Optional[jax.Array] = None,
        chunk: int = 256) -> Tuple[jax.Array, jax.Array]:
    """The recurrence of this module's docstring, in chunks of ``chunk``.

    x: ``[B, T, H, P]``; dt (the step, after its softplus): ``[B, T, H]``;
    a_log: ``[H]`` (``A = -exp(a_log)``); b, c: ``[B, T, N]`` or ``[B, T, G,
    N]`` with ``H`` a multiple of ``G`` (head ``i`` reads group ``i // (H /
    G)``); d_skip: ``[H]``; s0: ``[B, H, P, N]`` or None for zeros.  Returns
    ``(y [B, T, H, P] in x's dtype, final state [B, H, P, N] float32)``.
    Any T: the tail is padded to a multiple of the chunk with dt = 0 (a
    position that neither forgets nor writes) and cut off again; a row
    shorter than ``chunk`` is one chunk of its own length.
    """
    y, s_final, _ = _ssd(x, dt, a_log, b, c, d_skip, s0, chunk)
    return y, s_final


def _ssd(x, dt, a_log, b, c, d_skip, s0, chunk) -> Tuple[
        jax.Array, jax.Array, Dict[str, jax.Array]]:
    """:func:`ssd` and what a layer counts of it: positions padded, the
    largest decay exponent of a chunk, the largest carried |S|."""
    bsz, t, h, p = x.shape
    if b.ndim == 3:
        b, c = b[:, :, None], c[:, :, None]
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"{h} heads are no multiple of {g} groups")
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // q
    dt = dt.astype(jnp.float32)
    # c_t: the log decay from the chunk's start to position t, inclusive
    cs = jnp.cumsum((dt * -jnp.exp(a_log.astype(jnp.float32))).reshape(
        bsz, nc, q, h), axis=2)                              # [B,nc,Q,H]
    s0 = (jnp.zeros((bsz, h, p, n), jnp.float32) if s0 is None
          else s0.astype(jnp.float32))
    interpret = kernels.dispatch(h, g, p, n, q)
    get_registry().inc("ssm.scan_traces",
                       path="jnp" if interpret is None else "kernel")
    if interpret is None:
        y, s_final, s_abs_max = _chunked_jax(x, dt, cs, b, c, d_skip, s0)
    else:
        y, s_final, s_abs_max = kernels.chunk_kernels(
            x, dt, cs.reshape(dt.shape), b, c, d_skip, s0, q, interpret)
    stats = jax.lax.stop_gradient({
        "tokens_padded": jnp.asarray(bsz * pad, jnp.int32),
        "chunk_decay_exponent_max": (-cs[:, :, -1]).max(),
        "state_abs_max": s_abs_max.max()})
    return y[:, :t], s_final, stats


def _chunked_jax(x, dt, cs, b, c, d_skip, s0):
    """The chunked form in ``jax.numpy``: x ``[B, T, H, P]``, dt ``[B, T,
    H]`` float32, cs (the running sums ``c_t``) ``[B, chunks, Q, H]``, b and
    c ``[B, T, G, N]``, s0 ``[B, H, P, N]`` float32, T a whole number of
    chunks.  Returns y, the final state and the largest |S| at a chunk's
    boundary.  Autodiff gives its backward pass."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    nc, q = cs.shape[1:3]
    r = h // g
    dtype = x.dtype
    f32 = dict(preferred_element_type=jnp.float32)

    def chunks(v, *tail):  # [B, T, ...] -> [B, nc, Q, *tail]
        return v.reshape((bsz, nc, q) + tail)

    xc = chunks(x, g, r, p)
    bc, cc = chunks(b, g, n), chunks(c, g, n)
    dtc = chunks(dt, g, r)
    cs = cs.reshape(bsz, nc, q, g, r)
    x_dt = (xc * dtc[..., None]).astype(dtype)

    @jax.checkpoint
    def within(cc, bc, cs, x_dt):
        """What a chunk's positions read of the chunk's own writes.  Its
        [B, nc, G, R, Q, Q] float32 terms are the largest of the layer:
        recomputed in the backward pass, never kept."""
        cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cc, bc, **f32)
        ct = jnp.moveaxis(cs, 2, -1)                         # [B,nc,G,R,Q]
        # masked before the exp: above the diagonal c_t - c_s > 0 can
        # overflow, and an inf in the branch a ``where`` drops still
        # poisons its gradient
        seen = jnp.tril(jnp.ones((q, q), bool))
        decay = jnp.exp(jnp.where(seen, ct[..., :, None] - ct[..., None, :],
                                  -jnp.inf))
        m = (cb[:, :, :, None] * decay).astype(dtype)
        return jnp.einsum("bcgrqs,bcsgrp->bcqgrp", m, x_dt, **f32)

    y = within(cc, bc, cs, x_dt)

    # what each chunk adds to the state by its end, and how much of the
    # incoming state is left by then
    to_end = jnp.exp(cs[:, :, -1:] - cs)                     # [B,nc,Q,G,R]
    x_end = (xc * (dtc * to_end)[..., None]).astype(dtype)
    wrote = jnp.einsum("bcsgrp,bcsgn->bcgrpn", x_end, bc, **f32)
    kept = jnp.exp(cs[:, :, -1])                             # [B,nc,G,R]

    def carry(s, step):
        wrote_i, kept_i = step
        return s * kept_i[..., None, None] + wrote_i, s

    s_final, s_in = jax.lax.scan(
        carry, s0.reshape(bsz, g, r, p, n),
        (jnp.moveaxis(wrote, 1, 0), jnp.moveaxis(kept, 1, 0)))
    s_in = checkpoint_name(jnp.moveaxis(s_in, 0, 1), "mamba2_ssd_states")

    # what the incoming state answers, decayed to each position
    y = y + jnp.einsum("bcqgn,bcgrpn->bcqgrp", cc, s_in.astype(dtype),
                       **f32) * jnp.exp(cs)[..., None]
    y = y + xc.astype(jnp.float32) * d_skip.astype(jnp.float32).reshape(
        g, r, 1)
    y = checkpoint_name(y.astype(dtype).reshape(bsz, t, h, p),
                        "mamba2_ssd_out")
    s_abs_max = jnp.maximum(jnp.abs(s_in).max(), jnp.abs(s_final).max())
    return y, s_final.reshape(bsz, h, p, n), s_abs_max


class Mamba2(Module):
    """Mamba-2 mixer: ``[B, T, D] -> [B, T, D]``, causal.

    ``in_proj`` gives the gate z (``num_heads * head_dim``), x | B | C
    (``num_heads * head_dim + 2 * n_groups * state_size``) and one step
    ``dt`` a head, in that order.  x | B | C pass a depthwise causal
    convolution of ``conv_kernel`` positions (with a bias under
    ``conv_bias``) and a SiLU; ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` in float32.  The recurrence is :func:`ssd` with the skip
    ``D``; its output times ``silu(z)`` is RMS-normalised over all
    ``num_heads * head_dim`` channels (one weight vector) and projected
    back.  No biases on the projections.  Holds no cache: a sequence
    starts from a zero state.

    State: ``counters`` — device-side counters and levels the Estimator
    reads once an epoch (``COUNTER_KEYS``, ``LEVEL_KEYS``; series ``ssm.*``).
    """

    def __init__(self, num_heads: int, head_dim: int, state_size: int,
                 n_groups: int = 1, conv_kernel: int = 4, chunk: int = 256,
                 conv_bias: bool = True, epsilon: float = 1e-5,
                 kernel_init: Any = "glorot_uniform",
                 name: Optional[str] = None):
        super().__init__(name)
        if num_heads % n_groups:
            raise ValueError(f"num_heads {num_heads} is not a multiple of "
                             f"n_groups {n_groups}")
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.n_groups = state_size, n_groups
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.conv_bias, self.epsilon = conv_bias, epsilon
        self.kernel_init = kernel_init

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        bsz, t, d = x.shape
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.n_groups)
        inner = h * p

        def dense(units):
            return Dense(units, use_bias=False, kernel_init=self.kernel_init)
        zxbcdt = scope.child(dense(2 * inner + 2 * g * n + h), x,
                             name="in_proj")
        z = zxbcdt[..., :inner]
        xbc = scope.child(
            CausalConv1D(self.conv_kernel, activation="silu",
                         use_bias=self.conv_bias),
            zxbcdt[..., inner:2 * inner + 2 * g * n], name="conv")
        a_log = scope.param("A_log", initializers.a_log_init(), (h,))
        dt_bias = scope.param("dt_bias", initializers.dt_bias_init(), (h,))
        d_skip = scope.param("D", initializers.get("ones"), (h,))
        dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * g * n:]
                             .astype(jnp.float32) + dt_bias)

        with jax.named_scope("ssd"):  # the scan, for a profile's readers
            y, _, stats = _ssd(
                xbc[..., :inner].reshape(bsz, t, h, p), dt, a_log,
                xbc[..., inner:inner + g * n].reshape(bsz, t, g, n),
                xbc[..., inner + g * n:].reshape(bsz, t, g, n), d_skip,
                None, self.chunk)
        y = y.reshape(bsz, t, inner)
        y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
        y = scope.child(RMSNorm(self.epsilon), y, name="norm")

        grew = {"tokens": jnp.asarray(bsz * t, jnp.int32),
                "tokens_padded": stats["tokens_padded"]}
        seen = scope.variable("counters", lambda: {
            **{"ssm." + k: jnp.zeros((), jnp.int32) for k in COUNTER_KEYS},
            **{"ssm." + k: jnp.zeros((), jnp.float32) for k in LEVEL_KEYS}})
        scope.put_variable("counters", {
            **{"ssm." + k: seen["ssm." + k] + grew[k] for k in COUNTER_KEYS},
            **{"ssm." + k: stats[k].astype(jnp.float32)
               for k in LEVEL_KEYS}})
        return scope.child(dense(d), y, name="out_proj")
