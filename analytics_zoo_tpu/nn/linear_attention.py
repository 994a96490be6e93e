"""Linear attention with a matrix-valued recurrent state: the gated delta
rule (Gated DeltaNet; Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464) in
chunked form, and the mixer layer built on it.

Per value head the state ``S`` (``[d_k, d_v]``, zero at the sequence's
start) follows, position by position::

    S <- exp(g_t) * S                 # forget      (g_t <= 0)
    u  = beta_t * (v_t - S^T k_t)     # what the key does not yet recall
    S <- S + k_t u^T                  # write
    o_t = S^T q_t                     # read

Absent from the reference (its recurrent layers are LSTM/GRU,
``nn/recurrent.py``).  Run that way the recurrence is T dependent steps of
rank-one updates: nothing for the MXU.  The chunked form (the WY
representation of a product of Householder-like factors) turns ``C``
positions into dense products: inside a chunk the ``u`` of every position
solve one unit-lower-triangular system, ``(I + tril(diag(beta) (K K^T * D),
-1)) U = diag(beta) (V - (K * d) S)``, and only the chunk-to-chunk carry of
``S`` is sequential: ``T / C`` steps of two ``[C, d] x [d, d]`` products a
head.

Two implementations of that one algorithm, chosen by :func:`gated_delta_rule`
from the backend and the shapes.  On a TPU, for heads of whole 128-lane
tiles, the Pallas kernels of ``ops/gated_delta_rule.py``: a chunk's ``[C, C]``
terms and the carried state live in VMEM, forward (``gated_delta_rule_fwd``)
and backward (``gated_delta_rule_bwd``, a ``jax.custom_vjp`` that walks the
chunks in reverse and reads the chunk-start states and the inverses the
forward kept).  Elsewhere the ``jax.numpy`` form below, where everything
outside the carry is computed for all chunks at once and the backward pass
is autodiff through the same program (``lax.scan``'s transpose walks the
chunks in reverse); it is also the oracle of the kernels' tests.  Wrap the
layer in ``nn.Remat`` to keep a block's activations out of the saved set;
``save_names=GatedDeltaNet.remat_keep`` keeps what the backward kernel
reads, so the recomputation runs no kernel.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..core.metrics import get_registry
from ..ops import gated_delta_rule as kernels
from ..ops import gdn_gated_norm
from ..ops.gdn_qkv_conv import qkv_conv
from . import initializers
from .layers import Dense, _rms_norm
from .module import Module, Scope

_HIGHEST = jax.lax.Precision.HIGHEST


@jax.custom_vjp
def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + A)^-1`` for strictly lower-triangular ``A`` (``[..., C, C]``,
    float32).  ``A`` is nilpotent, so the Neumann series ends:
    ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...`` — log2(C) squarings, all
    dense products (a triangular solve would be C dependent steps).  Full
    float32 products: the result multiplies every value of the chunk.  The
    gradient is ``-M^T dM M^T`` from the result ``M`` alone, so the powers
    are not kept for the backward pass."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv = eye - a
    power = a
    for _ in range(max(0, int(np.ceil(np.log2(c))) - 1)):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, g):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(inv_t, g, precision=_HIGHEST), inv_t,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                     g: jax.Array, beta: jax.Array, chunk: int = 64,
                     initial_state: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence of this module's docstring, in chunks of ``chunk``.

    q, k: ``[B, T, H_k, d_k]``; v: ``[B, T, H, d_v]`` with ``H`` a multiple
    of ``H_k`` (value head ``i`` reads key head ``i // (H / H_k)``); g (log
    decay, <= 0) and beta: ``[B, T, H]`` float32.  q and k are used as given
    (normalise and scale them outside).  Returns ``(o [B, T, H, d_v] in v's
    dtype, final state [B, H, d_k, d_v] float32)``.  Matmul operands keep
    q/k/v's dtype (bf16 on the MXU), sums and the state are float32.  Any T:
    the tail is padded with g = 0, beta = 0 (a position that neither forgets
    nor writes) and cut off again.

    One algorithm, two implementations, chosen from what the call can see:
    on backend ``tpu``, for heads of whole 128-lane tiles and the chunk the
    kernels were written for, the Pallas kernels of
    ``ops/gated_delta_rule.py`` (or the compiler's error); elsewhere the
    ``jax.numpy`` form below, which is also the oracle of the kernels'
    tests (they set ``ops.gated_delta_rule.INTERPRET``).
    """
    b, t, hk, dk = k.shape
    h, dv = v.shape[2:]
    if h % hk:
        raise ValueError(f"{h} value heads are no multiple of {hk} key heads")
    interpret = kernels.dispatch(dk, dv, chunk)
    use_kernels = interpret is not None
    pad = -t % (kernels.tile_rows(chunk) if use_kernels else chunk)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                   for a in (g, beta))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    s0 = (jnp.zeros((b, h, dk, dv), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    if use_kernels:
        o, s_final = kernels.chunk_kernels(q, k, v, g, beta, s0, chunk,
                                           interpret)
    else:
        if h != hk:
            q, k = (jnp.repeat(a, h // hk, axis=2) for a in (q, k))
        o, s_final = _chunked_jax(q, k, v, g, beta, s0, chunk)
    return o[:, :t], s_final


def _chunked_jax(q, k, v, g, beta, s0, chunk):
    """The chunked form in ``jax.numpy``: every head its own q and k, T a
    multiple of ``chunk``, g and beta float32.  Autodiff gives its backward
    pass (``lax.scan``'s transpose walks the chunks in reverse)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    dt = v.dtype
    n = t // chunk

    def chunks(a):  # [B, T, H, ...] -> [B, H, N, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    f32 = dict(preferred_element_type=jnp.float32)

    def chunk_terms(q, k, v, g, beta):
        """Everything that needs no state, for all chunks at once."""
        g = jnp.cumsum(g, axis=-1)                           # [B,H,N,C]
        # decay from position j to position i of a chunk, i >= j.  Masked
        # before the exp: above the diagonal g_i - g_j > 0 can overflow, and
        # an inf in the branch a ``where`` drops still poisons its gradient
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(causal, g[..., :, None] - g[..., None, :],
                                  -jnp.inf))
        k_beta = (k * beta[..., None]).astype(dt)
        a = jnp.einsum("...ik,...jk->...ij", k_beta, k, **f32) * decay
        a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1), a, 0.0)
        solve = checkpoint_name(_unit_lower_inverse(a).astype(dt),
                                "gdn_inverse")               # [B,H,N,C,C]
        # U = T (beta V), W = T (beta K * exp(g)): the chunk's writes given
        # a zero state, and what the incoming state takes away from them
        u = jnp.einsum("...ij,...jd->...id", solve,
                       (v * beta[..., None]).astype(dt), **f32)
        w = jnp.einsum("...ij,...jd->...id", solve,
                       (k_beta * jnp.exp(g)[..., None]).astype(dt), **f32)
        g_last = g[..., -1:]
        k_tail = k * jnp.exp(g_last - g)[..., None]
        qk = jnp.einsum("...ik,...jk->...ij", q, k, **f32) * decay
        q_in = q * jnp.exp(g)[..., None]
        return (u, w.astype(dt), k_tail.astype(dt), jnp.exp(g_last),
                qk.astype(dt), q_in.astype(dt))

    # under an enclosing nn.Remat the block's forward is recomputed once for
    # the backward pass; the [C, C] float32 terms of every chunk would be its
    # largest saved activations, so they are recomputed once more instead:
    # all but the inverse, which is a dozen passes over them and is kept
    u, w, k_tail, decay_last, qk, q_in = jax.checkpoint(
        chunk_terms,
        policy=jax.checkpoint_policies.save_only_these_names("gdn_inverse"))(
        chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta))

    def carry(s, xs):
        w_i, u_i, k_i, decay_i = xs
        s_in = s.astype(dt)
        v_new = (u_i - jnp.einsum("bhck,bhkd->bhcd", w_i, s_in, **f32)
                 ).astype(dt)
        s_next = s * decay_i[..., None] + jnp.einsum(
            "bhck,bhcd->bhkd", k_i, v_new, **f32)
        return s_next, (s_in, v_new)

    lead = lambda x: jnp.moveaxis(x, 2, 0)                   # N first
    s_final, (s_in, v_new) = jax.lax.scan(
        carry, s0, (lead(w), lead(u), lead(k_tail), lead(decay_last)))
    s_in, v_new = jnp.moveaxis(s_in, 0, 2), jnp.moveaxis(v_new, 0, 2)

    # read: what the incoming state answers, plus the chunk's own writes
    o = jnp.einsum("...ck,...kd->...cd", q_in, s_in, **f32)
    o = o + jnp.einsum("...ij,...jd->...id", qk, v_new, **f32)
    o = jnp.moveaxis(o, 1, 3).reshape(b, t, h, dv)
    return o.astype(dt), s_final


class _QKVConv(Module):
    """The mixer's child ``conv``: it holds the depthwise conv's ``kernel``
    ``[K, 2 key_dim + value_dim]`` (the leaf a ``CausalConv1D`` under that
    name held, drawn the same way) and is everything between
    ``in_proj_qkvz``'s output and the delta rule's q, k, v:
    ``ops/gdn_qkv_conv.py``, one Pallas kernel each way on a TPU."""

    def __init__(self, num_k_heads: int, num_v_heads: int, k_head_dim: int,
                 v_head_dim: int, kernel_size: int, epsilon: float,
                 name: Optional[str] = None):
        super().__init__(name)
        self.heads = (num_k_heads, num_v_heads, k_head_dim, v_head_dim)
        self.kernel_size, self.epsilon = kernel_size, epsilon

    def forward(self, scope: Scope, qkvz: jax.Array):
        hk, hv, dk, dv = self.heads
        w = scope.param("kernel", initializers.get("lecun_uniform"),
                        (self.kernel_size, 2 * hk * dk + hv * dv))
        return qkv_conv(qkvz, w, *self.heads, self.epsilon)


def _gated_norm_jax(o, z, w, epsilon):
    """The mixer's way out in ``jax.numpy`` (autodiff and ``_rms_norm``'s
    rule give its backward pass): o ``[B, T, H, d_v]`` RMS-normalised a head
    and scaled by ``w``, then gated by ``silu(z)`` (``[B, T, H d_v]``)."""
    b, t, h, dv = o.shape
    o = _rms_norm(o, w, epsilon)
    # z lies as qkvz's last columns: gated there, no head axis to make
    return (o.reshape(b, t, h * dv)
            * jax.nn.silu(z.astype(jnp.float32)).astype(o.dtype))


class _GatedNorm(Module):
    """The mixer's child ``norm``: it holds the RMSNorm's ``weight``
    ``[d_v]`` (initialised to ones, the leaf an ``RMSNorm`` under that name
    held) and is everything between the delta rule's output and
    ``out_proj``: each head RMS-normalised, then gated by ``silu(z)``.
    ``ops/gdn_gated_norm.py``, one Pallas kernel each way, where its
    ``dispatch`` says so (z read in place from ``source``, the ``qkvz`` it
    was sliced from), else :func:`_gated_norm_jax`; which of the two a trace
    took is counted as ``gdn.norm_path{path="kernel" | "jnp"}``."""

    def __init__(self, epsilon: float, name: Optional[str] = None):
        super().__init__(name)
        self.epsilon = epsilon

    def forward(self, scope: Scope, o: jax.Array, z: jax.Array,
                source: jax.Array):
        t, dv = o.shape[1], o.shape[3]
        w = scope.param("weight", initializers.get("ones"), (dv,))
        interpret = gdn_gated_norm.dispatch(t, dv)
        get_registry().inc("gdn.norm_path",
                           path="jnp" if interpret is None else "kernel")
        if interpret is None:
            return _gated_norm_jax(o, z, w, self.epsilon)
        return gdn_gated_norm.gated_norm(o, z, w, self.epsilon, interpret,
                                         source)


class GatedDeltaNet(Module):
    """Gated DeltaNet mixer: ``[B, T, D] -> [B, T, D]``, causal.

    ``in_proj_qkvz`` gives q, k (``num_k_heads`` of ``k_head_dim``) and v, z
    (``num_v_heads`` of ``v_head_dim``); ``in_proj_ba`` one write strength
    ``beta = sigmoid(b)`` and one forget gate ``g = -exp(A_log) *
    softplus(a + dt_bias)`` a value head (float32).  q, k, v pass a
    depthwise causal convolution of ``conv_kernel`` positions and a SiLU;
    q and k are L2-normalised over the head, q scaled by
    ``1/sqrt(k_head_dim)`` (all of that is the child ``conv``:
    ``ops/gdn_qkv_conv.py``, one Pallas kernel each way on a TPU, the
    ``jax.numpy`` lines elsewhere); each key head serves ``num_v_heads /
    num_k_heads`` value heads.  The recurrence is :func:`gated_delta_rule`;
    its output is RMS-normalised a head and gated by ``silu(z)`` (the child
    ``norm``: ``ops/gdn_gated_norm.py``, one Pallas kernel each way on a TPU,
    the ``jax.numpy`` lines elsewhere) and projected back.  No biases.
    Holds no cache: a sequence starts from a zero state.
    """

    # what the delta rule's backward kernel reads: kept, a recomputation of
    # the block runs neither kernel again
    remat_keep = ("gated_delta_rule_out", "gated_delta_rule_states",
                  "gated_delta_rule_inverse")

    def __init__(self, num_k_heads: int, num_v_heads: int, k_head_dim: int,
                 v_head_dim: int, conv_kernel: int = 4, chunk: int = 64,
                 epsilon: float = 1e-6, kernel_init: Any = "glorot_uniform",
                 name: Optional[str] = None):
        super().__init__(name)
        if num_v_heads % num_k_heads:
            raise ValueError(f"num_v_heads {num_v_heads} is not a multiple "
                             f"of num_k_heads {num_k_heads}")
        self.num_k_heads, self.num_v_heads = num_k_heads, num_v_heads
        self.k_head_dim, self.v_head_dim = k_head_dim, v_head_dim
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.epsilon = epsilon
        self.kernel_init = kernel_init

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        hk, hv = self.num_k_heads, self.num_v_heads
        dk, dv = self.k_head_dim, self.v_head_dim
        key_dim, value_dim = hk * dk, hv * dv

        def dense(units):
            return Dense(units, use_bias=False, kernel_init=self.kernel_init)
        qkvz = scope.child(dense(2 * key_dim + 2 * value_dim), x,
                           name="in_proj_qkvz")
        ba = scope.child(dense(2 * hv), x, name="in_proj_ba")
        # conv, SiLU, the split into heads, q's and k's l2norm and q's scale
        q, k, v, z = scope.child(
            _QKVConv(hk, hv, dk, dv, self.conv_kernel, self.epsilon), qkvz,
            name="conv")

        a_log = scope.param("A_log", initializers.a_log_init(), (hv,))
        dt_bias = scope.param("dt_bias", initializers.dt_bias_init(),
                              (hv,))
        ba = ba.astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        # value head i reads key head i // (hv / hk)
        o, _ = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)

        o = scope.child(_GatedNorm(self.epsilon), o, z, qkvz, name="norm")
        return scope.child(dense(d), o, name="out_proj")
