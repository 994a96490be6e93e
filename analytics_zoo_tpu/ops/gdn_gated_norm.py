"""The Gated DeltaNet mixer's way out of its kernels as one pass each way.

Between the delta rule's output ``o`` (``[B, T, H, d_v]``) and ``out_proj``
the mixer normalises each head (an RMSNorm over its ``d_v`` lanes with one
weight ``w`` ``[d_v]`` shared by every head) and gates the result by
``silu(z)``, z being the last ``H d_v`` columns of ``in_proj_qkvz``'s output::

    y = (o * rsqrt(mean(o^2) + eps) * w) * silu(z)

Written with ``jax.numpy`` (``nn/linear_attention.py`` ``_gated_norm_jax``:
the form every backend but a TPU runs, and the kernels' oracle) XLA makes
several passes of it a layer: the norm's float32 statistics and product, a
float32 ``silu(z)``, copies between ``[B, T, H d_v]`` and ``[B, T, H, d_v]``,
the gate's product in ``out_proj``'s fusions, all of it again in the block's
recomputation and once more backward.  Here it is two Pallas kernels, each a
single pass over a ``[B, T, H d_v]`` view of o (the delta rule's kernels
write and read o in that layout, so the view is free) and z's columns read
in place from ``qkvz`` (a block spec at their offset, so the slice that
makes z is never made):

``gdn_gated_norm_fwd``
    grid (B, row blocks, column blocks: one at the cell's shapes).  A step
    reads a block of rows of o and z, walks its heads a 128-lane tile at a
    time, and writes y once.
``gdn_gated_norm_bwd``
    the same grid.  A step reads o, z and dy once, rebuilds the statistics
    from o, and writes do, dz and a float32 partial sum of the weight's
    gradient (``[8, d_v]`` a step, summed outside).  dz goes to
    ``qkv_conv``'s backward as the cotangent of its z output, which that
    kernel carries into ``qkvz``'s cotangent: nothing is padded or added.

Same arithmetic as the ``jax.numpy`` form: statistics and ``silu`` in
float32, the same epsilon, one cast of y to o's dtype.  The one difference is
that the gate's product is taken in float32 and rounded once, where the
``jax.numpy`` form rounds the normalised o and ``silu(z)`` to o's dtype
before it multiplies them; the backward likewise rounds do and dz once.
``INTERPRET`` runs the kernels in Pallas interpret mode (tests, CPU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False  # tests set True to exercise the kernels on the CPU

_LANES = 128
#: rows of a grid step, a head's rows a pass of the loop: the largest that
#: divides T (the kernels take a T that is a multiple of the smallest).  At
#: the Qwen cell's sizes a call takes the same time at 256, 512 and 1,024
#: rows (0.68-0.69 ms forward, 1.09-1.14 backward on a v5e, where XLA copies
#: one of its arrays in 0.46): both kernels move their bytes at the rate a
#: copy does
ROW_BLOCKS = (512, 256, 128)


def _row_block(t: int):
    return next((n for n in ROW_BLOCKS if t % n == 0), None)


def dispatch(t: int, head_dim: int):
    """Who computes a call of these shapes here: ``None`` the ``jax.numpy``
    form, else the kernels, interpreted if ``True``.  On backend ``tpu`` the
    compiled kernels for heads of whole 128-lane tiles and a ``T`` of whole
    row blocks; elsewhere the kernels only when a test sets ``INTERPRET``."""
    if head_dim % _LANES or _row_block(t) is None:
        return None
    if jax.default_backend() == "tpu":
        return False
    return True if INTERPRET else None


def _heads(o_ref, head: int, body, carry):
    """``body(lanes, carry)`` over every head of a step's block."""
    def one(h, carry):
        return body(pl.ds(pl.multiple_of(h * head, _LANES), head), carry)
    return jax.lax.fori_loop(0, o_ref.shape[2] // head, one, carry)


def _stats(x, epsilon: float):
    """``x * rsqrt(mean(x^2) + eps)`` over the lanes and the ``rsqrt``."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + epsilon)
    return x * r, r


def _params(step_bytes: int):
    """Double-buffered blocks and the loop's temporaries, with room."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3,
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 3 * step_bytes))))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, epsilon: float):
    f32 = jnp.float32
    w = w_ref[...]

    def body(lanes, carry):
        xh, _ = _stats(o_ref[0, :, lanes].astype(f32), epsilon)
        z = z_ref[0, :, lanes].astype(f32)
        y_ref[0, :, lanes] = (xh * w * (z * jax.nn.sigmoid(z))).astype(
            y_ref.dtype)
        return carry

    _heads(o_ref, w_ref.shape[1], body, None)


def _specs(t, width, offset):
    """Rows a grid step, its columns and the block specs of o (and of what
    is written in o's shape) and of z's columns of the source."""
    rows, cols = _row_block(t), math.gcd(width, offset)
    first = offset // cols
    mine = pl.BlockSpec((1, rows, cols), lambda i, s, c: (i, s, c))
    source = pl.BlockSpec((1, rows, cols), lambda i, s, c: (i, s, first + c))
    return rows, cols, mine, source


@functools.lru_cache(maxsize=64)
def _forward(b, t, width, head, offset, epsilon, dtype, interpret):
    """The forward ``pallas_call`` for these sizes: built once, so that the
    layers of a model, and its ``init``, ``predict`` and train-step programs,
    trace the kernel once between them."""
    rows, cols, mine, source = _specs(t, width, offset)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, epsilon=epsilon),
        grid=(b, t // rows, width // cols),
        in_specs=[mine, source,
                  pl.BlockSpec((1, head), lambda i, s, c: (0, 0))],
        out_specs=mine,
        out_shape=jax.ShapeDtypeStruct((b, t, width), dtype),
        compiler_params=_params(2 * 3 * rows * cols * dtype.itemsize),
        interpret=interpret,
        name="gdn_gated_norm_fwd",  # the op's name in HLO and in a profile
    )


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *,
                epsilon: float):
    """y = n s with n = x^ w, x^ = x r, s = silu(z):
    dz = dy n s'(z); dn = dy s; dx = r (dn w - x^ mean(dn w x^));
    dw = sum over rows and heads of dn x^ (here as 8 rows of partial
    sums)."""
    f32 = jnp.float32
    w = w_ref[...]

    def body(lanes, dw):
        xh, r = _stats(o_ref[0, :, lanes].astype(f32), epsilon)
        z = z_ref[0, :, lanes].astype(f32)
        g = dy_ref[0, :, lanes].astype(f32)
        sig = jax.nn.sigmoid(z)
        dn = g * (z * sig)
        gy = dn * w
        dz_ref[0, :, lanes] = (g * (xh * w) * (sig * (1.0 + z * (1.0 - sig)))
                               ).astype(dz_ref.dtype)
        do_ref[0, :, lanes] = (r * (gy - xh * jnp.mean(
            gy * xh, axis=-1, keepdims=True))).astype(do_ref.dtype)
        part = dn * xh
        return dw + part.reshape(-1, 8, part.shape[-1]).sum(axis=0)

    dw_ref[0, 0, 0] = _heads(o_ref, w_ref.shape[1], body,
                             jnp.zeros(dw_ref.shape[3:], f32))


@functools.lru_cache(maxsize=64)
def _backward(b, t, width, head, offset, epsilon, dtype, interpret):
    """The backward ``pallas_call`` for these sizes, built once."""
    rows, cols, mine, source = _specs(t, width, offset)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, epsilon=epsilon),
        grid=(b, t // rows, width // cols),
        in_specs=[mine, source,
                  pl.BlockSpec((1, head), lambda i, s, c: (0, 0)), mine],
        out_specs=[mine, mine, pl.BlockSpec((1, 1, 1, 8, head),
                                            lambda i, s, c: (i, s, c, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, width), dtype),
                   jax.ShapeDtypeStruct((b, t, width), dtype),
                   jax.ShapeDtypeStruct((b, t // rows, width // cols, 8,
                                         head), jnp.float32)],
        compiler_params=_params(2 * 5 * rows * cols * dtype.itemsize),
        interpret=interpret,
        name="gdn_gated_norm_bwd",
    )


# ---------------------------------------------------------------------------
# The differentiable op
# ---------------------------------------------------------------------------

def _sizes(z, w, source):
    b, t, width = z.shape
    return b, t, width, w.shape[0], source.shape[2] - width


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _kernels(o, z, w, source, epsilon, interpret):
    return _forward(*_sizes(z, w, source), epsilon, o.dtype, interpret)(
        o.reshape(z.shape), source, w.astype(jnp.float32).reshape(1, -1))


def _vjp_fwd(o, z, w, source, epsilon, interpret):
    # all three are what the block's recomputation makes anyway; z is read
    # from the source, so a slice that made it is left to dead-code removal
    return _kernels(o, z, w, source, epsilon, interpret), (o, w, source)


def _vjp_bwd(epsilon, interpret, res, dy):
    o, w, source = res
    do, dz, dw = _backward(*_sizes(dy, w, source), epsilon, o.dtype,
                           interpret)(
        o.reshape(dy.shape), source, w.astype(jnp.float32).reshape(1, -1), dy)
    # z's cotangent is dz; the source's comes through z (None: a zero)
    return (do.reshape(o.shape), dz, dw.sum((0, 1, 2, 3)).astype(w.dtype),
            None)


_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def gated_norm(o, z, w, epsilon: float, interpret: bool, source=None):
    """``o`` ``[B, T, H, d_v]``, ``z`` ``[B, T, H d_v]`` (o's dtype) and the
    norm's weight ``w`` ``[d_v]`` -> ``y`` ``[B, T, H d_v]`` in o's dtype: o
    RMS-normalised a head, scaled by ``w`` and gated by ``silu(z)``, by the
    two kernels (interpreted if ``interpret``).  :func:`dispatch` says
    whether they take these shapes here.

    ``source``: an array whose LAST ``H d_v`` columns are z (``qkvz``, of
    which z is a slice), read there in place where z's first column in it
    is a multiple of ``d_v``, so that the slice need not be made; z's
    cotangent is still returned as z's, and the source's is zero."""
    if source is None or (source.shape[2] - z.shape[2]) % w.shape[0]:
        source = z
    return _kernels(o, z, w, source, float(epsilon), interpret)
