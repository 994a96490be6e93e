"""The Gated DeltaNet mixer's way into its kernels as one pass each way.

Between ``in_proj_qkvz``'s output ``[B, T, 2 key_dim + 2 value_dim]`` and the
delta rule's q, k, v lie a depthwise causal convolution of ``K`` taps over the
first ``2 key_dim + value_dim`` columns, a SiLU, the split into heads and, for
q and k, an l2norm over each head (q also scaled by ``d_k ** -0.5``).  Written
with ``jax.numpy`` (:func:`qkv_conv_jax`, the form every backend but a TPU
runs and the kernels' oracle) XLA makes a dozen bandwidth-bound passes of it a
layer: the slice that feeds the conv is a copy, the norm's square, sum,
broadcast and product are float32 arrays in HBM, and the backward is three
more fusions.  Here it is two Pallas kernels, each a single pass:

``gdn_qkv_conv_fwd``
    grid (B, column groups, time blocks).  A step reads the q, the k and the
    v columns of one group of heads IN PLACE from ``qkvz`` (three block specs
    over the same array, and three more of 16 rows for the ``K - 1`` rows
    before the time block: zeros before the sequence's start) and writes q,
    k and v once.
``gdn_qkv_conv_bwd``
    grid (B, column blocks of all of ``qkvz``'s columns, time blocks from the
    sequence's END).  A step reads one column block of dq, dk, dv or dz and,
    for the first three, the same columns of ``qkvz``; rebuilds the
    pre-activation, the SiLU and the norm in VMEM; writes that block of
    ``qkvz``'s cotangent (dz is passed through, so the WHOLE cotangent leaves
    in this one pass and XLA joins nothing) and adds to the ``[K, columns]``
    float32 weight gradient, resident over the time axis.  The conv's
    transpose needs the ``K - 1`` rows of the pre-activation's cotangent
    AFTER a block: they are carried in VMEM from the step before.

Same work, same precision as the ``jax.numpy`` form: taps summed in float32 in
its order, SiLU in float32, one cast to the input's dtype (the conv's
output), the norm in float32 from that value, one cast.  The backward rounds
the norm's cotangent to the input's dtype where autodiff does (the cotangent
of the conv's output), sums the taps' contributions to ``qkvz``'s cotangent in
float32 and rounds once (autodiff rounds each tap's: the kernel is no
coarser), and sums the weight gradient in float32.

The op returns z (the remaining columns, as they lie: ``[B, T, value_dim]``)
beside q, k, v only so that its cotangent comes back through the op.  With
``num_k_heads == 0`` it is a conv, a SiLU and a split alone.  ``INTERPRET``
runs the kernels in Pallas interpret mode (tests, CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False  # tests set True to exercise the kernels on the CPU

#: rows of a time block: the largest that divides T (the kernels take a T
#: that is a multiple of the smallest)
TIME_BLOCKS = (512, 256, 128)
#: rows before a time block that a step fetches beside it (a whole bf16 tile)
_HALO = 16
#: rows of the pre-activation's cotangent carried from the block after
_CARRY = 8
#: rows a pass of the forward's and of the backward's inner loop computes at
#: once (at most a time block).  Mosaic overlaps no pass with the next, so a
#: short pass is mostly its tail (the lane sums, the rsqrt): at the Qwen
#: cell's sizes 64 rows read 1.7 / 3.1 ms a call, 256 / 512 rows 1.2 / 2.7,
#: and a backward block walked in two passes 3.8 (my chip run, PR 36)
_SUB_ROWS = (256, 512)
#: columns of a step: the backward's block, the forward's q + k + v together
_STEP_COLUMNS = (512, 1024)


def _time_block(t: int):
    return next((n for n in TIME_BLOCKS if t % n == 0), None)


def dispatch(t: int, num_k_heads: int, num_v_heads: int, k_head_dim: int,
             v_head_dim: int, taps: int):
    """Who computes a call of these shapes here: ``None`` the ``jax.numpy``
    form, else the kernels, interpreted if ``True``.  On backend ``tpu`` the
    compiled kernels for heads of whole 128-lane tiles, a ``T`` of whole time
    blocks and a conv short enough for the rows a step carries; elsewhere
    the kernels only when a test sets ``INTERPRET``."""
    fits = (_time_block(t) is not None and 1 < taps <= _CARRY + 1
            and _plan(num_k_heads, num_v_heads, k_head_dim, v_head_dim)
            is not None)
    if jax.default_backend() == "tpu":
        return False if fits else None
    return True if INTERPRET and fits else None


# ---------------------------------------------------------------------------
# The jax.numpy form
# ---------------------------------------------------------------------------

def _l2norm(a, epsilon):
    af = a.astype(jnp.float32)
    return af * jax.lax.rsqrt(jnp.square(af).sum(-1, keepdims=True) + epsilon)


def qkv_conv_jax(qkvz, w, num_k_heads: int, num_v_heads: int,
                 k_head_dim: int, v_head_dim: int, epsilon: float):
    """The op in ``jax.numpy`` (autodiff gives its backward pass): what
    ``CausalConv1D(activation="silu")``, three slices and two l2norms
    compute.  Shapes as :func:`qkv_conv`."""
    b, t, _ = qkvz.shape
    key_dim, value_dim = num_k_heads * k_head_dim, num_v_heads * v_head_dim
    conv_dim = 2 * key_dim + value_dim
    taps = w.shape[0]
    xp = jnp.pad(qkvz[..., :conv_dim], ((0, 0), (taps - 1, 0), (0, 0)))
    # float32 inside the fusion: costs no traffic, saves K roundings
    y = sum(xp[:, j:j + t].astype(jnp.float32) * w[j] for j in range(taps))
    qkv = jax.nn.silu(y).astype(qkvz.dtype)
    q = qkv[..., :key_dim].reshape(b, t, num_k_heads, k_head_dim)
    k = qkv[..., key_dim:2 * key_dim].reshape(b, t, num_k_heads, k_head_dim)
    v = qkv[..., 2 * key_dim:].reshape(b, t, num_v_heads, v_head_dim)
    q = (_l2norm(q, epsilon) * k_head_dim ** -0.5).astype(qkvz.dtype)
    k = _l2norm(k, epsilon).astype(qkvz.dtype)
    return q, k, v, qkvz[..., conv_dim:]


# ---------------------------------------------------------------------------
# What both kernels compute for a sub-tile of rows and one head's lanes
# ---------------------------------------------------------------------------

def _window(x_ref, halo_ref, start, i, rows, lanes):
    """Rows ``[i rows - 16, (i + 1) rows)`` of a step's columns, float32:
    the sub-tile ``i`` of the time block and the 16 rows before it, which
    for the block's first sub-tile are the halo block's (zeros where
    ``start`` says the block is the sequence's first)."""
    f32 = jnp.float32
    r0 = pl.multiple_of(i * rows, rows)
    before = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO),
                            _HALO), lanes].astype(f32)
    halo = halo_ref[0, :, lanes].astype(f32)
    before = jnp.where(i == 0, jnp.where(start, 0.0, halo), before)
    return jnp.concatenate(
        [before, x_ref[0, pl.ds(r0, rows), lanes].astype(f32)], axis=0)


def _taps_of(xw, taps, rows):
    """The ``taps`` views of a window the conv multiplies: view ``j`` holds
    ``x[t - (taps - 1) + j]`` at row ``t`` of the sub-tile (a sublane
    rotation and an aligned slice each)."""
    return [(pltpu.roll(xw, taps - 1 - j, axis=0) if j < taps - 1 else xw)
            [_HALO:_HALO + rows] for j in range(taps)]


def _pre_activation(views, w):
    """``sum_j w[j] x[t - (K - 1) + j]`` in float32, in the order the
    ``jax.numpy`` form adds them."""
    pre = views[0] * w[0:1]
    for j in range(1, len(views)):
        pre = pre + views[j] * w[j:j + 1]
    return pre


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_part(x_ref, halo_ref, w_ref, out_ref, *, head: int, normalise: bool,
              scale: float, epsilon: float, taps: int, rows: int):
    """One part (q, k or v) of a step: its heads one by one, a head's rows
    ``rows`` at a time."""
    dt = out_ref.dtype
    subs = out_ref.shape[1] // rows
    start = pl.program_id(2) == 0

    def one_head(h, _):
        lanes = pl.ds(pl.multiple_of(h * head, 128), head)
        w = w_ref[:, lanes].astype(jnp.float32)

        def one(i, _):
            views = _taps_of(_window(x_ref, halo_ref, start, i, rows, lanes),
                             taps, rows)
            y = jax.nn.silu(_pre_activation(views, w)).astype(dt)
            if normalise:
                af = y.astype(jnp.float32)
                y = af * jax.lax.rsqrt(
                    jnp.sum(af * af, axis=-1, keepdims=True) + epsilon)
                y = (y * scale if scale != 1.0 else y).astype(dt)
            out_ref[0, pl.ds(pl.multiple_of(i * rows, rows), rows), lanes] = y

        jax.lax.fori_loop(0, subs, one, None)

    jax.lax.fori_loop(0, out_ref.shape[2] // head, one_head, None)


def _fwd_kernel(*refs, parts, epsilon: float, taps: int, rows: int):
    """Grid (B, groups of heads, time blocks).  ``refs``: a part's columns
    of ``qkvz``, the 16 rows before them and its taps, for every part; then
    every part's output."""
    n = len(parts)
    for p, (head, normalise, scale) in enumerate(parts):
        _fwd_part(*refs[3 * p:3 * p + 3], refs[3 * n + p], head=head,
                  normalise=normalise, scale=scale, epsilon=epsilon,
                  taps=taps, rows=rows)


def _plan(hk: int, hv: int, dk: int, dv: int):
    """How the columns are walked, or ``None`` if these heads cannot be:
    the forward's groups (a step takes ``key_dim / groups`` columns of q, as
    many of k and ``value_dim / groups`` of v: whole heads, each part's first
    column a multiple of its width) and the backward's column block (every
    part whole blocks, a block whole heads)."""
    key_dim, value_dim = hk * dk, hv * dv
    if dv % 128 or (hk and (dk % 128 or hv % hk)):
        return None
    groups = next((g for g in range(1, hv + 1)
                   if hv % g == 0 and hk % g == 0
                   and (2 * key_dim) % (value_dim // g) == 0
                   and (2 * key_dim + value_dim) // g <= _STEP_COLUMNS[1]),
                  None)
    block = next((c for c in (_STEP_COLUMNS[0], 256, 128)
                  if value_dim % c == 0 and key_dim % c == 0
                  and c % dv == 0 and c % (dk if hk else dv) == 0), None)
    return None if groups is None or block is None else (groups, block)


def _params(semantics, step_bytes: int):
    """Double-buffered blocks and the loop's temporaries, with room."""
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 8 * step_bytes))))


@functools.lru_cache(maxsize=64)
def _forward(b, t, hk, hv, dk, dv, taps, epsilon, dtype, interpret):
    """The forward ``pallas_call`` for these sizes: built once, so that the
    layers of a model, and its ``init``, ``predict`` and train-step programs,
    trace the kernel once between them."""
    key_dim, value_dim = hk * dk, hv * dv
    groups, _ = _plan(hk, hv, dk, dv)
    wq, wv = key_dim // groups, value_dim // groups
    rows = _time_block(t)
    # a part: its width, its first block in units of that width, its head,
    # whether it is normalised, its scale
    parts = [(wv, 2 * key_dim // wv, dv, False, 1.0)]
    if hk:
        parts = [(wq, 0, dk, True, dk ** -0.5),
                 (wq, groups, dk, True, 1.0)] + parts
    in_specs, out_specs = [], []
    for width, first, _, _, _ in parts:
        in_specs += [
            pl.BlockSpec((1, rows, width),
                         lambda i, g, s, first=first: (i, s, first + g)),
            pl.BlockSpec((1, _HALO, width), lambda i, g, s, first=first: (
                i, jnp.maximum(s * (rows // _HALO) - 1, 0), first + g)),
            pl.BlockSpec((taps, width),
                         lambda i, g, s, first=first: (0, first + g))]
        out_specs.append(
            pl.BlockSpec((1, rows, width), lambda i, g, s: (i, s, g)))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, parts=tuple(p[2:] for p in parts),
                          epsilon=epsilon, taps=taps,
                          rows=min(_SUB_ROWS[0], rows)),
        grid=(b, groups, t // rows), in_specs=in_specs, out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((b, t, p[0] * groups), dtype)
                   for p in parts],
        compiler_params=_params(
            ("parallel",) * 3,
            rows * (2 * wq + wv) * 2 * jnp.dtype(dtype).itemsize),
        interpret=interpret,
        name="gdn_qkv_conv_fwd",  # the op's name in HLO and in a profile
    )
    return lambda qkvz, w: call(*([qkvz, qkvz, w] * len(parts)))


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_block(cotangent, x_ref, halo_ref, w_ref, dx_ref, dw_ref, carry_ref,
               *, step, start, head: int, normalise: bool, scale,
               epsilon: float, taps: int, rows: int):
    """One column block of q, k or v: its heads one by one, a head's rows
    from the block's last sub-tile to its first, with the first rows of the
    sub-tile after (``after``: the pre-activation's cotangent there) in
    hand.  ``cotangent(at, lanes)``: the block's rows of dq, dk or dv;
    ``step``: the time block counted from the sequence's end; ``start``:
    whether it is the sequence's first."""
    dt = dx_ref.dtype
    f32 = jnp.float32
    subs = dx_ref.shape[1] // rows

    def one_head(h, _):
        lanes = pl.ds(pl.multiple_of(h * head, 128), head)
        w = w_ref[:, lanes].astype(f32)

        def one(n, state):
            after, sums = state
            i = subs - 1 - n
            at = pl.ds(pl.multiple_of(i * rows, rows), rows)
            views = _taps_of(_window(x_ref, halo_ref, start, i, rows, lanes),
                             taps, rows)
            pre = _pre_activation(views, w)
            sig = jax.nn.sigmoid(pre)
            g = cotangent(at, lanes).astype(f32)
            if normalise:
                # y -> n = af r, r = rsqrt(sum af^2 + eps); q also x scale
                af = (pre * sig).astype(dt).astype(f32)
                r = jax.lax.rsqrt(
                    jnp.sum(af * af, axis=-1, keepdims=True) + epsilon)
                dn = g * scale if scale != 1.0 else g
                inner = jnp.sum(dn * af, axis=-1, keepdims=True)
                g = (dn * r - af * (r * r * r * inner)).astype(dt).astype(f32)
            d_pre = g * (sig * (1.0 + pre * (1.0 - sig)))
            # the conv's transpose: dx[t] = sum_j w[j] d_pre[t + K - 1 - j]
            ext = jnp.concatenate([d_pre, after], axis=0)
            dx = d_pre * w[taps - 1:taps]
            for j in range(taps - 1):
                dx = dx + w[j:j + 1] * pltpu.roll(
                    ext, rows + _CARRY - (taps - 1 - j), axis=0)[:rows]
            dx_ref[0, at, lanes] = dx.astype(dt)
            sums = tuple(s + d_pre * v for s, v in zip(sums, views))
            return d_pre[:_CARRY], sums

        after = jnp.where(step == 0, 0.0, carry_ref[:, lanes])
        zeros = tuple(jnp.zeros((rows, head), f32) for _ in range(taps))
        after, sums = jax.lax.fori_loop(0, subs, one, (after, zeros))
        carry_ref[:, lanes] = after
        dw = jnp.concatenate(
            [jnp.sum(s, axis=0, keepdims=True) for s in sums], axis=0)
        dw_ref[0, :, lanes] = jnp.where(step == 0, dw,
                                        dw_ref[0, :, lanes] + dw)

    jax.lax.fori_loop(0, dx_ref.shape[2] // head, one_head, None)


def _bwd_kernel(dq_ref, dk_ref, dv_ref, dz_ref, x_ref, halo_ref, w_ref,
                dx_ref, dw_ref, carry_ref, *, blocks, heads, epsilon: float,
                taps: int, rows: int):
    """Grid (B, column blocks of ``qkvz``, time blocks from the end).
    ``blocks``: how many column blocks q (and k) and v (and z) are;
    ``carry_ref``: the pre-activation's cotangent at the first rows of the
    time block after."""
    nq, nv = blocks
    col, step = pl.program_id(1), pl.program_id(2)
    block = functools.partial(
        _bwd_block, x_ref=x_ref, halo_ref=halo_ref, w_ref=w_ref,
        dx_ref=dx_ref, dw_ref=dw_ref, carry_ref=carry_ref, step=step,
        start=step == pl.num_programs(2) - 1, epsilon=epsilon, taps=taps,
        rows=rows)

    if nq:
        @pl.when(col < nq)
        def _q():
            block(lambda at, lanes: dq_ref[0, at, lanes], head=heads[0],
                  normalise=True, scale=heads[0] ** -0.5)

        @pl.when((col >= nq) & (col < 2 * nq))
        def _k():
            block(lambda at, lanes: dk_ref[0, at, lanes], head=heads[0],
                  normalise=True, scale=1.0)

    @pl.when((col >= 2 * nq) & (col < 2 * nq + nv))
    def _v():
        block(lambda at, lanes: dv_ref[0, at, lanes], head=heads[1],
              normalise=False, scale=1.0)

    @pl.when(col >= 2 * nq + nv)
    def _z():
        dx_ref[...] = dz_ref[...]
        dw_ref[...] = jnp.zeros_like(dw_ref)


@functools.lru_cache(maxsize=64)
def _backward(b, t, hk, hv, dk, dv, taps, epsilon, dtype, interpret):
    """The backward ``pallas_call`` for these sizes, built once."""
    key_dim, value_dim = hk * dk, hv * dv
    _, block = _plan(hk, hv, dk, dv)
    nq, nv = key_dim // block, value_dim // block
    conv_blocks = 2 * nq + nv
    rows = _time_block(t)
    steps = t // rows

    def cotangent(first, count):
        """A part's cotangent: its own block while the grid is inside the
        part, else one block that does not move (nothing is fetched)."""
        if not count:
            return pl.BlockSpec((1, rows, block), lambda i, c, s: (0, 0, 0))

        def index(i, c, s):
            inside = (c >= first) & (c < first + count)
            return (i, jnp.where(inside, steps - 1 - s, 0),
                    jnp.where(inside, c - first, 0))
        return pl.BlockSpec((1, rows, block), index)

    def column(c):
        return jnp.minimum(c, conv_blocks - 1)

    def time(c, s):  # the z blocks read no x: theirs does not move
        return jnp.where(c < conv_blocks, steps - 1 - s, 0)

    return pl.pallas_call(
        functools.partial(_bwd_kernel, blocks=(nq, nv), heads=(dk, dv),
                          epsilon=epsilon, taps=taps,
                          rows=min(_SUB_ROWS[1], rows)),
        grid=(b, conv_blocks + nv, steps),
        in_specs=[
            cotangent(0, nq), cotangent(nq, nq), cotangent(2 * nq, nv),
            cotangent(conv_blocks, nv),
            pl.BlockSpec((1, rows, block),
                         lambda i, c, s: (i, time(c, s), column(c))),
            pl.BlockSpec((1, _HALO, block), lambda i, c, s: (
                i, jnp.maximum(time(c, s) * (rows // _HALO) - 1, 0),
                column(c))),
            pl.BlockSpec((taps, block), lambda i, c, s: (0, column(c)))],
        out_specs=[
            pl.BlockSpec((1, rows, block),
                         lambda i, c, s: (i, steps - 1 - s, c)),
            pl.BlockSpec((1, taps, block), lambda i, c, s: (i, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, 2 * key_dim + 2 * value_dim), dtype),
            jax.ShapeDtypeStruct((b, taps, 2 * key_dim + 2 * value_dim),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_CARRY, block), jnp.float32)],
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary"),
            rows * block * 3 * jnp.dtype(dtype).itemsize),
        interpret=interpret,
        name="gdn_qkv_conv_bwd",
    )


# ---------------------------------------------------------------------------
# The differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _kernels(qkvz, w, hk, hv, dk, dv, epsilon, interpret):
    b, t, _ = qkvz.shape
    out = _forward(b, t, hk, hv, dk, dv, w.shape[0], epsilon, qkvz.dtype,
                   interpret)(qkvz, w)
    v = out[-1].reshape(b, t, hv, dv)
    if hk:
        q, k = (a.reshape(b, t, hk, dk) for a in out[:2])
    else:
        q = k = jnp.zeros((b, t, 0, dk), qkvz.dtype)
    return q, k, v, qkvz[..., 2 * hk * dk + hv * dv:]


def _vjp_fwd(qkvz, w, hk, hv, dk, dv, epsilon, interpret):
    out = _kernels(qkvz, w, hk, hv, dk, dv, epsilon, interpret)
    return out, (qkvz, w)  # what the block's recomputation makes anyway


def _vjp_bwd(hk, hv, dk, dv, epsilon, interpret, res, cotangents):
    qkvz, w = res
    b, t, _ = qkvz.shape
    dq, dk_, dv_ = (a.reshape(b, t, -1) for a in cotangents[:3])
    dz = cotangents[3]
    if not hk:  # stand-ins of one block for cotangents that do not exist
        dq = dk_ = jnp.zeros((1, _time_block(t), _plan(hk, hv, dk, dv)[1]),
                             qkvz.dtype)
    dx, dw = _backward(b, t, hk, hv, dk, dv, w.shape[0], epsilon, qkvz.dtype,
                       interpret)(dq, dk_, dv_, dz, qkvz, qkvz, w)
    return dx, dw[:, :, :w.shape[1]].sum(0).astype(w.dtype)


_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def qkv_conv(qkvz, w, num_k_heads: int, num_v_heads: int, k_head_dim: int,
             v_head_dim: int, epsilon: float = 1e-6):
    """``qkvz`` ``[B, T, 2 H_k d_k + 2 H_v d_v]`` (q | k | v | z columns) and
    the depthwise conv's weight ``w`` ``[K, 2 H_k d_k + H_v d_v]`` ->
    ``(q, k [B, T, H_k, d_k], v [B, T, H_v, d_v], z [B, T, H_v d_v])`` in
    ``qkvz``'s dtype: q, k, v after the causal conv (zeros before the
    sequence's start) and a SiLU, q and k l2-normalised over the head and q
    scaled by ``d_k ** -0.5``; z the last columns as they came.  One
    algorithm, two implementations, chosen by :func:`dispatch` from what the
    call can see."""
    interpret = dispatch(qkvz.shape[1], num_k_heads, num_v_heads, k_head_dim,
                         v_head_dim, w.shape[0])
    if interpret is None:
        return qkv_conv_jax(qkvz, w, num_k_heads, num_v_heads, k_head_dim,
                            v_head_dim, epsilon)
    return _kernels(qkvz, w, num_k_heads, num_v_heads, k_head_dim,
                    v_head_dim, float(epsilon), interpret)
