"""The chunked gated delta rule as two Pallas TPU kernels.

``nn/linear_attention.py`` holds the algorithm and its ``jax.numpy`` form:
inside a chunk of ``C`` positions every write solves one unit-lower-
triangular system, and only the ``[d_k, d_v]`` state is carried from chunk
to chunk.  Written with ``jax.numpy`` every ``[C, C]`` term of every chunk
is an HBM array that a dozen fusions pass over.  Here a (batch row, key
head) pair is one row of the grid and its sequence is walked in order by
the grid's last (sequential) axis, in tiles of 128 rows — two chunks of 64,
whose ``[C, C]`` terms are the diagonal blocks of one ``[128, 128]`` array:
whole MXU tiles and whole vector registers.  A tile's terms — the
cumulative log-decay, ``decay``, ``A``, ``(I + A)^-1``, ``u``, ``w``,
``v_new`` — and the float32 states of the key head's value heads exist only
in VMEM.  HBM sees q, k, v, g and beta once, ``o`` and the final state once
and, when a gradient is wanted, what the backward pass reads: the bf16
state at every chunk's start (which the ``lax.scan`` of the ``jax.numpy``
form saves too) and every chunk's float32 inverse (which that form keeps
under the name ``gdn_inverse``).

The backward kernel (``gated_delta_rule_bwd``) walks the tiles in reverse
with the states' cotangents in VMEM, rebuilds a tile's terms from q, k, v,
g, beta, the saved chunk-start states and the saved inverse, and writes the
gradients of all five inputs and of the initial state.  Value head ``h``
reads key head ``h // (H_v / H_k)``: a grid row walks the value heads of
its key head, so q and k are never repeated in HBM and their gradients
leave the kernel summed.

Same work, same precision as the ``jax.numpy`` form: matmul operands are
cast to the inputs' dtype (bf16 on the MXU) exactly where that form casts
them, sums and the state are float32, and the inverse is float32 with
full-precision (``Precision.HIGHEST``) products.  The inverse is blocked:
the diagonal ``_INVERSE_BLOCK`` x ``_INVERSE_BLOCK`` blocks by the Neumann
doubling ``(I - A)(I + A^2)(I + A^4)...`` (exact: ``A`` is nilpotent), all
blocks of a tile side by side in the lanes of one product, then pairs of
blocks merged by ``[[L, 0], [B, R]]^-1 = [[L^-1, 0], [-R^-1 B L^-1, R^-1]]``
until a block is a chunk: the same inverse as the whole chunk's Neumann
series in fewer, fuller products.

``INTERPRET`` runs the kernels in Pallas interpret mode (tests, CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False  # tests set True to exercise the kernels on the CPU

#: the chunk the kernels were compiled, measured and tuned for on the chip
KERNEL_CHUNK = 64
#: side of the diagonal blocks the inverse starts from (see module docstring)
_INVERSE_BLOCK = 16
#: tiles a grid step walks (the largest that divides the sequence's tiles)
_TILES_PER_STEP = (4, 2, 1)

_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def dispatch(dk: int, dv: int, chunk: int):
    """Who computes a call of these shapes here: ``None`` the ``jax.numpy``
    form, else the kernels, interpreted if ``True``.  On backend ``tpu`` the
    compiled kernels (or the compiler's error) for heads of whole 128-lane
    tiles and the chunk they were written for; elsewhere the kernels only
    when a test sets ``INTERPRET``."""
    if jax.default_backend() == "tpu":
        fits = dk % 128 == 0 and dv % 128 == 0 and chunk == KERNEL_CHUNK
        return False if fits else None
    return True if INTERPRET else None


# ---------------------------------------------------------------------------
# A tile's terms (values in VMEM / registers; shared by both kernels)
# ---------------------------------------------------------------------------

def _block_of(s, n):
    """``[s, n]``: which diagonal block of side ``s`` a lane belongs to."""
    if s == n:
        return jnp.zeros((s, n), jnp.int32)
    return jax.lax.shift_right_logical(
        _iota((s, n), 1), np.int32(s.bit_length() - 1))


def _spread(p):
    """Packed ``[s, n]`` (the ``n / s`` diagonal blocks of side ``s`` side
    by side, block ``b`` in lanes ``[b s, (b + 1) s)``) -> block diagonal
    ``[n, n]``.  Masks and a sublane concatenation: nothing crosses lanes."""
    s, n = p.shape
    if s == n:
        return p
    blk = _block_of(s, n)
    return jnp.concatenate(
        [jnp.where(blk == b, p, 0.0) for b in range(n // s)], axis=0)


def _unit_lower_inverse(a, chunk, block):
    """``(I + a)^-1``, packed ``[chunk, n]``, for a float32 ``[n, n]`` that
    is block diagonal in chunks of ``chunk`` and strictly lower triangular
    inside each.

    A packed left operand against a block-diagonal right operand multiplies
    every block by its own partner in one product of ``s`` rows; only masks
    and sublane-aligned row slices move data."""
    n = a.shape[0]
    doublings = chunk // block if chunk % block == 0 else 0
    s = block if doublings > 1 and doublings & (doublings - 1) == 0 else chunk
    hi = functools.partial(_dot, precision=_HIGHEST)
    blk = _block_of(s, n)
    p = sum(jnp.where(blk == b, a[b * s:(b + 1) * s, :], 0.0)
            for b in range(n // s))
    r = jnp.where(_iota((s, n), 1) - blk * s == _iota((s, n), 0), 1.0, 0.0) - p
    squarings = max(0, (s - 1).bit_length() - 1)
    if squarings:
        # r (I + p^2)(I + p^4)...: each product of the loop squares p and
        # multiplies r by the previous square in one pass ([p; r] stacked)
        p = hi(p, _spread(p))
        for _ in range(squarings - 1):
            both = hi(jnp.concatenate([p, r], axis=0), _spread(p))
            p, r = both[:s], r + both[s:]
        r = r + hi(r, _spread(p))
    while s < chunk:  # merge pairs of blocks: [[L, 0], [B, R]]
        blk = _block_of(s, n)
        even = blk & 1 == 0
        below = sum(jnp.where(blk == b, a[(b + 1) * s:(b + 2) * s, :], 0.0)
                    for b in range(0, n // s, 2))            # the B of a pair
        t = hi(below, _spread(r))                            # B L^-1
        rows = [jnp.where(blk == b - 1, t, 0.0) if b % 2 else jnp.zeros_like(t)
                for b in range(n // s)]
        u = hi(jnp.where(even, 0.0, r), jnp.concatenate(rows, axis=0))
        r = jnp.concatenate([jnp.where(even, r, 0.0),
                             jnp.where(even, 0.0, r) - u], axis=0)
        s *= 2
    return r


def _tile_terms(q, k, v, g_row, b_row, chunk, inverse=None):
    """What a tile of ``R`` rows — ``R / chunk`` whole chunks — needs before
    it meets the state: every ``[C, C]`` term as the diagonal blocks of one
    ``[R, R]`` array (at ``R`` = 128 a whole MXU tile and whole vector
    registers, where a 64-wide chunk alone fills a quarter and a half).
    q, k ``[R, d_k]`` and v ``[R, d_v]`` in the inputs' dtype, g_row and
    b_row ``[1, R]`` float32 (lane-major, as HBM holds them); per-position
    factors come out as ``[R, 1]`` columns.  ``inverse``: the packed
    ``(I + a)^-1`` if the forward pass kept it."""
    r, dt = q.shape[0], v.dtype
    f32 = jnp.float32
    ii, jj = _iota((r, r), 0), _iota((r, r), 1)
    eye = ii == jj
    if r > chunk:
        shift = np.int32(chunk.bit_length() - 1)
        same = (jax.lax.shift_right_logical(ii, shift)
                == jax.lax.shift_right_logical(jj, shift))
    else:
        same = jnp.ones((r, r), bool)
    lower, strict = same & (ii >= jj), same & (ii > jj)

    def column(row):  # [1, R] -> [R, 1] without a transpose
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    gam = jnp.sum(jnp.where(lower, g_row, 0.0), axis=1, keepdims=True)
    gam_row = jnp.sum(jnp.where(same & (ii <= jj), column(g_row), 0.0),
                      axis=0, keepdims=True)
    gam_last = jnp.sum(jnp.where(same, g_row, 0.0), axis=1, keepdims=True)
    beta = column(b_row)
    # masked before the exp: above the diagonal the difference is positive
    # and can overflow
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gam - gam_row, 0.0)),
                      0.0)
    e_gam, e_tail = jnp.exp(gam), jnp.exp(gam_last - gam)

    kf, qf, vf = k.astype(f32), q.astype(f32), v.astype(f32)
    kb = (kf * beta).astype(dt)
    a = jnp.where(strict, _dot(kb, k, _NT) * decay, 0.0)     # [R, R]
    if inverse is None:
        inverse = _unit_lower_inverse(a, chunk, _INVERSE_BLOCK)
    inv = _spread(inverse)
    solve = inv.astype(dt)
    vb = (vf * beta).astype(dt)
    kbg = (kb.astype(f32) * e_gam).astype(dt)
    y = _dot(q, k, _NT)
    return dict(
        same=same, lower=lower, strict=strict, eye=eye, beta=beta,
        decay=decay, e_gam=e_gam, e_tail=e_tail, e_last=jnp.exp(gam_last),
        kf=kf, qf=qf, vf=vf, kb=kb, a=a, inv=inv, packed=inverse,
        solve=solve, vb=vb, kbg=kbg, u=_dot(solve, vb),
        w=_dot(solve, kbg).astype(dt), y=y, k_tail=(kf * e_tail).astype(dt),
        qk=(y * decay).astype(dt), q_in=(qf * e_gam).astype(dt))


def tile_rows(chunk: int) -> int:
    """Rows of a tile: as many whole chunks as fill 128 rows.  The kernels
    take a T that is a multiple of it."""
    fits = chunk < 128 and 128 % chunk == 0
    return 128 if fits else chunk


# ---------------------------------------------------------------------------
# The grid both kernels walk
# ---------------------------------------------------------------------------

def _plan(q, k, v, g, beta, chunk):
    """[B, T, H, d] -> the views the kernels index (heads folded into the
    lanes, which is free; g and beta as ``[B, H_v, tiles, R]``, two small
    transposes) and the grid's sizes.  One row of the grid is a batch row
    and a KEY head: the ``rep`` value heads that read it are walked inside
    the kernel, so q and k are fetched once for them and their gradients
    leave the backward kernel already summed."""
    b, t, hk, dk = k.shape
    hv, dv = v.shape[2:]
    tile = tile_rows(chunk)
    tiles = t // tile
    per_step = next(m for m in _TILES_PER_STEP if tiles % m == 0)
    rows = lambda a: jnp.moveaxis(a, 1, 2).reshape(b, hv, tiles, tile)
    views = (q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
             v.reshape(b, t, hv * dv), rows(g), rows(beta))
    return views, dict(b=b, t=t, hk=hk, hv=hv, rep=hv // hk, dk=dk, dv=dv,
                       n=t // chunk, tile=tile, tiles=tiles, chunk=chunk,
                       per_step=per_step, steps=tiles // per_step)


def _specs(d, backward: bool):
    """Block specs over the grid (B, H_k, steps): ``seq(width)`` a step's
    rows of a ``[B, T, heads * width]`` view, ``group(*tail)`` something a
    value head has once a sequence, ``walk(count, *tail)`` something it has
    ``count`` of a step.  The backward kernel walks the steps from the
    sequence's end."""
    m, tile, rep, steps = d["per_step"], d["tile"], d["rep"], d["steps"]
    at = (lambda s: steps - 1 - s) if backward else (lambda s: s)
    seq = lambda width: pl.BlockSpec(
        (1, m * tile, width), lambda i, h, s: (i, at(s), h))
    group = lambda *tail: pl.BlockSpec(
        (1, rep) + tail, lambda i, h, s: (i, h) + (0,) * len(tail))
    walk = lambda count, *tail: pl.BlockSpec(
        (1, rep, count) + tail,
        lambda i, h, s: (i, h, at(s)) + (0,) * len(tail))
    return seq, group, walk


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, sn_ref,
                *rest, chunk: int, tile: int, per_step: int, rep: int):
    """Grid (B, H_k, steps), the last axis sequential: ``s_scr`` carries
    the states of the key head's ``rep`` value heads across a sequence's
    chunks."""
    states_ref, inv_ref = rest[:2] if len(rest) == 3 else (None, None)
    s_scr = rest[-1]
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _start():
        s_scr[...] = s0_ref[0]

    dt = v_ref.dtype
    chunks = tile // chunk
    dv = v_ref.shape[-1] // rep

    def one_tile(j, _):
        rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
        n = step * per_step + j
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        for r in range(rep):
            lanes = slice(r * dv, (r + 1) * dv)
            t = _tile_terms(q, k, v_ref[0, rows, lanes],
                            g_ref[0, r, pl.ds(n, 1), :],
                            b_ref[0, r, pl.ds(n, 1), :], chunk)
            if inv_ref is not None:
                inv_ref[0, r, j] = t["packed"]
            v_new, from_state = [], []
            for c in range(chunks):
                part = slice(c * chunk, (c + 1) * chunk)
                s = s_scr[r]
                s_in = s.astype(dt)
                if states_ref is not None:
                    states_ref[0, r, j * chunks + c] = s_in
                v_new.append(
                    (t["u"][part] - _dot(t["w"][part], s_in)).astype(dt))
                from_state.append(_dot(t["q_in"][part], s_in))
                s_scr[r] = (s * t["e_last"][c * chunk:c * chunk + 1]
                            + _dot(t["k_tail"][part], v_new[-1], _TN))
            o = (jnp.concatenate(from_state, axis=0)
                 + _dot(t["qk"], jnp.concatenate(v_new, axis=0)))
            o_ref[0, rows, lanes] = o.astype(o_ref.dtype)

    # a loop, not an unrolled body: the kernel is traced and lowered once
    # a tile, and every jit of a model with L layers lowers 2 L kernels
    jax.lax.fori_loop(0, per_step, one_tile, None)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        sn_ref[0] = s_scr[...]


@functools.lru_cache(maxsize=64)
def _forward(sizes, dtype, for_gradient: bool, interpret: bool):
    """The forward ``pallas_call`` for these sizes: built once, so that the
    layers of a model — and its ``init``, ``predict`` and train-step
    programs — trace the kernel once between them."""
    d = dict(sizes)
    b, t, hv, rep, dk, dv, m = (d[x] for x in (
        "b", "t", "hv", "rep", "dk", "dv", "per_step"))
    chunk, tile, tiles = d["chunk"], d["tile"], d["tiles"]
    seq, group, walk = _specs(d, backward=False)
    out_specs = [seq(rep * dv), group(dk, dv)]
    out_shape = [jax.ShapeDtypeStruct((b, t, hv * dv), dtype),
                 jax.ShapeDtypeStruct((b, hv, dk, dv), jnp.float32)]
    if for_gradient:
        out_specs += [walk(d["n"] // d["steps"], dk, dv),
                      walk(m, chunk, tile)]
        out_shape += [
            jax.ShapeDtypeStruct((b, hv, d["n"], dk, dv), dtype),
            jax.ShapeDtypeStruct((b, hv, tiles, chunk, tile), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, tile=tile, per_step=m,
                          rep=rep),
        grid=(b, d["hk"], d["steps"]),
        in_specs=[seq(dk), seq(dk), seq(rep * dv), group(tiles, tile),
                  group(tiles, tile), group(dk, dv)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), jnp.float32)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="gated_delta_rule_fwd",  # the op's name in HLO and in a profile
    )


def _fwd_call(q, k, v, g, beta, s0, chunk, for_gradient, interpret):
    """q, k ``[B, T, H_k, d_k]``, v ``[B, T, H_v, d_v]``, g and beta
    ``[B, T, H_v]`` and s0 ``[B, H_v, d_k, d_v]`` float32; T a multiple of
    ``tile_rows(chunk)``.  Returns o, the final state and, ``for_gradient``,
    what the backward pass reads: the state at every chunk's start (``[B,
    H_v, N, d_k, d_v]`` in v's dtype) and every tile's packed inverse
    (``[B, H_v, tiles, chunk, R]`` float32)."""
    views, d = _plan(q, k, v, g, beta, chunk)
    out = _forward(tuple(d.items()), v.dtype, for_gradient, interpret)(
        *views, s0)
    return (out[0].reshape(v.shape), out[1]) + tuple(out[2:])


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, inv_ref,
                do_ref, dsn_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                ds0_ref, ds_scr, *, chunk: int, tile: int, per_step: int,
                rep: int):
    """The forward's grid with the sequence walked from its end: block
    indices are reversed by the index maps, ``ds_scr`` carries the states'
    cotangents.  A tile's terms are rebuilt, all but the inverse: that and
    the chunk-start states were saved."""
    step = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(step == 0)
    def _start():
        ds_scr[...] = dsn_ref[0]

    dt = v_ref.dtype
    f32 = jnp.float32
    chunks = tile // chunk
    dv = v_ref.shape[-1] // rep
    hi = functools.partial(_dot, precision=_HIGHEST)
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)
    stack = lambda parts: jnp.concatenate(parts, axis=0)
    parts = [slice(c * chunk, (c + 1) * chunk) for c in range(chunks)]
    def one_tile(i, _):
        j = per_step - 1 - i  # the step's tiles from its last
        rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
        n = (last - step) * per_step + j
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        d_q = d_k = 0.0
        for r in range(rep):
            lanes = slice(r * dv, (r + 1) * dv)
            t = _tile_terms(q, k, v_ref[0, rows, lanes],
                            g_ref[0, r, pl.ds(n, 1), :],
                            b_ref[0, r, pl.ds(n, 1), :], chunk,
                            inv_ref[0, r, j])
            decay, e_gam, e_tail, beta = (
                t[x] for x in ("decay", "e_gam", "e_tail", "beta"))
            do = do_ref[0, rows, lanes]
            s_in = [states_ref[0, r, j * chunks + c] for c in range(chunks)]
            v_new = stack([(t["u"][p] - _dot(t["w"][p], s)).astype(dt)
                           for p, s in zip(parts, s_in)])
            # o = q_in s_in + qk v_new;  s' = s e_last + k_tail^T v_new;
            # v_new = u - w s_in: the chunks from the tile's last, ds carried
            d_v_new = _dot(t["qk"], do, _TN)                 # [R, d_v]
            d_v_new_c, d_k_tail, d_w, d_last = (
                [None] * chunks for _ in range(4))
            for c in reversed(range(chunks)):
                p, s = parts[c], s_in[c]
                ds = ds_scr[r]
                ds_b = ds.astype(dt)
                e_last = t["e_last"][c * chunk:c * chunk + 1]
                d_k_tail[c] = _dot(v_new[p], ds_b, _NT)      # [C, d_k]
                d_v_new_c[c] = (d_v_new[p]
                                + _dot(t["k_tail"][p], ds_b)).astype(dt)
                d_w[c] = (-_dot(d_v_new_c[c], s, _NT)).astype(dt)
                d_last[c] = jnp.sum(ds * s.astype(f32),
                                    keepdims=True) * e_last
                ds_scr[r] = (ds * e_last + _dot(t["q_in"][p], do[p], _TN)
                             - _dot(t["w"][p], d_v_new_c[c], _TN))
            d_q_in = stack([_dot(do[p], s, _NT)
                            for p, s in zip(parts, s_in)])
            d_v_new, d_k_tail, d_w = (stack(x) for x in
                                      (d_v_new_c, d_k_tail, d_w))
            # [u | w] = solve [vb | kbg]
            d_qk = _dot(do, v_new, _NT)                      # [R, R]
            d_solve = (_dot(d_v_new, t["vb"], _NT)
                       + _dot(d_w, t["kbg"], _NT))
            d_vb = _dot(t["solve"], d_v_new, _TN)            # [R, d_v]
            d_kbg = _dot(t["solve"], d_w, _TN)               # [R, d_k]
            # solve = (I + a)^-1: da = -inv^T dsolve inv^T, full precision
            d_a = jnp.where(
                t["strict"],
                -hi(hi(t["inv"], d_solve, _TN), t["inv"], _NT), 0.0)
            d_x = (d_a * decay).astype(dt)                   # a = x * decay
            d_y = (d_qk * decay).astype(dt)                  # qk = y * decay
            d_kb = d_kbg * e_gam + _dot(d_x, k)
            d_q = d_q + d_q_in * e_gam + _dot(d_y, k)
            d_k = (d_k + d_k_tail * e_tail + d_kb * beta
                   + _dot(d_x, t["kb"], _TN) + _dot(d_y, q, _TN))
            dv_ref[0, rows, lanes] = (d_vb * beta).astype(dv_ref.dtype)

            # the log-decay.  Through the cumulative sum inside a chunk
            # (decay: rows gain, columns lose; q_in; kbg; k_tail) to every
            # position up to it; through a chunk's total (k_tail, the
            # state's own forgetting) to every position of the chunk
            e = d_a * t["a"] + d_qk * (t["y"] * decay)
            k_tail_sum = rowsum(d_k_tail * t["kf"] * e_tail)
            d_gam = (rowsum(e)
                     - rowsum(jnp.where(
                         t["eye"], jnp.sum(e, axis=0, keepdims=True), 0.0))
                     + rowsum(d_q_in * t["qf"] * e_gam)
                     + rowsum(d_kbg * t["kb"].astype(f32) * e_gam)
                     - k_tail_sum)
            first = _iota((tile, 1), 0)
            d_total = k_tail_sum + sum(
                jnp.where(first == c * chunk, d_last[c], 0.0)
                for c in range(chunks))
            dg_ref[0, r, pl.ds(n, 1), :] = jnp.sum(
                jnp.where(t["lower"], d_gam, 0.0)
                + jnp.where(t["same"], d_total, 0.0), axis=0, keepdims=True)
            d_beta = rowsum(d_kb * t["kf"]) + rowsum(d_vb * t["vf"])
            db_ref[0, r, pl.ds(n, 1), :] = jnp.sum(
                jnp.where(t["eye"], d_beta, 0.0), axis=0, keepdims=True)
        dq_ref[0, rows, :] = d_q.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = d_k.astype(dk_ref.dtype)

    jax.lax.fori_loop(0, per_step, one_tile, None)

    @pl.when(step == last)
    def _finish():
        ds0_ref[0] = ds_scr[...]


@functools.lru_cache(maxsize=64)
def _backward(sizes, dtype, interpret: bool):
    """The backward ``pallas_call`` for these sizes, built once."""
    d = dict(sizes)
    b, t, hk, hv, rep, dk, dv, m = (d[x] for x in (
        "b", "t", "hk", "hv", "rep", "dk", "dv", "per_step"))
    chunk, tile, tiles = d["chunk"], d["tile"], d["tiles"]
    seq, group, walk = _specs(d, backward=True)
    gates = jax.ShapeDtypeStruct((b, hv, tiles, tile), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, tile=tile, per_step=m,
                          rep=rep),
        grid=(b, hk, d["steps"]),
        in_specs=[seq(dk), seq(dk), seq(rep * dv), group(tiles, tile),
                  group(tiles, tile), walk(d["n"] // d["steps"], dk, dv),
                  walk(m, chunk, tile), seq(rep * dv), group(dk, dv)],
        out_specs=[seq(dk), seq(dk), seq(rep * dv), group(tiles, tile),
                   group(tiles, tile), group(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((b, t, hk * dk), dtype),
                   jax.ShapeDtypeStruct((b, t, hk * dk), dtype),
                   jax.ShapeDtypeStruct((b, t, hv * dv), dtype),
                   gates, gates,
                   jax.ShapeDtypeStruct((b, hv, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), jnp.float32)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="gated_delta_rule_bwd",
    )


def _bwd_call(q, k, v, g, beta, states, inverse, do, dsn, chunk, interpret):
    """Gradients of q, k, v, g, beta and the initial state."""
    views, d = _plan(q, k, v, g, beta, chunk)
    b, t, hv = d["b"], d["t"], d["hv"]
    dq, dk, dv, dg, db, ds0 = _backward(
        tuple(d.items()), v.dtype, interpret)(
        *views, states, inverse, do.reshape(b, t, -1), dsn)
    times = lambda a: jnp.moveaxis(a.reshape(b, hv, t), 1, 2)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            times(dg), times(db), ds0)


# ---------------------------------------------------------------------------
# The differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunk_kernels(q, k, v, g, beta, s0, chunk, interpret=False):
    """``(o, final state)`` by the kernels; shapes as :func:`_fwd_call`."""
    return _fwd_call(q, k, v, g, beta, s0, chunk, False, interpret)


def _vjp_fwd(q, k, v, g, beta, s0, chunk, interpret):
    o, sn, states, inverse = _fwd_call(q, k, v, g, beta, s0, chunk, True,
                                       interpret)
    # named, so that an enclosing jax.checkpoint can be told to keep them
    # (nn.Remat(save_names=...)): with all three kept, the recomputation of
    # a block runs no kernel
    o = checkpoint_name(o, "gated_delta_rule_out")
    states = checkpoint_name(states, "gated_delta_rule_states")
    inverse = checkpoint_name(inverse, "gated_delta_rule_inverse")
    return (o, sn), (q, k, v, g, beta, states, inverse)


def _vjp_bwd(chunk, interpret, res, cotangents):
    do, dsn = cotangents
    return _bwd_call(*res, do, dsn, chunk, interpret)


chunk_kernels.defvjp(_vjp_fwd, _vjp_bwd)
