"""The chunked Mamba-2 scan as two Pallas TPU kernels.

``nn/state_space.py`` holds the algorithm and its ``jax.numpy`` form: inside
a chunk of ``Q`` positions a head's output is one masked ``[Q, Q]`` product,
``((C B^T) * L) (dt * X)``, plus what the state carried into the chunk
answers; only the ``[P, N]`` state is carried from chunk to chunk.  Written
with ``jax.numpy`` the decay ``L`` and ``(C B^T) * L`` of every chunk and
head are float32 HBM arrays that several fusions pass over, and the carry
is a ``lax.scan``.  Here the grid is ``(batch row, chunk)``, the chunks in
order: a step holds the chunk's ``x`` for ALL heads (``[Q, H * P]``, heads
side by side in the lanes), its ``B`` and ``C`` once, and walks the heads a
128-lane tile at a time (two heads of 64, one of 128 or wider) in a
``lax.fori_loop``, so the body is traced and lowered for one pass whatever
the number of heads.  ``C B^T`` is ONE ``[Q, Q]`` product a chunk and
group, kept in VMEM for every head of the group; the float32 states of all
heads live in VMEM scratch from the sequence's first chunk to its last,
held transposed (``[N, H * P]``) so that reading and writing them are
full-width products for a whole lane tile.  The ``[Q, Q]`` terms are built
by blocks of 128 rows and only up to the diagonal block: a quarter of a
chunk of 256 is never computed.  In VMEM only: ``C B^T``, each head's
masked ``exp(c_t - c_s)``, ``M = ((C B^T) * L)`` cast to the inputs'
dtype, ``M (x * dt)``, the state's answer ``exp(c_t) C_t S_in``, the skip
``D x`` and the states.  HBM sees ``x``, ``dt``, the chunk's running log
decay ``c_t`` (``[B, T, H]`` float32, a cumulative sum XLA takes outside:
autodiff then carries ``dt`` and ``A_log`` through it), ``B`` and ``C``
once, ``y``, the final state and the largest carried ``|S|`` once and,
when a gradient is wanted, the float32 state at every chunk's start
(``mamba2_ssd_states``), which the backward pass reads.

A head's per-position scalars (``dt``, ``c_t``) reach the kernels twice:
as columns of a ``[Q, H]`` block (a lane tile's heads are rotated to lanes
0, 1, ... and spread over their own lanes) and, for ``c_t``, as rows of a
``[H, Q]`` block, so that ``c_t - c_s`` is a column less a row and nothing
is transposed in the kernel.

The backward kernel (``mamba2_ssd_bwd``) walks the chunks from the
sequence's end with the states' cotangents in VMEM, rebuilds a chunk's
terms from ``x``, ``dt``, ``c_t``, ``B``, ``C`` and the saved chunk-start
state, and writes ``dx``, ``ddt``, the cotangent of ``c_t`` (through which
XLA's transposed cumulative sum reaches ``dt`` again and ``A_log``), ``dB``
and ``dC`` summed over the group's heads, ``dD`` summed over a chunk's
positions and ``ds0``.  Its per-position sums over a head's lanes leave
the kernel as ROWS (``[H, Q]``): they are taken on the MXU against a 0 / 1
matrix with the float32 operand in three bf16 parts (exact to float32),
because a lane reduction on the vector unit leaves a column a head, and
every column then costs a lane broadcast to be stored.

Same work, same precision as the ``jax.numpy`` form: every exponent is a
difference of float32 sums taken, and masked, before the ``exp``; matmul
operands are cast to the inputs' dtype exactly where that form casts them
(``m``, ``x * dt``, ``x`` decayed to the chunk's end, the incoming state)
and cotangents where autodiff's transposed products round them; sums and
the state are float32.

``INTERPRET`` runs the kernels in Pallas interpret mode (tests, CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False  # tests set True to exercise the kernels on the CPU

_LANES = 128
#: lane tiles a pass of the head loop takes (the first that divides a group's)
_TILES_A_PASS = (2, 1)
#: what the decay's exponent reads above the diagonal: exp gives 0.0
_MASKED = -1e30

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def fits(h: int, g: int, p: int, n: int, chunk: int) -> bool:
    """Shapes the kernels take: a head is a divisor or a multiple of the
    128 lanes, a group's heads side by side and the state's width are whole
    128-lane tiles, the chunk a multiple of 128."""
    if h % g or n % _LANES or chunk % _LANES:
        return False
    if _LANES % p and p % _LANES:
        return False
    return (h // g * p) % _LANES == 0


def dispatch(h: int, g: int, p: int, n: int, chunk: int):
    """Who computes a call of these shapes here: ``None`` the ``jax.numpy``
    form, else the kernels, interpreted if ``True``.  On backend ``tpu`` the
    compiled kernels for shapes :func:`fits` takes; elsewhere the kernels
    only when a test sets ``INTERPRET``."""
    if not fits(h, g, p, n, chunk):
        return None
    if jax.default_backend() == "tpu":
        return False
    return True if INTERPRET else None


# ---------------------------------------------------------------------------
# The grid both kernels walk
# ---------------------------------------------------------------------------

def _plan(x, b, chunk):
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    unit = max(p, _LANES)   # lanes a pass of the head loop takes
    return dict(b=bsz, t=t, h=h, p=p, g=g, n=n, q=chunk, nc=t // chunk,
                hp=-(-h // _LANES) * _LANES, unit=unit, per=unit // p,
                tiles=h // g * p // unit)   # lane tiles of a group


def _views(d, x, dt, cs, b, c, d_skip):
    """``[B, T, H, ...]`` -> what the kernels index: heads folded into the
    lanes (free); ``dt`` and ``c_t`` with their heads padded to whole lane
    tiles (columns of a chunk) and ``c_t`` as ``[B, H, T]`` too (rows: 2 MB
    passes); ``D`` repeated over a head's lanes."""
    bsz, t = d["b"], d["t"]
    cols = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, d["hp"] - d["h"])))
    return (x.reshape(bsz, t, -1), cols(dt), cols(cs),
            jnp.swapaxes(cs, 1, 2), b.reshape(bsz, t, -1),
            c.reshape(bsz, t, -1),
            jnp.repeat(d_skip.astype(jnp.float32), d["p"])[None])


def _states_in(d, s):   # [B, H, P, N] -> [B, N, H * P]
    return jnp.swapaxes(s.reshape(d["b"], d["h"] * d["p"], d["n"]), 1, 2)


def _states_out(d, s):  # ... and back
    return jnp.swapaxes(s, 1, 2).reshape(d["b"], d["h"], d["p"], d["n"])


def _specs(d, backward: bool):
    """Block specs over the grid (B, chunks): the backward kernel walks the
    chunks from the sequence's end."""
    q, n, w, nc = d["q"], d["n"], d["h"] * d["p"], d["nc"]
    at = (lambda s: nc - 1 - s) if backward else (lambda s: s)
    seq = lambda width: pl.BlockSpec((1, q, width), lambda i, s: (i, at(s), 0))
    return dict(
        x=seq(w), cols=seq(d["hp"]), groups=seq(d["g"] * n),
        rows=pl.BlockSpec((1, d["h"], q), lambda i, s: (i, 0, at(s))),
        skip=pl.BlockSpec((1, w), lambda i, s: (0, 0)),
        whole=lambda rows: pl.BlockSpec((1, rows, w), lambda i, s: (i, 0, 0)),
        states=pl.BlockSpec((1, 1, n, w), lambda i, s: (i, at(s), 0, 0)))


def _params(d, itemsize, seqs):
    """``seqs``: the ``[Q, H * P]`` blocks a step moves."""
    q, n, w = d["q"], d["n"], d["h"] * d["p"]
    step = seqs * q * w * itemsize + 4 * q * d["g"] * n * itemsize + (
        5 * q * d["hp"] * 4 + n * w * 4)
    held = 5 * n * w * 4 + 16 * q * q * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20,
                                                2 * step + held))))


# ---------------------------------------------------------------------------
# A lane tile's terms (values in VMEM / registers; shared by both kernels)
# ---------------------------------------------------------------------------

def _heads(d):
    """The heads of a lane tile: ``(index in the tile, the mask of its lanes
    over [Q, unit] and over [1, unit], or None where the tile is one
    head's)``."""
    p, unit, q, per = d["p"], d["unit"], d["q"], d["per"]
    if per == 1:
        return [(0, None, None)]
    return [(i, _iota((q, unit), 1) // p == i, _iota((1, unit), 1) // p == i)
            for i in range(per)]


def _only(mask):
    """Keep a head's lanes of a tile."""
    return (lambda a: a) if mask is None else (
        lambda a: jnp.where(mask, a, jnp.zeros_like(a)))


def _columns(ref, h0):
    """``[Q, 128]`` of a ``[1, Q, heads]`` block with head ``h0`` (traced)
    in lane 0, ``h0 + 1`` in lane 1, ...: a lane tile and a rotation."""
    base = pl.multiple_of(h0 // _LANES * _LANES, _LANES)
    return pltpu.roll(ref[0, :, pl.ds(base, _LANES)],
                      (_LANES - h0 % _LANES) % _LANES, 1)


def _spread(cols, heads, shape):
    """Column ``i`` of ``cols`` over the lanes of the tile's head ``i``:
    ``shape`` = ``[Q, unit]``, or ``[1, unit]`` for the columns' last
    entries."""
    rows = slice(None) if shape[0] > 1 else slice(cols.shape[0] - 1, None)
    out = None
    for i, mine, mine_row in heads:
        col = cols[rows, i:i + 1]
        out = jnp.broadcast_to(col, shape) if out is None else jnp.where(
            mine if shape[0] > 1 else mine_row, col, out)
    return out


def _tile(d, heads, u, x_ref, dt_ref, ccol_ref):
    """What both kernels read of lane tile ``u`` first: its lanes, its first
    head, x in float32, the heads' ``c_t`` as columns, ``dt`` and ``c_t``
    spread over their heads' lanes, and ``c_Q`` spread (``[1, unit]``)."""
    lanes = pl.ds(pl.multiple_of(u * d["unit"], d["unit"]), d["unit"])
    h0 = u * d["per"]
    xf = x_ref[0, :, lanes].astype(jnp.float32)
    c_cols = _columns(ccol_ref, h0)
    return (lanes, h0, xf, c_cols,
            _spread(_columns(dt_ref, h0), heads, xf.shape),
            _spread(c_cols, heads, xf.shape),
            _spread(c_cols, heads, (1, d["unit"])))


def _blocks(q):
    """The chunk's ``[Q, Q]`` terms by blocks of 128 rows: ``(rows, the
    columns at or under the diagonal block)``; nothing above is computed."""
    return [(slice(i * _LANES, (i + 1) * _LANES), (i + 1) * _LANES)
            for i in range(q // _LANES)]


def _decay(ccol, crow, rows, width, causal):
    """``exp(c_t - c_s)`` for the 128 positions t of ``rows`` and the
    ``width`` positions s up to their diagonal block's end: masked (the
    diagonal block's upper triangle: there the difference is positive and
    can overflow) before the exp.  ``ccol`` ``[Q, 1]``, ``crow`` ``[1,
    Q]``, ``causal`` ``[128, 128]``: 0 where s <= t, else ``_MASKED``."""
    diff = ccol[rows] - crow[:, :width]
    if width > _LANES:
        return jnp.exp(jnp.concatenate(
            [diff[:, :width - _LANES], diff[:, width - _LANES:] + causal],
            axis=1))
    return jnp.exp(diff + causal)


def _walk(d, g, one_tile):
    """``one_tile(u)`` for the lane tiles of group ``g``, ``_TILES_A_PASS``
    a pass of a loop (not unrolled: the body is traced and lowered once)."""
    tiles = d["tiles"]
    a_pass = next(m for m in _TILES_A_PASS if tiles % m == 0)

    def one_pass(i, _):
        for k in range(a_pass):
            one_tile(i * a_pass + k)

    jax.lax.fori_loop(g * tiles // a_pass, (g + 1) * tiles // a_pass,
                      one_pass, None)


def _lane_sums(z, keep):
    """Sums of ``z`` (``[Q, W]`` float32) over the lanes each row of
    ``keep`` (``[8, W]``, 0 / 1 in bf16) keeps, as rows: ``[8, Q]``, positions
    in the lanes.  On the MXU and still float32: ``z`` goes in as three bf16
    parts against the exact 0 / 1 matrix, summed in float32 (a lane
    reduction on the vector unit leaves columns, which then cost a lane
    broadcast each to meet the rows).  A part is ``z``'s leading 8 bits of
    mantissa, cut by a mask and not by a cast: XLA may drop a float32 ->
    bf16 -> float32 round trip (the interpreter runs under it), and the
    remainder must be exact."""
    out, rest = 0.0, z
    for _ in range(3):
        part = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rest, jnp.int32) & -65536,
            jnp.float32)
        out = out + _dot(keep, part.astype(jnp.bfloat16), _NT)
        rest = rest - part
    return out


def _fold(a):
    """``[R, k * 128]`` -> ``[R, 128]``: the lane tiles added up."""
    return sum(a[:, k:k + _LANES] for k in range(0, a.shape[1], _LANES))


def _causal():
    at = (_LANES, _LANES)
    return jnp.where(_iota(at, 0) >= _iota(at, 1), 0.0, _MASKED)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, dt_ref, ccol_ref, crow_ref, b_ref, c_ref, d_ref,
                s0_ref, y_ref, sn_ref, big_ref, *rest, d):
    """Grid (B, chunks): ``s_scr`` carries every head's state across the
    sequence's chunks; a step walks the chunk's lane tiles (``lax.fori_loop``:
    the body is traced once), ``cb_scr`` holds the group's ``C B^T`` for
    them."""
    states_ref = rest[0] if len(rest) == 3 else None
    s_scr, cb_scr = rest[-2:]
    step = pl.program_id(1)
    q, n = d["q"], d["n"]
    dtype = x_ref.dtype
    colmax = lambda a: jnp.max(jnp.abs(a), axis=0, keepdims=True)

    @pl.when(step == 0)
    def _start():
        s_scr[...] = s0_ref[0]
        big_ref[0] = colmax(s0_ref[0])

    if states_ref is not None:
        states_ref[0, 0] = s_scr[...]
    heads = _heads(d)
    causal = _causal()

    for g in range(d["g"]):
        group = slice(g * n, (g + 1) * n)
        cb_scr[...] = _dot(c_ref[0, :, group], b_ref[0, :, group], _NT)

        def one_tile(u):
            lanes, h0, xf, c_cols, dt, cs, last = _tile(
                d, heads, u, x_ref, dt_ref, ccol_ref)
            x_dt = (xf * dt).astype(dtype)
            s_in = s_scr[:, lanes]
            y = (_dot(c_ref[0, :, group], s_in.astype(dtype)) * jnp.exp(cs)
                 + xf * d_ref[:, lanes])
            for i, mine, _ in heads:
                crow = crow_ref[0, pl.ds(h0 + i, 1), :]
                within = []
                for rows, width in _blocks(q):
                    m = (cb_scr[rows, :width] * _decay(
                        c_cols[:, i:i + 1], crow, rows, width, causal))
                    within.append(_dot(m.astype(dtype), x_dt[:width]))
                y = y + _only(mine)(jnp.concatenate(within, axis=0))
            y_ref[0, :, lanes] = y.astype(y_ref.dtype)
            x_end = (xf * (dt * jnp.exp(last - cs))).astype(dtype)
            s_out = s_in * jnp.exp(last) + _dot(b_ref[0, :, group], x_end,
                                                _TN)
            s_scr[:, lanes] = s_out
            big_ref[0, :, lanes] = jnp.maximum(big_ref[0, :, lanes],
                                               colmax(s_out))

        _walk(d, g, one_tile)

    @pl.when(step == pl.num_programs(1) - 1)
    def _finish():
        sn_ref[0] = s_scr[...]


@functools.lru_cache(maxsize=64)
def _forward(sizes, dtype, for_gradient: bool, interpret: bool):
    """The forward ``pallas_call`` for these sizes: built once, so that the
    layers of a model — and its ``init``, ``predict`` and train-step
    programs — trace the kernel once between them."""
    d = dict(sizes)
    bsz, t, n, nc, q = (d[x] for x in ("b", "t", "n", "nc", "q"))
    w = d["h"] * d["p"]
    s = _specs(d, backward=False)
    out_specs = [s["x"], s["whole"](n), s["whole"](1)]
    out_shape = [jax.ShapeDtypeStruct((bsz, t, w), dtype),
                 jax.ShapeDtypeStruct((bsz, n, w), jnp.float32),
                 jax.ShapeDtypeStruct((bsz, 1, w), jnp.float32)]
    if for_gradient:
        out_specs.append(s["states"])
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, n, w), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d),
        grid=(bsz, nc),
        in_specs=[s["x"], s["cols"], s["cols"], s["rows"], s["groups"],
                  s["groups"], s["skip"], s["whole"](n)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, w), jnp.float32),
                        pltpu.VMEM((q, q), jnp.float32)],
        compiler_params=_params(d, jnp.dtype(dtype).itemsize, 2),
        interpret=interpret,
        name="mamba2_ssd_fwd",  # the op's name in HLO and in a profile
    )


def _fwd_call(x, dt, cs, b, c, d_skip, s0, chunk, for_gradient, interpret):
    """x ``[B, T, H, P]``, dt and cs (the chunk's running sum of ``dt * A``)
    ``[B, T, H]`` float32, b and c ``[B, T, G, N]``, d_skip ``[H]``, s0
    ``[B, H, P, N]`` float32; T a multiple of ``chunk``.  Returns y, the
    final state, the largest ``|S|`` a head had at a chunk's boundary (``[B,
    H]``) and, ``for_gradient``, the state at every chunk's start (``[B,
    chunks, N, H * P]`` float32, transposed as the kernels hold it)."""
    d = _plan(x, b, chunk)
    out = _forward(tuple(d.items()), x.dtype, for_gradient, interpret)(
        *_views(d, x, dt, cs, b, c, d_skip), _states_in(d, s0))
    big = out[2].reshape(d["b"], d["h"], d["p"]).max(-1)
    return (out[0].reshape(x.shape), _states_out(d, out[1]), big) + tuple(
        out[3:])


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, dt_ref, ccol_ref, crow_ref, b_ref, c_ref, d_ref,
                states_ref, dy_ref, dsn_ref, dx_ref, ddt_ref, dcs_ref,
                db_ref, dc_ref, dd_ref, ds0_ref, ds_scr, cb_scr, dcb_scr,
                read_scr, end_scr, dsb_scr, *, d):
    """The forward's grid with the sequence walked from its end: ``ds_scr``
    carries the states' cotangents; ``dcb_scr`` sums a chunk's ``d(C B^T)``
    over the heads of a group; ``read_scr``, ``end_scr`` and ``dsb_scr`` keep
    every lane tile's operands of ``dC`` and ``dB``, which are then ONE
    product each over all the group's lanes."""
    step = pl.program_id(1)
    q, n, unit, per = d["q"], d["n"], d["unit"], d["per"]
    dtype = x_ref.dtype
    f32 = jnp.float32
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)

    @pl.when(step == 0)
    def _start():
        ds_scr[...] = dsn_ref[0]
        dd_ref[...] = jnp.zeros_like(dd_ref)

    heads = _heads(d)
    causal = _causal()
    at_last = _iota((1, q), 1) == q - 1
    # row i keeps the lanes of a tile's head i; every lane of a 128-lane fold
    of_head = (_iota((8, unit), 1) // d["p"] == _iota((8, unit), 0)).astype(
        jnp.bfloat16)
    every = jnp.ones((8, _LANES), jnp.bfloat16)

    for g in range(d["g"]):
        group = slice(g * n, (g + 1) * n)
        cb_scr[...] = _dot(c_ref[0, :, group], b_ref[0, :, group], _NT)
        dcb_scr[...] = jnp.zeros_like(dcb_scr)

        def one_tile(u):
            lanes, h0, xf, c_cols, dt, cs, last = _tile(
                d, heads, u, x_ref, dt_ref, ccol_ref)
            bmat, cmat = b_ref[0, :, group], c_ref[0, :, group]
            dy = dy_ref[0, :, lanes]
            dyf = dy.astype(f32)
            s_in = states_ref[0, 0, :, lanes]
            s_b = s_in.astype(dtype)
            ds = ds_scr[:, lanes]
            ds_b = ds.astype(dtype)
            e, kept, to_end = jnp.exp(cs), jnp.exp(last), jnp.exp(last - cs)
            x_dt = (xf * dt).astype(dtype)
            x_tail = xf * (dt * to_end)
            x_end = x_tail.astype(dtype)
            # y = (C S_in^T) e + within + D x;  S_out = S_in kept + B^T x_end
            from_state = _dot(cmat, s_b) * e                 # [Q, unit]
            d_read = (dyf * e).astype(dtype)
            read_scr[:, lanes], end_scr[:, lanes] = d_read, x_end
            dsb_scr[:, lanes] = ds_b
            d_x_end = _dot(bmat, ds_b)                       # [Q, unit]
            # the state's own forgetting: a head's lanes are summed below
            d_kept = colsum(ds * s_in) * kept                # [1, unit]
            ds_scr[:, lanes] = ds * kept + _dot(cmat, d_read, _TN)
            dd_ref[0, :, lanes] += colsum(dyf * xf)
            # what c_t gains through exp(c_t) on the state's answer and
            # loses through exp(c_Q - c_t) on the state's next writes; the
            # lost part comes back at c_Q, with the state's own forgetting
            d_tail = d_x_end * x_tail
            # ... but for c_Q itself, where exp(c_Q - c_Q) is 1 whatever c:
            # its two large shares would cancel to a rounding error
            d_tail = jnp.concatenate(
                [d_tail[:q - 8], jnp.where(_iota((8, unit), 0) == 7, 0.0,
                                           d_tail[q - 8:])], axis=0)
            gain_lanes = dyf * from_state - d_tail
            d_last = colsum(d_tail) + d_kept                 # [1, unit]
            d_x_dt = [jnp.zeros((_LANES, unit), f32)] * (q // _LANES)
            for i, mine, mine_row in heads:
                only = _only(mine)
                c_h = c_cols[:, i:i + 1]
                crow = crow_ref[0, pl.ds(h0 + i, 1), :]
                dy_h = only(dy)
                gain = only(gain_lanes)
                gains, loses = [], [0.0] * (q // _LANES)
                for k, (rows, width) in enumerate(_blocks(q)):
                    decay = _decay(c_h, crow, rows, width, causal)
                    cb = cb_scr[rows, :width]
                    d_m = _dot(dy_h[rows], x_dt[:width], _NT)
                    back = _dot((cb * decay).astype(dtype), dy_h[rows], _TN)
                    d_x_dt = [a + back[j * _LANES:(j + 1) * _LANES]
                              if j <= k else a for j, a in enumerate(d_x_dt)]
                    d_cb = d_m * decay
                    dcb_scr[rows, :width] += d_cb
                    # c_t gains along a row of the decay, c_s loses along
                    # a column
                    d_diff = d_cb * cb
                    loses = [a + colsum(d_diff[:, j * _LANES:(j + 1) * _LANES])
                             if j <= k else a for j, a in enumerate(loses)]
                    gains.append(_fold(d_diff) + _fold(gain[rows]))
                gains = _lane_sums(jnp.concatenate(gains, axis=0), every)
                dcs_ref[0, pl.ds(h0 + i, 1), :] = (
                    gains[:1] - jnp.concatenate(loses, axis=1) + jnp.where(
                        at_last,
                        jnp.sum(_only(mine_row)(d_last), keepdims=True), 0.0))
            # d(x dt), over both its uses
            reach = jnp.concatenate(d_x_dt, axis=0) + d_x_end * to_end
            dx_ref[0, :, lanes] = (dt * reach + dyf * d_ref[:, lanes]).astype(
                dx_ref.dtype)
            # ddt's own part; the rest reaches dt through c_t, outside
            d_dt = _lane_sums(xf * reach, of_head)
            for i in range(per):
                ddt_ref[0, pl.ds(h0 + i, 1), :] = d_dt[i:i + 1]

        _walk(d, g, one_tile)
        d_cb = dcb_scr[...].astype(dtype)
        its = slice(g * d["tiles"] * unit, (g + 1) * d["tiles"] * unit)
        db_ref[0, :, group] = (
            _dot(end_scr[:, its], dsb_scr[:, its], _NT)
            + _dot(d_cb, c_ref[0, :, group], _TN)).astype(db_ref.dtype)
        dc_ref[0, :, group] = (
            _dot(read_scr[:, its], states_ref[0, 0, :, its].astype(dtype),
                 _NT)
            + _dot(d_cb, b_ref[0, :, group])).astype(dc_ref.dtype)

    @pl.when(step == pl.num_programs(1) - 1)
    def _finish():
        ds0_ref[0] = ds_scr[...]


@functools.lru_cache(maxsize=64)
def _backward(sizes, dtype, interpret: bool):
    """The backward ``pallas_call`` for these sizes, built once."""
    d = dict(sizes)
    bsz, t, n, g, q = (d[x] for x in ("b", "t", "n", "g", "q"))
    w = d["h"] * d["p"]
    s = _specs(d, backward=True)
    rows = jax.ShapeDtypeStruct((bsz, d["h"], t), jnp.float32)
    groups = jax.ShapeDtypeStruct((bsz, t, g * n), dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d),
        grid=(bsz, d["nc"]),
        in_specs=[s["x"], s["cols"], s["cols"], s["rows"], s["groups"],
                  s["groups"], s["skip"], s["states"], s["x"],
                  s["whole"](n)],
        out_specs=[s["x"], s["rows"], s["rows"], s["groups"], s["groups"],
                   s["whole"](1), s["whole"](n)],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, w), dtype), rows, rows,
                   groups, groups,
                   jax.ShapeDtypeStruct((bsz, 1, w), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n, w), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, w), jnp.float32),
                        pltpu.VMEM((q, q), jnp.float32),
                        pltpu.VMEM((q, q), jnp.float32),
                        pltpu.VMEM((q, w), dtype), pltpu.VMEM((q, w), dtype),
                        pltpu.VMEM((n, w), dtype)],
        compiler_params=_params(d, jnp.dtype(dtype).itemsize, 3),
        interpret=interpret,
        name="mamba2_ssd_bwd",
    )


def _bwd_call(x, dt, cs, b, c, d_skip, states, dy, dsn, chunk, interpret):
    """Gradients of x, dt, cs, b, c, d_skip and the initial state."""
    d = _plan(x, b, chunk)
    bsz, t, h = d["b"], d["t"], d["h"]
    dx, ddt, dcs, db, dc, dd, ds0 = _backward(
        tuple(d.items()), x.dtype, interpret)(
        *_views(d, x, dt, cs, b, c, d_skip), states, dy.reshape(bsz, t, -1),
        _states_in(d, dsn))
    dd = dd.reshape(bsz, h, d["p"]).sum((0, 2)).astype(d_skip.dtype)
    times = lambda a: jnp.swapaxes(a, 1, 2)
    return (dx.reshape(x.shape), times(ddt), times(dcs), db.reshape(b.shape),
            dc.reshape(c.shape), dd, _states_out(d, ds0))


# ---------------------------------------------------------------------------
# The differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def chunk_kernels(x, dt, cs, b, c, d_skip, s0, chunk, interpret=False):
    """``(y, final state, largest |S| a head)`` by the kernels; shapes as
    :func:`_fwd_call`."""
    return _fwd_call(x, dt, cs, b, c, d_skip, s0, chunk, False, interpret)


def _vjp_fwd(x, dt, cs, b, c, d_skip, s0, chunk, interpret):
    y, sn, big, states = _fwd_call(x, dt, cs, b, c, d_skip, s0, chunk, True,
                                   interpret)
    # named, so that an enclosing jax.checkpoint can be told to keep them
    # (nn.Remat(save_names=...)): with both kept, the recomputation of a
    # block runs no kernel
    y = checkpoint_name(y, "mamba2_ssd_out")
    states = checkpoint_name(states, "mamba2_ssd_states")
    return (y, sn, big), (x, dt, cs, b, c, d_skip, states)


def _vjp_bwd(chunk, interpret, res, cotangents):
    dy, dsn, _ = cotangents   # the largest |S| is a statistic: no gradient
    return _bwd_call(*res, dy, dsn, chunk, interpret)


chunk_kernels.defvjp(_vjp_fwd, _vjp_bwd)
