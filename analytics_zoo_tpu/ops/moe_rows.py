"""The expert layer's row movement as two Pallas TPU kernels.

``parallel/moe.py`` ``DroplessMoE`` sorts its (token, pick) pairs by expert
and walks that order in windows of ``n`` rows: a window's rows pass two
grouped matmuls (``jax.lax.ragged_dot``).  The rows reach them, and return
to their tokens, here:

``gather_rows``   row ``p < live`` of the window is ``src[idx[p]]``, times
                  ``scale[p]`` in float32 when a scale is given
                  (``moe_gather_rows``)
``combine_rows``  token ``t``'s output is ``sum_r w[p] rows[p]`` over the
                  held picks ``p = pos[t, r]`` that lie in the window,
                  summed in float32 and written once: no read-modify-write
                  of a token's row in HBM, so nothing is serialised
                  (``moe_combine_rows``)

Each is the other's transpose: ``combine_rows`` with weights ``w`` takes a
cotangent back as ``gather_rows`` with scale ``w``, and the gradient of
``w`` (``<rows[p], g[idx[p]]>``) comes from that same pass, which reads
``rows`` beside the gathered ``g``; ``gather_rows`` takes its cotangent
back as ``combine_rows``.  :func:`gather_rows` and :func:`combine_rows` are
that ``custom_vjp`` pair; ``DroplessMoE``'s hand-written backward calls the
kernels themselves (:func:`gather_call`, :func:`combine_call`), so that the
combine of ``dx`` also carries the weights' gradients from the window's
rows to the picks (``pick_vals``), and no scatter is left anywhere.

A window is described by a :class:`Route`: ``idx[p]`` the token of row
``p``, ``pos[t * kk + r]`` the place in the sorted order of token ``t``'s
``r``-th held pick (``-1`` past its count: a token's held picks come first),
``win = (start, live)``: the window is places ``start .. start + n`` of
that order, the first ``live`` of them held pairs.  ``pos`` holds absolute
places, so one array serves every window.

Rows move by DMA, one row a descriptor, several hundred in flight: a DMA
moves whole tiles, and in the ``[S, D]`` layout a row is one sublane of
tiles of eight or sixteen rows, so the kernels see ``src`` and ``rows`` as
``[S, D / 128, 128]``, where a row is a tile of its own (XLA relays the
array out once; the kernels' own outputs are ``[.., D]``).  The indices and
per-row scalars come through scalar prefetch; ``combine_rows`` walks a
block of tokens' picks from a 1-D scalar-memory block of ``pos``.

Rows past ``live`` are neither read nor written.  ``jax.lax.ragged_dot``
leaves rows outside its groups alone, forward and in both transposes: on a
TPU v5e rows of NaN or inf past the groups change nothing inside them or in
the weights' gradient, and the CPU's implementation selects them away.  So
the gather's rows past ``live`` hold whatever the buffer held, and nothing
reads them.

``INTERPRET`` runs the kernels in Pallas interpret mode (tests, CPU).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False  # tests set True to exercise the kernels on the CPU

_LANES = 128
#: rows a grid step of the gather moves: the first that divides the window
_ROW_BLOCKS = (256, 128, 64, 32, 16)
#: rows the combine holds in VMEM between two waits
_CHUNK = 256
#: tokens a grid step of the combine takes, at least
_MIN_TOKENS = 128
#: a 1-D array in scalar memory is blocked in whole tiles of this many words
_SMEM_TILE = 1024
#: scalar memory of a TensorCore (v5e: 1 MiB); the kernels keep to half
_SMEM_BYTES = 1 << 20


class Route(NamedTuple):
    """One window's rows and the picks they belong to (module docstring)."""
    idx: jax.Array   # [n] int32
    pos: jax.Array   # [S * kk] int32
    win: jax.Array   # [2] int32: start, live


def _row_block(n: int) -> Optional[int]:
    return next((r for r in _ROW_BLOCKS if n % r == 0), None)


def _token_block(s: int, kk: int) -> Optional[int]:
    """Tokens a combine step takes: its ``tb * kk`` picks are whole tiles
    of scalar memory, and ``tb`` divides ``s``."""
    tb = _SMEM_TILE // math.gcd(kk, _SMEM_TILE)
    tb *= -(-_MIN_TOKENS // tb)
    return tb if s % tb == 0 else None


def fits(s: int, n: int, d: int, kk: int) -> bool:
    """Shapes the kernels take: rows of whole 128-lane tiles, a window of
    whole row blocks, tokens in whole blocks, and a window's indices and
    scalars within half the scalar memory."""
    tb, r = _token_block(s, kk), _row_block(n)
    if d % _LANES or tb is None or r is None:
        return False
    return 4 * (2 * n + 4 * tb * kk + 2 * _CHUNK) <= _SMEM_BYTES // 2


def dispatch(s: int, n: int, d: int, kk: int):
    """Who moves a window's rows here: ``None`` the ``jax.numpy`` form, else
    the kernels, interpreted if ``True``.  On backend ``tpu`` the compiled
    kernels for shapes :func:`fits` takes; elsewhere the kernels only when
    a test sets ``INTERPRET``."""
    if not fits(s, n, d, kk):
        return None
    if jax.default_backend() == "tpu":
        return False
    return True if INTERPRET else None


def _tiles(a: jax.Array) -> jax.Array:
    """``[rows, D]`` -> ``[rows, D / 128, 128]``: a row is a tile of its own."""
    return a.reshape(a.shape[0], -1, _LANES)


def _params(vmem: int):
    """A sequential grid, and no bounds check a DMA: every row index is a
    token's or a live row's (half the bundles of a row's issue were the
    two checks)."""
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 2 * vmem))),
        disable_bounds_checks=True)


def _copy(src, dst, sem):
    return pltpu.make_async_copy(src, dst, sem)


# ---------------------------------------------------------------------------
# gather: a window's rows from their tokens
# ---------------------------------------------------------------------------

def _gather_kernel(live_ref, idx_ref, *refs, r, d, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    src_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    base = pl.program_id(0) * r
    rows = jnp.clip(live_ref[0] - base, 0, r)

    @pl.when(rows > 0)
    def _move():
        def start(i, c):
            _copy(src_ref.at[idx_ref[base + i]], buf.at[i], sem).start()
            return c
        jax.lax.fori_loop(0, rows, start, 0)

        def wait(i, c):
            _copy(src_ref.at[0], buf.at[0], sem).wait()
            return c
        jax.lax.fori_loop(0, rows, wait, 0)
        v = buf[...].reshape(r, d)
        if dotted:   # per row <src[idx[p]], dot[p]>, as 128 lane partials
            prod = v.astype(jnp.float32) * dot_ref[...].astype(jnp.float32)
            dots_ref[...] = sum(prod[:, c * _LANES:(c + 1) * _LANES]
                                for c in range(d // _LANES))
        if scaled:
            v = v.astype(jnp.float32) * scale_ref[:, :1]
        out_ref[...] = v.astype(out_ref.dtype)


@functools.lru_cache(maxsize=64)
def _gather(n, s, d, src_dtype, out_dtype, scaled, dotted, interpret):
    """The gather's ``pallas_call`` for these sizes: built once, so that the
    layers of a model and its programs trace it once between them."""
    r = _row_block(n)

    def block(b, live, idx):   # steps past ``live`` keep the last live block
        return jnp.minimum(b, jnp.maximum(live[0] - 1, 0) // r), 0

    in_specs = [pl.BlockSpec((r, _LANES), block)] if scaled else []
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_specs = [pl.BlockSpec((r, d), block)]
    out_shape = [jax.ShapeDtypeStruct((n, d), out_dtype)]
    if dotted:
        in_specs.append(pl.BlockSpec((r, d), block))
        out_specs.append(pl.BlockSpec((r, _LANES), block))
        out_shape.append(jax.ShapeDtypeStruct((n, _LANES), jnp.float32))
    row = d * jnp.dtype(src_dtype).itemsize
    return pl.pallas_call(
        functools.partial(_gather_kernel, r=r, d=d, scaled=scaled,
                          dotted=dotted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // r,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((r, d // _LANES, _LANES), src_dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        compiler_params=_params(r * (row + 3 * d * 4 + 2 * _LANES * 4)),
        interpret=interpret,
        name="moe_gather_rows",  # the op's name in HLO and in a profile
    )


def gather_call(src, route: Route, scale=None, dot=None, out_dtype=None,
                interpret=False):
    """The gather kernel: ``rows[p] = scale[p] * src[idx[p]]`` for ``p <
    live`` (``[n, D]``, ``out_dtype`` or the source's), and with ``dot``
    (``[n, D]``) also ``<src[idx[p]], dot[p]>`` in float32 (``[n]``)."""
    n = route.idx.shape[0]
    s, d = src.shape
    call = _gather(n, s, d, jnp.dtype(src.dtype),
                   jnp.dtype(out_dtype or src.dtype), scale is not None,
                   dot is not None, interpret)
    args = [] if scale is None else [jnp.broadcast_to(
        scale.astype(jnp.float32)[:, None], (n, _LANES))]
    args.append(_tiles(src))
    if dot is not None:
        args.append(dot)
    out = call(route.win[1:], route.idx, *args)
    if dot is None:
        return out[0]
    return out[0], out[1].sum(-1)


# ---------------------------------------------------------------------------
# combine: a window's rows back to their tokens
# ---------------------------------------------------------------------------

def _combine_kernel(win_ref, vals_ref, pos_ref, rows_ref, *refs, tb, kk, c,
                    d, weighted, carried, picked):
    refs = list(refs)
    carry_ref = refs.pop(0) if carried else None
    out_ref = refs.pop(0)
    picks_ref = refs.pop(0) if picked else None
    buf, acc, slot_t, slot_w, sem = refs
    start, live = win_ref[0], win_ref[1]
    acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def flush(count):
        def wait(i, carry):
            _copy(rows_ref.at[0], buf.at[0], sem).wait()
            return carry
        jax.lax.fori_loop(0, count, wait, 0)

        def add(i, carry):
            row = buf[i].astype(jnp.float32)
            if weighted:
                row = row * slot_w[i]
            acc[slot_t[i]] += row
            return carry
        jax.lax.fori_loop(0, count, add, 0)

    def token(t, slot):
        def pick(state):
            r, slot, _ = state
            p = pos_ref[t * kk + r] - start
            held = (p >= 0) & (p < live)
            if picked:   # a pick outside the window is left as it is
                picks_ref[t * kk + r] = vals_ref[
                    jnp.clip(p, 0, vals_ref.shape[0] - 1)]

            def issue(slot):
                _copy(rows_ref.at[p], buf.at[slot], sem).start()
                slot_t[slot] = t
                if weighted:
                    slot_w[slot] = vals_ref[p]

                def full(slot):
                    flush(c)
                    return jnp.int32(0)
                return jax.lax.cond(slot + 1 == c, full, lambda s: s,
                                    slot + 1)
            slot = jax.lax.cond(held, issue, lambda s: s, slot)
            more = (r + 1 < kk) & (
                pos_ref[t * kk + jnp.minimum(r + 1, kk - 1)] >= 0)
            return r + 1, slot, more
        _, slot, _ = jax.lax.while_loop(
            lambda state: state[2], pick,
            (jnp.int32(0), slot, pos_ref[t * kk] >= 0))
        return slot
    flush(jax.lax.fori_loop(0, tb, token, jnp.int32(0)))
    out = acc[...].reshape(tb, d)
    out_ref[...] = out + carry_ref[...] if carried else out


@functools.lru_cache(maxsize=64)
def _combine(s, n, d, kk, rows_dtype, weighted, carried, picked, interpret):
    """The combine's ``pallas_call`` for these sizes (built once)."""
    tb, c = _token_block(s, kk), min(_CHUNK, n)
    picks = pl.BlockSpec((tb * kk,), lambda b, *_: (b,),
                         memory_space=pltpu.SMEM)
    tokens = pl.BlockSpec((tb, d), lambda b, *_: (b, 0))
    in_specs = [picks, pl.BlockSpec(memory_space=pl.ANY)]
    in_specs += [tokens] if carried else []
    out_specs = [tokens] + ([picks] if picked else [])
    out_shape = [jax.ShapeDtypeStruct((s, d), jnp.float32)]
    if picked:
        out_shape.append(jax.ShapeDtypeStruct((s * kk,), jnp.float32))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tb=tb, kk=kk, c=c, d=d,
                          weighted=weighted, carried=carried, picked=picked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s // tb,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((c, d // _LANES, _LANES), rows_dtype),
                pltpu.VMEM((tb, d // _LANES, _LANES), jnp.float32),
                pltpu.SMEM((c,), jnp.int32), pltpu.SMEM((c,), jnp.float32),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        input_output_aliases={4: 0} if carried else {},
        compiler_params=_params(
            tb * d * 4 * 5 + c * d * jnp.dtype(rows_dtype).itemsize),
        interpret=interpret,
        name="moe_combine_rows",  # the op's name in HLO and in a profile
    )


def combine_call(rows, route: Route, s: int, weight=None, carry=None,
                 pick_vals=None, interpret=False):
    """The combine kernel: ``[S, D]`` float32, ``carry`` (``[S, D]``
    float32) plus each token's held picks in the window, weighted by
    ``weight[p]`` (``[n]``) when given; with ``pick_vals`` (``[n]``) also
    ``pick_vals[p]`` at each of those picks' places in ``pos``
    (``[S * kk]``; what a place outside the window holds is undefined)."""
    n, d = rows.shape
    kk = route.pos.shape[0] // s
    vals = weight if pick_vals is None else pick_vals
    if weight is not None and pick_vals is not None:
        raise ValueError("one per-row scalar: a weight or values to pass on")
    call = _combine(s, n, d, kk, jnp.dtype(rows.dtype), weight is not None,
                    carry is not None, pick_vals is not None, interpret)
    vals = (jnp.zeros((n,), jnp.float32) if vals is None
            else vals.astype(jnp.float32))
    args = [route.pos, _tiles(rows)] + ([] if carry is None else [carry])
    out = call(route.win, vals, *args)
    return out if pick_vals is not None else out[0]


# ---------------------------------------------------------------------------
# the pair, each the other's transpose
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gather_rows(src, scale, route: Route, s: int, interpret: bool = False):
    """``rows[p] = scale[p] * src[idx[p]]`` for the window's live rows
    (``scale`` may be None); its transpose is :func:`combine_rows`."""
    return gather_call(src, route, scale, interpret=interpret)


def _gather_rows_fwd(src, scale, route, s, interpret):
    return gather_rows(src, scale, route, s, interpret), (src, scale, route)


def _gather_rows_bwd(s, interpret, res, g):
    src, scale, route = res
    d_src = combine_call(g, route, s, weight=scale, interpret=interpret)
    d_scale = None
    if scale is not None:
        _, dots = gather_call(src, route, dot=g, interpret=interpret)
        d_scale = _live(dots, route)
    return d_src.astype(src.dtype), d_scale, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def combine_rows(rows, weight, route: Route, s: int,
                 interpret: bool = False):
    """``out[t] = sum_r weight[p] * rows[p]`` over token ``t``'s held picks
    ``p`` in the window, float32 ``[S, D]`` (``weight`` may be None); its
    transpose is :func:`gather_rows`, and the weights' gradient comes from
    that same pass."""
    return combine_call(rows, route, s, weight=weight, interpret=interpret)


def _combine_rows_fwd(rows, weight, route, s, interpret):
    return combine_rows(rows, weight, route, s, interpret), (
        rows, weight, route)


def _combine_rows_bwd(s, interpret, res, g):
    rows, weight, route = res
    if weight is None:
        return gather_call(g, route, out_dtype=rows.dtype,
                           interpret=interpret), None, None
    d_rows, dots = gather_call(g, route, weight, dot=rows,
                               out_dtype=rows.dtype, interpret=interpret)
    return d_rows, _live(dots, route), None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _live(per_row, route):
    """A per-row scalar with the rows past ``live`` (never written) as 0."""
    return jnp.where(jnp.arange(per_row.shape[0]) < route.win[1], per_row,
                     0.0)
