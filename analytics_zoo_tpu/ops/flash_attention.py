"""Flash attention: Pallas TPU kernels with online softmax.

Reference (SURVEY.md §2.3/§5.7): the reference's attention was the Scala
Keras-zoo TransformerLayer/BERT self-attention — plain materialized-logits
attention on CPU (seq<=512).  TPU-native redesign: blocked kernels that never
materialize the [Tq, Tk] logits matrix in HBM, so memory is O(T·D).

Forward (``flash_attention_fwd``; ``flash_attention_window_fwd`` on a band)
and backward (``flash_attention_bwd``; ``flash_attention_window_bwd``) walk
a flat list of the tiles that hold a visible pair: every tile when not
causal, the triangle when causal, the band with a ``window``.  The lists
(``_fwd_tiles``: query block outermost; ``_bwd_tiles``: key block outermost)
are read by the index maps from scalar-prefetch tables, so a tile outside is
neither stepped over nor fetched, and only the tiles an edge crosses (the
diagonal, the band's far edge, padded keys) build a mask.  Tiles are large
(a grid step costs what a 256 x 256 tile's products do) and come from the
shapes (``_fwd_blocks``, ``_bwd_blocks``).  Both hold a tile's scores
TRANSPOSED, ``[keys, queries]``: what is one number a query (the forward's
running max and sum, the backward's lse and delta) is then a row along the
lanes, reduced and broadcast along sublanes.

Forward: running max/sum ("online softmax") accumulate per query block in
VMEM scratch while its key blocks stream through, the accumulator as
``out^T`` turned once where the block is written; a tile's keys are walked
in chunks, two matmuls a chunk.  Backward: `jax.custom_vjp` whose residuals
are just (q, k, v, out, lse); one kernel recomputes a tile's probabilities
from q, k and lse — the standard flash-attention-2 trade, extra FLOPs for
O(T) memory; scores, probabilities, dp and ds of a tile exist only in VMEM;
dk and dv of the key block and dq of the whole head accumulate in float32
scratch and reach HBM once; five matmuls a tile.  In both, the matmuls'
operands are in the inputs' dtype (p and ds cast to it, as XLA:TPU's default
precision rounds the operands of the ``jax.numpy`` forms' float32 einsums to
bf16); s, exp, the statistics and the accumulators are float32.

``window`` (causal only) is sliding-window attention: position i sees the
``window`` keys ``i - window + 1 .. i``.  Every path then visits the blocks
that intersect that band and no other, so the work is ``T * window`` and not
``T^2``: the kernels' tile lists hold the band's tiles, and the blocked forms
slice the band out of k (forward) or out of q (backward) block by block.

On platform ``tpu`` forward and backward are always the compiled kernels (or
the compiler's error).  On other backends they are the same math blocked in
``jax.numpy`` (``_blocked_*_jax``, ``_band_*_jax``: a ``lax.scan`` over k
blocks), or the kernels in Pallas interpret mode when a test sets
``INTERPRET``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

# what a tile of a walk has to do besides its products; the forward's walk
# opens and closes a QUERY block, the backward's a KEY block
_FIRST_OF_Q = _FIRST_OF_K = 1
_LAST_OF_Q = _LAST_OF_K = 2
_MASKED = 4

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _fwd_tiles(tq, tk, block_q, block_k, true_tk, causal, window):
    """The (query block, key block) tiles the forward visits, query block
    outermost, as three int32 arrays the kernel's index maps read: query
    block, key block, flags.  Not causal: every key block; causal: those up
    to the diagonal (the triangle); with a ``window`` the band's alone.
    ``_MASKED`` is set only where a tile holds a hidden pair too: the
    diagonal crosses it, the band's far edge does, or it holds padded keys.
    ``tq`` and ``tk`` are the padded lengths (a padded query row counts as a
    row: it is computed and sliced off).  Every query block has a tile: key
    block 0 unless a ``window`` hides it, then the diagonal's."""
    qis, kjs, flags = [], [], []
    for i in range(tq // block_q):
        q0, q1 = i * block_q, i * block_q + block_q - 1
        seen = []
        for j in range(tk // block_k):
            k0, k1 = j * block_k, j * block_k + block_k - 1
            if causal and k0 > q1:
                break
            if window is not None and q0 - k1 >= window:
                continue
            masked = (k1 >= true_tk or (causal and k1 > q0)
                      or (window is not None and q1 - k0 >= window))
            seen.append((j, _MASKED if masked else 0))
        for n, (j, flag) in enumerate(seen):
            qis.append(i)
            kjs.append(j)
            flags.append(flag | (_FIRST_OF_Q if n == 0 else 0)
                         | (_LAST_OF_Q if n == len(seen) - 1 else 0))
    return tuple(np.asarray(a, np.int32) for a in (qis, kjs, flags))


def _fwd_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int, chunk: int,
                seq_k: Optional[int], window: Optional[int]):
    """Grid = (BH, tiles): a head's tiles in the order of ``_fwd_tiles``.
    ``seq_k`` is the unpadded key length where keys were padded, else None.
    Scores are held transposed, ``[keys, block_q]``, as the backward holds
    them: the online softmax's running max and sum are then rows (lanes),
    reduced and broadcast along sublanes, and the accumulator is ``out^T``
    of ``[d, block_q]``, rescaled by a row and turned once, where the query
    block is written.  A tile's keys are walked ``chunk`` at a time, each
    chunk one step of the online softmax: with 128 keys a chunk both
    products hold one MXU tile of k (of v) and stream the query block past
    it, and a chunk's ``exp`` runs beside the next one's products."""
    t = pl.program_id(1)
    qi, kj, flag = qi_ref[t], kj_ref[t], flag_ref[t]

    @pl.when(flag & _FIRST_OF_Q != 0)
    def _new_query_block():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked: bool):
        q = q_ref[0]                               # [bq, d]
        for first in range(0, block_k, chunk):
            k = k_ref[0, first:first + chunk, :]   # [chunk, d]
            v = v_ref[0, first:first + chunk, :]
            s = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                key0 = kj * block_k + first
                seen = []                  # what a visible pair has to meet
                if causal:
                    # how far the query lies ahead of the key: the two
                    # positions inside the tile and one scalar
                    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                             - key + (qi * block_q - key0))
                    seen.append(ahead >= 0)
                    if window is not None:
                        seen.append(ahead < window)
                if seq_k is not None:
                    seen.append(key < seq_k - key0)
                if seen:
                    s = jnp.where(functools.reduce(jnp.logical_and, seen), s,
                                  _NEG_INF)
            m_prev = m_scr[...]                    # [1, bq]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                 # [chunk, bq]
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=0,
                                                      keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

    pl.when(flag & _MASKED != 0)(lambda: tile(True))
    pl.when(flag & _MASKED == 0)(lambda: tile(False))

    @pl.when(flag & _LAST_OF_Q != 0)
    def _write_query_block():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).T.astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(denom)


@functools.lru_cache(maxsize=64)
def _fwd_call(bh, tq, tk, d, dtype, scale, causal, block_q, block_k, chunk,
              true_tk, interpret, window):
    """The forward ``pallas_call`` over q, k, v of ``[BH, T, D]`` (D padded
    to 128, T padded to block; ``true_tk`` is the unpadded key length:
    padded key positions are masked out), giving out and lse of ``[BH, 1,
    Tq]``: built once for its sizes, so that the layers of a model (and its
    ``init``, ``predict`` and train-step programs) trace the kernel once
    between them.  A chunk's scores and probabilities are float32 terms of
    ``[chunk, block_q]`` (1 MB each at 128 x 2,048, 4 MB where a caller's
    key block of 1,024 is walked whole against 1,024 queries), so the limit
    is raised to what the sizes need."""
    tiles = _fwd_tiles(tq, tk, block_q, block_k, true_tk, causal, window)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, chunk=chunk,
                               seq_k=true_tk if true_tk < tk else None,
                               window=window)

    def q_block(b, t, qi, kj, flag):
        return (b, qi[t], 0)

    def k_block(b, t, qi, kj, flag):
        return (b, kj[t], 0)

    def q_row(b, t, qi, kj, flag):
        return (b, 0, qi[t])

    size = jnp.dtype(dtype).itemsize
    # the streamed blocks and the output's, twice; out^T and its turned
    # copy; a handful of [chunk, block_q] float32 terms
    vmem = (4 * (block_q + block_k) * d * size + 2 * block_q * d * 4
            + 6 * block_q * chunk * 4)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(tiles[0])),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, block_k, d), k_block),
                pl.BlockSpec((1, block_k, d), k_block),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), q_block),
                # lse as [BH, 1, T]: a row, as the kernel holds it
                pl.BlockSpec((1, 1, block_q), q_row),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((d, block_q), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
        # the op's name in HLO and in a profile: a trace tells a windowed
        # layer's kernel from a full one's
        name=("flash_attention_fwd" if window is None
              else "flash_attention_window_fwd"),
    )
    return functools.partial(call, *tiles)


def _fwd_blocks(tq: int, tk: int, window: Optional[int]):
    """(query block, key block, key chunk) of the forward kernel, from the
    shapes.  A grid step costs what a small tile's products do, so tiles are
    large.  Where the triangle or the square is walked, 2,048 queries
    against 1,024 keys in chunks of 128: 16.5 ms at 32 x 16,384 x 128 causal
    on a v5e, where 1,024 x 1,024 reads 20.5 chunked or whole (a chunk of
    128 keys pays only with a query block long enough to stream past it),
    512 x 512 23.8 and 256 x 256 53.3.  On a band, whose edge tiles compute
    pairs outside it, 512 x 512 whole: a band of 2,048 reads 7.6 ms (8.9 in
    chunks; 7.4 at 1,024 x 1,024, 9.0 at 2,048 x 512), and a narrower band
    wastes less.  Multiples of the 128 lanes; a shorter sequence is one
    block.  The head's width decides nothing: 128 and 256 order the sizes
    alike."""
    bq, bk = (2048, 1024) if window is None else (512, 512)
    bq, bk = min(bq, _ceil_to(tq, 128)), min(bk, _ceil_to(tk, 128))
    return bq, bk, 128 if window is None else bk


# ---------------------------------------------------------------------------
# Pallas backward kernel
# ---------------------------------------------------------------------------

def _bwd_tiles(tq, tk, block_q, block_k, true_tk, causal, window):
    """The (key block, query block) tiles the backward visits, key block
    outermost, as three int32 arrays the kernel's index maps read: key
    block, query block, flags.  Causal: the tiles with a visible pair (the
    triangle); with a ``window`` the band's alone.  ``_MASKED`` is set only
    where a tile holds a hidden pair too: the diagonal crosses it, the
    band's far edge does, or it holds padded keys.  A key block no query
    sees (causal, ``Tk > Tq``) keeps one tile, fully masked, to write its
    zeros."""
    kjs, qis, flags = [], [], []
    for j in range(tk // block_k):
        k0, k1 = j * block_k, j * block_k + block_k - 1
        seen = []
        for i in range(tq // block_q):
            q0, q1 = i * block_q, i * block_q + block_q - 1
            if causal and q1 < k0:
                continue
            if window is not None and q0 - k1 >= window:
                continue
            masked = (k1 >= true_tk or (causal and q0 < k1)
                      or (window is not None and q1 - k0 >= window))
            seen.append((i, _MASKED if masked else 0))
        seen = seen or [(tq // block_q - 1, _MASKED)]
        for n, (i, flag) in enumerate(seen):
            kjs.append(j)
            qis.append(i)
            flags.append(flag | (_FIRST_OF_K if n == 0 else 0)
                         | (_LAST_OF_K if n == len(seen) - 1 else 0))
    return tuple(np.asarray(a, np.int32) for a in (kjs, qis, flags))


def _bwd_kernel(kj_ref, qi_ref, flag_ref, q_ref, k_ref, v_ref, g_ref,
                lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                dq_scr, dk_scr, dv_scr, *, scale: float, causal: bool,
                block_q: int, block_k: int, seq_k: int,
                window: Optional[int]):
    """Grid = (BH, tiles): a head's tiles in the order of ``_bwd_tiles``.
    Scores are held transposed, ``[block_k, block_q]``: lse and delta are
    then rows (lanes), and dv and dk are plain products.  dk and dv of the
    key block and dq of the WHOLE head accumulate in float32 VMEM scratch;
    ``scale`` is applied once, where they are written."""
    t = pl.program_id(1)
    kj, qi, flag = kj_ref[t], qi_ref[t], flag_ref[t]
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(t == 0)
    def _new_head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(flag & _FIRST_OF_K != 0)
    def _new_key_block():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(masked: bool):
        q, g = q_ref[0], g_ref[0]                  # [bq, d]
        k, v = k_ref[0], v_ref[0]                  # [bk, d]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = kpos < seq_k
            if causal:
                qpos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                mask = mask & (qpos >= kpos)
                if window is not None:
                    mask = mask & (qpos - kpos < window)
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])                # [bk, bq]
        dv_scr[...] += jnp.dot(p.astype(g.dtype), g,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, g, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0])).astype(q.dtype)
        dk_scr[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_scr[rows, :] += jax.lax.dot_general(
            ds, k, _TN, preferred_element_type=jnp.float32)

    pl.when(flag & _MASKED != 0)(lambda: tile(True))
    pl.when(flag & _MASKED == 0)(lambda: tile(False))

    @pl.when(flag & _LAST_OF_K != 0)
    def _write_key_block():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(1) - 1)
    def _write_head():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


@functools.lru_cache(maxsize=64)
def _bwd_call(bh, tq, tk, d, dtype, scale, causal, block_q, block_k, true_tk,
              interpret, window):
    """The backward ``pallas_call`` over q, k, v, g of ``[BH, T, D]`` and
    lse, delta of ``[BH, 1, Tq]`` (padded as for ``_fwd_call``), giving dq,
    dk, dv: built once for its sizes.  A head's dq stays in VMEM until its
    last tile (8 MB of float32 at 16,384 x 128 or 8,192 x 256, and the
    output block twice), so the limit is raised to what the sizes need; a
    head too long for the chip's VMEM is the compiler's to refuse."""
    tiles = _bwd_tiles(tq, tk, block_q, block_k, true_tk, causal, window)
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               seq_k=true_tk, window=window)

    def q_block(b, t, kj, qi, flag):
        return (b, qi[t], 0)

    def k_block(b, t, kj, qi, flag):
        return (b, kj[t], 0)

    def q_row(b, t, kj, qi, flag):
        return (b, 0, qi[t])

    size = jnp.dtype(dtype).itemsize
    # dq of a head, float32, and its double-buffered output; the streamed
    # blocks twice; dk, dv; a handful of [block_k, block_q] float32 terms
    vmem = (tq * d * (4 + 2 * size) + 4 * (block_q + 2 * block_k) * d * size
            + 2 * block_k * d * 4 + 8 * block_q * block_k * 4)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(tiles[0])),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, block_k, d), k_block),
                pl.BlockSpec((1, block_k, d), k_block),
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, 1, block_q), q_row),
                pl.BlockSpec((1, 1, block_q), q_row),
            ],
            out_specs=[
                pl.BlockSpec((1, tq, d), lambda b, t, *_: (b, 0, 0)),
                pl.BlockSpec((1, block_k, d), k_block),
                pl.BlockSpec((1, block_k, d), k_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((tq, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), dtype),
            jax.ShapeDtypeStruct((bh, tk, d), dtype),
            jax.ShapeDtypeStruct((bh, tk, d), dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
        name=("flash_attention_bwd" if window is None
              else "flash_attention_window_bwd"),
    )
    return functools.partial(call, *tiles)


def _bwd_blocks(tq: int, tk: int, window: Optional[int]):
    """(query block, key block) of the backward kernel.  A grid step costs
    about what a 256 x 256 tile's products do, so tiles are large: 1,024
    where the triangle or the square is walked (32.4 ms at 32 x 16,384 x 128
    against 36.7 at 512 and 76.4 at 256, on a v5e); 512 on a band, whose
    edge tiles compute pairs outside it (a band of 2,048: 11.2 ms at 512,
    12.0 at 1,024 x 512, 20.6 at 256).  Multiples of the 128 lanes; a
    shorter sequence is one block."""
    block = 1024 if window is None else 512
    return min(block, _ceil_to(tq, 128)), min(block, _ceil_to(tk, 128))


def _padded_pallas_bwd(q3, k3, v3, out, lse, g, scale, causal, interpret,
                       window=None):
    """The backward kernel on q, k, v, out, g of ``[BH, T, D]``: T padded to
    the blocks (zero rows add nothing; padded keys are masked), D to the
    128-lane tile."""
    bh, tq, d = q3.shape
    tk = k3.shape[1]
    bq, bk = _bwd_blocks(tq, tk, window)
    tq_p, tk_p, d_p = _ceil_to(tq, bq), _ceil_to(tk, bk), _ceil_to(d, 128)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)

    def pad(a, t_p):
        return jnp.pad(a, ((0, 0), (0, t_p - a.shape[1]), (0, d_p - d)))

    def row(a):
        return jnp.pad(a, ((0, 0), (0, tq_p - tq)))[:, None, :]
    dq, dk, dv = _bwd_call(
        bh, tq_p, tk_p, d_p, jnp.dtype(q3.dtype), scale, causal, bq, bk, tk,
        interpret, window)(pad(q3, tq_p), pad(k3, tk_p), pad(v3, tk_p),
                           pad(g, tq_p), row(lse), row(delta))
    return dq[:, :tq, :d], dk[:, :tk, :d], dv[:, :tk, :d]


# ---------------------------------------------------------------------------
# Blocked pure-JAX math (forward and backward off the chip)
# ---------------------------------------------------------------------------

def _blocked_fwd_jax(q, k, v, scale, causal, block_k):
    """Online-softmax forward as a lax.scan over k blocks.  [BH, T, D]."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    tk_p = _ceil_to(tk, block_k)
    k = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0)))
    nk = tk_p // block_k
    kb = k.reshape(bh, nk, block_k, d)
    vb = v.reshape(bh, nk, block_k, d)
    qf = q.astype(jnp.float32)
    qpos = jnp.arange(tq)[:, None]

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kj, vj, j = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, kj.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        kpos = j * block_k + jnp.arange(block_k)[None, :]
        mask = kpos < tk
        if causal:
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bqk,bkd->bqd", p,
                                       vj.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((bh, tq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((bh, tq, 1), jnp.float32),
            jnp.zeros((bh, tq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        step, init,
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nk)))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l).astype(q.dtype)
    lse = (m + jnp.log(l))[..., 0]
    return out, lse


#: the causal backward walks the k blocks in this many groups, each with
#: the queries that can see it (see _blocked_bwd_jax)
_CAUSAL_BWD_GROUPS = 8


def _blocked_bwd_jax(q, k, v, out, lse, g, scale, causal, block_k):
    """Flash-attention-2 style backward: rematerialize p per k block.

    Causal self-attention needs only the lower triangle: queries before a
    k block's first position get nothing from it.  A ``lax.scan`` wants one
    shape for all its steps, so the k blocks are walked in up to
    ``_CAUSAL_BWD_GROUPS`` groups and each group's scan takes the queries
    from the group's first key on: (G + 1) / 2G of the full square's work
    (56% at G = 8) instead of all of it."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    tk_p = _ceil_to(tk, block_k)
    kp = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0)))
    nk = tk_p // block_k
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = out.astype(jnp.float32)
    delta = jnp.sum(of * gf, axis=-1, keepdims=True)        # [BH, Tq, 1]
    kb = kp.reshape(bh, nk, block_k, d).swapaxes(0, 1)
    vb = vp.reshape(bh, nk, block_k, d).swapaxes(0, 1)

    def walk(first_block, blocks, row0):
        """k blocks ``first_block ..`` against the queries from ``row0``."""
        qf_, gf_, lse_, delta_ = (a[:, row0:] for a in (qf, gf, lse, delta))
        qpos = row0 + jnp.arange(tq - row0)[:, None]

        def step(dq, blk):
            kj, vj, j = blk
            kjf = kj.astype(jnp.float32)
            vjf = vj.astype(jnp.float32)
            s = jnp.einsum("bqd,bkd->bqk", qf_, kjf,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * block_k + jnp.arange(block_k)[None, :]
            mask = kpos < tk
            if causal:
                mask = mask & (qpos >= kpos)
            s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp(s - lse_[..., None])                 # softmax probs
            dp = jnp.einsum("bqd,bkd->bqk", gf_, vjf)
            ds = p * (dp - delta_) * scale
            dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kjf)
            dk_j = jnp.einsum("bqk,bqd->bkd", ds, qf_)
            dv_j = jnp.einsum("bqk,bqd->bkd", p, gf_)
            return dq, (dk_j, dv_j)

        sel = slice(first_block, first_block + blocks)
        return jax.lax.scan(
            step, jnp.zeros((bh, tq - row0, d), jnp.float32),
            (kb[sel], vb[sel], jnp.arange(first_block, first_block + blocks)))

    groups = 1
    if causal and tq == tk:
        groups = max(n for n in range(1, _CAUSAL_BWD_GROUPS + 1)
                     if nk % n == 0)
    per = nk // groups
    dq = jnp.zeros((bh, tq, d), jnp.float32)
    dks, dvs = [], []
    for i in range(groups):
        row0 = i * per * block_k
        dq_i, (dk_i, dv_i) = walk(i * per, per, row0)
        dq = dq.at[:, row0:].add(dq_i) if row0 else dq_i
        dks.append(dk_i)
        dvs.append(dv_i)
    dk, dv = jnp.concatenate(dks), jnp.concatenate(dvs)
    dk = dk.swapaxes(0, 1).reshape(bh, tk_p, d)[:, :tk]
    dv = dv.swapaxes(0, 1).reshape(bh, tk_p, d)[:, :tk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# The same two passes over a band (sliding-window attention)
# ---------------------------------------------------------------------------

def _band_fwd_jax(q, k, v, scale, window, block):
    """Windowed causal forward, a query block at a time: the block's band
    (``block + window - 1`` keys ending at its last row) is sliced out of k
    and v and takes one plain softmax.  Work ``T * (block + window)``."""
    bh, t, d = q.shape
    nq = -(-t // block)
    span = block + window - 1
    qb = jnp.pad(q, ((0, 0), (0, nq * block - t), (0, 0))).reshape(
        bh, nq, block, d).swapaxes(0, 1)
    # key position p sits at index p + window - 1 of the padded k
    kp = jnp.pad(k, ((0, 0), (window - 1, nq * block - t), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window - 1, nq * block - t), (0, 0)))

    def rows(_, blk):
        qi, i = blk
        kj = jax.lax.dynamic_slice_in_dim(kp, i * block, span, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(vp, i * block, span, axis=1)
        s = jnp.einsum("bqd,bkd->bqk", qi.astype(jnp.float32),
                       kj.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        qpos = i * block + jnp.arange(block)[:, None]
        kpos = i * block - (window - 1) + jnp.arange(span)[None, :]
        mask = (kpos >= 0) & (kpos < t) & (kpos <= qpos) \
            & (qpos - kpos < window)
        s = jnp.where(mask, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        out = jnp.einsum("bqk,bkd->bqd", p, vj.astype(jnp.float32)) / l
        return None, (out.astype(q.dtype), (m + jnp.log(l))[..., 0])

    _, (out, lse) = jax.lax.scan(rows, None, (qb, jnp.arange(nq)))
    out = out.swapaxes(0, 1).reshape(bh, nq * block, d)[:, :t]
    lse = lse.swapaxes(0, 1).reshape(bh, nq * block)[:, :t]
    return out, lse


def _band_bwd_jax(q, k, v, out, lse, g, scale, window, block):
    """Windowed causal backward, a k block at a time: the queries that see
    the block (``block + window - 1`` rows from its first key on) are
    sliced out, p is rematerialized for them, dk and dv of the block come
    out whole and dq is added into its rows.  Work ``T * (block + window)``
    where the plain causal walk does ``T^2 / 2``."""
    bh, t, d = q.shape
    nk = -(-t // block)
    span = block + window - 1
    rows = nk * block + window - 1        # every slice lies inside the pad
    of, gf = out.astype(jnp.float32), g.astype(jnp.float32)
    delta = jnp.sum(of * gf, axis=-1, keepdims=True)

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, rows - t)) + ((0, 0),) * (a.ndim - 2))
    qf, gf, lse_p, delta = (pad(a) for a in (
        q.astype(jnp.float32), gf, lse, delta))
    kb = jnp.pad(k, ((0, 0), (0, nk * block - t), (0, 0))).reshape(
        bh, nk, block, d).swapaxes(0, 1)
    vb = jnp.pad(v, ((0, 0), (0, nk * block - t), (0, 0))).reshape(
        bh, nk, block, d).swapaxes(0, 1)

    def step(dq, blk):
        kj, vj, j = blk
        kjf, vjf = kj.astype(jnp.float32), vj.astype(jnp.float32)
        q_, g_, lse_, delta_ = (
            jax.lax.dynamic_slice_in_dim(a, j * block, span, axis=1)
            for a in (qf, gf, lse_p, delta))
        s = jnp.einsum("bqd,bkd->bqk", q_, kjf,
                       preferred_element_type=jnp.float32) * scale
        qpos = j * block + jnp.arange(span)[:, None]
        kpos = j * block + jnp.arange(block)[None, :]
        mask = (qpos < t) & (kpos < t) & (kpos <= qpos) \
            & (qpos - kpos < window)
        p = jnp.where(mask, jnp.exp(s - lse_[..., None]), 0.0)
        dp = jnp.einsum("bqd,bkd->bqk", g_, vjf)
        ds = p * (dp - delta_) * scale
        dq_rows = jax.lax.dynamic_slice_in_dim(dq, j * block, span, axis=1)
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, dq_rows + jnp.einsum("bqk,bkd->bqd", ds, kjf), j * block,
            axis=1)
        return dq, (jnp.einsum("bqk,bqd->bkd", ds, q_),
                    jnp.einsum("bqk,bqd->bkd", p, g_))

    dq, (dk, dv) = jax.lax.scan(
        step, jnp.zeros((bh, rows, d), jnp.float32),
        (kb, vb, jnp.arange(nk)))
    dk = dk.swapaxes(0, 1).reshape(bh, nk * block, d)[:, :t]
    dv = dv.swapaxes(0, 1).reshape(bh, nk * block, d)[:, :t]
    return dq[:, :t].astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = False,
                  window: Optional[int] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Materialized-logits reference ([B, T, H, D]) for differential tests;
    ``window`` and ``scale`` as in :func:`flash_attention`."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32)) if scale is None \
        else s * scale
    if causal:
        tq, tk = s.shape[-2:]
        seen = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = seen >= 0 if window is None else (seen >= 0) & (seen < window)
        s = jnp.where(mask, s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, block_q, block_k, window=None, scale=None):
    out, _ = _flash_fwd_dispatch(q3, k3, v3, causal, block_q, block_k,
                                 window, scale)
    return out


INTERPRET = False  # tests set True to exercise the Pallas kernel on CPU


def _softmax_scale(scale: Optional[float], d: int) -> float:
    """The logits' multiplier: 1/sqrt(D) unless the caller names one."""
    return 1.0 / (d ** 0.5) if scale is None else float(scale)


def _jax_block(block_k: Optional[int], tk: int) -> int:
    """The k block of the ``jax.numpy`` forms: the caller's, else 256."""
    return min(block_k or 256, tk)


def _flash_fwd_dispatch(q3, k3, v3, causal, block_q, block_k, window=None,
                        scale=None):
    scale = _softmax_scale(scale, q3.shape[-1])
    if jax.default_backend() == "tpu" or INTERPRET:
        # on the chip the compiled kernel or the compiler's error: no
        # interpret mode, no pure-JAX stand-in on the device the kernel was
        # written for
        return _padded_pallas(q3, k3, v3, scale, causal, block_q, block_k,
                              interpret=jax.default_backend() != "tpu",
                              window=window)
    if window is not None:
        return _band_fwd_jax(q3, k3, v3, scale, window,
                             _jax_block(block_k, k3.shape[1]))
    return _blocked_fwd_jax(q3, k3, v3, scale, causal,
                            _jax_block(block_k, k3.shape[1]))


def _padded_pallas(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                   window=None):
    """The forward kernel on q, k, v of ``[BH, T, D]``: T padded to the
    blocks (the caller's, else ``_fwd_blocks``'; padded keys are masked,
    padded queries sliced off), D to the 128-lane tile."""
    bh, tq, d = q3.shape
    tk = k3.shape[1]
    bq, bk, chunk = _fwd_blocks(tq, tk, window)
    if block_q is not None:
        bq = min(block_q, _ceil_to(tq, 8))
    if block_k is not None:
        bk = chunk = min(block_k, _ceil_to(tk, 8))
    tq_p, tk_p, d_p = _ceil_to(tq, bq), _ceil_to(tk, bk), _ceil_to(d, 128)
    qp = jnp.pad(q3, ((0, 0), (0, tq_p - tq), (0, d_p - d)))
    kp = jnp.pad(k3, ((0, 0), (0, tk_p - tk), (0, d_p - d)))
    vp = jnp.pad(v3, ((0, 0), (0, tk_p - tk), (0, d_p - d)))
    out, lse = _fwd_call(bh, tq_p, tk_p, d_p, jnp.dtype(q3.dtype), scale,
                         causal, bq, bk, chunk, tk, interpret, window)(
                             qp, kp, vp)
    return out[:, :tq, :d], lse[:, 0, :tq]


def _flash_vjp_fwd(q3, k3, v3, causal, block_q, block_k, window=None,
                   scale=None):
    out, lse = _flash_fwd_dispatch(q3, k3, v3, causal, block_q, block_k,
                                   window, scale)
    # named, so that an enclosing jax.checkpoint can be told to keep them
    # (policy save_only_these_names): [BH, T, D] + [BH, T] kept spare the
    # recomputation a second run of the whole kernel
    out = checkpoint_name(out, "flash_attention_out")
    lse = checkpoint_name(lse, "flash_attention_lse")
    return out, (q3, k3, v3, out, lse)


def _flash_bwd_dispatch(q3, k3, v3, out, lse, g, causal, block_k,
                        window=None, scale=None):
    """As ``_flash_fwd_dispatch``: on the chip the compiled kernel or the
    compiler's error, elsewhere the blocked ``jax.numpy`` form, or the
    kernel in interpret mode when a test asks for it."""
    scale = _softmax_scale(scale, q3.shape[-1])
    if jax.default_backend() == "tpu" or INTERPRET:
        return _padded_pallas_bwd(q3, k3, v3, out, lse, g, scale, causal,
                                  interpret=jax.default_backend() != "tpu",
                                  window=window)
    if window is not None:
        return _band_bwd_jax(q3, k3, v3, out, lse, g, scale, window,
                             _jax_block(block_k, k3.shape[1]))
    return _blocked_bwd_jax(q3, k3, v3, out, lse, g, scale, causal,
                            _jax_block(block_k, k3.shape[1]))


def _flash_vjp_bwd(causal, block_q, block_k, window, scale, res, g):
    return _flash_bwd_dispatch(*res, g, causal, block_k, window, scale)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Flash attention over [B, T, H, D] tensors: ``softmax(scale * q k^T)
    v``, ``scale`` 1/sqrt(D) unless given (a model that publishes its own
    multiplier passes it; forward and backward, kernels and ``jax.numpy``
    forms alike take it as a static number).

    Differentiable; O(T·D) memory.  Matches :func:`mha_reference` to fp
    tolerance (see tests/test_ops.py).  ``block_q`` and ``block_k`` are left
    at None: both kernels take their blocks from the shapes (``_fwd_blocks``,
    ``_bwd_blocks``).  A value is honoured by the forward kernel (on the
    chip a multiple of the 128 lanes for ``block_q``, of 16 sublanes for
    ``block_k``) and, ``block_k``, by the ``jax.numpy`` forms that stand in
    off the chip (256 when None).  ``window`` (causal self-attention only):
    position i sees keys ``i - window + 1 .. i``; forward and backward then
    visit the band's tiles alone.  A window that covers the row is plain
    causal attention and takes its path.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if window is not None:
        if not causal or tq != tk or window < 1:
            raise ValueError("window needs causal self-attention and a "
                             f"window >= 1; got causal={causal}, Tq={tq}, "
                             f"Tk={tk}, window={window}")
        if window >= tk:
            window = None
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    out = _flash(q3, k3, v3, causal, block_q, block_k, window, scale)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
