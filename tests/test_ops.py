"""Differential tests: flash attention vs materialized reference.

Mirrors the reference's TFNet/TorchNet differential-test pattern (SURVEY.md
§4.4): run both implementations on the same inputs, compare within tolerance.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import flash_attention, mha_reference

# the MODULE: ``import analytics_zoo_tpu.ops.flash_attention as m`` binds the
# function of the same name that ops/__init__ re-exports, and setting
# INTERPRET on that would leave the kernel unexercised
fa_mod = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")


def _qkv(rng, b=2, t=64, h=2, d=16):
    shape = (b, t, h, d)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(rng, causal):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(rng, causal):
    q, k, v = _qkv(rng, b=1, t=32, h=2, d=8)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=8,
                               block_k=8).sum()

    def loss_ref(q, k, v):
        return mha_reference(q, k, v, causal=causal).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_pallas_kernel_interpret_mode(rng):
    """Run the actual Pallas kernel (interpret mode) against the reference,
    including a T that does not divide the block size (padding path)."""
    q, k, v = _qkv(rng, b=1, t=24, h=1, d=8)
    fa_mod.INTERPRET = True
    try:
        out = flash_attention(q, k, v, block_q=16, block_k=16)
    finally:
        fa_mod.INTERPRET = False
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pallas_kernel_interpret_causal(rng):
    q, k, v = _qkv(rng, b=1, t=32, h=1, d=8)
    fa_mod.INTERPRET = True
    try:
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    finally:
        fa_mod.INTERPRET = False
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# -- the forward kernels (flash_attention_fwd / flash_attention_window_fwd) -----
#
# Self-attention: {not causal, causal, a window of 50} x {256 rows: a
# multiple of every block here; 200: T padded to the block, padded keys
# masked, padded queries sliced off} x heads of {64, 128, 256} x {blocks
# from the shapes; explicit and unequal}.  Then Tq != Tk (blocks unequal the
# other way among them), a multiplier of the model's own, and rows long
# enough for blocks from the shapes to make several tiles (2,048 x 1,024 on
# the triangle or square, 512 x 512 on a band).
# name: tq, tk, d, causal, window, (block_q, block_k), scale
FWD_CASES = {
    f"{mode}_t{t}_d{d}_{'x'.join(map(str, blocks)) if blocks else 'shapes'}":
        (t, t, d, mode != "full", 50 if mode == "window" else None, blocks,
         None)
    for mode in ("full", "causal", "window") for t in (256, 200)
    for d in (64, 128, 256) for blocks in (None, (64, 32))}
FWD_CASES.update({
    "cross_full_tk_gt_tq": (200, 700, 64, False, None, None, None),
    "cross_full_tk_gt_tq_64x32": (200, 700, 64, False, None, (64, 32), None),
    "cross_full_tq_gt_tk": (700, 200, 64, False, None, None, None),
    "cross_full_tq_gt_tk_32x64": (700, 200, 64, False, None, (32, 64), None),
    "cross_causal_tk_gt_tq": (200, 700, 64, True, None, None, None),
    "cross_causal_tk_gt_tq_64x32": (200, 700, 64, True, None, (64, 32), None),
    "scale_of_the_models_own_d64": (200, 200, 64, True, None, None, 1 / 64),
    "scale_with_a_window_64x32": (256, 256, 64, True, 50, (64, 32), 0.3),
    "triangle_of_three_blocks": (2500, 2500, 64, True, None, None, None),
    "square_of_two_by_three": (1100, 2100, 64, False, None, None, None),
    "band_of_600_in_blocks_of_512": (2048, 2048, 128, True, 600, None, None),
    "band_narrower_than_a_block": (1100, 1100, 64, True, 100, None, None),
})


def _lse_reference(q, k, causal, window, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
        q.shape[-1] ** -0.5 if scale is None else scale)
    if causal:
        seen = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])
        s = jnp.where((seen >= 0) if window is None
                      else (seen >= 0) & (seen < window), s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)        # [B, H, Tq]
    return lse.reshape(-1, q.shape[1])


@pytest.mark.parametrize("case", list(FWD_CASES))
def test_forward_kernel_matches_reference_and_blocked_form(rng, case,
                                                           monkeypatch):
    """The Pallas forward (interpret mode), out AND lse (the backward reads
    it), against ``mha_reference`` and against the ``jax.numpy`` form that
    stands in for it off the chip."""
    tq, tk, d, causal, window, blocks, scale = FWD_CASES[case]
    q, k, v, _ = _bwd_inputs(rng, tq, tk, d, jnp.float32)
    bq, bk = blocks or (None, None)
    want = mha_reference(q, k, v, causal=causal, window=window, scale=scale)
    want_lse = _lse_reference(q, k, causal, window, scale)

    def run(interpret):
        monkeypatch.setattr(fa_mod, "INTERPRET", interpret)
        q3, k3, v3 = (a.transpose(0, 2, 1, 3).reshape(-1, a.shape[1], d)
                      for a in (q, k, v))
        out, lse = fa_mod._flash_fwd_dispatch(q3, k3, v3, causal, bq, bk,
                                              window, scale)
        assert out.shape == (2, tq, d) and lse.shape == (2, tq)
        assert lse.dtype == jnp.float32
        return out.reshape(1, 2, tq, d).transpose(0, 2, 1, 3), lse

    for out, lse in (run(True), run(False)):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=5e-6, rtol=5e-6)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   atol=5e-6, rtol=5e-6)
    # the public entry takes the same blocks (None: from the shapes)
    got = flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=5e-6)


def test_forward_kernel_keeps_bf16_operands_and_float32_statistics(
        rng, monkeypatch):
    """bf16 q, k, v go to the MXU as they are (p cast to v's dtype for the
    second product); out comes back bf16 within its rounding, lse float32
    and as close as float32 operands leave it."""
    q, k, v, _ = _bwd_inputs(rng, 1024, 1024, 128, jnp.bfloat16)
    monkeypatch.setattr(fa_mod, "INTERPRET", True)
    q3, k3, v3 = (a.transpose(0, 2, 1, 3).reshape(2, 1024, 128)
                  for a in (q, k, v))
    out, lse = fa_mod._flash_fwd_dispatch(q3, k3, v3, True, None, None)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    want = mha_reference(*f32, causal=True).transpose(0, 2, 1, 3)[0]
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) \
        <= 2 ** -8 * float(jnp.abs(want).max()) + 2e-3
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(_lse_reference(*f32[:2], True, None,
                                                   None)), atol=1e-5)


# (tq, tk, block_q, block_k, true_tk, causal, window): small enough to count
# every pair; rows and windows that are no multiple of a block, unequal
# blocks both ways, Tq != Tk both ways, padded keys
TILE_CASES = [
    (64, 64, 16, 16, 64, False, None), (64, 64, 16, 16, 64, True, None),
    (64, 64, 16, 16, 64, True, 16), (64, 64, 16, 16, 64, True, 19),
    (96, 96, 32, 16, 90, True, 19), (96, 96, 16, 32, 90, True, 19),
    (96, 96, 32, 16, 90, True, 1), (64, 160, 16, 32, 150, True, None),
    (64, 160, 16, 32, 150, False, None), (160, 64, 32, 16, 50, False, None),
    (128, 128, 64, 16, 128, True, 40), (128, 128, 16, 64, 128, True, 40),
]


@pytest.mark.parametrize("tq,tk,bq,bk,true_tk,causal,window", TILE_CASES)
def test_forward_tiles_by_hand(tq, tk, bq, bk, true_tk, causal, window):
    """``_fwd_tiles`` against every pair counted in numpy: each visible pair
    lies in exactly one tile, no tile is without one, ``_MASKED`` is set
    where a tile holds a hidden pair too and nowhere else, and each query
    block opens and closes once, its tiles together and in key order."""
    qpos, kpos = np.arange(tq)[:, None], np.arange(tk)[None, :]
    visible = np.broadcast_to(kpos < true_tk, (tq, tk)).copy()
    if causal:
        visible &= kpos <= qpos
    if window is not None:
        visible &= qpos - kpos < window
    qi, kj, flag = fa_mod._fwd_tiles(tq, tk, bq, bk, true_tk, causal, window)
    assert qi.dtype == kj.dtype == flag.dtype == np.int32
    covered = np.zeros((tq, tk), int)
    for i, j, f in zip(qi, kj, flag):
        tile = visible[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
        assert tile.any(), (i, j)
        assert bool(f & fa_mod._MASKED) == (not tile.all()), (i, j)
        covered[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk] += 1
    assert (covered[visible] == 1).all() and covered.max() == 1
    assert list(qi) == sorted(qi) and set(qi) == set(range(tq // bq))
    for i in set(qi):
        mine, keys = flag[qi == i], kj[qi == i]
        assert list(keys) == sorted(keys)
        assert [bool(f & fa_mod._FIRST_OF_Q) for f in mine] \
            == [n == 0 for n in range(len(mine))]
        assert [bool(f & fa_mod._LAST_OF_Q) for f in mine] \
            == [n == len(mine) - 1 for n in range(len(mine))]


# What the forward walks at the cells' shapes, blocks from the shapes: tiles a
# head, how many of them build a mask, pairs computed, pairs visible.  The
# parent's rectangular grid stepped over 4,096 / 576 / 1,024 blocks of 256 a
# head and fetched k and v for each.
CELL_WALKS = {
    "trinity_full_16384_d128": (16384, None, (2048, 1024, 128),
                                72, 16, 150_994_944, 134_225_920),
    "trinity_band_2048_of_16384": (16384, 2048, (512, 512, 512),
                                   150, 60, 39_321_600, 31_458_304),
    "qwen_and_granite_8192": (8192, None, (2048, 1024, 128),
                              20, 8, 41_943_040, 33_558_528),
}


@pytest.mark.parametrize("cell", list(CELL_WALKS))
def test_forward_walk_at_the_cells_shapes(cell):
    t, window, blocks, tiles, masked, computed, visible = CELL_WALKS[cell]
    assert fa_mod._fwd_blocks(t, t, window) == blocks
    bq, bk, _ = blocks
    qi, kj, flag = fa_mod._fwd_tiles(t, t, bq, bk, t, True, window)
    assert len(qi) == tiles
    assert int(np.count_nonzero(flag & fa_mod._MASKED)) == masked
    assert tiles * bq * bk == computed
    assert sum(min(i + 1, window or t) for i in range(t)) == visible
    # nothing above the diagonal, nothing before the band
    assert (kj * bk <= qi * bq + bq - 1).all()
    if window is not None:
        assert (qi * bq - (kj * bk + bk - 1) < window).all()


@pytest.mark.parametrize("tq,tk,window,want", [
    (200, 200, None, (256, 256, 128)), (200, 700, None, (256, 768, 128)),
    (1024, 1024, None, (1024, 1024, 128)), (5000, 300, None, (2048, 384, 128)),
    (200, 200, 50, (256, 256, 256)), (4096, 4096, 1024, (512, 512, 512))])
def test_forward_blocks_come_from_the_shapes(tq, tk, window, want):
    """Multiples of the 128 lanes, a short sequence one block, the key
    chunk a divisor of the key block (the block itself on a band)."""
    assert fa_mod._fwd_blocks(tq, tk, window) == want


# -- the backward kernels (flash_attention_bwd / flash_attention_window_bwd) ---
#
# tq, tk, d, causal, window, dtype: causal and not, a band of 2,048 in 4,096
# (whole tiles inside the band, masked ones at its two edges), 50 in 200
# (nothing aligned: T padded to the block, D to the lanes, padded keys
# masked), a window that covers the row (flash_attention takes the causal
# path), heads of 64 / 128 / 256, Tq != Tk, float32 and bf16 operands
BWD_CASES = {
    "causal_d64": (256, 256, 64, True, None, jnp.float32),
    "full_d64": (256, 256, 64, False, None, jnp.float32),
    "causal_d128_two_blocks": (1536, 1536, 128, True, None, jnp.float32),
    "causal_d256": (640, 640, 256, True, None, jnp.float32),
    "window_2048_of_4096": (4096, 4096, 128, True, 2048, jnp.float32),
    "window_50_of_200": (200, 200, 64, True, 50, jnp.float32),
    "window_covers_the_row": (200, 200, 64, True, 200, jnp.float32),
    "unaligned_causal": (200, 200, 40, True, None, jnp.float32),
    "cross_tq_gt_tk": (700, 200, 64, False, None, jnp.float32),
    "cross_tq_lt_tk": (200, 700, 64, False, None, jnp.float32),
    "causal_bf16": (1024, 1024, 128, True, None, jnp.bfloat16),
    "window_bf16": (1024, 1024, 128, True, 300, jnp.bfloat16),
    "full_bf16_d256": (256, 640, 256, False, None, jnp.bfloat16),
}


def _bwd_inputs(rng, tq, tk, d, dtype, heads=2):
    q, g = (jnp.asarray(rng.normal(size=(1, tq, heads, d)), dtype)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, tk, heads, d)), dtype)
            for _ in range(2))
    return q, k, v, g


@pytest.mark.parametrize("case", list(BWD_CASES))
def test_backward_kernel_matches_reference_and_blocked_form(rng, case,
                                                            monkeypatch):
    """The Pallas backward (interpret mode) against ``mha_reference``'s
    gradients — to 1e-5 of their size in float32 — and against the blocked
    ``jax.numpy`` backward it replaces on the chip, from the same
    residuals."""
    tq, tk, d, causal, window, dtype = BWD_CASES[case]
    q, k, v, g = _bwd_inputs(rng, tq, tk, d, dtype)
    f32 = [a.astype(jnp.float32) for a in (q, k, v, g)]

    def grads(attend, q, k, v, g):
        return jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window)

    want = grads(lambda q, k, v: mha_reference(q, k, v, causal=causal,
                                               window=window), *f32)
    blocked = grads(flash, q, k, v, g)
    monkeypatch.setattr(fa_mod, "INTERPRET", True)
    got = grads(flash, q, k, v, g)
    exact = dtype == jnp.float32
    for a, b, c in zip(got, blocked, want):
        assert a.dtype == dtype and a.shape == c.shape
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        size = np.abs(c).max()
        assert np.abs(a - c).max() <= (1e-5 if exact else 3e-2) * size
        assert np.abs(a - b).max() <= (1e-5 if exact else 2e-2) * size


def test_backward_kernel_band_edges_by_hand(rng, monkeypatch):
    """The band's edge tiles, worked out in numpy: the first row (one key:
    no gradient for q), the last row, the last key (one query sees it) and
    the first key (the first ``window`` rows see it).  600 rows in blocks of
    512 with a window of 130: every tile is an edge tile of some kind."""
    t, d, window = 600, 64, 130
    q, k, v, g = (np.asarray(a[0, :, 0], np.float64)
                  for a in _bwd_inputs(rng, t, t, d, jnp.float32, heads=1))
    scale = d ** -0.5

    def row(i):
        """p, ds of query i over the keys it sees, and their first index."""
        lo = max(0, i - window + 1)
        s = k[lo:i + 1] @ q[i] * scale
        p = np.exp(s - s.max())
        p /= p.sum()
        dp = v[lo:i + 1] @ g[i]
        return p, p * (dp - p @ dp) * scale, lo

    monkeypatch.setattr(fa_mod, "INTERPRET", True)
    as4 = lambda a: jnp.asarray(a, jnp.float32)[None, :, None, :]
    dq, dk, dv = (np.asarray(a[0, :, 0]) for a in jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window) * as4(g)),
        argnums=(0, 1, 2))(as4(q), as4(k), as4(v)))
    np.testing.assert_allclose(dq[0], 0.0, atol=1e-6)
    p, ds, lo = row(t - 1)
    np.testing.assert_allclose(dq[t - 1], ds @ k[lo:], atol=1e-5)
    np.testing.assert_allclose(dv[t - 1], p[-1] * g[t - 1], atol=1e-5)
    np.testing.assert_allclose(dk[t - 1], ds[-1] * q[t - 1], atol=1e-5)
    first = [row(i) for i in range(window)]      # the rows that see key 0
    np.testing.assert_allclose(
        dv[0], sum(p[0] * g[i] for i, (p, _, _) in enumerate(first)),
        atol=1e-5)
    np.testing.assert_allclose(
        dk[0], sum(ds[0] * q[i] for i, (_, ds, _) in enumerate(first)),
        atol=1e-5)
    # ... and row ``window`` no longer does
    assert row(window)[2] == 1


def test_backward_tiles_are_the_triangle_and_the_band():
    """``_bwd_tiles``: no tile above the diagonal or outside the band, masks
    on the tiles an edge crosses and on no other."""
    kj, qi, flag = fa_mod._bwd_tiles(2048, 2048, 512, 512, 2048, True, None)
    assert sorted(zip(kj, qi)) == [(j, i) for j in range(4)
                                   for i in range(j, 4)]
    assert [bool(f & fa_mod._MASKED) for f in flag] == \
        [j == i for j, i in zip(kj, qi)]
    # a band of 1,024 in blocks of 512: key block j is seen by query blocks
    # j .. j + 2, the diagonal's and the far edge's tiles masked
    kj, qi, flag = fa_mod._bwd_tiles(4096, 4096, 512, 512, 4096, True, 1024)
    assert sorted(zip(kj, qi)) == [(j, i) for j in range(8)
                                   for i in range(j, min(j + 3, 8))]
    assert [bool(f & fa_mod._MASKED) for f in flag] == \
        [i != j + 1 for j, i in zip(kj, qi)]
    # every key block starts and ends once, in order
    for tiles in (fa_mod._bwd_tiles(4096, 4096, 512, 512, 4096, True, 1024),
                  fa_mod._bwd_tiles(512, 1536, 512, 512, 1500, True, None),
                  fa_mod._bwd_tiles(1024, 512, 256, 512, 300, False, None)):
        kj, qi, flag = tiles
        assert list(kj) == sorted(kj)
        for j in set(kj):
            mine = flag[kj == j]
            assert mine[0] & fa_mod._FIRST_OF_K
            assert mine[-1] & fa_mod._LAST_OF_K
            assert not any(f & fa_mod._FIRST_OF_K for f in mine[1:])
            assert not any(f & fa_mod._LAST_OF_K for f in mine[:-1])
    # causal with more keys than queries: the key blocks no query sees
    # keep one masked tile each, which writes their zeros
    kj, qi, flag = fa_mod._bwd_tiles(512, 1536, 512, 512, 1500, True, None)
    assert list(kj) == [0, 1, 2] and all(f & fa_mod._MASKED for f in flag)


def test_flash_under_jit_and_mha_layer(rng):
    """use_flash=True path of nn.MultiHeadAttention compiles and runs."""
    import analytics_zoo_tpu.nn as nn
    x = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
    mha = nn.MultiHeadAttention(num_heads=4, use_flash=True)
    variables = mha.init(jax.random.PRNGKey(0), x)
    out, _ = jax.jit(lambda v, x: mha.apply(v, x))(variables, x)
    assert out.shape == (2, 16, 32)


def test_fused_softmax_xent_matches_naive():
    """Loss value AND all three gradients must match the materialized
    logits path (chunked recompute is numerics-preserving in f32)."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused_softmax_xent
    rng = np.random.default_rng(0)
    B, S, D, V = 2, 8, 16, 50
    h = rng.normal(size=(B, S, D)).astype(np.float32)
    w = (rng.normal(size=(D, V)) * 0.1).astype(np.float32)
    labels = rng.integers(0, V, (B, S))

    bias = (rng.normal(size=(V,)) * 0.1).astype(np.float32)

    def naive(h, w, bias):
        logits = (h @ w).astype(jnp.float32) + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        corr = jnp.take_along_axis(
            logits, jnp.asarray(labels)[..., None], axis=-1)[..., 0]
        return (lse - corr).mean()

    def fused(h, w, bias):
        return fused_softmax_xent(h, w, jnp.asarray(labels), 4, bias=bias)

    ln, gn = jax.value_and_grad(naive, argnums=(0, 1, 2))(h, w, bias)
    lf, gf = jax.value_and_grad(fused, argnums=(0, 1, 2))(h, w, bias)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-6)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fused_softmax_xent_bf16_close():
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused_softmax_xent
    rng = np.random.default_rng(1)
    h = rng.normal(size=(1, 16, 8)).astype(np.float32)
    w = (rng.normal(size=(8, 30)) * 0.2).astype(np.float32)
    labels = jnp.asarray(rng.integers(0, 30, (1, 16)))
    lf32 = fused_softmax_xent(jnp.asarray(h), jnp.asarray(w), labels, 8)
    lbf = fused_softmax_xent(jnp.asarray(h, jnp.bfloat16),
                             jnp.asarray(w, jnp.bfloat16), labels, 8)
    np.testing.assert_allclose(float(lbf), float(lf32), rtol=3e-2)


def test_fused_softmax_xent_rejects_bad_chunk():
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused_softmax_xent
    with pytest.raises(ValueError, match="divisible"):
        fused_softmax_xent(jnp.zeros((2, 5, 4)), jnp.zeros((4, 7)),
                           jnp.zeros((2, 5), jnp.int32), 3)
