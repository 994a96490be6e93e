"""The Mamba-2 scan's two Pallas kernels (``ops/mamba2_ssd.py``) under
``INTERPRET`` on the CPU, at small shapes of whole 128-lane tiles: against
the ``jax.numpy`` chunked form (``nn.state_space._chunked_jax``, what every
other backend runs) and against the recurrence token by token
(``benchmark/families/granite_hybrid.py``): output, final state, the layer's
statistics and the gradient of every input; a decay that underflows inside
one chunk; a sequence cut in two; who computes which shapes, and the counter
that says so."""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import analytics_zoo_tpu.nn as nn  # noqa: E402
from analytics_zoo_tpu.core import metrics  # noqa: E402
from analytics_zoo_tpu.nn import state_space  # noqa: E402
from benchmark.families import granite_hybrid as fam  # noqa: E402

kernels = importlib.import_module("analytics_zoo_tpu.ops.mamba2_ssd")

NAMES = ("x", "dt", "a_log", "b", "c", "d_skip", "s0")


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(kernels, "INTERPRET", True)


def _inputs(t, groups=1, carried=True, dtype=jnp.float32, seed=0, bsz=1,
            h=4, p=64, n=128, strength=0.1):
    """``strength`` scales dt: 0.1 keeps a chunk of 128 inside a few e-folds
    (every term of the [Q, Q] product matters), 400 underflows it."""
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    return dict(
        x=f(bsz, t, h, p).astype(dtype),
        dt=jnp.asarray(np.log1p(np.exp(r.standard_normal((bsz, t, h)) - 2))
                       * strength, jnp.float32),
        a_log=jnp.asarray(np.log(r.uniform(0.5, 16, (h,))), jnp.float32),
        b=f(bsz, t, groups, n).astype(dtype),
        c=f(bsz, t, groups, n).astype(dtype), d_skip=f(h),
        s0=f(bsz, h, p, n) if carried else None)


def _rel(a, b):
    a, b = (jnp.asarray(v, jnp.float32) for v in (a, b))
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def _recurrence(x, dt, a_log, b, c, d_skip, s0, chunk=None):
    f32 = lambda v: None if v is None else v.astype(jnp.float32)
    return fam.recurrence_reference(f32(x), dt, -jnp.exp(a_log), f32(b),
                                    f32(c), d_skip, s0)


_scan = state_space._ssd   # (y, final state, the layer's statistics)


def _value_and_grads(fn, a, chunk, seed=9):
    """Output, final state, what else ``fn`` returns, and the gradient of a
    random projection of output and state by every input ``a`` holds."""
    given = [k for k in NAMES if a[k] is not None]
    r = np.random.default_rng(seed)
    wy = jnp.asarray(r.standard_normal(a["x"].shape), jnp.float32)

    def of(*args):
        out = fn(**{**a, **dict(zip(given, args))}, chunk=chunk)
        y, s = out[:2]
        return (y.astype(jnp.float32) * wy).sum() + jnp.square(s).sum(), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        of, argnums=range(len(given)), has_aux=True))(*(a[k] for k in given))
    return out, dict(zip(given, grads))


# groups 1 and 2, a given state and none, T a whole number of chunks and
# not, float32 and bf16: each level of each twice in four cases
CASES = [(256, 1, True, jnp.float32), (200, 2, True, jnp.float32),
         (256, 2, False, jnp.bfloat16), (200, 1, False, jnp.bfloat16)]


@pytest.mark.parametrize("t,groups,carried,dtype", CASES, ids=[
    f"T{t}-G{g}-{'s0' if s else 'zero'}-{jnp.dtype(d).name}"
    for t, g, s, d in CASES])
def test_the_kernels_match_the_jnp_form_and_the_recurrence(
        t, groups, carried, dtype, monkeypatch):
    a = _inputs(t, groups, carried, dtype)
    (y_j, s_j, stats_j), g_j = _value_and_grads(_scan, a, 128)
    monkeypatch.setattr(kernels, "INTERPRET", True)
    (y_k, s_k, stats_k), g_k = _value_and_grads(_scan, a, 128)
    (y_r, s_r), g_r = _value_and_grads(_recurrence, a, None)
    assert y_k.dtype == dtype and y_k.shape == a["x"].shape
    assert s_k.dtype == jnp.float32 and s_k.shape == (1, 4, 64, 128)
    # float32: the two chunked forms do the same sums in another order; the
    # recurrence multiplies 200 decays where they take one exp of a sum.
    # bf16: both round their matmul operands where the other does, the
    # recurrence rounds nothing
    near, far = (2e-5, 2e-4) if dtype == jnp.float32 else (2e-2, 5e-2)
    assert _rel(y_k, y_j) < near and _rel(s_k, s_j) < near
    assert _rel(y_k, y_r) < far and _rel(s_k, s_r) < far
    assert set(g_k) == set(g_j) == set(g_r)
    for name in g_k:
        assert g_k[name].dtype == a[name].dtype, name
        assert bool(jnp.isfinite(g_k[name].astype(jnp.float32)).all()), name
        assert _rel(g_k[name], g_j[name]) < near, name
        assert _rel(g_k[name], g_r[name]) < far, name
    for key in stats_j:
        assert _rel(stats_k[key], stats_j[key]) < 1e-6 or (
            float(stats_j[key]) == float(stats_k[key]) == 0.0), key
    assert int(stats_k["tokens_padded"]) == -t % 128


def test_a_chunk_of_two_row_blocks_at_either_decay(interpreted):
    """Chunk 256 as the cell has it: the [Q, Q] terms in two blocks of 128
    rows, nothing computed above the diagonal block; one row of the batch at
    a mild decay (every term of the product matters), one at dt x A up to a
    few hundred a position (the cell reads 604 a chunk): exp(c_Q) is 0 in
    float32 and exp(c_t) / exp(c_s) would be 0 / 0; differences taken, and
    masked, before the exp give the recurrence's numbers, forward and
    backward."""
    a = _inputs(512, bsz=2, seed=2)
    a["dt"] = a["dt"] * jnp.asarray([0.5, 4000.0])[:, None, None]
    (y, s, stats), g_k = _value_and_grads(_scan, a, 256)
    assert float(stats["chunk_decay_exponent_max"]) > 87.0
    (y_r, s_r), g_r = _value_and_grads(_recurrence, a, None)
    assert bool(jnp.isfinite(y).all())
    for row in (0, 1):
        assert _rel(y[row], y_r[row]) < 2e-4 and _rel(s[row], s_r[row]) < 2e-4
    for name in ("x", "dt", "b", "c", "s0"):
        assert bool(jnp.isfinite(g_k[name]).all()), name
        for row in (0, 1):
            assert _rel(g_k[name][row], g_r[name][row]) < 2e-4, (name, row)
    # A_log's is a sum of c_t's gains and losses, each up to 1e6 at the strong
    # decay and cancelling to 1e2: either chunked form keeps two digits of it
    assert _rel(g_k["d_skip"], g_r["d_skip"]) < 2e-4
    assert _rel(g_k["a_log"], g_r["a_log"]) < 0.2


def test_two_calls_that_carry_the_state_are_one_call(interpreted):
    """The second call starts from the first's final state.  Heads of 128:
    a lane tile is one head, no mask."""
    a = _inputs(256, bsz=2, h=2, p=128)
    scan = jax.jit(functools.partial(state_space.ssd, chunk=128))
    whole_y, whole_s = scan(**a)
    for cut in (128,):
        part = lambda lo, hi: {k: v[:, lo:hi] for k, v in a.items()
                               if k in ("x", "dt", "b", "c")}
        fixed = dict(a_log=a["a_log"], d_skip=a["d_skip"])
        y1, s1 = scan(**part(0, cut), s0=a["s0"], **fixed)
        y2, s2 = scan(**part(cut, 256), s0=s1, **fixed)
        assert _rel(jnp.concatenate([y1, y2], 1), whole_y) < 2e-5
        assert _rel(s2, whole_s) < 2e-5


@pytest.mark.parametrize("sizes,fit", [
    # the cell; two groups; heads of 128 and 256; a head block of one tile
    ((64, 1, 64, 128, 256), True), ((8, 2, 64, 128, 128), True),
    ((4, 1, 128, 256, 128), True), ((2, 1, 256, 128, 128), True),
    ((2, 1, 64, 128, 128), True), ((4, 1, 32, 128, 128), True),
    # the rehearsal's chunk of 24; a state of 16; one head of 64 a group; a
    # head of 96; a row shorter than 128 (the chunk is its length)
    ((4, 1, 64, 128, 24), False), ((4, 1, 64, 16, 128), False),
    ((2, 2, 64, 128, 128), False), ((4, 1, 96, 128, 128), False),
    ((4, 1, 64, 128, 100), False), ((6, 4, 64, 128, 128), False)])
def test_which_shapes_the_kernels_take(sizes, fit, monkeypatch):
    assert kernels.fits(*sizes) is fit
    # the CPU runs the jax.numpy form whatever the shapes, unless a test
    # asks for the interpreter, and then only shapes that fit
    assert kernels.dispatch(*sizes) is None
    monkeypatch.setattr(kernels, "INTERPRET", True)
    assert kernels.dispatch(*sizes) is (True if fit else None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.dispatch(*sizes) is (False if fit else None)


def test_every_trace_of_the_scan_counts_the_path_it_took(monkeypatch):
    registry = metrics.get_registry()
    count = lambda path: registry.counter("ssm.scan_traces", path=path).value
    fit, unfit = _inputs(256), _inputs(64, h=4, p=8, n=16)
    was = count("jnp"), count("kernel")
    jax.jit(functools.partial(state_space.ssd, chunk=128))(**fit)
    assert (count("jnp"), count("kernel")) == (was[0] + 1, was[1])
    monkeypatch.setattr(kernels, "INTERPRET", True)
    scan = jax.jit(functools.partial(state_space.ssd, chunk=128))
    scan(**fit)
    assert (count("jnp"), count("kernel")) == (was[0] + 1, was[1] + 1)
    scan(**fit)      # a cached trace: counted when traced, not when run
    assert (count("jnp"), count("kernel")) == (was[0] + 1, was[1] + 1)
    # shapes the kernels refuse fall to the jax.numpy form, and say so
    y, _ = jax.jit(functools.partial(state_space.ssd, chunk=16))(**unfit)
    assert (count("jnp"), count("kernel")) == (was[0] + 2, was[1] + 1)
    want, _ = _recurrence(**unfit)
    assert _rel(y, want) < 2e-5


def test_a_mamba2_layer_is_the_same_layer_on_either_path(monkeypatch):
    """``nn.Mamba2``: its output and its counters and levels (the
    gradients through the scan are the cases above)."""
    layer = nn.Mamba2(num_heads=2, head_dim=64, state_size=128, chunk=128)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 200, 64)),
                    jnp.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    run = lambda: jax.jit(lambda v, x: layer.apply(v, x))(variables, x)
    y_j, state_j = run()
    monkeypatch.setattr(kernels, "INTERPRET", True)
    y_k, state_k = run()
    assert _rel(y_k, y_j) < 2e-5
    for got, want in zip(jax.tree_util.tree_leaves(state_k),
                         jax.tree_util.tree_leaves(state_j)):
        assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
