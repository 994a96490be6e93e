"""``ops/gdn_qkv_conv.py``: the two Pallas kernels in interpret mode against
the ``jax.numpy`` lines they replace (``qkv_conv_jax``: what
``CausalConv1D(activation="silu")``, three slices and two l2norms computed),
at sizes a CPU walks in seconds: two rows of 256 and 384 positions, 2 key
and 4 value heads of 128, a conv of 4 taps, time blocks of 128 so that a
block's boundary is crossed, forward (halo) and backward (carry)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn

op = importlib.import_module("analytics_zoo_tpu.ops.gdn_qkv_conv")

D, HV, TAPS, EPS, BLOCK = 128, 4, 4, 1e-6, 128
# name -> (dtype, key heads, T); "plain": no normalised heads, a conv, a SiLU
# and a split alone
CASES = {"f32_t256": (jnp.float32, 2, 256),
         "bf16_t384": (jnp.bfloat16, 2, 384),
         "plain_f32": (jnp.float32, 0, 256),
         "plain_bf16": (jnp.bfloat16, 0, 384)}
PARTS = ("q", "k", "v", "z")


class _flags:
    """The module's two switches for the length of a ``with``."""

    def __init__(self, interpret):
        self.new = (interpret, (BLOCK,))

    def __enter__(self):
        self.old = (op.INTERPRET, op.TIME_BLOCKS)
        op.INTERPRET, op.TIME_BLOCKS = self.new

    def __exit__(self, *exc):
        op.INTERPRET, op.TIME_BLOCKS = self.old


def _f32(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32))


def _spacing(a):
    """The distance between neighbouring bf16 values at ``|a|``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 1e-30))) - 7)


def _vjp(f, x, w, heads, cotangents):
    out, pull = jax.vjp(lambda x, w: f(x, w, *heads, EPS), x, w)
    return out, pull(tuple(cotangents))


@functools.lru_cache(maxsize=None)
def _case(name):
    """Inputs, and outputs and gradients three ways: the kernels
    (interpreted), the ``jax.numpy`` form on the same inputs, and that form
    on the inputs as float32."""
    dtype, hk, t = CASES[name]
    heads = (hk, HV, D, D)
    ks = jax.random.split(jax.random.PRNGKey(len(name) + t), 8)
    x = jax.random.normal(ks[0], (2, t, 2 * hk * D + 2 * HV * D)).astype(dtype)
    w = jax.random.normal(ks[1], (TAPS, 2 * hk * D + HV * D)) * 0.5
    shapes = [(2, t, hk, D)] * 2 + [(2, t, HV, D), (2, t, HV * D)]
    cts = [jax.random.normal(k, s).astype(dtype)
           for k, s in zip(ks[2:], shapes)]
    with _flags(True):
        assert op.dispatch(t, *heads, TAPS) is True
        kernels = _vjp(op.qkv_conv, x, w, heads, cts)
    with _flags(False):
        assert op.dispatch(t, *heads, TAPS) is None
        form = _vjp(op.qkv_conv, x, w, heads, cts)
    exact = _vjp(op.qkv_conv_jax, x.astype(jnp.float32), w, heads,
                 [c.astype(jnp.float32) for c in cts])
    return dict(x=x, w=w, cts=cts, heads=heads, kernels=kernels, form=form,
                exact=exact, dtype=dtype, t=t)


def _close(got, want, dtype, what):
    """float32: to 1e-5 of the array's largest value (summation order);
    bf16: to one bf16 spacing, element by element (a value a thousand times
    under the array's largest, what is left where the taps cancel, to the
    spacing there)."""
    assert got.shape == want.shape and got.dtype == want.dtype, what
    got, want = _f32(got), _f32(want)
    if not got.size:
        return
    if dtype == jnp.float32:
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max(), what
    else:
        room = _spacing(np.maximum(np.maximum(np.abs(got), np.abs(want)),
                                   np.abs(want).max() / 1024))
        assert (np.abs(got - want) <= room).all(), what


@pytest.mark.parametrize("part", range(4), ids=PARTS)
@pytest.mark.parametrize("name", list(CASES))
def test_forward_is_the_jax_forms(name, part):
    c = _case(name)
    got, want = c["kernels"][0][part], c["form"][0][part]
    hk, t = c["heads"][0], c["t"]
    assert got.shape == ((2, t, HV * D) if part == 3 else
                         (2, t, hk if part < 2 else HV, D))
    assert got.dtype == c["dtype"]
    _close(got, want, c["dtype"], PARTS[part])
    if part == 3:  # z as it came
        assert (_f32(got) == _f32(c["x"])[..., -HV * D:]).all()


# the first K - 1 rows (zeros before the sequence's start), the rows on each
# side of every block boundary, the last row
@pytest.mark.parametrize("name,row", [
    (name, row) for name in ("f32_t256", "bf16_t384", "plain_bf16")
    for row in (0, 1, 2, 3, 126, 127, 128, 129, 130, 131, 254, 255, 256, 257,
                258, 259, 383) if row < CASES[name][2]])
def test_a_row_at_the_start_and_beside_a_block_boundary(name, row):
    c = _case(name)
    for got, want in zip(c["kernels"][0], c["form"][0]):
        _close(got[:, row], want[:, row], c["dtype"], f"forward row {row}")
    got, want = c["kernels"][1][0][:, row], c["form"][1][0][:, row]
    if c["dtype"] == jnp.float32:
        _close(got, want, c["dtype"], f"d qkvz row {row}")
    else:
        exact = _f32(c["exact"][1][0][:, row])
        assert np.abs(_f32(got) - exact).mean() \
            <= 1.1 * np.abs(_f32(want) - exact).mean()


@pytest.mark.parametrize("name", list(CASES))
def test_gradient_of_qkvz(name):
    """float32 to summation order.  In bf16 autodiff rounds every tap's
    contribution and adds the four in bf16, the kernel adds them in float32
    and rounds once: it is held to lie NEARER the float32 form than autodiff
    does, and within four spacings of the largest value of autodiff's."""
    c = _case(name)
    got, want = c["kernels"][1][0], c["form"][1][0]
    assert got.shape == c["x"].shape and got.dtype == c["dtype"]
    if c["dtype"] == jnp.float32:
        return _close(got, want, c["dtype"], "d qkvz")
    got, want, exact = _f32(got), _f32(want), _f32(c["exact"][1][0])
    assert np.abs(got - exact).mean() <= np.abs(want - exact).mean()
    assert np.abs(got - want).max() <= 4 * _spacing(np.abs(want).max())


@pytest.mark.parametrize("name", list(CASES))
def test_gradient_of_the_weight(name):
    """Summed in float32 both ways; with bf16 inputs the terms differ by
    their roundings, so the sum by a few parts in ten thousand."""
    c = _case(name)
    got, want = c["kernels"][1][1], c["form"][1][1]
    assert got.shape == c["w"].shape and got.dtype == jnp.float32
    tol = 1e-5 if c["dtype"] == jnp.float32 else 1e-3
    assert np.abs(_f32(got) - _f32(want)).max() \
        <= tol * np.abs(_f32(want)).max()


@pytest.mark.parametrize("name", ["f32_t256", "bf16_t384", "plain_f32"])
def test_z_columns_gradient_is_what_came_for_z(name):
    """... exactly: zero from this op when nothing reads z, dz itself when
    something does (the backward kernel passes it through, so that the whole
    cotangent of ``qkvz`` leaves in its one pass)."""
    c = _case(name)
    z_columns = _f32(c["kernels"][1][0])[..., -HV * D:]
    assert (z_columns == _f32(c["cts"][3])).all()
    no_z = c["cts"][:3] + [jnp.zeros_like(c["cts"][3])]
    with _flags(True):
        _, (dx, _) = _vjp(op.qkv_conv, c["x"], c["w"], c["heads"], no_z)
    dx = _f32(dx)
    assert (dx[..., -HV * D:] == 0).all()
    conv = dx.shape[-1] - HV * D
    assert (dx[..., :conv] == _f32(c["kernels"][1][0])[..., :conv]).all()


def test_no_normalised_heads_is_a_conv_a_silu_and_a_split():
    c = _case("plain_f32")
    q, k, v, z = c["kernels"][0]
    assert q.shape == k.shape == (2, 256, 0, D)
    x, w = _f32(c["x"]), _f32(c["w"])
    xp = np.pad(x[..., :HV * D], ((0, 0), (TAPS - 1, 0), (0, 0)))
    pre = sum(xp[:, j:j + 256] * w[j] for j in range(TAPS))
    want = pre / (1 + np.exp(-pre))
    assert np.abs(_f32(v).reshape(2, 256, -1) - want).max() < 1e-5


@pytest.mark.parametrize("t,hk,hv,dk,dv,taps,fits", [
    (256, 2, 4, 128, 128, 4, True), (8192, 16, 32, 128, 128, 4, True),
    (256, 0, 4, 128, 128, 4, True), (256, 2, 4, 256, 128, 2, True),
    (200, 2, 4, 128, 128, 4, False),    # T no multiple of a time block
    (256, 2, 4, 64, 128, 4, False),     # a head is no whole 128-lane tile
    (256, 2, 4, 128, 64, 4, False),
    (256, 2, 4, 128, 128, 1, False),    # no conv
    (256, 2, 4, 128, 128, 12, False),   # more taps than a step carries rows
    (256, 3, 4, 128, 128, 4, False)])   # value heads no multiple of key heads
def test_dispatch_is_one_predicate_of_what_the_call_sees(
        t, hk, hv, dk, dv, taps, fits, monkeypatch):
    monkeypatch.setattr(op, "TIME_BLOCKS", (BLOCK,))
    assert op.dispatch(t, hk, hv, dk, dv, taps) is None     # a CPU, no flag
    monkeypatch.setattr(op, "INTERPRET", True)
    assert op.dispatch(t, hk, hv, dk, dv, taps) is (True if fits else None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert op.dispatch(t, hk, hv, dk, dv, taps) is (False if fits else None)


def test_each_pallas_call_is_built_once_for_its_sizes():
    c = _case("f32_t256")
    before = op._forward.cache_info().misses, op._backward.cache_info().misses
    with _flags(True):
        _vjp(op.qkv_conv, c["x"], c["w"], c["heads"], c["cts"])
        _vjp(op.qkv_conv, c["x"] + 1, c["w"], c["heads"], c["cts"])
    assert (op._forward.cache_info().misses,
            op._backward.cache_info().misses) == before


def test_the_kernels_carry_their_names():
    """``gdn_qkv_conv_fwd`` / ``_bwd`` in HLO and in a profile; neither
    starts ``gated_delta_rule_``, which ``gdn_kernel_roofline_pct`` reads."""
    import inspect
    import re
    names = re.findall(r'name="(\w+)"', inspect.getsource(op))
    assert names == ["gdn_qkv_conv_fwd", "gdn_qkv_conv_bwd"]


# -- the layer ---------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _layer(dtype_name, interpret):
    dtype = jnp.dtype(dtype_name)
    layer = nn.GatedDeltaNet(2, HV, D, D, chunk=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 32)).astype(dtype)
    with _flags(interpret):
        variables = layer.init(jax.random.PRNGKey(1), x)

        def loss(params, x):
            out, _ = layer.apply({"params": params,
                                  "state": variables["state"]}, x)
            return jnp.sum(jnp.square(out.astype(jnp.float32))), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(variables["params"], x)
    return variables["params"], out, grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layer_is_the_same_with_the_kernels_and_without(dtype):
    """Same output, same gradients and the SAME parameter tree: the conv's
    weight stays ``conv/kernel`` ``[K, 2 key_dim + value_dim]``, drawn as
    ``CausalConv1D`` drew it."""
    params, out, (g_params, g_x) = _layer(dtype, True)
    params0, out0, (g_params0, g_x0) = _layer(dtype, False)
    flat = lambda tree: {jax.tree_util.keystr(p): l for p, l in
                         jax.tree_util.tree_leaves_with_path(tree)}
    assert list(params) == list(params0) == [
        "in_proj_qkvz", "in_proj_ba", "conv", "A_log", "dt_bias", "norm",
        "out_proj"]
    assert params["conv"]["kernel"].shape == (4, 2 * 2 * D + HV * D)
    for key, leaf in flat(params).items():
        assert (np.asarray(leaf) == np.asarray(flat(params0)[key])).all(), key
    drawn = nn.CausalConv1D(4, activation="silu").init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8, 8 * D)))["params"]["kernel"]
    conv = nn.GatedDeltaNet(2, HV, D, D).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8, 64)))["params"]["conv"]
    assert list(conv) == ["kernel"] and conv["kernel"].shape == drawn.shape
    tol = 2e-5 if dtype == "float32" else 3e-2
    rel = lambda a, b: float(np.abs(_f32(a) - _f32(b)).max()
                             / np.abs(_f32(b)).max())
    assert rel(out, out0) < tol
    assert rel(g_x, g_x0) < tol
    for key, leaf in flat(g_params).items():
        assert rel(leaf, flat(g_params0)[key]) < tol, key
