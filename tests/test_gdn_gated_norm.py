"""``ops/gdn_gated_norm.py``: the two Pallas kernels of the DeltaNet mixer's
way out (per-head RMSNorm, then the ``silu(z)`` gate) in interpret mode
against the ``jax.numpy`` lines they replace (``nn.linear_attention.
_gated_norm_jax``), at sizes a CPU walks in seconds: two rows of 256
positions, 4 heads of 128, row blocks of 128 so that a block's boundary is
crossed, z read in place from a wider array at its column offset; and one
row block at the Qwen cell's width (32 heads of 128, z the last 4,096 of
``qkvz``'s 12,288 columns)."""

import functools
import importlib
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import metrics
from analytics_zoo_tpu.nn.linear_attention import _gated_norm_jax

op = importlib.import_module("analytics_zoo_tpu.ops.gdn_gated_norm")

EPS, BLOCK = 1e-6, 128
# name -> (dtype, B, T, heads, head width, columns of the source before z:
# None reads z itself; 768 is no multiple of z's 512 columns, so a grid
# step takes 256 of them)
CASES = {"f32": (jnp.float32, 2, 256, 4, 128, 1024),
         "bf16": (jnp.bfloat16, 2, 256, 4, 128, 1024),
         "bf16_columns": (jnp.bfloat16, 2, 256, 4, 128, 768),
         "f32_own_z": (jnp.float32, 2, 256, 4, 128, None),
         "bf16_head256": (jnp.bfloat16, 1, 384, 2, 256, 512),
         "bf16_cell_width": (jnp.bfloat16, 1, 512, 32, 128, 8192)}


class _flags:
    """The module's two switches for the length of a ``with``."""

    def __init__(self, interpret, blocks=(BLOCK,)):
        self.new = (interpret, blocks)

    def __enter__(self):
        self.old = (op.INTERPRET, op.ROW_BLOCKS)
        op.INTERPRET, op.ROW_BLOCKS = self.new

    def __exit__(self, *exc):
        op.INTERPRET, op.ROW_BLOCKS = self.old


def _f32(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32))


def _spacing(a):
    """The distance between neighbouring bf16 values at ``|a|``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 1e-30))) - 7)


def _inputs(name):
    dtype, b, t, h, d, before = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name) + t), 5)
    o = jax.random.normal(ks[0], (b, t, h, d)).astype(dtype)
    source = (2 * jax.random.normal(ks[1], (b, t, (before or 0) + h * d))
              ).astype(dtype)
    w = 1.0 + 0.2 * jax.random.normal(ks[2], (d,))
    dy = jax.random.normal(ks[3], (b, t, h * d)).astype(dtype)
    return o, source, w, dy, before


def _vjp(f, o, source, w, dy):
    out, pull = jax.vjp(f, o, source, w)
    return out, pull(dy)


@functools.lru_cache(maxsize=None)
def _case(name):
    """Output and gradients (of o, of the source, of w) three ways: the
    kernels (interpreted), the ``jax.numpy`` form on the same inputs, and
    that form on the inputs as float32."""
    o, source, w, dy, before = _inputs(name)
    width = dy.shape[-1]

    def kernels(o, s, w):
        return op.gated_norm(o, s[..., -width:], w, EPS, True,
                             None if before is None else s)

    def form(o, s, w):
        return _gated_norm_jax(o, s[..., -width:], w, EPS)

    blocks = (BLOCK,) if o.shape[1] % 512 else (512,)
    with _flags(True, blocks):
        assert op.dispatch(o.shape[1], o.shape[3]) is True
        got = _vjp(kernels, o, source, w, dy)
    f32 = lambda a: a.astype(jnp.float32)
    return dict(kernels=got, form=_vjp(form, o, source, w, dy),
                exact=_vjp(form, f32(o), f32(source), w, f32(dy)),
                dtype=o.dtype, width=width, o=o)


def _nearer(got, want, exact, what):
    """bf16: the kernels round once where the ``jax.numpy`` form rounds its
    factors first, so they lie NEARER the float32 form on average, and
    within two spacings of it element by element."""
    got, want, exact = _f32(got), _f32(want), _f32(exact)
    assert np.abs(got - exact).mean() <= np.abs(want - exact).mean(), what
    room = 2 * _spacing(np.maximum(np.abs(exact), np.abs(exact).max() / 256))
    assert (np.abs(got - exact) <= room).all(), what


@pytest.mark.parametrize("name", list(CASES))
def test_forward_is_the_jax_forms(name):
    c = _case(name)
    got, want = c["kernels"][0], c["form"][0]
    assert got.shape == want.shape == c["o"].shape[:2] + (c["width"],)
    assert got.dtype == want.dtype == c["dtype"]
    if c["dtype"] == jnp.float32:
        assert np.abs(_f32(got) - _f32(want)).max() \
            <= 1e-5 * np.abs(_f32(want)).max()
    else:
        _nearer(got, want, c["exact"][0], "y")


@pytest.mark.parametrize("part", ["do", "dz"])
@pytest.mark.parametrize("name", list(CASES))
def test_gradient_of_o_and_z(name, part):
    """do in o's layout ``[B, T, H, d_v]``; dz as the cotangent of z's
    columns of the source, and nothing in the columns before them."""
    c = _case(name)
    i = 0 if part == "do" else 1
    got, want, exact = (v[1][i] for v in (c["kernels"], c["form"],
                                          c["exact"]))
    assert got.shape == want.shape and got.dtype == want.dtype
    if part == "dz":
        assert (_f32(got)[..., :-c["width"]] == 0).all()
        got, want, exact = (a[..., -c["width"]:] for a in (got, want, exact))
    if c["dtype"] == jnp.float32:
        assert np.abs(_f32(got) - _f32(want)).max() \
            <= 1e-5 * np.abs(_f32(want)).max()
    else:
        _nearer(got, want, exact, part)


@pytest.mark.parametrize("name", list(CASES))
def test_gradient_of_the_weight(name):
    """Summed over rows and heads in float32 from unrounded terms: the
    float32 form's sum on the same inputs to summation order, in either
    dtype (the ``jax.numpy`` form on bf16 inputs rounds each term's factors
    and lies ~2e-3 of the largest value from it)."""
    c = _case(name)
    got, want, exact = (_f32(v[1][2]) for v in (c["kernels"], c["form"],
                                                c["exact"]))
    assert got.shape == want.shape and c["kernels"][1][2].dtype == jnp.float32
    assert np.abs(got - exact).max() <= 1e-5 * np.abs(exact).max()
    assert np.abs(got - exact).max() <= max(np.abs(want - exact).max(),
                                            1e-5 * np.abs(exact).max())


def test_each_pallas_call_is_built_once_for_its_sizes():
    o, source, w, dy, _ = _inputs("f32")
    f = lambda o, s, w: op.gated_norm(o, s[..., -512:], w, EPS, True, s)
    with _flags(True):
        _vjp(f, o, source, w, dy)
        before = (op._forward.cache_info().misses,
                  op._backward.cache_info().misses)
        _vjp(f, o + 1, source, w, dy)
    assert (op._forward.cache_info().misses,
            op._backward.cache_info().misses) == before


def test_the_kernels_carry_their_names():
    """``gdn_gated_norm_fwd`` / ``_bwd`` in HLO and in a profile; neither
    starts ``gated_delta_rule_`` nor ``gdn_qkv_conv_``, the names other
    metrics read."""
    names = re.findall(r'name="(\w+)"', inspect.getsource(op))
    assert names == ["gdn_gated_norm_fwd", "gdn_gated_norm_bwd"]


@pytest.mark.parametrize("t,head,fits", [
    (256, 128, True), (8192, 128, True), (384, 256, True),
    (200, 128, False),      # T no multiple of a row block
    (256, 64, False),       # a head is no whole 128-lane tile
    (256, 192, False)])
def test_dispatch_is_one_predicate_of_what_the_call_sees(t, head, fits,
                                                         monkeypatch):
    monkeypatch.setattr(op, "ROW_BLOCKS", (BLOCK,))
    assert op.dispatch(t, head) is None     # a CPU, no flag
    monkeypatch.setattr(op, "INTERPRET", True)
    assert op.dispatch(t, head) is (True if fits else None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert op.dispatch(t, head) is (False if fits else None)


# -- the layer ---------------------------------------------------------------

def _layer(dtype_name, interpret, v_head=128, t=128):
    """A GatedDeltaNet's output, parameters and gradients, and what
    ``gdn.norm_path`` counted while it was built and differentiated."""
    dtype = jnp.dtype(dtype_name)
    layer = nn.GatedDeltaNet(2, 4, 128, v_head, chunk=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 32)).astype(dtype)
    registry = metrics.get_registry()
    count = lambda path: registry.counter("gdn.norm_path", path=path).value
    was = count("kernel"), count("jnp")
    with _flags(interpret):
        variables = layer.init(jax.random.PRNGKey(1), x)

        def loss(params, x):
            out, _ = layer.apply({"params": params,
                                  "state": variables["state"]}, x)
            return jnp.sum(jnp.square(out.astype(jnp.float32))), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(variables["params"], x)
    counted = count("kernel") - was[0], count("jnp") - was[1]
    return variables["params"], out, grads, counted


@functools.lru_cache(maxsize=None)
def _layer_both(dtype_name):
    return _layer(dtype_name, True), _layer(dtype_name, False)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layer_is_the_same_with_the_kernels_and_without(dtype):
    """Same output and gradients through both paths (bf16: the kernels'
    one rounding of the gate's product against the form's three)."""
    (_, out, (g_params, g_x), _), (_, out0, (g_params0, g_x0), _) = \
        _layer_both(dtype)
    flat = lambda tree: {jax.tree_util.keystr(p): l for p, l in
                         jax.tree_util.tree_leaves_with_path(tree)}
    tol = 2e-5 if dtype == "float32" else 3e-2
    rel = lambda a, b: float(np.abs(_f32(a) - _f32(b)).max()
                             / np.abs(_f32(b)).max())
    assert rel(out, out0) < tol
    assert rel(g_x, g_x0) < tol
    for key, leaf in flat(g_params).items():
        assert rel(leaf, flat(g_params0)[key]) < tol, key


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_parameter_tree_is_unchanged(dtype):
    """The child ``norm`` holds ``weight`` ``[d_v]`` initialised to ones,
    the leaf ``RMSNorm`` held, on both paths, drawn alike."""
    (params, *_), (params0, *_) = _layer_both(dtype)
    assert list(params) == list(params0) == [
        "in_proj_qkvz", "in_proj_ba", "conv", "A_log", "dt_bias", "norm",
        "out_proj"]
    assert list(params["norm"]) == ["weight"]
    assert params["norm"]["weight"].shape == (128,)
    assert (np.asarray(params["norm"]["weight"]) == 1.0).all()
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params0)):
        assert (np.asarray(a) == np.asarray(b)).all()
    want = nn.RMSNorm().init(jax.random.PRNGKey(3),
                             jnp.zeros((1, 4, 128)))["params"]
    assert jax.tree_util.tree_structure(want) \
        == jax.tree_util.tree_structure(dict(params["norm"]))


@pytest.mark.parametrize("v_head,t,path", [
    (128, 128, "kernel"), (64, 128, "jnp"), (128, 192, "jnp")])
def test_the_counter_says_which_path_a_trace_took(v_head, t, path):
    """``init`` and the differentiated ``apply`` trace the layer once each:
    two counts on the path the dispatch chose (a head of 64 and a T off the
    row blocks take the ``jax.numpy`` lines), none on the other."""
    *_, counted = _layer("float32", True, v_head, t)
    assert counted == ((2, 0) if path == "kernel" else (0, 2))
