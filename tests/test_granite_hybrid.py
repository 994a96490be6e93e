"""What granite-4.0-h-micro (``models.GraniteHybrid``) forced, each piece
against the plain float32 reference kept with the benchmark
(``benchmark/families/granite_hybrid.py``), at small sizes on the CPU: the
chunked state-space scan (``nn.state_space.ssd``) against the recurrence
token by token, forward, carried state and gradients; the biased causal
convolution; a softmax scale other than ``1/sqrt(D)`` on the dense path and
through the flash kernels at heads of 64; the whole model's logits, loss
and gradients; the tied table; the layer's counters; and the
configuration's parameter count at the published widths."""

import functools
import importlib
import json
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
from analytics_zoo_tpu.models import GraniteHybrid  # noqa: E402
from analytics_zoo_tpu.nn.state_space import ssd  # noqa: E402
from analytics_zoo_tpu.ops import flash_attention, mha_reference  # noqa: E402
from analytics_zoo_tpu.orca.learn import Estimator  # noqa: E402
from benchmark.families import granite_hybrid as fam  # noqa: E402

fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")

MAMBA, ATTENTION = "mamba", "attention"
SMALL = dict(vocab_size=128, hidden_size=64, n_layers=3,
             layer_types=[MAMBA, MAMBA, ATTENTION], mamba_heads=4,
             mamba_head_dim=32, mamba_state=16, chunk=16, num_heads=4,
             num_kv_heads=2, head_dim=16, ff_units=96, dtype="float32")


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(0, 128, shape).astype(
        np.int32)


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def _scan_inputs(t, groups=1, seed=0, b=2, h=4, p=8, n=16, strength=1.0):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    return dict(
        x=f(b, t, h, p),
        dt=jnp.asarray(np.log1p(np.exp(r.standard_normal((b, t, h)) - 2))
                       * strength, jnp.float32),
        a_log=jnp.asarray(np.log(r.uniform(0.5, 16, (h,))), jnp.float32),
        b=f(b, t, groups, n), c=f(b, t, groups, n), d_skip=f(h),
        s0=f(b, h, p, n))


@jax.jit
def _recurrence(x, dt, a_log, b, c, d_skip, s0=None):
    return fam.recurrence_reference(x, dt, -jnp.exp(a_log), b, c, d_skip, s0)


@functools.partial(jax.jit, static_argnames="chunk")
def _ssd_jit(x, dt, a_log, b, c, d_skip, s0=None, chunk=16):
    return ssd(x, dt, a_log, b, c, d_skip, s0, chunk)


# -- the chunked scan against the recurrence ------------------------------------

# a whole number of chunks, a padded last chunk, a row shorter than a chunk,
# two groups of heads, one chunk exactly
SCAN_CASES = [(64, 1, 16), (70, 1, 16), (10, 1, 16), (48, 2, 16), (32, 1, 32)]


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("t,groups,chunk", SCAN_CASES)
def test_ssd_matches_the_recurrence_token_by_token(t, groups, chunk, carried):
    a = _scan_inputs(t, groups)
    if not carried:
        a["s0"] = None
    y, s = _ssd_jit(**a, chunk=chunk)
    want_y, want_s = _recurrence(**a)
    assert y.shape == a["x"].shape and s.shape == (2, 4, 8, 16)
    assert _rel(y, want_y) < 1e-5 and _rel(s, want_s) < 1e-5


def test_ssd_takes_b_and_c_without_a_group_axis():
    a = _scan_inputs(40)
    y, s = _ssd_jit(**a)
    a3 = dict(a, b=a["b"][:, :, 0], c=a["c"][:, :, 0])
    y3, s3 = _ssd_jit(**a3)
    assert float(jnp.abs(y - y3).max()) == 0.0 == float(jnp.abs(s - s3).max())


@pytest.mark.parametrize("cut", [32, 23])
def test_two_calls_that_carry_the_state_are_one_call(cut):
    """The second call starts from the first's final state: at a chunk's
    boundary and inside a chunk."""
    a = _scan_inputs(64)
    whole_y, whole_s = _ssd_jit(**a)
    part = lambda lo, hi: {k: v[:, lo:hi] for k, v in a.items()
                           if k in ("x", "dt", "b", "c")}
    fixed = dict(a_log=a["a_log"], d_skip=a["d_skip"])
    y1, s1 = _ssd_jit(**part(0, cut), s0=a["s0"], **fixed)
    y2, s2 = _ssd_jit(**part(cut, 64), s0=s1, **fixed)
    assert _rel(jnp.concatenate([y1, y2], 1), whole_y) < 1e-5
    assert _rel(s2, whole_s) < 1e-5


@pytest.mark.parametrize("t,groups,chunk", [(70, 1, 16), (10, 1, 16),
                                            (48, 2, 16)])
def test_ssd_gradients_are_autodiff_of_the_recurrence(t, groups, chunk):
    a = _scan_inputs(t, groups, seed=1)
    names = sorted(a)
    weight = jnp.asarray(np.random.default_rng(9).standard_normal(
        a["x"].shape), jnp.float32)

    def loss(fn):
        def of(*args):
            y, s = fn(**dict(zip(names, args)))
            return (y * weight).sum() + jnp.square(s).sum()
        return jax.jit(jax.grad(of, argnums=range(len(names))))(
            *(a[k] for k in names))
    got = loss(lambda **kw: ssd(**kw, chunk=chunk))
    want = loss(_recurrence)
    for name, g, w in zip(names, got, want):
        assert _rel(g, w) < 2e-5, name


def test_decays_that_underflow_inside_a_chunk_stay_finite():
    """dt x A up to a few hundred a position: exp(c_Q) is 0 in float32 and
    exp(c_t) / exp(c_s) would be 0 / 0; differences taken before the exp
    give the recurrence's numbers, forward and backward."""
    a = _scan_inputs(64, strength=400.0, seed=2)
    decay = -(a["dt"] * jnp.exp(a["a_log"])).reshape(2, 4, 16, 4).sum(2)
    assert float(jnp.exp(decay).max()) == 0.0       # every chunk underflows
    y, s = _ssd_jit(**a)
    want_y, want_s = _recurrence(**a)
    assert bool(jnp.isfinite(y).all()) and _rel(y, want_y) < 1e-5
    assert _rel(s, want_s) < 1e-5
    grads = jax.jit(jax.grad(lambda x, dt, b, c: jnp.square(ssd(
        **dict(a, x=x, dt=dt, b=b, c=c), chunk=16)[0]).sum(),
        argnums=(0, 1, 2, 3)))(a["x"], a["dt"], a["b"], a["c"])
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


# -- the convolution's bias and the softmax's scale ------------------------------

def test_causal_conv_takes_a_bias_before_its_activation():
    x = _normal(0, 2, 9, 6)
    conv = nn.CausalConv1D(4, activation="silu", use_bias=True)
    variables = conv.init(jax.random.PRNGKey(1), x)
    assert variables["params"]["bias"].shape == (6,)
    assert float(jnp.abs(variables["params"]["bias"]).max()) == 0.0
    bias = jnp.arange(6.0) / 3 - 1
    variables["params"]["bias"] = bias
    w = variables["params"]["kernel"]
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(sum(padded[:, j:j + 9] * w[j] for j in range(4))
                       + bias)
    assert _rel(conv.apply(variables, x)[0], want) < 1e-6
    # the default keeps the layer the delta rule's mixer has: no bias leaf
    plain = nn.CausalConv1D(4, activation="silu")
    assert set(plain.init(jax.random.PRNGKey(1), x)["params"]) == {"kernel"}
    gdn = jax.eval_shape(lambda: nn.GatedDeltaNet(2, 4, 8, 8).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 16))))
    assert set(gdn["params"]["conv"]) == {"kernel"}


def _heads_of_64(t=40, seed=0):
    return [_normal(seed + i, 1, t, 2, 64) for i in range(3)]


@pytest.mark.parametrize("path", ["blocked_jax", "pallas_interpret"])
def test_flash_attention_takes_a_scale_at_heads_of_64(path, monkeypatch):
    """Forward and gradients against ``mha_reference(scale=)``; heads of 64
    are padded to the 128 lanes by the kernels' wrapper."""
    monkeypatch.setattr(fa, "INTERPRET", path == "pallas_interpret")
    q, k, v = _heads_of_64()
    scale = 0.015625

    def both(fn):
        return jax.jit(fn)(q, k, v), jax.jit(jax.grad(
            lambda *a: jnp.square(fn(*a)).sum(), argnums=(0, 1, 2)))(q, k, v)
    got, got_g = both(lambda *a: flash_attention(
        *a, causal=True, block_q=16, block_k=16, scale=scale))
    want, want_g = both(lambda *a: mha_reference(*a, causal=True,
                                                 scale=scale))
    assert _rel(got, want) < 1e-5
    for g, w in zip(got_g, want_g):
        assert _rel(g, w) < 1e-5
    # None is 1/sqrt(D), and 1/64 is far from it
    default = jax.jit(lambda *a: flash_attention(
        *a, causal=True, block_q=16, block_k=16))(q, k, v)
    assert _rel(default, mha_reference(q, k, v, causal=True, scale=0.125)) \
        < 1e-5
    assert _rel(default, want) > 0.1


@pytest.mark.parametrize("path", ["dense", "dense_remat", "flash"])
def test_attention_layer_takes_a_scale(path):
    x = _normal(0, 2, 24, 32)
    kw = dict(head_dim=16, causal=True, num_kv_heads=2,
              use_flash=path == "flash", remat=path == "dense_remat")
    layer = nn.MultiHeadAttention(4, scale=0.02, **kw)
    variables = layer.init(jax.random.PRNGKey(1), x)
    m = dict(num_heads=4, num_kv_heads=2, head_dim=16,
             attention_multiplier=0.02)
    got = jax.jit(lambda v: layer.apply(v, x)[0])(variables)
    want = jax.jit(lambda p: fam.attention_reference(p, x, m))(
        variables["params"])
    assert _rel(got, want) < 1e-5
    # the same weights at the default scale, 1/sqrt(16): another function
    plain = nn.MultiHeadAttention(4, **kw)
    default = jax.jit(lambda v: plain.apply(v, x)[0])(variables)
    assert _rel(default, jax.jit(lambda p: fam.attention_reference(
        p, x, dict(m, attention_multiplier=0.25)))(variables["params"])) \
        < 1e-5
    assert _rel(default, got) > 0.05
    with pytest.raises(ValueError, match="scale"):
        nn.MultiHeadAttention(4, scale=0.02, use_ring=True)


# -- the mixer and the whole model ----------------------------------------------

@pytest.mark.parametrize("t", [32, 37])
def test_mamba_layer_matches_the_reference(t):
    x = _normal(0, 2, t, 64)
    layer = nn.Mamba2(4, 32, 16, chunk=16)
    variables = jax.jit(lambda: layer.init(jax.random.PRNGKey(1), x))()
    variables["params"]["conv"]["bias"] = 0.3 * _normal(2, 160)
    m = GraniteHybrid(**SMALL)._config
    got, state = jax.jit(lambda v: layer.apply(v, x, training=True))(
        variables)
    want = jax.jit(lambda p: fam.mamba_reference(p, x, m))(
        variables["params"])
    assert _rel(got, want) < 1e-5
    assert set(variables["params"]) == {"in_proj", "conv", "A_log",
                                        "dt_bias", "D", "norm", "out_proj"}
    assert variables["params"]["in_proj"]["kernel"].shape == (
        64, 128 + 160 + 4)
    counted = state["counters"]
    assert int(counted["ssm.tokens"]) == 2 * t
    assert int(counted["ssm.tokens_padded"]) == 2 * (-t % 16)


def _system_loss(model, variables, ids, labels):
    def loss(params):
        out, _ = model.apply({"params": params,
                              "state": variables["state"]}, ids,
                             training=True)
        return nn.losses.sparse_categorical_crossentropy(out, labels), out
    return jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])


def _perturbed(model, ids):
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(1), ids))()
    # norm weights, conv biases, D off their initial values
    variables["params"] = jax.tree_util.tree_map(
        lambda a: a + 0.1 * _normal(a.size, *a.shape) if a.ndim == 1 else a,
        variables["params"])
    return variables


def _plain(tree):
    """A tree of ``remat_i: {layer_i: ...}`` as ``remat=False`` names it."""
    return {k[6:] and f"layer_{k[6:]}" if k.startswith("remat_") else k:
            v[f"layer_{k[6:]}"] if k.startswith("remat_") else v
            for k, v in tree.items()}


def _worst(grads, want):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grads, want)))


@pytest.fixture(scope="module")
def row():
    """A row of 37 in chunks of 16 (two whole chunks and a padded one), the
    weights off their initial values, and the reference's logits, loss and
    gradients on them."""
    config = {"model": dict(SMALL, use_flash=True)}
    ids = _ids(0, 2, 37)
    labels = np.roll(ids, -1, axis=1)
    variables = _perturbed(fam.build(config), ids)
    loss, grads = fam.reference_loss_and_grads(config, variables, ids,
                                               labels)
    return dict(config=config, ids=ids, labels=labels, variables=variables,
                logits=fam.reference(config, variables, ids), loss=loss,
                grads=grads)


# each block recomputed (the flash outputs kept, as the cell has it), the
# scan's output and chunk-start states kept as well, nothing recomputed
@pytest.mark.parametrize("model_args", [
    dict(remat=True), dict(remat=False),
    dict(remat=True, remat_save=["mamba2_ssd_out", "mamba2_ssd_states"])],
    ids=["remat", "plain", "remat_keeping_the_scan"])
def test_whole_model_logits_loss_and_gradients_match_the_reference(
        model_args, row):
    """The attention layer on the flash path's blocked forms."""
    model = fam.build({"model": dict(row["config"]["model"], **model_args)})
    variables, want = row["variables"], row["grads"]
    if not model_args["remat"]:
        variables = {k: _plain(v) for k, v in variables.items()}
        want = _plain(want)
    (loss, logits), grads = _system_loss(model, variables, row["ids"],
                                         row["labels"])
    assert _rel(logits, row["logits"]) < 2e-5
    assert abs(float(loss) - float(row["loss"])) < 1e-5 * float(row["loss"])
    assert _worst(grads, want) < 2e-4


def test_the_attention_block_alone_has_no_positions():
    """The attention layer carries no position signal: with an attention
    block alone the last position's logits ignore the order of the rest;
    a state-space block before it makes them depend on it."""
    ids = _ids(3, 1, 12)
    shuffled = np.concatenate([ids[:, :-1][:, ::-1], ids[:, -1:]], axis=1)
    for types, moves in (([ATTENTION], False), ([MAMBA, ATTENTION], True)):
        model = GraniteHybrid(**dict(SMALL, n_layers=len(types),
                                     layer_types=types, remat=False))
        variables = jax.jit(lambda: model.init(jax.random.PRNGKey(1), ids))()
        run = jax.jit(lambda v, a: model.apply(v, a)[0][:, -1])
        moved = _rel(run(variables, shuffled), run(variables, ids))
        assert (moved > 1e-3) == moves, (types, moved)


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 6.0), ("attention_multiplier", 0.25),
    ("residual_multiplier", 0.5), ("logits_scaling", 4.0)])
def test_each_multiplier_sits_where_the_reference_has_it(key, value, row):
    config = {"model": dict(row["config"]["model"], **{key: value})}
    model = fam.build(config)
    out = jax.jit(lambda v: model.apply(v, row["ids"])[0])(row["variables"])
    assert _rel(out, row["logits"]) > 1e-3
    assert _rel(out, fam.reference(config, row["variables"], row["ids"])) \
        < 2e-5
    if key == "logits_scaling":     # 8 -> 4: twice the logits
        assert _rel(out, 2 * row["logits"]) < 2e-5


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    small = dict(SMALL, n_layers=1, layer_types=[MAMBA])
    ids = _ids(0, 2, 20)
    labels = np.roll(ids, -1, axis=1)
    tied = GraniteHybrid(**small)
    variables = _perturbed(tied, ids)
    assert "head" not in variables["params"]
    (loss, logits), grads = _system_loss(tied, variables, ids, labels)
    twin = GraniteHybrid(**dict(small, tie_embeddings=False))
    table = variables["params"]["embed"]["embeddings"]
    untied = {"params": dict(variables["params"],
                             head={"kernel": table.T}),
              "state": variables["state"]}
    assert set(jax.eval_shape(lambda: twin.init(
        jax.random.PRNGKey(1), ids))["params"]) == set(untied["params"])
    (loss_u, logits_u), grads_u = _system_loss(twin, untied, ids, labels)
    assert _rel(logits_u, logits) < 1e-6 and abs(loss_u - loss) < 1e-6
    both = grads_u["embed"]["embeddings"] + grads_u["head"]["kernel"].T
    assert _rel(grads["embed"]["embeddings"], both) < 1e-5
    # each use alone is a different gradient
    assert _rel(grads["embed"]["embeddings"],
                grads_u["embed"]["embeddings"]) > 0.1


def test_the_counters_and_levels_on_a_known_input():
    """One head, dt and A fixed by hand: the decay exponent of a chunk is
    chunk x dt x A, the carried state a geometric sum."""
    t, chunk, dt, a = 20, 8, 0.25, 2.0
    from analytics_zoo_tpu.nn.state_space import _ssd
    x = jnp.ones((1, t, 1, 1))
    ones = jnp.ones((1, t, 1))
    _, s, stats = jax.jit(lambda: _ssd(
        x, dt * ones, jnp.log(jnp.asarray([a])), ones, ones,
        jnp.zeros((1,)), None, chunk))()
    assert int(stats["tokens_padded"]) == 4            # 20 -> 24
    assert abs(float(stats["chunk_decay_exponent_max"]) - chunk * dt * a) \
        < 1e-5
    # S_t = e^-0.5 S_(t-1) + 0.25: a geometric sum
    r = float(np.exp(-dt * a))
    want = dt * (1 - r ** t) / (1 - r)
    assert abs(float(s[0, 0, 0, 0]) - want) < 1e-5
    assert abs(float(stats["state_abs_max"]) - want) < 1e-5


def test_the_counters_reach_the_registry_once_an_epoch():
    model = GraniteHybrid(**SMALL)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 128, (8, 24)).astype(np.int32)
    reg = metrics.get_registry()
    before = reg.snapshot()
    est = Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                               optimizer="adamw", learning_rate=1e-2, seed=0)
    hist = est.fit({"x": x, "y": np.roll(x, -1, 1)}, epochs=3, batch_size=4,
                   verbose=False)
    assert hist["loss"][-1] < hist["loss"][0]
    after = reg.snapshot()
    grew = lambda k: after[k] - before.get(k, 0)
    # epochs x rows x tokens x Mamba layers; 24 = 16 + 8: 8 padded a row
    assert grew("ssm.tokens") == 3 * 8 * 24 * 2
    assert grew("ssm.tokens_padded") == 3 * 8 * 8 * 2
    for series in ("ssm.chunk_decay_exponent_max", "ssm.state_abs_max"):
        was = before.get(series) or {"count": 0, "sum": 0.0}
        assert after[series]["count"] - was["count"] == 3 * 2, series
        assert after[series]["sum"] - was["sum"] > 0.0, series
    logits = np.asarray(est.predict(x, batch_size=4), np.float32)
    assert logits.shape == (8, 24, 128) and np.isfinite(logits).all()


# -- the configuration ----------------------------------------------------------

def test_configuration_holds_the_parameters_of_its_table():
    """ISSUE 33's arithmetic, counted from the built model's tree at the
    published widths (shapes only: no weights are allocated)."""
    with open(os.path.join(
            REPO, "benchmark/configs/granite_4_0_h_micro_pp4.json")) as f:
        config = json.load(f)
    model = fam.build(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree_util.tree_leaves(tree))
    params = shapes["params"]
    mamba = params["remat_0"]["layer_0"]
    attn = params["remat_9"]["layer_9"]
    mixer = mamba["mamba"]
    assert mixer["in_proj"]["kernel"].shape == (2048, 4096 + 4352 + 64)
    assert count(mixer["in_proj"]) == 17_432_576
    assert count(mixer["conv"]) == 4352 * 4 + 4352 == 21_760
    assert count(mixer["A_log"]) + count(mixer["D"]) \
        + count(mixer["dt_bias"]) == 3 * 64
    assert count(mixer["norm"]) == 4096
    assert count(mixer["out_proj"]) == 8_388_608
    assert count(mixer) == 25_847_232
    assert count(attn["attn"]) == 4_194_304 + 2 * 1_048_576 + 4_194_304 \
        == 10_485_760
    assert count(mamba["mlp"]) == count(attn["mlp"]) == 50_331_648
    norms = ("input_norm", "post_mixer_norm")
    assert sum(count(mamba[n]) for n in norms) == 4096
    assert count(mamba) == 76_182_976 and count(attn) == 60_821_504
    blocks = sum(count(params[f"remat_{i}"]) for i in range(10))
    assert blocks == 9 * 76_182_976 + 60_821_504 == 746_468_288
    assert count(params["embed"]) == 12_544 * 2048 == 25_690_112
    assert "head" not in params and count(params["final_norm"]) == 2048
    total = count(params)
    assert total == 772_160_448
    assert abs(16 * total - 12.35e9) < 0.01e9      # 16 B a parameter
    # the published widths, key by key; the two cuts and nothing else
    pub, m = config["published"], config["model"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in pub.items():
        assert config[key] == value or key in config["reduced"], key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (
        m["n_layers"], m["vocab_size"]) == (10, 12544)
    assert (pub["num_hidden_layers"], pub["vocab_size"]) == (40, 100352)
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    assert m["layer_types"] == pub["layer_types"][6:16] \
        == [MAMBA] * 9 + [ATTENTION]                       # one period
    assert GraniteHybrid()._config["layer_types"] == pub["layer_types"]
    for ours, theirs in [
            ("hidden_size", "hidden_size"),
            ("mamba_heads", "mamba_n_heads"),
            ("mamba_head_dim", "mamba_d_head"),
            ("mamba_state", "mamba_d_state"),
            ("mamba_groups", "mamba_n_groups"),
            ("mamba_conv_kernel", "mamba_d_conv"),
            ("mamba_conv_bias", "mamba_conv_bias"),
            ("chunk", "mamba_chunk_size"),
            ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"),
            ("ff_units", "shared_intermediate_size"),
            ("embedding_multiplier", "embedding_multiplier"),
            ("attention_multiplier", "attention_multiplier"),
            ("residual_multiplier", "residual_multiplier"),
            ("logits_scaling", "logits_scaling"),
            ("tie_embeddings", "tie_word_embeddings"),
            ("rms_eps", "rms_norm_eps")]:
        assert m[ours] == pub[theirs], ours
    assert m["mamba_heads"] * m["mamba_head_dim"] \
        == pub["mamba_expand"] * pub["hidden_size"]
    assert m["head_dim"] * m["num_heads"] == pub["hidden_size"]
    assert (pub["num_local_experts"], pub["position_embedding_type"],
            pub["mamba_proj_bias"], pub["attention_bias"]) == (
        0, "nope", False, False)
    # the constructor's defaults are the published sizes
    defaults = GraniteHybrid()._config
    for key in m:
        if key not in ("vocab_size", "n_layers", "layer_types"):
            assert defaults[key] == (tuple(m[key]) if key == "remat_save"
                                     else m[key]), key
    assert (defaults["vocab_size"], defaults["n_layers"]) == (100352, 40)
