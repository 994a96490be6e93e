"""What GLM-4.7-Flash (``models.GlmMoeLite``) forced, each piece against the
plain float32 reference kept with the benchmark
(``benchmark/families/glm_moe_lite.py``), at small sizes on the CPU: latent
attention on the dense path and through the flash kernels, the dispatch
lifted out of ``MultiHeadAttention``, the multi-token prediction module with
its shared embedding and head, ``multi_token_crossentropy``, the share of the
experts, the counters, the configuration's parameter count, and the model
through ``Estimator.fit``."""

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
from analytics_zoo_tpu.models import GlmMoeLite  # noqa: E402
from analytics_zoo_tpu.nn import attention as attn_mod  # noqa: E402
from analytics_zoo_tpu.orca.learn import Estimator  # noqa: E402
from analytics_zoo_tpu.parallel import DroplessMoE  # noqa: E402
from benchmark.families import afmoe, glm_moe_lite as fam  # noqa: E402

fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")

# 5 heads (20 is no power of two either), rope + nope = v_dim as published
TINY = dict(vocab_size=128, hidden_size=64, n_layers=3, num_dense_layers=1,
            num_heads=5, q_rank=32, kv_rank=24, nope_dim=24, rope_dim=8,
            v_dim=32, dense_units=96, num_experts=8, top_k=2, moe_units=32,
            shared_units=32, experts_held=4, dtype="float32")


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def _leaf_errors(got, want):
    return {jax.tree_util.keystr(p): float(
        jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                             jax.tree_util.tree_leaves(want))}


# -- LatentAttention -----------------------------------------------------------

def _latent(v_dim=32, use_flash=False, heads=5, theta=1e6):
    return nn.LatentAttention(heads, 32, 24, 24, 8, v_dim, rope_theta=theta,
                              norm_epsilon=1e-5, use_flash=use_flash)


def _latent_m(v_dim=32, heads=5, theta=1e6):
    return dict(num_heads=heads, rope_dim=8, nope_dim=24, v_dim=v_dim,
                kv_rank=24, rms_eps=1e-5, rope_theta=theta)


def _latent_params(layer, x, seed=1):
    params = layer.init(jax.random.PRNGKey(seed), x)["params"]
    for i, norm in enumerate(("q_norm", "kv_norm")):
        w = params[norm]["weight"]
        assert w.tolist() == [1.0] * w.size                      # plain
        params[norm]["weight"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(seed + 1 + i), w.shape)
    return params


@pytest.mark.parametrize("path,v_dim", [
    ("dense", 16), ("dense", 32), ("flash", 32), ("flash_interpret", 32)])
def test_latent_attention_matches_the_reference(path, v_dim, monkeypatch):
    """Forward and every leaf's gradient, where value heads are narrower
    than key heads (dense path only) and where they are as wide (dense, the
    blocked ``jax.numpy`` forms, the Pallas kernels in interpret mode)."""
    monkeypatch.setattr(fa, "INTERPRET", path == "flash_interpret")
    layer = _latent(v_dim, use_flash=path.startswith("flash"))
    m = _latent_m(v_dim)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = _latent_params(layer, x)
    assert {k: v.shape for k, v in params.items() if k[0] == "w"} == {
        "wq_a": (64, 32), "wq_b": (32, 5 * 32), "wkv_a": (64, 24 + 8),
        "wkv_b": (24, 5 * (24 + v_dim)), "wo": (5 * v_dim, 64)}
    reference = jax.jit(lambda p: fam.attention_reference(p, x, m))
    want = reference(params)

    def run(p):
        return layer.apply({"params": p, "state": {}}, x)
    out, state = jax.jit(run)(params)
    assert _rel(out, want) < 2e-5
    # the level: the largest |c_kv| after its norm, by hand
    c_kv = afmoe._rms((x @ params["wkv_a"])[..., :24],
                      params["kv_norm"]["weight"], 1e-5)
    assert abs(float(state["counters"]["mla.kv_latent_abs_max"])
               - float(jnp.abs(c_kv).max())) < 1e-5
    g_got = jax.jit(jax.grad(
        lambda p: jnp.sum(jnp.square(run(p)[0]))))(params)
    g_want = jax.jit(jax.grad(
        lambda p: jnp.sum(jnp.square(reference(p)))))(params)
    errors = _leaf_errors(g_got, g_want)
    assert len(errors) == 7 and max(errors.values()) < 2e-4, errors


@functools.lru_cache(maxsize=None)
def _rotated(theta):
    """The layer at ``theta``, compiled once for the three cases below."""
    return jax.jit(_latent(theta=theta).apply)


@pytest.mark.parametrize("what", ["query_rope_columns", "key_rope_columns",
                                  "nothing"])
def test_the_rotation_touches_the_rope_slice_only(what):
    """Positions reach the scores through ``rope_dim`` dims of q and the one
    shared key head alone: with either side's rotary columns zeroed the
    layer forgets ``rope_theta``, which a rotation laid over any of the
    ``nope`` dims would not let it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 64))
    params = _latent_params(_latent(), x)
    if what == "query_rope_columns":       # the first 8 dims of every head
        w = params["wq_b"].reshape(32, 5, 32).at[:, :, :8].set(0.0)
        params["wq_b"] = w.reshape(32, 160)
    elif what == "key_rope_columns":       # the 8 columns after the latent
        params["wkv_a"] = params["wkv_a"].at[:, 24:].set(0.0)
    outs = [_rotated(theta)({"params": params, "state": {}}, x)[0]
            for theta in (1e6, 10.0)]
    if what == "nothing":
        assert _rel(outs[0], outs[1]) > 1e-3
    else:
        assert _rel(outs[0], outs[1]) < 1e-6


def test_the_shared_key_head_reaches_every_query_head():
    """hidden = heads x v_dim and ``wo`` the identity: the output IS the
    heads' contexts.  Moving the one rotary key head moves every head."""
    layer = _latent(heads=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    params = _latent_params(layer, x)
    params["wo"] = jnp.eye(64)
    moved = dict(params, wkv_a=params["wkv_a"].at[:, 24:].multiply(-1.5))
    run = jax.jit(lambda p: layer.apply({"params": p, "state": {}}, x)[0])
    a, b = (run(p).reshape(16, 2, 32) for p in (params, moved))
    assert all(_rel(a[1:, h], b[1:, h]) > 1e-3 for h in range(2))
    assert _rel(a[0], b[0]) < 1e-6       # position 0 sees itself alone


def test_rotary_angles_are_float32_at_the_cells_length():
    """theta 1e6 at 8,192 positions: a bf16 angle is off by whole turns, a
    float32 one by ~5e-4 rad; the output's own rounding is all that shows."""
    t, r = 8192, 64
    x = jnp.ones((1, t, 1, r), jnp.bfloat16)
    got = np.asarray(attn_mod.rotary_embedding(x, r, 1e6), np.float64)
    ang = np.arange(t)[:, None] * 1e6 ** (-np.arange(r // 2) * 2.0 / r)
    want = np.concatenate([np.cos(ang) - np.sin(ang),
                           np.cos(ang) + np.sin(ang)], -1)
    assert np.abs(got[0, :, 0] - want).max() < 0.01     # bf16 spacing at ~1.4


def test_the_flash_path_refuses_value_heads_of_another_width():
    x = jax.ShapeDtypeStruct((1, attn_mod.FLASH_AUTO_MIN_SEQ, 64),
                             jnp.float32)
    for use_flash in (True, "auto"):
        layer = _latent(16, use_flash=use_flash)
        with pytest.raises(ValueError, match="value heads as wide"):
            jax.eval_shape(lambda a: layer.init(jax.random.PRNGKey(0), a), x)
    # ... and "auto" below the flash length is the dense path, any widths
    short = jax.ShapeDtypeStruct((1, 32, 64), jnp.float32)
    layer = _latent(16, use_flash="auto")
    jax.eval_shape(lambda a: layer.init(jax.random.PRNGKey(0), a), short)


# -- the dispatch lifted out of MultiHeadAttention -----------------------------

def _mha_before_the_lift(layer, params, x):
    """``MultiHeadAttention.forward``'s dense path as it stood before
    ``attention_core`` was lifted out of it (PR 36's lines)."""
    h, kv_h, d_head = layer.num_heads, layer.num_kv_heads, layer.head_dim

    def proj(name, heads, width=d_head):
        return jnp.dot(x, params[name]).reshape(x.shape[:-1] + (heads, width))
    gate = None
    if layer.gate:
        q = proj("wq", h, 2 * d_head)
        q, gate = q[..., :d_head], q[..., d_head:]
    else:
        q = proj("wq", h)
    k, v = proj("wk", kv_h), proj("wv", kv_h)
    if layer.rotary_dim:
        q = attn_mod.rotary_embedding(q, layer.rotary_dim, layer.rope_theta)
        k = attn_mod.rotary_embedding(k, layer.rotary_dim, layer.rope_theta)
    if kv_h != h:
        k = jnp.repeat(k, h // kv_h, axis=2)
        v = jnp.repeat(v, h // kv_h, axis=2)
    mask = None
    if layer.causal:
        mask = attn_mod.causal_mask(x.shape[1], x.shape[1], layer.window)
    core = functools.partial(attn_mod.dot_product_attention,
                             scale=layer.scale)
    ctx = (jax.checkpoint(core) if layer.remat else core)(q, k, v, mask)
    if gate is not None:
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
    return jnp.dot(ctx.reshape(x.shape[:-1] + (h * d_head,)), params["wo"])


@pytest.mark.parametrize("options", [
    dict(), dict(causal=True, window=8, rotary_dim=16),
    dict(causal=True, scale=0.015625, num_kv_heads=2),
    dict(causal=True, gate=True, remat=True)], ids=[
        "dense", "window", "scale", "gate"])
def test_multi_head_attention_is_bit_equal_across_the_lifted_dispatch(
        options):
    layer = nn.MultiHeadAttention(4, head_dim=16, **options)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    got = layer.apply({"params": params, "state": {}}, x)[0]
    assert np.array_equal(np.asarray(got), np.asarray(
        _mha_before_the_lift(layer, params, x)))


# -- the loss -------------------------------------------------------------------

def _by_hand(logits, y, depth_weight):
    logp = np.asarray(jax.nn.log_softmax(logits.astype(np.float64), -1))
    b, k, t, _ = logits.shape
    total = 0.0
    for depth in range(k):
        rows = [-logp[n, depth, i, y[n, i + depth]] for n in range(b)
                for i in range(t - depth)]
        total += (1.0 if depth == 0 else depth_weight) * np.mean(rows)
    return total


@pytest.mark.parametrize("case", ["by_hand", "last_position_of_depth_1",
                                  "depth_weight_0", "one_depth", "by_name"])
def test_multi_token_crossentropy(case):
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 2, 6, 11)).astype(np.float32)
    y = rng.integers(0, 11, (2, 6)).astype(np.int32)
    loss = nn.losses.multi_token_crossentropy
    plain = nn.losses.sparse_categorical_crossentropy
    if case == "by_hand":
        assert abs(float(loss(logits, y)) - _by_hand(logits, y, 0.3)) < 1e-5
        assert abs(float(loss(logits, y, depth_weight=0.7))
                   - _by_hand(logits, y, 0.7)) < 1e-5
    elif case == "last_position_of_depth_1":
        other = logits.copy()
        other[:, 1, -1] = 50.0 * rng.normal(size=(2, 11))
        assert float(loss(other, y)) == float(loss(logits, y))
        g = jax.grad(lambda a: loss(a, y))(jnp.asarray(logits))
        assert float(jnp.abs(g[:, 1, -1]).max()) == 0.0
        assert float(jnp.abs(g[:, 1, -2]).min()) > 0.0
        other[:, 1, -2, 0] += 1.0            # the one before it does count
        assert float(loss(other, y)) != float(loss(logits, y))
    elif case == "depth_weight_0":
        assert abs(float(loss(logits, y, depth_weight=0.0))
                   - float(plain(logits[:, 0], y))) < 1e-6
    elif case == "one_depth":
        assert abs(float(loss(logits[:, :1], y))
                   - float(plain(logits[:, 0], y))) < 1e-6
    else:
        assert nn.losses.get("multi_token_crossentropy") is loss
        assert fam.DEPTH_WEIGHT == 0.3          # the default, the reference's


# -- the whole model -------------------------------------------------------------

def _data(seed=0, rows=2, t=17):
    ids = np.random.default_rng(seed).integers(0, 128, (rows, t))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def _variables(model, x, seed=0):
    """Initial variables with the norms' weights and the biases moved off 1
    and 0, so that no term drops out of the comparison."""
    v = jax.jit(lambda k: model.init(k, x))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))
    v["params"] = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.uniform(next(keys), a.shape)
        if a.ndim == 1 else a, v["params"])
    for _, s in fam._blocks(v["state"], model._config):
        if "moe" in s:
            s["moe"]["expert_bias"] = 0.3 * jax.random.normal(
                next(keys), s["moe"]["expert_bias"].shape)
    return v


class _Case:
    """One model on one batch: the system's logits, state, loss and
    gradients (each jitted once) beside the reference's."""

    def __init__(self, **options):
        self.config = {"model": dict(TINY, **options)}
        self.model = model = GlmMoeLite(**self.config["model"])
        self.x, self.y = x, y = _data()
        self.variables = v = _variables(model, x)
        self.logits, self.state = jax.jit(
            lambda v: model.apply(v, x, training=False))(v)

        def loss(params):
            out, _ = model.apply({"params": params, "state": v["state"]}, x,
                                 training=True)
            return nn.losses.multi_token_crossentropy(out, y)
        self.loss, self.grads = jax.jit(jax.value_and_grad(loss))(v["params"])
        self.want = fam.reference(self.config, v, x)
        self.want_loss, self.want_grads = fam.reference_loss_and_grads(
            self.config, v, x, y)


@pytest.fixture(scope="module")
def case():
    return _Case()


def _check_against_the_reference(c):
    depths = 1 + c.model.mtp_layers
    assert c.logits.shape == c.want.shape == (2, depths, 16, 128)
    for k in range(depths):                # each depth in its own range
        assert _rel(c.logits[:, k], c.want[:, k]) < 2e-5, k
    assert abs(float(c.loss) - float(c.want_loss)) < 2e-5
    errors = _leaf_errors(c.grads, c.want_grads)
    assert len(errors) == len(jax.tree_util.tree_leaves(
        c.variables["params"]))
    assert max(errors.values()) < 5e-4, max(errors.items(),
                                            key=lambda kv: kv[1])


def test_whole_model_logits_loss_and_gradients_match_the_reference(case):
    """Both depths' logits, the two-depth loss and every leaf's gradient,
    each block under ``nn.Remat``."""
    _check_against_the_reference(case)


def test_without_the_module_the_model_is_a_plain_decoder():
    c = _Case(mtp_layers=0, remat=False, n_layers=2)
    _check_against_the_reference(c)
    plain = nn.losses.sparse_categorical_crossentropy(c.logits[:, 0], c.y)
    assert abs(float(c.loss) - float(plain)) < 1e-6
    assert "mtp" not in c.variables["params"]
    assert "counters" not in c.variables["state"]
    assert "layer_0" in c.variables["params"]            # no remat_0


def test_the_shared_leaves_gradients_are_the_sum_of_both_uses(case):
    """Embedding and head are one leaf each, read by the main model and by
    the prediction module: the leaf's gradient is the sum of what a twin
    with separate copies gives the two."""
    params, m = case.variables["params"], case.model._config
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "remat",
                                  "head_norm"}           # no table, no head
    twin = {"embed": params["embed"]["embeddings"],
            "head": params["head"]["kernel"]}
    biases = fam.expert_biases(case.variables["state"], m)
    g_main, g_twin = jax.jit(jax.grad(
        lambda p, t: fam.loss_reference(p, biases, case.x, case.y, m, t),
        argnums=(0, 1)))(params, twin)
    for leaf, main, second in [
            (case.grads["embed"]["embeddings"],
             g_main["embed"]["embeddings"], g_twin["embed"]),
            (case.grads["head"]["kernel"], g_main["head"]["kernel"],
             g_twin["head"])]:
        assert float(jnp.abs(second).max()) > 1e-4      # both uses count
        assert _rel(leaf, main + second) < 2e-4
        assert _rel(leaf, main) > 1e-2


def test_the_counters_on_a_known_input(case):
    """``mtp.positions`` counts the T - 2 positions a row whose target the
    model holds, ``mtp.top1_hits`` those where depth 1's arg-max is
    ``ids[i + 2]``, ``mtp.loss`` their mean cross-entropy; the counts add up
    over applications, the level does not."""
    depth1, target = np.asarray(case.logits[:, 1, :-2]), case.x[:, 2:]
    hits = int((depth1.argmax(-1) == target).sum())
    nll = float(nn.losses.sparse_categorical_crossentropy(depth1, target))
    got = case.state["counters"]
    assert int(got["mtp.positions"]) == 2 * 14
    assert int(got["mtp.top1_hits"]) == hits
    assert abs(float(got["mtp.loss"]) - nll) < 1e-5
    _, again = jax.jit(lambda s: case.model.apply(
        {"params": case.variables["params"], "state": s}, case.x))(
            case.state)
    assert int(again["counters"]["mtp.positions"]) == 2 * 2 * 14
    assert int(again["counters"]["mtp.top1_hits"]) == 2 * hits
    assert abs(float(again["counters"]["mtp.loss"]) - nll) < 1e-5
    levels = [float(s["attn"]["counters"]["mla.kv_latent_abs_max"])
              for _, s in fam._blocks(again, case.model._config)]
    assert len(levels) == 4 and all(1.0 < v < 24 ** 0.5 * 1.3 for v in levels)


# -- the share of the experts, tied to this model's router ---------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs guide, section 4, with this model's router (sigmoid,
    top-4 of 16 here for 64, scale 1.8, a bias on the selection): the routed
    parts of all eight shares, plus the shared expert counted once, are the
    uncut reference layer."""
    m = dict(GlmMoeLite(**TINY)._config, num_experts=16, top_k=4)

    def layer(**kw):
        return DroplessMoE(16, 4, 32, score_func="sigmoid", route_scale=1.8,
                           norm_epsilon=1e-20, shared_gate=False,
                           balance_coeff=0.001, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64))
    whole = jax.jit(layer(shared_units=32).init)(jax.random.PRNGKey(1), x)
    p = whole["params"]
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))

    def apply(module, params):
        state = jax.jit(module.init)(jax.random.PRNGKey(0), x)["state"]
        state["expert_bias"] = bias
        return jax.jit(module.apply)({"params": params, "state": state}, x)
    want, _ = jax.jit(lambda p: afmoe.moe_reference(
        p, x, m, bias, first=0, held=16))(p)
    uncut, _ = apply(layer(shared_units=32), p)       # experts_held = all
    assert _rel(uncut, want) < 2e-5
    routed = jnp.zeros_like(x)
    for first in range(0, 16, 2):
        share = {"router": p["router"],
                 "w_gate_up": p["w_gate_up"][first:first + 2],
                 "w_down": p["w_down"][first:first + 2]}
        part, state = apply(layer(experts_held=2, first_expert=first), share)
        assert int(state["counters"]["moe.pairs_dropped"]) == 0
        routed = routed + part
    shared = afmoe._swiglu(p["shared_expert"], x.reshape(-1, 64))
    assert _rel(routed + shared.reshape(x.shape), want) < 2e-5


# -- the configuration ------------------------------------------------------------

def test_configuration_holds_the_parameters_of_its_table():
    """ISSUE 37's arithmetic, counted from the built model at the published
    widths (``jax.eval_shape``: no weight is allocated)."""
    with open(os.path.join(REPO, "benchmark/configs",
                           "glm_4_7_flash_ep8.json")) as f:
        config = json.load(f)
    model = fam.build(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree_util.tree_leaves(tree))
    params = shapes["params"]
    dense = params["remat_0"]["layer_0"]
    sparse = params["remat_4"]["layer_4"]
    attn = dense["attn"]
    assert (attn["wq_a"].shape, attn["wq_b"].shape, attn["wkv_a"].shape,
            attn["wkv_b"].shape, attn["wo"].shape) == (
        (2048, 768), (768, 20 * 256), (2048, 512 + 64),
        (512, 20 * (192 + 256)), (20 * 256, 2048))
    assert count(attn) == count(sparse["attn"]) == 21_759_232 \
        == 1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520 \
        + 10_485_760
    assert count(dense) == 84_677_888 == 21_759_232 + 3 * 2048 * 10240 + 4096
    moe = sparse["moe"]
    assert set(moe) == {"router", "shared_expert", "w_gate_up", "w_down"}
    assert moe["w_gate_up"].shape == (8, 2048, 3072)
    assert moe["w_down"].shape == (8, 1536, 2048)
    assert count(moe["router"]) == 131_072
    assert count(moe["shared_expert"]) == 9_437_184
    assert count(sparse) == 106_829_056 == 31_331_584 + 8 * 9_437_184
    assert count(params["embed"]) + count(params["head"]) == 79_298_560
    mtp = params["mtp"]
    assert count(mtp["eh_proj"]) == 8_388_608
    assert count(mtp["remat"]["block"]) == 106_829_056
    assert count(mtp) == 115_223_808
    total = count(params)
    assert total == 706_518_528 == 84_677_888 + 4 * 106_829_056 \
        + 79_298_560 + 2048 + 115_223_808
    assert abs(16 * total - 11.30e9) < 0.01e9        # 16 B a parameter
    # the biases are state and no parameter: 64 a layer, all experts
    biases = fam.expert_biases(shapes["state"], model._config)
    assert sorted(biases) == ["layer_1", "layer_2", "layer_3", "layer_4",
                              "mtp"]
    assert all(b.shape == (64,) for b in biases.values())
    assert model._config["n_layers"] == 5 and GlmMoeLite().n_layers == 47
    with pytest.raises(ValueError, match="mtp_layers"):
        GlmMoeLite(mtp_layers=2)


# -- through the Estimator --------------------------------------------------------

def test_model_trains_predicts_and_publishes_its_series_through_the_estimator():
    model = GlmMoeLite(**dict(TINY, n_layers=2))
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 16, (8, 25)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    reg = metrics.get_registry()
    before = reg.snapshot()
    est = Estimator.from_keras(model, loss="multi_token_crossentropy",
                               optimizer="adamw", learning_rate=1e-2, seed=0)
    hist = est.fit({"x": x, "y": y}, epochs=6, batch_size=4, verbose=False)
    assert hist["loss"][-1] < hist["loss"][0]
    after = reg.snapshot()
    grew = lambda k: after[k] - before.get(k, 0)
    assert grew("mtp.positions") == 6 * 8 * 22           # epochs x rows x T-2
    assert 0 < grew("mtp.top1_hits") <= grew("mtp.positions")
    count = lambda k: after[k]["count"] - (before.get(k) or {"count": 0})[
        "count"]
    assert count("mtp.loss") == 6                        # a level an epoch
    assert count("mla.kv_latent_abs_max") == 6 * 3       # ... and layer
    assert count("moe.expert_bias_abs_max") == 6 * 2
    last = after["mtp.loss"]["sum"] - (before.get("mtp.loss")
                                       or {"sum": 0.0})["sum"]
    assert 0.0 < last / 6 < np.log(128) + 1.0
    logits = np.asarray(est.predict(x, batch_size=4), np.float32)
    assert logits.shape == (8, 2, 24, 128) and np.isfinite(logits).all()
    scores = est.evaluate({"x": x, "y": y}, batch_size=4)
    assert 0.0 < scores["loss"] < hist["loss"][0]
