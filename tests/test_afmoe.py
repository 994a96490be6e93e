"""What Trinity-Mini (``models.AFMoE``) forced, each piece against the plain
float32 reference kept with the benchmark (``benchmark/families/afmoe.py``),
at small sizes on the CPU: sliding-window attention on every path of
``flash_attention`` (the Pallas kernel in interpret mode, the blocked
``jax.numpy`` forward and backward) and of ``MultiHeadAttention``, the
sigmoid router with its selection bias, the bias update, the share of the
experts, the whole model's logits, loss and gradients, the configuration's
parameter count, and the bias's level through ``Estimator.fit``."""

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
from analytics_zoo_tpu.models import AFMoE  # noqa: E402
from analytics_zoo_tpu.ops import flash_attention, mha_reference  # noqa: E402
from analytics_zoo_tpu.orca.learn import Estimator  # noqa: E402
from analytics_zoo_tpu.parallel import DroplessMoE  # noqa: E402
from analytics_zoo_tpu.parallel.moe import balance_bias  # noqa: E402
from benchmark.families import afmoe as fam  # noqa: E402

fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")

SLIDING, FULL = "sliding_attention", "full_attention"
TINY = dict(vocab_size=128, hidden_size=64, n_layers=5, num_dense_layers=1,
            layer_types=[SLIDING] * 4 + [FULL], window=16, num_heads=4,
            num_kv_heads=2, head_dim=16, dense_units=96, num_experts=8,
            top_k=2, moe_units=32, shared_units=32, experts_held=4,
            dtype="float32")
# one layer of each kind: dense + sliding, experts + sliding, experts + full
SMALL = dict(TINY, n_layers=3, layer_types=[SLIDING, SLIDING, FULL])


def _config(name="trinity_mini_ep8"):
    with open(os.path.join(REPO, "benchmark/configs", name + ".json")) as f:
        return json.load(f)


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def _qkv(t, seed=0, b=2, h=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b, t, h, d)) for k in ks]


# -- the window, on every path of ops/flash_attention.py -----------------------

# rows that are no multiple of the block, windows that are no multiple of it,
# a window of one block, a window narrower than a block, a window of one key
WINDOW_CASES = [(50, 13, 16), (64, 16, 16), (70, 17, 32), (45, 1, 8)]


@pytest.mark.parametrize("path", ["blocked_jax", "pallas_interpret"])
@pytest.mark.parametrize("t,window,block", WINDOW_CASES)
def test_windowed_forward_matches_the_reference(path, t, window, block,
                                                monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", path == "pallas_interpret")
    q, k, v = _qkv(t)
    got = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          window=window)
    want = mha_reference(q, k, v, causal=True, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-6
    # ... and a window does narrow what plain causal attention sees
    assert float(jnp.abs(want - mha_reference(q, k, v, causal=True)).max()) \
        > 1e-3


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (16, 32), (8, 24)])
def test_windowed_kernel_takes_unequal_blocks(block_q, block_k, monkeypatch):
    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(70, seed=1)
    got = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, window=19)
    want = mha_reference(q, k, v, causal=True, window=19)
    assert float(jnp.abs(got - want).max()) < 2e-6


@pytest.mark.parametrize("t,window,block", WINDOW_CASES)
def test_windowed_backward_matches_the_references_gradient(t, window, block):
    q, k, v = _qkv(t, seed=2)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def of(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * cot), (0, 1, 2))(q, k, v)
    got = of(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, window=window))
    want = of(lambda q, k, v: mha_reference(q, k, v, causal=True,
                                            window=window))
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 2e-5


@pytest.mark.parametrize("window", [40, 64, 1000])
def test_a_window_that_covers_the_row_is_plain_causal_attention(window):
    q, k, v = _qkv(40, seed=3)
    run = lambda w: jax.value_and_grad(lambda q: jnp.sum(jnp.square(
        flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                        window=w))))(q)
    (got, g_got), (want, g_want) = run(window), run(None)
    assert float(got) == float(want) and bool(jnp.all(g_got == g_want))
    assert float(jnp.abs(mha_reference(q, k, v, causal=True, window=window)
                         - mha_reference(q, k, v, causal=True)).max()) == 0.0


def test_the_band_is_what_the_forward_walks():
    """A sliding layer of the cell, 16,384 rows and a window of 2,048 in the
    blocks the shapes give (512 x 512): a query block walks its own key
    block and the four before it, 150 tiles a head where the rectangular
    grid stepped over 32 x 32, and only the diagonal's tile and the far
    edge's build a mask."""
    assert fa._fwd_blocks(16384, 16384, 2048) == (512, 512, 512)
    qi, kj, flag = fa._fwd_tiles(16384, 16384, 512, 512, 16384, True, 2048)
    assert len(qi) == 150
    for i in range(32):
        assert list(kj[qi == i]) == list(range(max(0, i - 4), i + 1))
    assert [bool(f & fa._MASKED) for f in flag] \
        == [j in (i, i - 4) for i, j in zip(qi, kj)]
    # 70 rows, a window of 19, blocks of 32 x 16 (rows padded to 96, keys
    # to 80): rows 32..63 see keys 14..63, rows 64..95 keys 46..79
    qi, kj, flag = fa._fwd_tiles(96, 80, 32, 16, 70, True, 19)
    assert [list(kj[qi == i]) for i in range(3)] \
        == [[0, 1], [0, 1, 2, 3], [2, 3, 4]]
    # ... and every one of those tiles is crossed by an edge or by padding
    assert all(f & fa._MASKED for f in flag)


@pytest.mark.parametrize("bad", [dict(causal=False, window=8),
                                 dict(causal=True, window=0)])
def test_a_window_needs_causal_self_attention(bad):
    q, k, v = _qkv(32)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, **bad)


# -- MultiHeadAttention(window=) ----------------------------------------------

@pytest.mark.parametrize("sliding", [True, False])
@pytest.mark.parametrize("path", ["dense", "dense_remat", "flash",
                                  "flash_interpret"])
def test_attention_layer_matches_the_reference(path, sliding, monkeypatch):
    """Both kinds of layer of the model: windowed with rotary embedding,
    full with none; a plain (not zero-centred) q/k norm; the gate."""
    m = AFMoE(**TINY)._config
    monkeypatch.setattr(fa, "INTERPRET", path == "flash_interpret")
    layer = nn.MultiHeadAttention(
        4, head_dim=16, causal=True, num_kv_heads=2, qk_norm=True,
        qk_norm_zero_centered=False, gate=True, norm_epsilon=m["rms_eps"],
        rotary_dim=16 if sliding else 0, window=16 if sliding else None,
        use_flash=path.startswith("flash"), remat=path == "dense_remat")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    for norm in ("q_norm", "k_norm"):
        assert params[norm]["weight"].tolist() == [1.0] * 16   # plain
        params[norm]["weight"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(2), (16,))
    want = fam.attention_reference(params, x, m, sliding)

    def run(p):
        return layer.apply({"params": p, "state": {}}, x)[0]
    assert _rel(run(params), want) < 2e-5
    g_got = jax.grad(lambda p: jnp.sum(jnp.square(run(p))))(params)
    g_want = jax.grad(lambda p: jnp.sum(jnp.square(
        fam.attention_reference(p, x, m, sliding))))(params)
    for name in ("wq", "wk", "wv", "wo"):
        assert _rel(g_got[name], g_want[name]) < 2e-4, name
    for norm in ("q_norm", "k_norm"):
        assert _rel(g_got[norm]["weight"], g_want[norm]["weight"]) < 2e-4


def test_a_window_is_refused_where_it_cannot_be_kept():
    with pytest.raises(ValueError, match="window"):
        nn.MultiHeadAttention(4, window=8)                 # not causal
    with pytest.raises(ValueError, match="window"):
        nn.MultiHeadAttention(4, causal=True, window=8, use_ring=True)
    layer = nn.MultiHeadAttention(2, head_dim=8, causal=True, window=8,
                                  use_flash="auto")
    t = nn.attention.FLASH_AUTO_MIN_SEQ
    x = jax.ShapeDtypeStruct((1, t, 16), jnp.float32)
    variables = jax.eval_shape(
        lambda a: layer.init(jax.random.PRNGKey(0), a), x)
    mask = jax.ShapeDtypeStruct((1, 1, t, t), jnp.bool_)
    with pytest.raises(ValueError, match="explicit mask"):
        jax.eval_shape(lambda v, a, k: layer.apply(v, a, mask=k)[0],
                       variables, x, mask)
    # without the mask the same layer takes the flash path at that length
    out = jax.eval_shape(lambda v, a: layer.apply(v, a)[0], variables, x)
    assert out.shape == (1, t, 16)


# -- the router: sigmoid scores, a bias on the selection, an ungated shared ----

def _moe(**kw):
    return DroplessMoE(8, 2, 32, score_func="sigmoid", route_scale=2.826,
                       norm_epsilon=1e-20, shared_gate=False,
                       balance_coeff=0.001, **kw)


def _moe_inputs(seed=0, tokens=(2, 24), d=64, held=8):
    x = jax.random.normal(jax.random.PRNGKey(seed), tokens + (d,))
    return x, _moe(experts_held=held, shared_units=32).init(
        jax.random.PRNGKey(seed + 1), x)


def _apply(layer, params, x, bias, training=False):
    state = layer.init(jax.random.PRNGKey(0), x)["state"]
    state["expert_bias"] = bias
    return layer.apply({"params": params, "state": state}, x,
                       training=training)


BIAS = jnp.asarray([0.3, -0.2, 0.0, 0.25, -0.3, 0.1, 0.0, -0.1])


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the routed parts of all eight (here
    four) shares, plus what every chip computes alike (the shared expert)
    counted once, are the uncut reference layer; with a bias that moves the
    selection."""
    m = AFMoE(**TINY)._config
    x, whole = _moe_inputs()
    p = whole["params"]
    assert "shared_gate" not in p and "aux_loss" not in whole["state"]
    want, top_e = fam.moe_reference(p, x, m, BIAS, first=0, held=8)
    _, unbiased = fam.moe_reference(p, x, m, 0.0 * BIAS, first=0, held=8)
    assert float((jnp.sort(top_e) != jnp.sort(unbiased)).mean()) > 0.1
    routed = jnp.zeros_like(x)
    for first in (0, 2, 4, 6):
        share = {"router": p["router"],
                 "w_gate_up": p["w_gate_up"][first:first + 2],
                 "w_down": p["w_down"][first:first + 2]}
        part, state = _apply(_moe(experts_held=2, first_expert=first),
                             share, x, BIAS)
        # ... and each share is the reference's share
        ref_part, _ = fam.moe_reference(share, x, m, BIAS, first=first,
                                        held=2, shared=False)
        assert _rel(part, ref_part) < 2e-5
        assert int(state["counters"]["moe.pairs_dropped"]) == 0
        assert bool(jnp.all(state["expert_bias"] == BIAS))   # inference
        routed = routed + part
    with_shared, _ = _apply(
        _moe(experts_held=2, first_expert=6, shared_units=32),
        dict(share, shared_expert=p["shared_expert"]), x, BIAS)
    assert _rel(routed + (with_shared - part), want) < 2e-5


def test_expert_layer_gradients_match_the_reference():
    m = AFMoE(**TINY)._config
    x, variables = _moe_inputs(seed=3, held=4)
    layer = _moe(experts_held=4, shared_units=32)

    def system(p, x):
        return jnp.sum(jnp.square(_apply(layer, p, x, BIAS, True)[0]))

    def reference(p, x):
        return jnp.sum(jnp.square(fam.moe_reference(p, x, m, BIAS)[0]))
    got = jax.grad(system, (0, 1))(variables["params"], x)
    want = jax.grad(reference, (0, 1))(variables["params"], x)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        got, want)
    assert max(jax.tree_util.tree_leaves(worst)) < 2e-4, worst
    # the weights carry the scale: the kept scores sum to route_scale
    scores = jax.nn.sigmoid(x.reshape(-1, 64)
                            @ variables["params"]["router"]["kernel"])
    _, top_e = fam.moe_reference(variables["params"], x, m, BIAS)
    kept = jnp.take_along_axis(scores, top_e, -1)
    w = kept / (kept.sum(-1, keepdims=True) + 1e-20) * 2.826
    assert np.allclose(w.sum(-1), 2.826, rtol=1e-6)


def test_the_bias_update_follows_three_hand_worked_steps():
    """``d = coeff * sign(mean(c) - c); b += d - mean(d)`` on four experts,
    coefficient 0.001."""
    b = jnp.zeros(4)
    # picks 10, 2, 4, 4: mean 5; signs -1, +1, +1, +1; d - mean(d):
    # -0.0015, 0.0005, 0.0005, 0.0005
    b = balance_bias(b, jnp.asarray([10, 2, 4, 4]), 0.001)
    assert np.allclose(b, [-0.0015, 0.0005, 0.0005, 0.0005], atol=1e-9)
    # picks 5, 5, 5, 5: balanced, nothing moves
    b = balance_bias(b, jnp.asarray([5, 5, 5, 5]), 0.001)
    assert np.allclose(b, [-0.0015, 0.0005, 0.0005, 0.0005], atol=1e-9)
    # picks 2, 8, 6, 4: mean 5; signs +1, -1, -1, +1; mean(d) 0
    b = balance_bias(b, jnp.asarray([2, 8, 6, 4]), 0.001)
    assert np.allclose(b, [-0.0005, -0.0005, -0.0005, 0.0015], atol=1e-9)
    assert abs(float(b.sum())) < 1e-9            # the bias stays centred


def test_the_training_forward_moves_the_bias_and_inference_does_not():
    x, variables = _moe_inputs(seed=5, held=4)
    layer = _moe(experts_held=4, shared_units=32)
    m = AFMoE(**TINY)._config
    _, top_e = fam.moe_reference(variables["params"], x, m, BIAS)
    picks = (top_e[..., None] == jnp.arange(8)).sum((0, 1))
    out, state = _apply(layer, variables["params"], x, BIAS, training=True)
    assert np.allclose(state["expert_bias"],
                       balance_bias(BIAS, picks, 0.001), atol=1e-9)
    assert np.isclose(float(state["counters"]["moe.expert_bias_abs_max"]),
                      float(jnp.abs(state["expert_bias"]).max()))
    # the step's output was routed by the bias it was given
    assert _rel(out, fam.moe_reference(variables["params"], x, m,
                                       BIAS)[0]) < 2e-5
    _, state = _apply(layer, variables["params"], x, BIAS, training=False)
    assert bool(jnp.all(state["expert_bias"] == BIAS))


def test_the_layers_defaults_are_the_softmax_router_with_its_loss():
    layer = DroplessMoE(8, 2, 32, shared_units=32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64))
    variables = layer.init(jax.random.PRNGKey(1), x)
    assert set(variables["state"]) >= {"aux_loss", "counters"}
    assert "expert_bias" not in variables["state"]
    assert "shared_gate" in variables["params"]
    assert set(variables["state"]["counters"]) == {
        "moe.pairs_total", "moe.pairs_local", "moe.pairs_dropped",
        "moe.load_max_over_mean"}
    with pytest.raises(ValueError, match="score_func"):
        DroplessMoE(8, 2, 32, score_func="tanh").init(
            jax.random.PRNGKey(1), x)


# -- the whole model -----------------------------------------------------------

def _system_loss(model, variables, ids, labels):
    def loss(params):
        out, _ = model.apply({"params": params,
                              "state": variables["state"]}, ids,
                             training=True)
        return nn.losses.sparse_categorical_crossentropy(out, labels), out
    return jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])


@pytest.mark.parametrize("remat", [True, False])
def test_whole_model_logits_loss_and_gradients_match_the_reference(remat):
    """A row of 37 with a window of 16 (narrower than the row, on the
    flash path's blocked forms) and a bias that is not zero."""
    config = {"model": dict(SMALL, remat=remat, use_flash=True)}
    model = fam.build(config)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 37), 0,
                                        128))
    labels = np.roll(ids, -1, axis=1)
    variables = model.init(jax.random.PRNGKey(1), ids)
    # norm weights off their initial 1, biases off their initial 0
    variables["params"] = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape) if a.ndim == 1 else a,
        variables["params"])
    for i, (_, s) in enumerate(fam._blocks(variables["state"],
                                           model._config)):
        if "moe" in s:
            s["moe"]["expert_bias"] = jnp.roll(BIAS, i)
    assert len(fam.expert_biases(variables["state"], model._config)) == 2
    (loss, logits), grads = _system_loss(model, variables, ids, labels)
    assert _rel(logits, fam.reference(config, variables, ids)) < 2e-5
    want_loss, want_grads = fam.reference_loss_and_grads(
        config, variables, ids, labels)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grads, want_grads)
    assert max(jax.tree_util.tree_leaves(worst)) < 2e-4, worst
    # the dense path (the band mask built by the layer) gives the same
    dense = fam.build({"model": dict(SMALL, remat=remat, use_flash=False)})
    assert _rel(jax.jit(lambda v: dense.apply(v, ids)[0])(variables),
                logits) < 2e-5


def test_the_embedding_is_scaled_and_the_full_layer_has_no_positions():
    model = AFMoE(**dict(TINY, remat=False))
    kinds = [(b.attn.window, b.attn.rotary_dim, b.ff_name)
             for b in map(model._block, range(5))]
    assert kinds == [(16, 16, "mlp")] + [(16, 16, "moe")] * 3 \
        + [(None, 0, "moe")]
    assert AFMoE()._config["layer_types"] == ([SLIDING] * 3 + [FULL]) * 8
    with pytest.raises(ValueError, match="layer_types"):
        AFMoE(n_layers=2, layer_types=[SLIDING])
    # one dense layer is enough to tell sqrt(hidden) from anything else
    one = AFMoE(**dict(TINY, n_layers=1, layer_types=[SLIDING],
                       remat=False))
    ids = jnp.arange(8, dtype=jnp.int32)[None]
    variables = one.init(jax.random.PRNGKey(0), ids)
    reference = lambda m: fam.forward_reference(
        fam._float32(variables["params"]), {}, ids, m)
    scaled = reference(one._config)
    assert _rel(one.apply(variables, ids)[0], scaled) < 2e-5
    # sqrt(16) for sqrt(64)
    assert _rel(reference(dict(one._config, hidden_size=16)), scaled) > 1e-3


def test_configuration_holds_the_parameters_of_its_table():
    """ISSUE 31's arithmetic, reckoned again from the built model's tree.
    The issue's line for attention (27,263,360) is 128 over what its own
    parts add up to (three 8,388,608, two 1,048,576, two vectors of 128:
    27,263,232), so its total is 5 x 128 over the tree's."""
    config = _config()
    model = fam.build(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree_util.tree_leaves(tree))
    params = shapes["params"]
    dense = params["remat_0"]["layer_0"]
    sparse = params["remat_4"]["layer_4"]
    assert count(dense["attn"]) == count(sparse["attn"]) \
        == 3 * 8_388_608 + 2 * 1_048_576 + 2 * 128 == 27_263_232
    norms = ("input_norm", "post_attn_norm", "pre_ff_norm", "post_ff_norm")
    assert sum(count(dense[n]) for n in norms) == 8192
    assert count(dense["mlp"]) == 37_748_736
    moe = sparse["moe"]
    assert moe["w_gate_up"].shape == (16, 2048, 2048)
    assert moe["w_down"].shape == (16, 1024, 2048)
    assert count(moe["router"]) == 262_144
    assert count(moe["shared_expert"]) == 6_291_456
    assert count(moe["w_gate_up"]) + count(moe["w_down"]) == 100_663_296
    assert set(moe) == {"router", "shared_expert", "w_gate_up", "w_down"}
    assert count(dense) == 65_020_288 - 128
    assert count(sparse) == 134_488_448 - 128
    assert count(params["embed"]) + count(params["head"]) == 102_498_304
    total = count(params)
    assert total == 705_473_792 == 705_474_432 - 5 * 128
    assert abs(16 * total - 11.29e9) < 0.01e9      # 16 B a parameter
    # the bias is state and no parameter: 128 a layer, all experts
    biases = fam.expert_biases(shapes["state"], model._config)
    assert sorted(biases) == [1, 2, 3, 4]
    assert all(b.shape == (128,) for b in biases.values())
    # the published widths, key by key; the four cuts and nothing else
    pub, m = config["published"], config["model"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    for key, value in pub.items():
        assert config[key] == value or key in config["reduced"], key
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (
        m["n_layers"], m["num_dense_layers"], m["experts_held"],
        m["vocab_size"]) == (5, 1, 16, 25024)
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    assert m["layer_types"] == [SLIDING] * 4 + [FULL]
    assert m["layer_types"][1:] == pub["layer_types"][4:8]   # one period
    for ours, theirs in [
            ("hidden_size", "hidden_size"),
            ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("rope_theta", "rope_theta"), ("window", "sliding_window"),
            ("dense_units", "intermediate_size"),
            ("num_experts", "num_experts"), ("top_k", "num_experts_per_tok"),
            ("moe_units", "moe_intermediate_size"),
            ("full_attention_interval", "global_attn_every_n_layers"),
            ("route_scale", "route_scale"),
            ("balance_coeff", "load_balance_coeff"),
            ("rms_eps", "rms_norm_eps")]:
        assert m[ours] == pub[theirs], ours
    assert m["shared_units"] == pub["num_shared_experts"] \
        * pub["moe_intermediate_size"]
    assert (pub["score_func"], pub["route_norm"], pub["mup_enabled"]) \
        == ("sigmoid", True, True)


def test_the_bias_level_reaches_the_registry_once_an_epoch():
    """``fit`` moves each layer's bias every step and publishes its largest
    magnitude, a level and no count, with the counters at the epoch's
    read-back; the layers publish no ``aux_loss``."""
    model = AFMoE(**SMALL)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 128, (8, 24)).astype(np.int32)
    reg = metrics.get_registry()
    before = reg.snapshot()
    est = Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                               optimizer="sgd", learning_rate=0.0, seed=0)
    est.fit({"x": x, "y": np.roll(x, -1, 1)}, epochs=2, batch_size=4,
            verbose=False)
    after = reg.snapshot()
    grew = lambda k: after[k] - before.get(k, 0)
    assert grew("moe.pairs_total") == 2 * 8 * 24 * 2 * 2   # epochs..layers
    assert grew("moe.pairs_dropped") == 0
    hist = after["moe.expert_bias_abs_max"]
    was = before.get("moe.expert_bias_abs_max") or {"count": 0, "sum": 0.0}
    assert hist["count"] - was["count"] == 2 * 2           # epochs x layers
    state = est.get_model()["state"]
    biases = fam.expert_biases(state, model._config)
    # four steps of at most 0.001 + |mean(d)| each
    assert all(0.0 < float(jnp.abs(b).max()) <= 4 * 0.002 for b in
               biases.values())
    assert all(abs(float(b.sum())) < 1e-6 for b in biases.values())
    level = (hist["sum"] - was["sum"]) / (2 * 2)
    assert 0.0 < level <= 4 * 0.002
    assert not any("aux_loss" in jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(state)[0])


def test_model_trains_and_predicts_through_the_estimator():
    model = AFMoE(**dict(SMALL, dtype="bfloat16"))
    rng = np.random.default_rng(1)
    x = rng.integers(0, 16, (8, 24)).astype(np.int32)
    est = Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                               optimizer="adamw", learning_rate=1e-2, seed=0)
    hist = est.fit({"x": x, "y": np.roll(x, -1, 1)}, epochs=6, batch_size=4,
                   verbose=False)
    assert hist["loss"][-1] < hist["loss"][0]
    logits = np.asarray(est.predict(x, batch_size=4), np.float32)
    assert logits.shape == (8, 24, 128) and np.isfinite(logits).all()
