"""The layers Qwen3-Next forced, each against the plain float32 reference
kept with the benchmark (``benchmark/families/qwen3_next.py``), at small
sizes on the CPU (the chunked gated delta rule's own tests are in
``tests/test_gated_delta_rule.py``): ``MultiHeadAttention``'s decoder
options on the dense and the flash path, the dropless expert layer and its
share of the experts, the whole model's logits, loss and gradients, the
configuration's parameter count, and the device-side counters through
``Estimator.fit``."""

import importlib
import inspect
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
from analytics_zoo_tpu.models import Qwen3Next  # noqa: E402
from analytics_zoo_tpu.orca.learn import Estimator  # noqa: E402
from analytics_zoo_tpu.orca.learn.estimator import ZooEstimator  # noqa: E402
from analytics_zoo_tpu.parallel import DroplessMoE  # noqa: E402
from benchmark.families import qwen3_next as fam  # noqa: E402

fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")

TINY = dict(vocab_size=128, hidden_size=64, n_layers=4, num_heads=2,
            num_kv_heads=1, head_dim=16, linear_num_k_heads=2,
            linear_num_v_heads=4, linear_k_head_dim=16, linear_v_head_dim=16,
            chunk=8, num_experts=8, top_k=2, moe_units=32, shared_units=32,
            experts_held=4, dtype="float32")


def _config(name="qwen3_next_80b_a3b_ep16"):
    with open(os.path.join(REPO, "benchmark/configs", name + ".json")) as f:
        return json.load(f)


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def test_gated_delta_net_layer_matches_the_reference():
    m = Qwen3Next(**TINY)._config
    layer = nn.GatedDeltaNet(2, 4, 16, 16, chunk=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 29, 64))
    variables = layer.init(jax.random.PRNGKey(1), x)
    assert variables["params"]["in_proj_qkvz"]["kernel"].shape == (64, 192)
    assert variables["params"]["conv"]["kernel"].shape == (4, 128)
    got, _ = layer.apply(variables, x)
    assert _rel(got, fam.gdn_reference(variables["params"], x, m)) < 2e-5


@pytest.mark.parametrize("path", ["dense", "dense_remat", "flash",
                                  "flash_interpret"])
def test_attention_options_match_the_reference(path, monkeypatch):
    m = Qwen3Next(**dict(TINY, num_heads=4, num_kv_heads=2))._config
    if path == "flash_interpret":
        monkeypatch.setattr(fa, "INTERPRET", True)
    layer = nn.MultiHeadAttention(
        4, head_dim=16, causal=True, num_kv_heads=2, qk_norm=True, gate=True,
        rotary_dim=4, rope_theta=m["rope_theta"],
        use_flash=path.startswith("flash"), remat=path == "dense_remat")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    variables = layer.init(jax.random.PRNGKey(1), x)
    params = variables["params"]
    for norm in ("q_norm", "k_norm"):  # zero-centred: 0 would hide the 1 +
        params[norm]["weight"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(2), (16,))
    assert params["wq"].shape == (64, 4 * 32)
    assert params["wk"].shape == params["wv"].shape == (64, 2 * 16)
    want = fam.attention_reference(params, x, m)

    def run(p):
        return layer.apply({"params": p, "state": {}}, x)[0]
    assert _rel(run(params), want) < 2e-5
    g_got = jax.grad(lambda p: jnp.sum(jnp.square(run(p))))(params)
    g_want = jax.grad(lambda p: jnp.sum(jnp.square(
        fam.attention_reference(p, x, m))))(params)
    for name in ("wq", "wk", "wv", "wo"):
        assert _rel(g_got[name], g_want[name]) < 2e-4, name


def test_attention_defaults_build_the_plain_layer():
    layer = nn.MultiHeadAttention(4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert {k: v.shape for k, v in params.items() if k != "drop"} == {
        "wq": (32, 32), "wk": (32, 32), "wv": (32, 32), "wo": (32, 32)}
    with pytest.raises(ValueError):
        nn.MultiHeadAttention(4, num_kv_heads=3)


def _moe(**kw):
    return DroplessMoE(8, 2, 32, **kw)


def _apply(layer, params, x):
    """``layer`` on ``params`` from its initial state (zeroed counters)."""
    state = layer.init(jax.random.PRNGKey(0), x)["state"]
    return layer.apply({"params": params, "state": state}, x)


def _moe_inputs(seed=0, tokens=(2, 24), d=64, held=8):
    x = jax.random.normal(jax.random.PRNGKey(seed), tokens + (d,))
    return x, _moe(experts_held=held, shared_units=32).init(
        jax.random.PRNGKey(seed + 1), x)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the routed parts of all
    ``num_experts / held`` shares, plus what every chip computes alike (the
    shared expert) counted once, are the uncut reference layer."""
    m = Qwen3Next(**TINY)._config
    x, whole = _moe_inputs()
    p = whole["params"]
    want, _, _ = fam.moe_reference(p, x, m, first=0, held=8)
    routed = jnp.zeros_like(x)
    for first in (0, 2, 4, 6):
        share = {"router": p["router"],
                 "w_gate_up": p["w_gate_up"][first:first + 2],
                 "w_down": p["w_down"][first:first + 2]}
        part, state = _apply(_moe(experts_held=2, first_expert=first),
                             share, x)
        # ... and each share is the reference's share
        ref_part, _, _ = fam.moe_reference(
            share, x, dict(m, shared_units=0), first=first, held=2)
        assert _rel(part, ref_part) < 2e-5
        assert int(state["counters"]["moe.pairs_dropped"]) == 0
        routed = routed + part
    with_shared, _ = _apply(
        _moe(experts_held=2, first_expert=6, shared_units=32),
        dict(share, shared_expert=p["shared_expert"],
             shared_gate=p["shared_gate"]), x)
    assert _rel(routed + (with_shared - part), want) < 2e-5


def test_no_pair_is_dropped_when_every_token_picks_the_same_experts():
    x, variables = _moe_inputs(tokens=(2, 64), held=4)
    params = dict(variables["params"])
    # a router that sends every token to experts 1 and 3, both held here
    x = jnp.abs(x)
    params["router"] = {"kernel": jnp.zeros((64, 8)).at[:, 1].set(1.0)
                        .at[:, 3].set(0.9)}
    layer = _moe(experts_held=4, shared_units=32)
    got, state = _apply(layer, params, x)
    c = state["counters"]
    assert int(c["moe.pairs_total"]) == int(c["moe.pairs_local"]) == 256
    assert int(c["moe.pairs_dropped"]) == 0
    assert c["moe.load_max_over_mean"].tolist() == [0, 128, 0, 128]
    m = dict(Qwen3Next(**TINY)._config)
    want, _, _ = fam.moe_reference(params, x, m)
    assert _rel(got, want) < 2e-5


def test_expert_layer_gradients_and_aux_loss_match_the_reference():
    m = Qwen3Next(**TINY)._config
    x, variables = _moe_inputs(seed=3, held=4)
    layer = _moe(experts_held=4, shared_units=32)

    def system(p, x):
        out, state = _apply(layer, p, x)
        return jnp.sum(jnp.square(out)) + state["aux_loss"]

    def reference(p, x):
        out, aux, _ = fam.moe_reference(p, x, m)
        return jnp.sum(jnp.square(out)) + aux
    got = jax.grad(system, argnums=(0, 1))(variables["params"], x)
    want = jax.grad(reference, argnums=(0, 1))(variables["params"], x)
    flat_got = jax.tree_util.tree_leaves(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 8  # 7 parameters and x
    for a, b in zip(flat_got, flat_want):
        assert _rel(a, b) < 2e-4


def _system_loss(model, variables, ids, labels):
    def loss(params):
        out, state = model.apply({"params": params,
                                  "state": variables["state"]}, ids,
                                 training=True)
        aux = sum(s["moe"]["aux_loss"]
                  for _, s in fam._blocks(state, model._config))
        return nn.losses.sparse_categorical_crossentropy(out, labels) \
            + fam.AUX_LOSS_WEIGHT * aux, out
    return jax.value_and_grad(loss, has_aux=True)(variables["params"])


@pytest.mark.parametrize("remat", [True, False])
def test_whole_model_logits_loss_and_gradients_match_the_reference(remat):
    config = {"model": dict(TINY, remat=remat)}
    model = fam.build(config)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 29), 0,
                                        128))
    labels = np.roll(ids, -1, axis=1)
    variables = model.init(jax.random.PRNGKey(1), ids)
    # norm weights off their initial 0 / 1, so that "1 + w" is told from "w"
    variables["params"] = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape) if a.ndim == 1 else a,
        variables["params"])
    (loss, logits), grads = _system_loss(model, variables, ids, labels)
    assert _rel(logits, fam.reference(config, variables, ids)) < 2e-5
    want_loss, want_grads = fam.reference_loss_and_grads(
        config, variables, ids, labels)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grads, want_grads)
    assert max(jax.tree_util.tree_leaves(worst)) < 2e-4, worst
    assert inspect.signature(ZooEstimator.__init__).parameters[
        "aux_loss_weight"].default == fam.AUX_LOSS_WEIGHT


def test_configuration_holds_the_parameters_of_its_table():
    """ISSUE 27's table, reckoned again from the built model's tree."""
    config = _config()
    model = fam.build(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree_util.tree_leaves(tree))
    gdn = shapes["remat_0"]["layer_0"]
    full = shapes["remat_3"]["layer_3"]
    assert count(gdn["gdn"]) == 2048 * 12288 + 2048 * 64 + 8192 * 4 \
        + 4096 * 2048 + 32 + 32 + 128           # + A_log, dt_bias, norm
    assert count(full["attn"]) == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 + 2 * 256                 # + q_norm, k_norm
    moe = gdn["moe"]
    assert moe["w_gate_up"].shape == (32, 2048, 1024)
    assert moe["w_down"].shape == (32, 512, 2048)
    routed = count(moe["w_gate_up"]) + count(moe["w_down"])
    assert routed == 32 * 3 * 2048 * 512
    assert count(moe) - routed == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert count(shapes["embed"]) == count(shapes["head"]) == 18992 * 2048
    total = count(shapes)
    assert total == 625_667_136
    assert abs(total - 625.7e6) < 0.1e6         # the issue's table
    assert abs(16 * total - 10.0e9) < 0.02e9    # 16 B a parameter
    # the published widths, key by key; the three cuts and nothing else
    pub, m = config["published"], config["model"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in pub.items():
        assert config[key] == value or key in config["reduced"], key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (m["n_layers"], m["experts_held"],
                                      m["vocab_size"]) == (4, 32, 18992)
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    for ours, theirs in [
            ("hidden_size", "hidden_size"),
            ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("partial_rotary_factor", "partial_rotary_factor"),
            ("rope_theta", "rope_theta"),
            ("linear_num_k_heads", "linear_num_key_heads"),
            ("linear_num_v_heads", "linear_num_value_heads"),
            ("linear_k_head_dim", "linear_key_head_dim"),
            ("linear_v_head_dim", "linear_value_head_dim"),
            ("linear_conv_kernel", "linear_conv_kernel_dim"),
            ("num_experts", "num_experts"), ("top_k", "num_experts_per_tok"),
            ("moe_units", "moe_intermediate_size"),
            ("shared_units", "shared_expert_intermediate_size"),
            ("full_attention_interval", "full_attention_interval"),
            ("rms_eps", "rms_norm_eps"),
            ("norm_topk_prob", "norm_topk_prob")]:
        assert m[ours] == pub[theirs], ours


def test_flops_per_sample_is_the_issues_arithmetic():
    config = _config()
    traffic = {"seq_len": 8192, "global_batch": 2}
    per_token = fam.matmul_params_per_token(config["model"])
    assert abs(per_token - 192.0e6) < 0.2e6
    flops = fam.flops_per_sample(config, traffic)
    assert abs(flops / 8192 - 1.39e9) < 0.01e9       # ~1.39 GFLOP a token
    assert abs(2 * flops - 22.8e12) < 0.1e12         # a step of two rows


def test_counters_reach_the_registry_once_an_epoch():
    """``fit`` publishes the layers' device-side counters at the epoch's
    read-back: totals that match counts taken from the reference's routing
    of the same rows on the initial parameters (learning rate 0 keeps them
    there), none dropped, and one load-imbalance observation a layer."""
    config = {"model": dict(TINY)}
    model = fam.build(config)
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
    assert grew("moe.pairs_total") == 2 * 8 * 24 * 2 * 4   # epochs..layers
    assert grew("moe.pairs_dropped") == 0
    hist = after["moe.load_max_over_mean"]
    seen = hist["count"] - (before.get("moe.load_max_over_mean")
                            or {"count": 0})["count"]
    assert seen == 2 * 4                                   # epochs x layers

    variables = est.get_model()
    m = model._config
    params = fam._float32(variables["params"])
    h = params["embed"]["embeddings"][x]
    local = 0
    for _, p in fam._blocks(params, m):
        n = fam._rms(h, p["input_norm"]["weight"], m["rms_eps"])
        h = h + fam.gdn_reference(p["gdn"], n, m) if "gdn" in p \
            else h + fam.attention_reference(p["attn"], n, m)
        n = fam._rms(h, p["post_norm"]["weight"], m["rms_eps"])
        out, _, (top_e, _) = fam.moe_reference(p["moe"], n, m)
        local += int((top_e < 4).sum())
        h = h + out
    assert grew("moe.pairs_local") == 2 * local
    assert 1.0 <= hist["sum"] / hist["count"] <= 4.0
    # a second fit continues from what was read, it does not count it again
    est.fit({"x": x, "y": np.roll(x, -1, 1)}, epochs=1, batch_size=4,
            verbose=False)
    assert reg.snapshot()["moe.pairs_total"] - after["moe.pairs_total"] \
        == 8 * 24 * 2 * 4


def test_model_trains_and_predicts_through_the_estimator():
    model = Qwen3Next(**dict(TINY, dtype="bfloat16"))
    rng = np.random.default_rng(1)
    x = rng.integers(0, 16, (8, 24)).astype(np.int32)
    est = Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                               optimizer="adamw", learning_rate=1e-2, seed=0)
    hist = est.fit({"x": x, "y": np.roll(x, -1, 1)}, epochs=6, batch_size=4,
                   verbose=False)
    assert hist["loss"][-1] < hist["loss"][0]
    logits = np.asarray(est.predict(x, batch_size=4), np.float32)
    assert logits.shape == (8, 24, 128) and np.isfinite(logits).all()
