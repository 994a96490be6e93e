"""Scale-out training: quantized gradient collectives, 2D (data × model)
sharding from the Estimator, and large-batch optimizers (ROADMAP item 3;
PAPERS.md EQuARX + MLPerf-on-TPU-pods ladders).  Runs on the 8-device CPU
sim — real XLA collectives, no hardware."""

import logging
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.core import (MeshConfig, init_orca_context, metrics,
                                    stop_orca_context)
from analytics_zoo_tpu.core.context import make_mesh
from analytics_zoo_tpu.orca.learn import Estimator


def _mlp():
    import analytics_zoo_tpu.nn as nn
    return nn.Sequential([nn.Dense(32, activation="relu", name="ffn1"),
                          nn.Dense(4, name="ffn2")])


def _data(n=64, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.integers(0, classes, n).astype(np.int32))


def _flat_axes(spec):
    """Axis names appearing anywhere in a PartitionSpec."""
    out = []
    for e in spec:
        out.extend(e if isinstance(e, tuple) else ([e] if e else []))
    return out


def _fit(mesh_shape, epochs=2, **kw):
    stop_orca_context()
    init_orca_context("local", mesh_shape=mesh_shape)
    kw.setdefault("optimizer", "sgd")
    est = Estimator.from_keras(_mlp(),
                               loss="sparse_categorical_crossentropy",
                               learning_rate=0.1, seed=1, **kw)
    hist = est.fit(_data(), epochs=epochs, batch_size=32, verbose=False)
    return hist["loss"], est


# -- trim / fallback hardening ------------------------------------------------

def _fresh_fallbacks():
    from analytics_zoo_tpu.parallel.sharding import _reset_fallback_warnings
    _reset_fallback_warnings()


def test_non_dividing_dim_falls_back_with_warning_and_counter(caplog):
    """A rule whose mesh axis doesn't divide the tensor dim must replicate
    that dim (never error), WARN once, and count every occurrence."""
    from analytics_zoo_tpu.parallel import ShardingRule, infer_param_specs
    _fresh_fallbacks()
    mesh = init_orca_context("local", mesh_shape={"data": 4, "model": 2})
    params = {"odd": {"kernel": np.zeros((7, 3), np.float32)}}
    rules = [ShardingRule(r"kernel$", P("model", None))]
    with caplog.at_level(logging.WARNING, logger="analytics_zoo_tpu"):
        specs = infer_param_specs(params, rules, mesh)
        specs2 = infer_param_specs(params, rules, mesh)
    assert specs["odd"]["kernel"] == P()
    assert specs2["odd"]["kernel"] == P()
    warned = [r for r in caplog.records
              if "falling back to replication" in r.message]
    assert len(warned) == 1  # one-time per site, not per call
    snap = metrics.get_registry().snapshot()
    assert snap["train.sharding_fallbacks"] == 2  # counted every occurrence


def test_spec_longer_than_tensor_rank_falls_back(caplog):
    from analytics_zoo_tpu.parallel import ShardingRule, infer_param_specs
    _fresh_fallbacks()
    mesh = init_orca_context("local", mesh_shape={"data": 4, "model": 2})
    params = {"vec": {"bias": np.zeros((8,), np.float32)}}
    with caplog.at_level(logging.WARNING, logger="analytics_zoo_tpu"):
        specs = infer_param_specs(
            params, [ShardingRule(r"bias$", P(None, "model"))], mesh)
    assert specs["vec"]["bias"] == P()
    assert metrics.get_registry().snapshot()["train.sharding_fallbacks"] == 1
    assert any("has no such dim" in r.message for r in caplog.records)


def test_absent_axis_trims_silently(caplog):
    """Portability contract: a mesh that simply lacks the axis is NOT a
    fallback — no warning, no counter."""
    from analytics_zoo_tpu.parallel import (infer_param_specs,
                                            tensor_parallel_rules)
    _fresh_fallbacks()
    mesh = init_orca_context("local", mesh_shape={"data": 8})
    params = {"ffn1": {"kernel": np.zeros((8, 32), np.float32)}}
    with caplog.at_level(logging.WARNING, logger="analytics_zoo_tpu"):
        specs = infer_param_specs(params, tensor_parallel_rules(), mesh)
    assert specs["ffn1"]["kernel"] == P()
    snap = metrics.get_registry().snapshot()
    assert snap.get("train.sharding_fallbacks", 0) == 0
    assert not [r for r in caplog.records
                if "falling back" in r.message]


def test_rule_inference_on_nested_param_paths():
    """Patterns match the full /-joined path, so rules can pin one block's
    kernel while a generic rule covers the rest (first match wins)."""
    from analytics_zoo_tpu.parallel import ShardingRule, infer_param_specs
    mesh = init_orca_context("local", mesh_shape={"data": 4, "model": 2})
    params = {"encoder": {"block0": {"ffn1": {"kernel":
                                              np.zeros((8, 32), np.float32)}},
                          "block1": {"ffn1": {"kernel":
                                              np.zeros((8, 32), np.float32)}}},
              "head": {"kernel": np.zeros((32, 4), np.float32)}}
    rules = [ShardingRule(r"block1/ffn1/kernel$", P(None, "model")),
             ShardingRule(r"kernel$", P())]
    specs = infer_param_specs(params, rules, mesh)
    assert specs["encoder"]["block1"]["ffn1"]["kernel"] == P(None, "model")
    assert specs["encoder"]["block0"]["ffn1"]["kernel"] == P()
    assert specs["head"]["kernel"] == P()


def test_tp_and_fsdp_rule_specs_on_two_axis_mesh(rng):
    """tensor_parallel_rules / fsdp_rules spec correctness on the 2-axis
    data × model mesh the "2d" strategy builds."""
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.parallel import (fsdp_rules, infer_param_specs,
                                            tensor_parallel_rules)
    mesh = init_orca_context("local", mesh_shape="2d")
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
        {"data": 4, "model": 2}
    layer = nn.TransformerLayer(num_heads=4)
    variables = layer.init(jax.random.PRNGKey(0),
                           jnp.asarray(rng.normal(size=(2, 8, 64)),
                                       jnp.float32))
    specs = infer_param_specs(variables["params"],
                              tensor_parallel_rules(), mesh)
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert [v for k, v in flat.items() if k.endswith("'wq']")][0] == \
        P(None, "model")
    assert [v for k, v in flat.items() if k.endswith("'wo']")][0] == \
        P("model")
    # fsdp rules on a mesh WITHOUT an fsdp axis trim to replication
    specs_f = infer_param_specs(variables["params"], fsdp_rules(), mesh)
    leaves = jax.tree_util.tree_leaves(
        specs_f, is_leaf=lambda x: isinstance(x, P))
    assert all(s == P() for s in leaves)


# -- 2D mesh + strategy -------------------------------------------------------

def test_mesh_for_strategy_layouts():
    assert MeshConfig.for_strategy("dp").resolved(8)["data"] == 8
    assert MeshConfig.for_strategy("fsdp").resolved(8)["fsdp"] == 8
    tp = MeshConfig.for_strategy("tp").resolved(8)
    assert tp["model"] == 8 and tp["data"] == 1
    d2 = MeshConfig.for_strategy("2d").resolved(8)
    assert d2 == {"data": 4, "fsdp": 1, "seq": 1, "pipe": 1, "model": 2,
                  "expert": 1}
    # degrade: model axis can't fit the device count → pure dp, no error
    assert MeshConfig.for_strategy("2d", n_devices=3).resolved(3)["model"] \
        == 1
    with pytest.raises(ValueError, match="unknown mesh strategy"):
        MeshConfig.for_strategy("3d")


def test_make_mesh_accepts_strategy_string():
    init_orca_context("local")  # device runtime up
    mesh = make_mesh("2d")
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (4, 2)


def test_estimator_2d_matches_dp_loss():
    """Estimator(sharding="2d") on the data × model mesh trains to
    numerical equivalence with dp on a fixed seed (GSPMD partitioning is
    numerics-preserving up to fp reassociation)."""
    dp, _ = _fit({"data": 8}, sharding="dp")
    d2, est = _fit("2d", sharding="2d")
    np.testing.assert_allclose(dp, d2, rtol=1e-4)
    # and the params really are model-sharded, not silently replicated
    kernels = [l for p, l in jax.tree_util.tree_flatten_with_path(
        est._ts["params"])[0] if "kernel" in jax.tree_util.keystr(p)]
    assert any("model" in _flat_axes(k.sharding.spec) for k in kernels)


def test_2d_checkpoint_save_restore_roundtrip(tmp_path):
    """2D-sharded variables round-trip: load() restores the data × model
    layout (not a silent replication) and training continues."""
    _, est = _fit("2d", sharding="2d", epochs=1)
    path = str(tmp_path / "ckpt2d")
    est.save(path)
    est2 = Estimator.from_keras(_mlp(),
                                loss="sparse_categorical_crossentropy",
                                optimizer="sgd", learning_rate=0.1,
                                seed=1, sharding="2d")
    est2.load(path)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(est._ts["params"])[0],
            jax.tree_util.tree_flatten_with_path(est2._ts["params"])[0]):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if "kernel" in jax.tree_util.keystr(pa):
            # rule-matched kernels keep the 2D layout through the
            # round-trip (unmatched leaves like biases may differ: the
            # compiled step's GSPMD propagation shards them to follow
            # their kernel, load places them per the rules — replicated)
            assert a.sharding.spec == b.sharding.spec
            assert "model" in _flat_axes(a.sharding.spec)
    hist = est2.fit(_data(), epochs=1, batch_size=32, verbose=False)
    assert np.isfinite(hist["loss"][0])


def test_2d_on_data_only_mesh_warns_and_trains_dp(caplog):
    with caplog.at_level(logging.WARNING, logger="analytics_zoo_tpu"):
        losses, _ = _fit({"data": 8}, sharding="2d", epochs=1)
    assert np.isfinite(losses[0])
    assert any("no sized model axis" in r.message for r in caplog.records)


# -- quantized gradient collectives -------------------------------------------

def test_grad_compression_none_is_bitwise_identical():
    """THE bisection guard: grad_compression="none" must reproduce the
    default dp loss history bit-for-bit (same compiled step, metering
    only) — same pattern as PR-4's prefetch equivalence test."""
    base, _ = _fit({"data": 8})
    none, _ = _fit({"data": 8}, grad_compression="none")
    assert base == none


def test_grad_compression_quantized_tracks_uncompressed():
    """bf16/int8 change only the gradient wire width: loss histories stay
    within the bench guard's tolerance of the uncompressed baseline."""
    base, _ = _fit({"data": 8})
    bf16, _ = _fit({"data": 8}, grad_compression="bf16")
    i8, est = _fit({"data": 8}, grad_compression="int8")
    assert abs(bf16[-1] - base[-1]) < 0.02
    assert abs(i8[-1] - base[-1]) < 0.02
    # int8 carries per-shard error-feedback residuals in the train state
    assert "ef" in est._ts
    ef0 = jax.tree_util.tree_leaves(est._ts["ef"])[0]
    assert ef0.shape[0] == 8  # one residual slice per batch shard
    assert float(np.abs(np.asarray(ef0)).sum()) > 0  # banked rounding error


def test_grad_bytes_metered():
    """train.grad_bytes asserts the ≥4× int8 wire cut."""
    _fit({"data": 8}, grad_compression="none", epochs=1)
    snap = metrics.get_registry().snapshot()
    none_bytes = snap["train.grad_bytes"]
    assert none_bytes > 0
    metrics.get_registry().reset()
    _fit({"data": 8}, grad_compression="int8", epochs=1)
    int8_bytes = metrics.get_registry().snapshot()["train.grad_bytes"]
    assert none_bytes / int8_bytes >= 4.0


def test_int8_error_feedback_checkpoints(tmp_path):
    _, est = _fit({"data": 8}, grad_compression="int8", epochs=1)
    path = str(tmp_path / "ckpt_ef")
    est.save(path)
    est2 = Estimator.from_keras(_mlp(),
                                loss="sparse_categorical_crossentropy",
                                optimizer="sgd", learning_rate=0.1,
                                seed=1, grad_compression="int8")
    est2.load(path)
    for a, b in zip(jax.tree_util.tree_leaves(est._ts["ef"]),
                    jax.tree_util.tree_leaves(est2._ts["ef"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hist = est2.fit(_data(), epochs=1, batch_size=32, verbose=False)
    assert np.isfinite(hist["loss"][0])


def test_grad_compression_composes_with_2d():
    dp, _ = _fit({"data": 8}, epochs=1)
    d2, _ = _fit("2d", sharding="2d", grad_compression="int8", epochs=1)
    assert abs(d2[-1] - dp[-1]) < 0.02


def test_grad_compression_validation():
    init_orca_context("local")
    with pytest.raises(ValueError, match="grad_compression"):
        Estimator.from_keras(_mlp(), loss="mse", learning_rate=0.1,
                             grad_compression="fp4")
    with pytest.raises(ValueError, match="grad_accum"):
        Estimator.from_keras(_mlp(), loss="mse", learning_rate=0.1,
                             grad_compression="int8", grad_accum=2)


def test_compressed_allreduce_unit():
    """compressed_allreduce in isolation: int8 dequantized mean stays
    within one quantization step of the exact mean, and error feedback
    carries exactly the per-shard residual."""
    from analytics_zoo_tpu.parallel import compressed_allreduce
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32)
    exact = np.asarray(g).mean(0)
    red, ef = compressed_allreduce({"w": g}, "int8")
    assert ef is not None
    # per-shard max-abs/127 scales: mean error bounded by one step
    step = np.abs(np.asarray(g)).max(axis=(1, 2)).mean() / 127.0
    assert np.abs(np.asarray(red["w"]) - exact).max() <= step
    # residual = what quantization dropped, per shard
    q_contrib = np.asarray(g) - np.asarray(ef["w"])
    np.testing.assert_allclose(q_contrib.mean(0), np.asarray(red["w"]),
                               rtol=1e-6, atol=1e-7)
    red_b, ef_b = compressed_allreduce({"w": g}, "bf16")
    assert ef_b is None
    assert np.abs(np.asarray(red_b["w"]) - exact).max() < 0.02


def test_grad_wire_bytes_analytics():
    from analytics_zoo_tpu.parallel import grad_wire_bytes
    params = {"k": np.zeros((10, 10), np.float32),
              "b": np.zeros((10,), np.float32)}
    assert grad_wire_bytes(params, None) == 440
    assert grad_wire_bytes(params, "none") == 440
    assert grad_wire_bytes(params, "bf16") == 220
    assert grad_wire_bytes(params, "int8") == 110


# -- large-batch optimizers (LARS / LAMB) -------------------------------------

def test_lars_trust_ratio_hand_computed():
    from analytics_zoo_tpu.orca.learn.optimizers import lars
    tx = lars(1.0, momentum=0.0, weight_decay=0.0,
              trust_coefficient=0.001)
    params = {"w": {"kernel": jnp.asarray([3.0, 4.0])}}
    grads = {"w": {"kernel": jnp.asarray([0.3, 0.4])}}
    state = tx.init(params)
    updates, _ = tx.update(grads, state, params)
    # ratio = 0.001 * ||w|| / ||g|| = 0.001 * 5 / 0.5 = 0.01
    np.testing.assert_allclose(np.asarray(updates["w"]["kernel"]),
                               [-0.003, -0.004], rtol=1e-5)


def test_lars_excludes_bias_and_norm_params():
    from analytics_zoo_tpu.orca.learn.optimizers import lars
    tx = lars(0.5, momentum=0.0, weight_decay=0.1,
              trust_coefficient=0.001)
    params = {"d": {"kernel": jnp.asarray([3.0, 4.0]),
                    "bias": jnp.asarray([1.0, 2.0]),
                    "gamma": jnp.asarray([1.0, 1.0])}}
    g = jnp.asarray([0.3, 0.4])
    grads = {"d": {"kernel": g, "bias": g, "gamma": g}}
    updates, _ = tx.update(grads, tx.init(params), params)
    # excluded leaves: plain -lr * g — no trust ratio, no weight decay
    np.testing.assert_allclose(np.asarray(updates["d"]["bias"]),
                               np.asarray(-0.5 * g), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(updates["d"]["gamma"]),
                               np.asarray(-0.5 * g), rtol=1e-6)
    # the kernel IS adapted (different from plain sgd)
    assert not np.allclose(np.asarray(updates["d"]["kernel"]),
                           np.asarray(-0.5 * g))


def test_lamb_trust_ratio_first_step():
    from analytics_zoo_tpu.orca.learn.optimizers import lamb
    tx = lamb(0.1, weight_decay=0.0, eps=1e-6)
    p = np.asarray([3.0, 4.0], np.float32)
    g = np.asarray([0.3, -0.4], np.float32)
    params = {"w": {"kernel": jnp.asarray(p)}}
    updates, _ = tx.update({"w": {"kernel": jnp.asarray(g)}},
                           tx.init(params), params)
    # step 1: m̂ = g, v̂ = g² → u = g/(|g|+eps) ≈ sign(g); ratio = ||p||/||u||
    u = g / (np.abs(g) + 1e-6)
    expect = -0.1 * (np.linalg.norm(p) / np.linalg.norm(u)) * u
    np.testing.assert_allclose(np.asarray(updates["w"]["kernel"]), expect,
                               rtol=1e-4)


def test_lamb_excluded_leaf_is_plain_adam():
    from analytics_zoo_tpu.orca.learn.optimizers import lamb
    tx = lamb(0.1, weight_decay=0.5, eps=1e-6)
    p = jnp.asarray([1.0, 2.0])
    g = np.asarray([0.3, -0.4], np.float32)
    params = {"d": {"bias": p}}
    updates, _ = tx.update({"d": {"bias": jnp.asarray(g)}},
                           tx.init(params), params)
    expect = -0.1 * g / (np.abs(g) + 1e-6)  # no decay, no ratio
    np.testing.assert_allclose(np.asarray(updates["d"]["bias"]), expect,
                               rtol=1e-4)


def test_lars_lamb_resolvable_by_name_and_train():
    from analytics_zoo_tpu.orca.learn import optimizers as opt_lib
    import optax
    for name in ("lars", "lamb"):
        tx = opt_lib.get(name, 0.01)
        assert isinstance(tx, optax.GradientTransformation)
    losses, _ = _fit({"data": 8}, optimizer="lamb", epochs=2)
    assert losses[-1] < losses[0]  # it actually optimizes


def test_lars_momentum_accumulates():
    from analytics_zoo_tpu.orca.learn.optimizers import lars
    tx = lars(1.0, momentum=0.9, weight_decay=0.0, trust_coefficient=1.0)
    params = {"kernel": jnp.asarray([1.0, 0.0])}
    grads = {"kernel": jnp.asarray([1.0, 0.0])}
    state = tx.init(params)
    u1, state = tx.update(grads, state, params)
    u2, _ = tx.update(grads, state, params)
    # second step carries 0.9 * first velocity on top of the fresh term
    assert abs(float(u2["kernel"][0])) > abs(float(u1["kernel"][0]))


# -- grad_accum on a mesh with several batch shards (ISSUE 26) ----------------
# The [B] -> [accum, B/accum] split pins each micro-batch's ROWS to the
# mesh's batch axes, so a chip computes its share of every micro-batch and
# nothing twice.  The witness is the compiled program.  (Where the gradient
# all-reduce sits relative to the accumulation loop is the TPU compiler's
# loop code motion: tests/test_tpu_compile.py reads it off a described v5e;
# the CPU backend does not run that pass.)

def _wide_mlp():
    import analytics_zoo_tpu.nn as nn
    return nn.Sequential([nn.Dense(256, activation="relu", name="ffn1"),
                          nn.Dense(256, activation="relu", name="ffn2"),
                          nn.Dense(4, name="head")])


def _bn_net():
    import analytics_zoo_tpu.nn as nn
    return nn.Sequential([nn.Dense(16, name="ffn1"),
                          nn.BatchNormalization(name="bn"),
                          nn.Dense(4, name="head")])


def _accum_estimator(mesh_shape, accum, model=_mlp, sharding="dp"):
    stop_orca_context()
    mesh = init_orca_context("local", mesh_shape=mesh_shape)
    est = Estimator.from_keras(model(),
                               loss="sparse_categorical_crossentropy",
                               optimizer="sgd", learning_rate=0.1, seed=1,
                               grad_accum=accum, sharding=sharding)
    return est, mesh


def _compiled_train_step(mesh_shape, batch, accum, **kw):
    from analytics_zoo_tpu.data import shard_batch
    est, mesh = _accum_estimator(mesh_shape, accum, **kw)
    x, y = _data(n=batch, d=64)
    est._ensure_initialized(x[:1])
    placed = shard_batch({"x": x, "y": y}, mesh)
    return est._train_step.lower(est._ts, placed).compile()


@pytest.mark.parametrize("mesh_shape,sharding,accum", [
    ({"data": 4}, "dp", 2), ({"data": 4}, "dp", 4), ({"data": 4}, "dp", 8),
    ({"data": 1, "fsdp": 4}, "fsdp", 2), ({"data": 1, "fsdp": 4}, "fsdp", 8),
    ({"data": 2, "fsdp": 2}, "fsdp", 4),
], ids=["dp4-accum2", "dp4-accum4", "dp4-accum8", "fsdp4-accum2",
        "fsdp4-accum8", "dp2xfsdp2-accum4"])
def test_grad_accum_on_four_shards_computes_each_row_once(mesh_shape,
                                                          sharding, accum):
    """Four batch shards at global batch 4B against one device at batch B:
    a device's FLOPs agree.  (The parent read ~2x at accum 2 and ~4x at 4
    and 8 on {data: 4}: the [accum, B/accum] reshape had moved the batch
    sharding onto the accumulation axis and GSPMD replicated the
    micro-batch's rows.)"""
    from hlo_loops import collectives
    b = 16 * accum
    one = _compiled_train_step({"data": 1}, b, accum, model=_wide_mlp)
    four = _compiled_train_step(mesh_shape, 4 * b, accum, model=_wide_mlp,
                                sharding=sharding)
    ratio = four.cost_analysis()["flops"] / one.cost_analysis()["flops"]
    assert 0.95 < ratio < 1.05, ratio
    assert collectives(one.as_text()) == ({}, {})


def _three_steps(mesh_shape, accum, model, batch=32):
    est, _ = _accum_estimator(mesh_shape, accum, model=model)
    x, y = _data(n=3 * batch)
    hist = est.fit((x, y), epochs=1, batch_size=batch, verbose=False,
                   prefetch=0)
    assert est._py_step == 3
    return hist["loss"], est.get_model()


def _assert_same_training(got, want):
    (loss_a, vars_a), (loss_b, vars_b) = got, want
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(vars_a),
                    jax.tree_util.tree_leaves(vars_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)


@pytest.mark.parametrize("mesh_shape", [{"data": 4}, {"data": 1, "fsdp": 4},
                                        {"data": 2, "fsdp": 2}],
                         ids=["dp4", "fsdp4", "dp2xfsdp2"])
def test_grad_accum_on_four_shards_matches_one_device(mesh_shape):
    """Three steps of accum 2 from one seed: one device's losses and
    parameters at rtol 1e-4."""
    _assert_same_training(_three_steps(mesh_shape, 2, _mlp),
                          _three_steps({"data": 1}, 2, _mlp))


def test_grad_accum_batchnorm_statistics_span_the_micro_batch():
    """Pins the semantics the split chose: micro-batch ``i`` is rows
    ``i*B/accum ...`` on any mesh, and only their PLACEMENT follows the
    mesh, so a BatchNorm layer normalises by the statistics of the whole
    micro-batch (GSPMD reduces them over the shards), exactly as on one
    device: dp=4 x accum 2 gives one device's accum-2 losses, parameters
    and running statistics, and not those of accum 8 (the per-shard groups
    of four rows a local-BN scheme would see).  The other road — strided
    membership or per-shard accumulation — would save the input's
    all-to-all or a reduce inside the loop and change what a stateful
    layer computes; "same losses as one device for the same seed" was
    worth more."""
    on_four = _three_steps({"data": 4}, 2, _bn_net)
    _assert_same_training(on_four, _three_steps({"data": 1}, 2, _bn_net))
    assert not np.allclose(on_four[0],
                           _three_steps({"data": 1}, 8, _bn_net)[0],
                           rtol=1e-4)


@pytest.mark.parametrize("mesh_shape,batch,pinned", [
    ({"data": 1}, 32, False),              # one device
    ({"data": 1, "model": 4}, 32, False),  # no batch axis to shard over
    ({"data": 4}, 12, False),              # micro-batch of 6 rows, 4 shards
    ({"data": 4}, 32, True),
    ({"data": 2, "fsdp": 4}, 32, True),
], ids=["one_device", "model4", "dp4_b12", "dp4_b32", "dp2xfsdp4_b32"])
def test_grad_accum_pins_rows_only_where_batch_shards_divide_them(
        mesh_shape, batch, pinned):
    """On one batch shard the step is the program it was: no sharding
    constraint is traced (PERF.md, PR 26: the one-chip program compiles to
    the parent's FLOPs, bytes and memory).  With several shards the rows
    are pinned wherever a micro-batch divides into them; where it does not,
    GSPMD places the split as before."""
    from analytics_zoo_tpu.data import shard_batch
    est, mesh = _accum_estimator(mesh_shape, 2)
    x, y = _data(n=batch)
    est._ensure_initialized(x[:1])
    jaxpr = str(jax.make_jaxpr(est._train_step)(
        est._ts, shard_batch({"x": x, "y": y}, mesh)))
    assert ("sharding_constraint" in jaxpr) == pinned
