"""Sharded embedding engine (ROADMAP item 4 / recsys scale): deduped
gather, row sharding, sparse scatter-add gradients through the estimator,
and the structural guarantee that the backward pass never materializes a
dense [rows, dim] gradient."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import init_orca_context, metrics
from analytics_zoo_tpu.models import NeuralCF, WideAndDeep
from analytics_zoo_tpu.orca.learn import Estimator
from analytics_zoo_tpu.parallel import (ShardedEmbedding, dedup_lookup,
                                        embedding_row_rules, lookup_stats)
from analytics_zoo_tpu.parallel import embedding as emb


def _ratings(n=512, users=64, items=40, seed=42):
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(0, users, n),
                  rng.integers(0, items, n)], 1).astype(np.int32)
    y = (rng.random(n) < 0.5).astype(np.int32)
    return x, y


def _sharded_ncf(users=64, items=40, **kw):
    return NeuralCF(user_count=users, item_count=items, class_num=2,
                    user_embed=8, item_embed=8, hidden_layers=(16, 8),
                    mf_embed=8, sharded_embeddings=True, **kw)


# -- lookup ------------------------------------------------------------------

def test_dedup_lookup_matches_plain_take():
    init_orca_context("local")
    m = ShardedEmbedding(50, 8, name="tbl")
    ids = jnp.array([[3, 7], [3, 3]], jnp.int32)
    variables = m.init(jax.random.PRNGKey(0), ids)
    table = variables["params"]["sharded_embeddings"]  # registers at root
    out, _ = m.apply(variables, ids)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(table, ids, axis=0)),
                               atol=1e-6)


def test_dedup_lookup_masks_negative_ids():
    init_orca_context("local")
    table = jnp.asarray(np.random.default_rng(0).normal(size=(10, 4)),
                        jnp.float32)
    ids = jnp.array([[1, -1], [-1, -1]], jnp.int32)
    out = dedup_lookup(table, ids)
    np.testing.assert_allclose(np.asarray(out[0, 1]), np.zeros(4))
    np.testing.assert_allclose(np.asarray(out[1]), np.zeros((2, 4)))
    np.testing.assert_allclose(np.asarray(out[0, 0]),
                               np.asarray(table[1]), atol=1e-6)


def test_combiners_sum_mean_with_variable_multihot():
    init_orca_context("local")
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(20, 6)), jnp.float32)
    ids = jnp.array([[2, 5, 2], [7, -1, -1]], jnp.int32)
    s = dedup_lookup(table, ids, combiner="sum")
    m = dedup_lookup(table, ids, combiner="mean")
    np.testing.assert_allclose(np.asarray(s[0]),
                               np.asarray(2 * table[2] + table[5]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[1]), np.asarray(table[7]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(m[0]),
                               np.asarray((2 * table[2] + table[5]) / 3),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(m[1]), np.asarray(table[7]),
                               atol=1e-6)  # mean over the 1 valid id


def test_dedup_lookup_rejects_bad_combiner():
    with pytest.raises(ValueError, match="combiner"):
        dedup_lookup(jnp.zeros((4, 2)), jnp.array([0]), combiner="max")
    with pytest.raises(ValueError, match="combiner"):
        ShardedEmbedding(4, 2, combiner="max")


# -- params split/merge + tap protocol ---------------------------------------

def test_split_merge_roundtrip():
    params = {"a": {"sharded_embeddings": np.ones((4, 2))},
              "b": {"kernel": np.zeros((2, 2))},
              "sharded_embeddings": np.full((3, 2), 2.0)}
    dense, tables = emb.split_sparse(params)
    assert set(tables) == {"a/sharded_embeddings", "sharded_embeddings"}
    assert "sharded_embeddings" not in dense and "a" in dense
    merged = emb.merge_sparse(dense, tables)
    assert (jax.tree_util.tree_structure(merged)
            == jax.tree_util.tree_structure(params))
    assert emb.sparse_paths(params) == ("a/sharded_embeddings",
                                        "sharded_embeddings")


def test_inject_tap_gradients_equal_dense_reference():
    """The tap-protocol row gradient scatter-added into the table must
    reproduce the dense-autodiff table update exactly."""
    init_orca_context("local")
    m = ShardedEmbedding(50, 8, name="tbl")
    ids = jnp.array([[3, 7], [3, 11]], jnp.int32)
    variables = m.init(jax.random.PRNGKey(0), ids)
    table = variables["params"]["sharded_embeddings"]

    def loss_with_taps(tbl, taps, x):
        with emb.inject_taps(taps) as uniqs:
            o, _ = m.apply({"params": {"sharded_embeddings": tbl}}, x)
            return jnp.sum(o ** 2), uniqs

    def sparse_step(tbl, x):
        shapes = emb.record_tap_shapes(lambda: m.apply(
            {"params": {"sharded_embeddings": tbl}}, x))
        taps = {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}
        (_, uniqs), tap_grads = jax.value_and_grad(
            loss_with_taps, argnums=1, has_aux=True)(tbl, taps, x)
        (key,) = tap_grads
        assert emb.table_path_of(key) == "sharded_embeddings"
        return tbl.at[uniqs[key]].add(-0.1 * tap_grads[key])

    new_tbl = jax.jit(sparse_step)(table, ids)

    def dense_loss(tbl, x):
        o, _ = m.apply({"params": {"sharded_embeddings": tbl}}, x)
        return jnp.sum(o ** 2)

    ref = table - 0.1 * jax.grad(dense_loss)(table, ids)
    np.testing.assert_allclose(np.asarray(new_tbl), np.asarray(ref),
                               atol=1e-6)


# -- estimator training ------------------------------------------------------

class _PlainNCF(NeuralCF):
    """The pre-engine NeuralCF: every id table a plain ``nn.Embedding``
    built in the forward itself, no ``sharded_embeddings`` switch."""

    def forward(self, scope, x):
        users, items = x[:, 0], x[:, 1]
        ue = scope.child(nn.Embedding(self.user_count, self.user_embed),
                         users, name="mlp_user_embed")
        ie = scope.child(nn.Embedding(self.item_count, self.item_embed),
                         items, name="mlp_item_embed")
        h = jnp.concatenate([ue, ie], axis=-1)
        for i, units in enumerate(self.hidden_layers):
            h = scope.child(nn.Dense(units, activation="relu"), h,
                            name=f"mlp_{i}")
        mu = scope.child(nn.Embedding(self.user_count, self.mf_embed),
                         users, name="mf_user_embed")
        mi = scope.child(nn.Embedding(self.item_count, self.mf_embed),
                         items, name="mf_item_embed")
        h = jnp.concatenate([mu * mi, h], axis=-1)
        return scope.child(nn.Dense(self.class_num), h, name="head")


def test_default_path_bit_identical_to_baseline():
    """sharded_embeddings=False must be bit-for-bit the pre-engine model:
    its fixed-seed loss history equals that of the same network written
    on plain ``nn.Embedding`` layers, trained in this process (a history
    captured under another JAX would pin the compiler, not the model)."""
    init_orca_context("local")
    x, y = _ratings(users=50)
    kw = dict(user_count=50, item_count=40, class_num=2, user_embed=8,
              item_embed=8, hidden_layers=(16, 8), mf_embed=8)
    hist = []
    for m in (NeuralCF(**kw), _PlainNCF(**kw)):
        est = Estimator.from_keras(m, loss="sparse_categorical_crossentropy",
                                   optimizer="adam", learning_rate=1e-2,
                                   seed=7)
        hist.append(est.fit((x, y), epochs=3, batch_size=64,
                            verbose=False)["loss"])
    assert hist[0][-1] < hist[0][0]
    assert hist[0] == hist[1], hist


def test_sharded_ncf_trains_with_per_device_row_shards():
    """A table too large to replicate: rows partition as rows/num_shards
    per device under embedding_row_rules, and the loss still goes down.
    nan_policy="skip_step" composes with the sparse path (its guard
    wraps the scatter-add update too)."""
    mesh = init_orca_context("local")
    ndev = mesh.devices.size
    users = 512 * ndev  # replication would cost ndev x this memory
    x, y = _ratings(n=256, users=users)
    est = Estimator.from_keras(_sharded_ncf(users=users),
                               loss="sparse_categorical_crossentropy",
                               optimizer="adam", learning_rate=1e-2,
                               seed=7, sharding=embedding_row_rules(),
                               nan_policy="skip_step")
    h = est.fit((x, y), epochs=2, batch_size=64, verbose=False)
    assert h["loss"][-1] < h["loss"][0]
    assert est.bad_steps == 0  # finite run: the guard never fired
    leaf = est._ts["params"]["mlp_user_embed"]["sharded_embeddings"]
    assert leaf.shape == (users, 8)
    assert leaf.addressable_shards[0].data.shape[0] == users // ndev
    # eval/predict run the plain (tap-free) lookup on the same params
    ev = est.evaluate((x, y), batch_size=64)
    assert np.isfinite(ev["loss"])
    assert np.asarray(est.predict(x[:16], batch_size=16)).shape == (16, 2)


def _table_shaped_prims(jaxpr, shape):
    """Primitive-name counts of every equation output at ``shape``,
    recursing into sub-jaxprs (pjit bodies, scan/while/cond branches)."""
    import collections
    prims = collections.Counter()

    def walk(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if (aval is not None and hasattr(aval, "shape")
                        and tuple(aval.shape) == shape):
                    prims[eqn.primitive.name] += 1
            for val in jax.tree_util.tree_leaves(
                    tuple(eqn.params.values()),
                    is_leaf=lambda x: hasattr(x, "eqns")
                    or hasattr(x, "jaxpr")):
                if hasattr(val, "jaxpr"):  # ClosedJaxpr
                    val = val.jaxpr
                if hasattr(val, "eqns"):
                    walk(val)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return prims


def _traced_table_prims(sharded: bool):
    """Primitive counts at table shape in the traced train step for an
    NCF whose table shapes collide with nothing else."""
    init_orca_context("local")
    users, items = 97, 89  # primes: no accidental shape collisions
    x, y = _ratings(n=128, users=users, items=items)
    m = NeuralCF(user_count=users, item_count=items, class_num=2,
                 user_embed=8, item_embed=8, hidden_layers=(16, 8),
                 mf_embed=8, sharded_embeddings=sharded)
    est = Estimator.from_keras(m, loss="sparse_categorical_crossentropy",
                               optimizer="adam", learning_rate=1e-2, seed=7)
    # init only -- make_jaxpr traces the step without compiling it, so a
    # fit (init + compile + steps) would pay for nothing this test reads
    est._ensure_initialized(jnp.asarray(x[:32]))
    batch = {"x": jnp.asarray(x[:32]), "y": jnp.asarray(y[:32])}
    jaxpr = jax.make_jaxpr(lambda ts, b: est._train_step(ts, b))(
        est._ts, batch)
    return _table_shaped_prims(jaxpr, (users, 8))


# equation outputs at table shape that do NOT materialize a new dense
# array: jit results (the primitive the installed JAX 0.9 calls ``jit``,
# older ones ``pjit``) are the returned updated tables, stop_gradient is
# an identity alias on the forward lookup
_TABLE_ALIAS_PRIMS = {"jit", "stop_gradient"}


def test_backward_never_materializes_dense_table_grad():
    """Structural guarantee, asserted on the traced train step: the
    sparse path's only [rows, dim] computations are the scatter-add
    table updates themselves (one per user table) — no dense gradient,
    no optimizer-moment arithmetic at table shape.  The dense reference
    (adam on nn.Embedding) does dozens of elementwise ops there."""
    sparse = _traced_table_prims(sharded=True)
    dense = _traced_table_prims(sharded=False)
    sparse_work = {k: v for k, v in sparse.items()
                   if k not in _TABLE_ALIAS_PRIMS}
    # two user-count tables (mlp_user_embed, mf_user_embed): one
    # scatter-add update each, nothing else
    assert sparse_work == {"scatter-add": 2}, sparse_work
    dense_math = sum(v for k, v in dense.items()
                     if k not in _TABLE_ALIAS_PRIMS | {"scatter-add"})
    assert dense_math > 10, dict(dense)  # adam's dense-grad moment math


def test_sharded_checkpoint_roundtrip(tmp_path):
    init_orca_context("local")
    x, y = _ratings(n=256)
    kw = dict(loss="sparse_categorical_crossentropy", optimizer="adam",
              learning_rate=1e-2, seed=7, sharding=embedding_row_rules())
    est = Estimator.from_keras(_sharded_ncf(), **kw)
    est.fit((x, y), epochs=1, batch_size=64, verbose=False)
    est.save(str(tmp_path / "m"))
    est2 = Estimator.from_keras(_sharded_ncf(), **kw)
    est2.load(str(tmp_path / "m"))
    for name in ("mlp_user_embed", "mf_item_embed"):
        np.testing.assert_allclose(
            np.asarray(est._ts["params"][name]["sharded_embeddings"]),
            np.asarray(est2._ts["params"][name]["sharded_embeddings"]))
    # restored table keeps its row sharding
    leaf = est2._ts["params"]["mlp_user_embed"]["sharded_embeddings"]
    assert leaf.addressable_shards[0].data.shape[0] == 64 // 8


def test_embedding_lr_decouples_table_step_size():
    """embedding_lr=0.0 freezes the tables (the supported alternative to
    frozen=) while the dense tower still trains."""
    init_orca_context("local")
    x, y = _ratings(n=256)
    est = Estimator.from_keras(_sharded_ncf(),
                               loss="sparse_categorical_crossentropy",
                               optimizer="adam", learning_rate=1e-2,
                               seed=7, embedding_lr=0.0)
    est.fit((x, y), epochs=1, batch_size=64, verbose=False)
    t0 = np.asarray(est._ts["params"]["mlp_user_embed"]["sharded_embeddings"])
    k0 = np.asarray(est._ts["params"]["mlp_0"]["kernel"])
    est.fit((x, y), epochs=1, batch_size=64, verbose=False)
    t1 = np.asarray(est._ts["params"]["mlp_user_embed"]["sharded_embeddings"])
    k1 = np.asarray(est._ts["params"]["mlp_0"]["kernel"])
    np.testing.assert_array_equal(t0, t1)
    assert not np.allclose(k0, k1)


def test_sparse_guardrails_raise_actionable_errors():
    init_orca_context("local")
    x, y = _ratings(n=128)
    for kw, pat in [
        (dict(grad_accum=2), "grad_accum"),
        (dict(grad_compression="int8"), "grad_compression"),
        (dict(frozen=["mlp_user_embed"]), "embedding_lr=0.0"),
    ]:
        est = Estimator.from_keras(
            _sharded_ncf(), loss="sparse_categorical_crossentropy",
            learning_rate=1e-2, seed=7, **kw)
        with pytest.raises(ValueError, match=pat):
            est.fit((x, y), epochs=1, batch_size=64, verbose=False)


def test_wide_and_deep_sharded_embeddings_flag():
    init_orca_context("local")
    rng = np.random.default_rng(0)
    n = 128
    x = np.concatenate([
        rng.random((n, 4), np.float32).astype(np.float32),
        np.stack([rng.integers(0, 24, n), rng.integers(0, 16, n)],
                 1).astype(np.float32),
        rng.normal(size=(n, 1)).astype(np.float32),
    ], axis=1)
    y = rng.integers(0, 2, n).astype(np.int32)
    m = WideAndDeep(class_num=2, wide_cross_dims=[4],
                    embed_in_dims=[24, 16], embed_out_dims=[8, 8],
                    continuous_cols=1, sharded_embeddings=True)
    est = Estimator.from_keras(m, loss="sparse_categorical_crossentropy",
                               learning_rate=1e-2, seed=3)
    h = est.fit((x, y), epochs=1, batch_size=32, verbose=False)
    assert np.isfinite(h["loss"][-1])
    assert emb.sparse_paths(est._ts["params"]) == (
        "embed_0/sharded_embeddings", "embed_1/sharded_embeddings")


# -- accounting ---------------------------------------------------------------

def test_lookup_stats_counts_deduped_vs_naive():
    reg = metrics.get_registry()
    d, n = lookup_stats(np.array([1, 1, 2, 2, 2, -1]), dim=8)
    assert (d, n) == (2, 5)
    snap = reg.snapshot()
    assert snap["embed.gather_rows"] == 2
    assert snap["embed.gather_rows_naive"] == 5
    assert snap["embed.gather_bytes"] == 2 * 8 * 4
    assert snap["embed.gather_bytes_naive"] == 5 * 8 * 4
