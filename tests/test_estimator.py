"""End-to-end Estimator tests: the SURVEY.md §7 stage-3 milestone.

Covers: fit reduces loss (LeNet/MNIST-like), metrics, predict exactness,
save/load round-trip, XShards + DataFrame column paths, and the golden
data-parallel consistency check (§7 stage 4): same data+seed ⇒ same result
regardless of mesh layout, because the global batch is what defines the step.
"""

import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import init_orca_context, stop_orca_context
from analytics_zoo_tpu.data import XShards
from analytics_zoo_tpu.orca.learn import Estimator, EveryEpoch


def make_blobs(n=256, dim=8, classes=4, seed=0):
    """Linearly separable clusters — tiny stand-in for MNIST."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, dim)) * 3
    y = rng.integers(0, classes, n)
    x = centers[y] + rng.normal(size=(n, dim)).astype(np.float32)
    return x.astype(np.float32), y.astype(np.int32)


def mlp(classes=4):
    return nn.Sequential([
        nn.Dense(32, activation="relu"),
        nn.Dense(classes),
    ])


def test_fit_reduces_loss_and_learns():
    init_orca_context("local")
    x, y = make_blobs()
    est = Estimator.from_keras(mlp(), loss="sparse_categorical_crossentropy",
                               optimizer="adam", learning_rate=1e-2,
                               metrics=["accuracy"])
    hist = est.fit((x, y), epochs=5, batch_size=64)
    assert hist["loss"][-1] < hist["loss"][0] * 0.5
    res = est.evaluate((x, y), batch_size=64)
    assert res["accuracy"] > 0.9


def test_lenet_mnist_smoke():
    """LeNet on synthetic digits: the BASELINE LeNet/MNIST config at toy scale."""
    init_orca_context("local")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, 64).astype(np.int32)
    model = nn.Sequential([
        nn.Conv2D(6, 5, activation="relu"), nn.MaxPooling2D(2),
        nn.Conv2D(16, 5, padding="valid", activation="relu"),
        nn.MaxPooling2D(2), nn.Flatten(),
        nn.Dense(120, activation="relu"), nn.Dense(84, activation="relu"),
        nn.Dense(10),
    ])
    est = Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                               learning_rate=5e-3)
    hist = est.fit((x, y), epochs=3, batch_size=32)
    assert hist["loss"][-1] < hist["loss"][0]  # memorizing noise: loss drops
    preds = est.predict(x, batch_size=32)
    assert preds.shape == (64, 10)


def test_predict_exact_rows_with_remainder():
    init_orca_context("local")
    x, y = make_blobs(n=70)  # not divisible by batch or 8 devices
    est = Estimator.from_keras(mlp(), loss="sparse_categorical_crossentropy")
    est.fit((x, y), epochs=1, batch_size=32)
    preds = est.predict(x, batch_size=32)
    assert preds.shape[0] == 70


def test_save_load_roundtrip(tmp_path):
    init_orca_context("local")
    x, y = make_blobs()
    est = Estimator.from_keras(mlp(), loss="sparse_categorical_crossentropy",
                               learning_rate=1e-2)
    est.fit((x, y), epochs=2, batch_size=64)
    p1 = est.predict(x)
    est.save(str(tmp_path / "m"))

    est2 = Estimator.from_keras(mlp(), loss="sparse_categorical_crossentropy",
                                learning_rate=1e-2)
    est2.load(str(tmp_path / "m"))
    p2 = est2.predict(x)
    np.testing.assert_allclose(p1, p2, rtol=1e-5)
    # resumed training continues from the same step count
    assert int(est2._ts["step"]) == int(est._ts["step"])


def test_checkpoint_trigger_writes(tmp_path):
    init_orca_context("local")
    x, y = make_blobs(n=128)
    est = Estimator.from_keras(mlp(), loss="sparse_categorical_crossentropy",
                               model_dir=str(tmp_path / "ckpt"))
    est.fit((x, y), epochs=1, batch_size=64, checkpoint_trigger=EveryEpoch())
    from analytics_zoo_tpu.core import checkpoint as ck
    assert ck.exists(str(tmp_path / "ckpt"))


def test_fit_from_xshards_dataframe_cols():
    import pandas as pd
    init_orca_context("local")
    x, y = make_blobs(n=120, dim=3)
    df = pd.DataFrame({"f1": x[:, 0], "f2": x[:, 1], "f3": x[:, 2], "label": y})
    shards = XShards([df.iloc[:60], df.iloc[60:]])
    est = Estimator.from_keras(mlp(), loss="sparse_categorical_crossentropy",
                               learning_rate=1e-2, metrics=["accuracy"])
    est.fit(shards, epochs=3, batch_size=40,
            feature_cols=["f1", "f2", "f3"], label_cols=["label"])
    res = est.evaluate(shards, batch_size=40,
                       feature_cols=["f1", "f2", "f3"], label_cols=["label"])
    assert res["accuracy"] > 0.5


def test_dp_consistency_across_mesh_layouts():
    """Golden §7-stage-4 test: with identical global batches, training on a
    1-wide vs 8-wide data axis gives the same params (psum == single-device
    sum).  CPU f32 math is deterministic enough for a near-exact match."""
    x, y = make_blobs(n=64, seed=3)

    def run(mesh_shape):
        stop_orca_context()
        init_orca_context("local", mesh_shape=mesh_shape)
        est = Estimator.from_keras(
            mlp(), loss="sparse_categorical_crossentropy",
            optimizer="sgd", learning_rate=0.1, seed=7)
        est.fit((x, y), epochs=2, batch_size=32)
        return est.predict(x)

    p_wide = run({"data": 8})
    p_one = run({"data": 1})
    np.testing.assert_allclose(p_wide, p_one, rtol=2e-3, atol=2e-4)


def test_batchnorm_model_trains():
    """State (running stats) threads through fit and is used in eval."""
    init_orca_context("local")
    x, y = make_blobs(n=128)
    model = nn.Sequential([nn.Dense(16), nn.BatchNormalization(),
                           nn.Activation("relu"), nn.Dense(4)])
    est = Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                               learning_rate=1e-2)
    est.fit((x, y), epochs=2, batch_size=64)
    stats = est.get_model()["state"]
    leaves = [np.asarray(v) for v in
              __import__("jax").tree_util.tree_leaves(stats)]
    assert any(np.abs(l).sum() > 0 for l in leaves)
    preds = est.predict(x)
    assert preds.shape == (128, 4)


def test_evaluate_dataset_smaller_than_batch():
    # masked padded batches: a 2-row dataset evaluates exactly even with
    # batch_size 64 (previously raised "no batches")
    init_orca_context("local")
    est = Estimator.from_keras(mlp(), loss="mse")
    x = np.ones((2, 4), np.float32)
    y = np.zeros((2, 1), np.float32)
    est.fit((np.ones((8, 4), np.float32), np.zeros((8, 1), np.float32)),
            epochs=1, batch_size=8, verbose=False)
    res = est.evaluate((x, y), batch_size=64)
    pred = est.predict(x, batch_size=64)
    assert abs(res["loss"] - float(np.square(pred - y).mean())) < 1e-5


def test_save_uninitialized_raises(tmp_path):
    init_orca_context("local")
    est = Estimator.from_keras(mlp(), loss="mse")
    with pytest.raises(ValueError):
        est.save(str(tmp_path / "x"))


def test_evaluate_covers_remainder_rows(rng):
    """evaluate() must include rows beyond the last full batch (regression:
    code-review finding — previously silently dropped)."""
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.core import init_orca_context
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    model = nn.Sequential([nn.Dense(1)])
    est = Estimator.from_keras(model, loss="mse", metrics=["mae"])
    x = rng.normal(size=(70, 4)).astype(np.float32)
    y = np.zeros((70, 1), np.float32)
    est.fit((x[:32], y[:32]), epochs=1, batch_size=32, verbose=False)
    res = est.evaluate((x, y), batch_size=32)
    # mae over ALL 70 rows: hand-compute from the model's own predictions
    pred = est.predict(x, batch_size=32)
    expect_mae = float(np.abs(pred - y).mean())
    assert abs(res["mae"] - expect_mae) < 1e-5
    expect_loss = float(np.square(pred - y).mean())
    assert abs(res["loss"] - expect_loss) < 1e-5


def test_profiler_trace_written(tmp_path, rng):
    """jax.profiler integration (SURVEY §5.1): fit with profile_dir writes
    a trace capture under the directory."""
    import os
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.core import init_orca_context
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    model = nn.Sequential([nn.Dense(1)])
    prof = str(tmp_path / "prof")
    est = Estimator.from_keras(model, loss="mse", profile_dir=prof,
                               profile_steps=(1, 3))
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = np.zeros((64, 1), np.float32)
    est.fit((x, y), epochs=1, batch_size=16, verbose=False)
    assert not est._profiling
    found = [os.path.join(r, f) for r, _, fs in os.walk(prof) for f in fs]
    assert found, "no profiler trace files written"


def test_summary_readback(tmp_path, rng):
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.core import init_orca_context
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    est = Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                               metrics=["mae"], log_dir=str(tmp_path),
                               app_name="t")
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = np.zeros((32, 1), np.float32)
    est.fit((x, y), epochs=3, batch_size=16, validation_data=(x, y),
            verbose=False)
    train = est.get_train_summary("loss")
    assert len(train) == 3 and all(np.isfinite(v) for _, v in train)
    val = est.get_validation_summary("mae")
    assert len(val) == 3


def test_evaluate_shuffled_drop_remainder_exact_coverage():
    """Regression (VERDICT r2 weak #7): a SHUFFLED drop_remainder feed now
    evaluates exactly — the dropped tail of the epoch permutation is
    covered by a padded+masked extra batch, so metrics equal the
    unshuffled full-coverage result."""
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.data import DataFeed
    from analytics_zoo_tpu.orca.learn import Estimator

    rng = np.random.default_rng(0)
    x = rng.normal(size=(37, 6)).astype(np.float32)   # 37 % 16 = 5 dropped
    y = rng.integers(0, 2, 37).astype(np.int32)
    est = Estimator.from_keras(
        nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(2)]),
        loss="sparse_categorical_crossentropy", metrics=["accuracy"])
    est.fit((x[:32], y[:32]), epochs=1, batch_size=16, verbose=False)

    shuffled = DataFeed({"x": x, "y": y}, 16, shuffle=True, seed=3,
                        drop_remainder=True)
    exact = est.evaluate((x, y), batch_size=16)
    got = est.evaluate(shuffled, batch_size=16)
    assert got["loss"] == pytest.approx(exact["loss"], rel=1e-5)
    assert got["accuracy"] == pytest.approx(exact["accuracy"], rel=1e-6)


def test_grad_accum_matches_full_batch_step():
    """grad_accum=N must produce EXACTLY the full-batch update: mean of
    equal micro-batch mean-gradients == full-batch mean gradient."""
    import analytics_zoo_tpu.nn as nn
    rng = np.random.default_rng(11)
    x = rng.normal(size=(16, 6)).astype(np.float32)
    y = rng.integers(0, 3, 16).astype(np.int32)

    def make(accum):
        init_orca_context("local")
        model = nn.Sequential([nn.Dense(16, activation="relu"),
                               nn.Dense(3)])
        est = Estimator.from_keras(
            model, loss="sparse_categorical_crossentropy", optimizer="sgd",
            learning_rate=0.1, grad_accum=accum)
        hist = est.fit((x, y), epochs=2, batch_size=16, verbose=False)
        return hist["loss"], est.get_model()

    import jax
    loss1, p1 = make(1)
    loss4, p4 = make(4)
    np.testing.assert_allclose(loss1, loss4, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_grad_accum_rejects_indivisible_batch():
    import analytics_zoo_tpu.nn as nn
    init_orca_context("local")
    est = Estimator.from_keras(nn.Sequential([nn.Dense(2)]),
                               loss="mse", optimizer="sgd",
                               learning_rate=0.1, grad_accum=3)
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 2), np.float32)
    with pytest.raises(ValueError, match="divisible"):
        est.fit((x, y), epochs=1, batch_size=8, verbose=False)


def test_fit_prefetch_matches_inline_bitwise():
    """fit(prefetch=2) must be a pure scheduling change: the same batches
    in the same order through the same compiled step — loss history
    identical to the inline prefetch=0 baseline (bisection contract)."""
    init_orca_context("local")
    x, y = make_blobs()

    def run(prefetch):
        est = Estimator.from_keras(
            mlp(), loss="sparse_categorical_crossentropy",
            optimizer="adam", learning_rate=1e-2, seed=3)
        return est.fit((x, y), epochs=3, batch_size=64, verbose=False,
                       prefetch=prefetch)

    inline = run(prefetch=0)
    prefetched = run(prefetch=2)
    assert inline["loss"] == prefetched["loss"]


def test_fit_prefetch_records_depth_gauge():
    from analytics_zoo_tpu.core import metrics
    init_orca_context("local")
    x, y = make_blobs()
    est = Estimator.from_keras(mlp(),
                               loss="sparse_categorical_crossentropy",
                               learning_rate=1e-2)
    est.fit((x, y), epochs=1, batch_size=64, verbose=False, prefetch=2)
    snap = metrics.get_registry().snapshot()
    assert "train.prefetch_depth" in snap
    assert snap["train.prefetch_depth"]["max"] <= 2


def test_fit_prefetch_with_streaming_feed():
    """StreamingDataFeed composes with the estimator-level prefetcher:
    the stream's decode workers feed the prefetch thread, which feeds the
    step loop; row accounting stays exact."""
    from analytics_zoo_tpu.data import StreamingDataFeed
    init_orca_context("local")
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(96, 8)).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) > 0).astype(np.float32)

    def load(i, rng=None):
        return {"x": xs[i], "y": ys[i]}

    feed = StreamingDataFeed(96, load, batch_size=32, shuffle=False,
                             num_workers=2)
    est = Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                               learning_rate=1e-2)
    hist = est.fit(feed, epochs=2, batch_size=32, verbose=False,
                   prefetch=2)
    assert len(hist["loss"]) == 2


def test_repeated_fits_of_one_shape_compile_the_train_step_once():
    """The benchmark's own condition (``correct`` needs ``compile_count``
    1 after set-up and no more in the window): a one-step fit, a
    one-epoch fit and a three-epoch fit of the same batch shape on one
    estimator share one train-step executable."""
    from analytics_zoo_tpu.data import StreamingDataFeed
    init_orca_context("local")
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(128, 8)).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) > 0).astype(np.float32)

    def feed(rows):
        return StreamingDataFeed(
            rows, lambda i, rng=None: {"x": xs[i], "y": ys[i]},
            batch_size=32, shuffle=True, seed=1, num_workers=2)

    est = Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                               learning_rate=1e-2, profile=True)
    for rows, epochs in ((32, 1), (128, 1), (128, 3)):
        est.fit(feed(rows), epochs=epochs, batch_size=32, verbose=False)
        assert est.compile_count == 1, (rows, epochs)
    assert est._py_step == 1 + 4 + 12
