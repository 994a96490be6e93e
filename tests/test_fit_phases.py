"""``fit()``'s phases on the profiler's clock, its epoch boundary under two
counters, and module paths on the train step's ops (ISSUE 25)."""

import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import init_orca_context, metrics, trace
from analytics_zoo_tpu.data.stream import StreamingDataFeed
from analytics_zoo_tpu.orca.learn import Estimator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH, STEPS = 8, 3


def _estimator(**kwargs):
    model = nn.Sequential([nn.Dense(16, activation="relu", name="hidden"),
                           nn.Dense(4, name="head")])
    return Estimator.from_keras(model, loss="sparse_categorical_crossentropy",
                                optimizer="adam", learning_rate=1e-2,
                                **kwargs)


def _stream(steps=STEPS):
    def load_sample(i, rng=None):
        return {"x": np.full((8,), i % 4, np.float32), "y": np.int32(i % 4)}
    return StreamingDataFeed(num_samples=steps * BATCH,
                             load_sample=load_sample, batch_size=BATCH,
                             shuffle=False, seed=0)


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    found = []
    for base, _, files in os.walk(trace_dir):
        found += [os.path.join(base, f) for f in files
                  if f.endswith(".xplane.pb")]
    assert found, "the profiler wrote no .xplane.pb"
    profile = ProfileData.from_file(sorted(found)[-1])
    return [e.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_fit_under_a_profiler_session_leaves_its_phases_as_host_events(
        tmp_path):
    init_orca_context("local")
    est = _estimator()
    est.fit(_stream(), epochs=1, batch_size=BATCH, verbose=False)  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        est.fit(_stream(), epochs=2, batch_size=BATCH, verbose=False)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    count = {n: names.count(n) for n in set(names) if n.startswith("zoo:")}
    # per epoch: a wait for each batch and one for the end of the feed; a
    # dispatch and a placement for each batch; one epoch end
    assert count == {"zoo:fit.data_wait": 2 * (STEPS + 1),
                     "zoo:fit.dispatch": 2 * STEPS,
                     "zoo:feed.place": 2 * STEPS,
                     "zoo:fit.epoch_end": 2}


def test_an_exception_at_an_epochs_end_leaves_no_phase_open(tmp_path):
    """``zoo:fit.epoch_end`` is opened and closed by hand: a failure inside
    it (here: validation data that is no data) must still close it, or
    every later host event of the thread would nest under it."""
    init_orca_context("local")
    est = _estimator()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(Exception):
            est.fit(_stream(), epochs=2, batch_size=BATCH, verbose=False,
                    validation_data=object())
    finally:
        jax.profiler.stop_trace()
    # an event is written when its annotation closes
    assert _host_event_names(str(tmp_path)).count("zoo:fit.epoch_end") == 1


def test_the_epoch_boundary_is_observed_once_an_epoch():
    init_orca_context("local")
    est = _estimator()
    est.fit(_stream(), epochs=3, batch_size=BATCH, verbose=False)
    snap = metrics.get_registry().snapshot()
    assert snap["train.epoch_gap_ms"]["count"] == 3
    assert snap["train.first_batch_wait_ms"]["count"] == 3
    # the first wait is still one of the data waits: that series is as it was
    assert snap["train.data_wait_ms"]["count"] == 3 * STEPS
    # the gap holds the first wait
    assert snap["train.epoch_gap_ms"]["sum"] \
        >= snap["train.first_batch_wait_ms"]["sum"] > 0


# -- one feed pipeline a fit() (ISSUE 28) ------------------------------------

def _indexed_stream(calls=None, steps=STEPS, fail_at=None, **kw):
    """Rows that differ by index, shuffled anew each epoch, so a batch of
    the wrong epoch changes the loss; the loader ignores ``rng``."""
    seen = {"n": 0}

    def load_sample(i, rng=None):
        seen["n"] += 1
        if calls is not None:
            calls.append(i)
        if fail_at is not None and seen["n"] > fail_at:
            raise OSError("the disk went away")
        x = np.random.default_rng(i).normal(size=8).astype(np.float32)
        return {"x": x, "y": np.int32(i % 4)}
    return StreamingDataFeed(num_samples=steps * BATCH,
                             load_sample=load_sample, batch_size=BATCH,
                             shuffle=True, seed=11, **kw)


def _pipeline_threads():
    import threading
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("zoo-prefetch", "zoo-feed"))]


@pytest.mark.parametrize("epochs", [1, 4])
def test_epochs_carried_counts_every_boundary_but_the_first(epochs):
    """Inline (``prefetch=0``) the answer is exact: past a run's first
    batch the feed's consumer always holds the next one decoded."""
    init_orca_context("local")
    _estimator().fit(_indexed_stream(), epochs=epochs, batch_size=BATCH,
                     verbose=False, prefetch=0)
    snap = metrics.get_registry().snapshot()
    assert snap.get("feed.epochs_carried", 0) == epochs - 1
    assert snap["train.first_batch_wait_ms"]["count"] == epochs
    assert _pipeline_threads() == []


def test_epochs_are_carried_behind_the_prefetcher_too():
    """With the prefetcher, carried means: placed in its queue when
    ``fit()`` asks.  A step that takes 30 ms (armed delay) leaves the feed
    all the time it needs."""
    from analytics_zoo_tpu.core import faults
    init_orca_context("local")
    est = _estimator()
    with faults.get_registry().armed("worker.hang", delay=0.03):
        est.fit(_indexed_stream(), epochs=3, batch_size=BATCH,
                verbose=False)
    snap = metrics.get_registry().snapshot()
    assert snap.get("feed.epochs_carried", 0) == 2
    assert snap["train.steps"] == 3 * STEPS
    assert _pipeline_threads() == []


@pytest.mark.parametrize("prefetch", [0, 2])
def test_one_fit_of_three_epochs_is_three_fits_of_one(prefetch):
    init_orca_context("local")
    whole = _estimator(seed=3).fit(_indexed_stream(), epochs=3,
                                   batch_size=BATCH, verbose=False,
                                   prefetch=prefetch)["loss"]
    est = _estimator(seed=3)
    apart = [est.fit(_indexed_stream(), epochs=1, batch_size=BATCH,
                     verbose=False, prefetch=prefetch)["loss"][0]
             for _ in range(3)]
    assert whole == apart           # bit for bit
    assert len(set(whole)) == 3


def test_fit_loads_nothing_past_its_last_epoch():
    init_orca_context("local")
    calls = []
    est = _estimator()
    est.fit(_indexed_stream(calls, num_workers=4, prefetch_batches=8),
            epochs=2, batch_size=BATCH, verbose=False)
    rows = list(range(STEPS * BATCH))
    assert sorted(calls) == sorted(2 * rows)
    # the next call goes on at epoch 2, again with its own rows only
    est.fit(_indexed_stream(calls), epochs=1, batch_size=BATCH,
            verbose=False)
    assert sorted(calls) == sorted(3 * rows)


def test_a_rollback_drops_what_was_decoded_ahead(tmp_path):
    """NaN in the middle of the second epoch, with the third already in
    the feed's hands: the epoch is re-run from the checkpoint on its own
    batches, and the history is a clean run's."""
    from analytics_zoo_tpu.core import faults
    init_orca_context("local")
    clean = _estimator(seed=3).fit(_indexed_stream(), epochs=3,
                                   batch_size=BATCH, verbose=False)
    est = _estimator(seed=3, nan_policy="rollback",
                     model_dir=str(tmp_path / "ckpt"))
    with faults.get_registry().armed("step.nan", times=1, after=STEPS + 1):
        hist = est.fit(_indexed_stream(num_workers=4), epochs=3,
                       batch_size=BATCH, verbose=False,
                       checkpoint_trigger="every_epoch")
    assert est._rollbacks == 1
    assert est._py_step == 3 * STEPS
    assert len(hist["loss"]) == 3
    np.testing.assert_allclose(hist["loss"], clean["loss"], rtol=1e-6)
    assert _pipeline_threads() == []


def test_a_loader_exception_leaves_no_thread_behind():
    init_orca_context("local")
    est = _estimator()
    with pytest.raises(OSError, match="the disk went away"):
        est.fit(_indexed_stream(fail_at=STEPS * BATCH + 3), epochs=3,
                batch_size=BATCH, verbose=False)
    assert _pipeline_threads() == []
    assert est._epoch <= 1


def test_a_preemption_leaves_no_thread_behind(tmp_path):
    from analytics_zoo_tpu.core.failover import Preempted
    init_orca_context("local")
    est = _estimator(model_dir=str(tmp_path / "ckpt"),
                     preemption_checkpoint=True, preemption_sync_every=1)
    try:
        est.fit(_indexed_stream(), epochs=1, batch_size=BATCH,
                verbose=False)
        est._preempt._flag = True       # what the signal handler stores
        with pytest.raises(Preempted):
            est.fit(_indexed_stream(), epochs=3, batch_size=BATCH,
                    verbose=False)
    finally:
        est._preempt.uninstall()
    assert _pipeline_threads() == []


def test_the_process_backend_goes_through_the_same_fit():
    from analytics_zoo_tpu.data import shm_pool
    if not shm_pool.available():
        pytest.skip("process backend unavailable")
    init_orca_context("local")
    hist = _estimator().fit(
        _indexed_stream(workers="process", num_workers=2), epochs=2,
        batch_size=BATCH, verbose=False)
    assert len(hist["loss"]) == 2
    assert metrics.get_registry().snapshot()["train.steps"] == 2 * STEPS
    assert _pipeline_threads() == []


def test_the_kill_switch_silences_the_epoch_boundary_too():
    init_orca_context("local")
    reg = metrics.get_registry()
    reg.enabled = False
    try:
        _estimator().fit(_stream(), epochs=2, batch_size=BATCH,
                         verbose=False)
    finally:
        reg.enabled = True
    snap = reg.snapshot()
    for series in ("train.epoch_gap_ms", "train.first_batch_wait_ms",
                   "train.data_wait_ms"):
        assert snap.get(series, {"count": 0})["count"] == 0, series


@pytest.mark.parametrize("accum", [1, 2])
def test_the_train_steps_ops_carry_module_paths_and_the_optimizer(accum):
    init_orca_context("local")
    est = _estimator(grad_accum=accum)
    est.fit(_stream(1), epochs=1, batch_size=BATCH, verbose=False)
    batch = {"x": np.zeros((BATCH, 8), np.float32),
             "y": np.zeros((BATCH,), np.int32)}
    text = est._train_step.lower(est._ts, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # forward (under the gradient's jvp), and the transposed backward
    assert any(re.search(r"/jvp\(hidden\)/dot_general", n) for n in names), \
        sorted(names)[:20]
    assert any("/jvp(head)/" in n for n in names)
    assert any("/transpose(jvp(hidden))/" in n for n in names), \
        "the backward pass lost the module path"
    assert any("/optimizer/" in n for n in names)
    assert any("/grad_accum/" in n for n in names) == (accum > 1)


def test_the_trace_module_itself_imports_no_jax():
    """``phase`` imports JAX when it is called, not when the module loads
    (the package's ``__init__`` imports JAX for its own reasons; the module
    must not add to that)."""
    code = ("import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('zoo_trace', %r)\n"
            "mod = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(mod)\n"
            "assert callable(mod.phase)\n"
            "assert 'jax' not in sys.modules, 'core/trace.py imported jax'\n"
            % os.path.join(REPO, "analytics_zoo_tpu", "core", "trace.py"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_phase_is_the_one_place_that_builds_a_profiler_annotation():
    hits = []
    for base, _, files in os.walk(os.path.join(REPO, "analytics_zoo_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    text = fh.read()
                if re.search(r"TraceAnnotation\(|[\"']zoo:", text):
                    hits.append(os.path.relpath(os.path.join(base, f), REPO))
    assert hits == ["analytics_zoo_tpu/core/trace.py"]
    assert type(trace.phase("x")) is jax.profiler.TraceAnnotation
