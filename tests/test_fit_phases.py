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
