"""Device idle by program phase and the epoch boundary's counters (PR 25):
the reader against a trace built here, whose idle inside and outside each
named host span is known; every new metric file resolving in every cell; and
one traced rehearsal through the driver's command that prints them all."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, xplane  # noqa: E402
from benchmark.layer_metrics import Reading, trace_busy  # noqa: E402
from benchmark.layer_metrics import trace_idle_in_phase  # noqa: E402

SPEC = manifest.load(REPO)
TRAIN_CELLS = [w["name"] for w in SPEC["workloads"]]
FIT_THREAD = ["zoo:fit.data_wait", "zoo:fit.dispatch", "zoo:fit.epoch_end",
              "zoo:fit.save"]
NEW = {"idle_data_wait_pct", "idle_dispatch_pct", "idle_epoch_end_pct",
       "idle_feed_place_pct", "idle_unnamed_pct", "epoch_gap_ms",
       "epoch_gap_pct", "first_batch_wait_ms"}


def _trace(host, busy=((0, 100), (300, 400), (700, 1000))):
    """One chip busy over ``busy`` of a window [0, 1000): idle [100, 300)
    and [400, 700), 500 of 1000.  A second chip that never idles must not be
    the one that is read."""
    ops = [(float(s), float(e), f"fusion.{i}")
           for i, (s, e) in enumerate(busy)]
    devices = [xplane.Device("/device:TPU:0", ops=ops).settle(),
               xplane.Device("/device:TPU:1",
                             ops=[(0.0, 1000.0, "fusion.9")]).settle()]
    return xplane.Trace(devices, sorted(host), (0.0, 1000.0))


HOST = [(90.0, 250.0, "zoo:fit.data_wait"),    # 150 of the first gap
        (250.0, 290.0, "zoo:fit.dispatch"),    # 40 of it; 10 are unnamed
        (400.0, 500.0, "zoo:fit.epoch_end"),   # 100 of the second gap
        (500.0, 560.0, "zoo:fit.data_wait"),   # 60 more
        (120.0, 450.0, "zoo:feed.place"),      # another thread: 180 + 50
        (560.0, 700.0, "PjitFunction(step)")]  # JAX's own: 140 unnamed


def _read(args, trace):
    return trace_idle_in_phase.read(args, Reading(result=None, device={},
                                                  trace=trace))


def test_idle_falls_to_the_phase_the_host_was_in():
    trace = _trace(HOST)
    assert _read({"phase": "zoo:fit.data_wait"}, trace) == \
        pytest.approx(21.0)
    assert _read({"phase": "zoo:fit.dispatch"}, trace) == pytest.approx(4.0)
    assert _read({"phase": "zoo:fit.epoch_end"}, trace) == \
        pytest.approx(10.0)
    assert _read({"outside": FIT_THREAD}, trace) == pytest.approx(15.0)
    # the producer thread's phase overlaps those of the fit thread
    assert _read({"phase": "zoo:feed.place"}, trace) == pytest.approx(23.0)


def test_the_fit_threads_phases_and_unnamed_add_up_to_the_idle_share():
    trace = _trace(HOST)
    parts = [_read({"phase": p}, trace) for p in FIT_THREAD] \
        + [_read({"outside": FIT_THREAD}, trace)]
    idle = trace_busy.read({"stat": "idle_pct"},
                           Reading(result=None, device={}, trace=trace))
    assert idle == pytest.approx(50.0)
    assert sum(parts) == pytest.approx(idle)
    assert _read({"phase": "zoo:fit.save"}, trace) == 0.0  # never fired


@pytest.mark.parametrize("trace", [
    None,                                        # no --trace run
    xplane.Trace([], HOST, (0.0, 0.0)),          # no device in the trace
], ids=["no_trace", "no_device"])
def test_nothing_to_read_gives_none_and_does_not_raise(trace):
    assert _read({"phase": "zoo:fit.data_wait"}, trace) is None
    assert _read({"outside": FIT_THREAD}, trace) is None


def test_a_slice_that_holds_no_phase_reads_all_idle_as_unnamed():
    """The parent of PR 25 names no phase; and a slice that lies inside one
    long phase holds no event of it (dp4: the fit thread sits in the epoch's
    read-back), because the profiler records a span only when it closes."""
    trace = _trace([(560.0, 700.0, "PjitFunction(step)"),
                    (100.0, 300.0, "bench:feed.load_sample")])
    assert _read({"phase": "zoo:fit.data_wait"}, trace) == 0.0
    assert _read({"outside": FIT_THREAD}, trace) == pytest.approx(50.0)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_every_new_metric_resolves_in_every_cell(cell):
    by_name = {m.name: m for m in manifest.cell(SPEC, cell).per_layer}
    assert NEW <= set(by_name)
    for name in NEW:
        m = by_name[name]
        assert m.moves == "train_samples_per_s_chip" and m.better == "lower"
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m.reader + ".py"))
    assert {by_name[n].reader for n in NEW} == {"trace_idle_in_phase",
                                               "registry_hist"}


def test_a_traced_rehearsal_prints_the_idle_split_and_the_epoch_boundary():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload",
         "resnet50_fit_stream", "--seed", "2147483659", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert NEW <= set(got), sorted(NEW - set(got))
    split = [got[n] for n in ("idle_data_wait_pct", "idle_dispatch_pct",
                              "idle_epoch_end_pct", "idle_unnamed_pct")]
    assert all(0.0 <= v <= 100.0 for v in split + [
        got["idle_feed_place_pct"]])
    assert sum(split) == pytest.approx(got["device_idle_pct"], abs=1e-6)
    # one observation an epoch: the mean gap times the epochs of the window
    # is the sum that epoch_gap_pct puts over the window
    steps = manifest.cell(SPEC, "resnet50_fit_stream",
                          rehearse=True).traffic["steps_per_epoch"]
    epochs = line["attempted"] / steps
    window_ms = line["attempted"] * got["step_wall_ms"]
    assert got["epoch_gap_ms"] * epochs == pytest.approx(
        got["epoch_gap_pct"] / 100.0 * window_ms, rel=1e-6)
    assert got["epoch_gap_ms"] >= got["first_batch_wait_ms"] > 0
