"""The reduction from a profiler trace to busy and idle time, op time and
collective time, against a small trace whose totals are known: two chips,
each with two whole train steps between two that the slice's edges cut
(``benchmark/testdata/two_chips_synthetic.xplane.textproto``, in the shape
of the v5e traces PR 22 looked at).  Per whole step, in microseconds from
the step's start t0:

    while [0,600) spanning fusion.1 [0,200) and fusion.2 [250,550)
    all-reduce-start [600,602); its asynchronous span [600,600+D)
    fusion.2 [650,800) running under the all-reduce
    all-reduce-done [800,600+D); fusion.1 [900,1000)

with D = 250 on chip 0 and 300 on chip 1; steps start at 100 and 1300; chip
0 also runs a 20 us ``jit_mean`` at 2350; both chips have 50 us of a cut
step at 0 and at 2400.  The host is in ``bench:feed.load_sample`` during
[1100,1290), the gap between the steps.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import xplane  # noqa: E402

US = 1e-6


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    path = os.path.join(REPO, "benchmark", "testdata",
                        "two_chips_synthetic.xplane.textproto")
    with open(path) as f:
        return xplane.from_profile(ProfileData.from_text_proto(f.read()))


def test_planes_and_window(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    assert trace.window == (0.0, 2_450_000.0)
    assert xplane.window_seconds(trace) == pytest.approx(2450 * US)
    # the host line keeps what could explain a gap: 1 us events are dropped
    assert [h[2] for h in trace.host] == ["bench:feed.load_sample",
                                          "PjitFunction(train_step)"]


def test_busy_time_is_the_union_of_leaf_ops(trace):
    # a step's leaves: 200 + 300 + 2 + 150 + (D - 200) + 100; the while that
    # spans its body is no leaf, so the 100 us of gaps inside it stay idle
    chip0 = 50 + 2 * 802 + 20 + 50
    chip1 = 50 + 2 * 852 + 50
    assert xplane.busy_seconds(trace) == pytest.approx([chip0 * US,
                                                        chip1 * US])
    assert xplane.idle_pct(trace) == pytest.approx(
        100 * (1 - chip0 / 2450))  # the chip that idles most


def test_device_time_per_execution_leaves_out_the_cut_ones(trace):
    # dominant program: the two whole train steps of each chip
    assert xplane.busy_ms_per_execution(trace) == pytest.approx(
        (0.802 + 0.852) / 2)
    # every program: chip 0's jit_mean counts as a third execution
    assert xplane.busy_ms_per_execution(trace, "all") == pytest.approx(
        ((2 * 802 + 20) / 3 + 852) / 2 / 1000)
    assert [len(xplane.executions(d)) for d in trace.devices] == [2, 2]


def test_collective_time_and_its_exposed_part(trace):
    row = xplane.matching(trace)
    # chip 1 waits longest: 300 us a step from start to done, 150 of them
    # under fusion.2
    assert row["device"] == "/device:TPU:1" and row["executions"] == 2
    assert row["total_ms"] == pytest.approx(0.300)
    assert row["exposed_ms"] == pytest.approx(0.150)
    assert xplane.matching(trace, r"^%?no-such-op")["events"] == 0


def test_top_ops_by_self_time_with_short_labels(trace):
    ops = xplane.top_ops(trace, n=3)
    # fusion.2: 450 a step, plus chip 0's 20; mean over the two chips
    assert ops[0][0] == ("%fusion.2 = f32[1024] fusion(bf16[4,512] "
                         "%fusion.1), kind=kOutput")
    assert ops[0][1] == pytest.approx((4 * 450 + 20) / 2 * US)
    # fusion.1: 300 a step and the two cut steps' 50 each
    assert ops[1][0].startswith("%fusion.1 = bf16[4,512] fusion(")
    assert ops[1][1] == pytest.approx((4 * 300 + 4 * 50) / 2 * US)
    # the while's self time is what its body leaves uncovered: 100 a step
    whiles = [o for o in xplane.top_ops(trace, n=10)
              if o[0].startswith("%while.3")]
    assert whiles[0][1] == pytest.approx(4 * 100 / 2 * US)
    assert all(len(name) <= xplane.MAX_LABEL for name, _ in ops)


def test_idle_gaps_take_the_name_of_what_the_host_was_in(trace):
    gaps = dict(xplane.idle_gaps(trace))
    # all of chip 0's idle time is accounted for
    assert sum(gaps.values()) == pytest.approx((2450 - 1724) * US)
    assert gaps["bench:feed.load_sample"] == pytest.approx(200 * US)
    # [2300,2350) falls inside the host's 40 us dispatch: over half of it
    assert gaps["PjitFunction(train_step)"] == pytest.approx(50 * US)
    # [602,650) twice and [2370,2400): too short for a name
    assert gaps["gaps under 50 us, between ops"] == pytest.approx(
        (48 + 48 + 30) * US)
    assert gaps["no host event"] == pytest.approx(
        (726 - 200 - 50 - 126) * US)


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.subtract([(0, 4)], []) == [(0, 4)]
    own, leaf = xplane.self_times([(0, 10, "outer"), (1, 4, "a"),
                                   (2, 3, "b"), (5, 9, "c")])
    assert own == [3, 2, 1, 4] and leaf == [False, False, True, True]


def test_a_trace_without_device_events_reduces_to_nothing():
    from jax.profiler import ProfileData
    empty = xplane.from_profile(ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }'))
    assert empty.devices == [] and xplane.idle_pct(empty) is None
    assert xplane.busy_seconds(empty) == [] and xplane.idle_gaps(empty) == []
    assert xplane.find(os.path.join(REPO, "benchmark", "testdata")) is None
