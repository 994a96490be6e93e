"""A module's time on the device (PR 35): the reader ``trace_scope_time``
against a trace and a table built here, whose times by scope are known; the
ten metric files and their entries; and one traced rehearsal through the
driver's command that prints the four metrics every cell reports."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, xplane  # noqa: E402
from benchmark.layer_metrics import Reading, trace_scope_time  # noqa: E402

SPEC = manifest.load(REPO)
MS = 1e6  # a trace's clock is in ns

OPT = "(^|/)optimizer(/|$)"
HEAD = "(^|/)((mlm_)?head|loss)(/|$)"
MOE = "(^|/)moe(/|$)"
GDN = "(^|/)gdn(/|$)"
QWEN, TRINITY, GRANITE = ("qwen3next_ep16_fit_s8192",
                          "trinity_mini_ep8_fit_s16384",
                          "granite4h_micro_fit_s8192")
#: metric -> (arguments, cells; None: every cell)
METRICS = {
    "optimizer_device_pct": ({"stat": "pct_of_step", "scope": OPT}, None),
    "scope_mixed_pct": ({"stat": "mixed_pct"}, None),
    "scope_unattributed_pct": ({"stat": "unattributed_pct"}, None),
    "head_loss_device_pct": ({"stat": "pct_of_step", "scope": HEAD}, None),
    "moe_scope_ms_per_step": ({"stat": "ms_per_step", "scope": MOE},
                              [QWEN, TRINITY]),
    "moe_row_movement_ms_per_step": (
        {"stat": "ms_per_step", "scope": MOE,
         "not_scope": "(^|/)(router|shared_expert|shared_gate)(/|$)",
         "not_op": "^%?ragged[-_]dot"}, [QWEN, TRINITY]),
    "gdn_scope_ms_per_step": ({"stat": "ms_per_step", "scope": GDN}, [QWEN]),
    "gdn_outside_kernels_ms_per_step": (
        {"stat": "ms_per_step", "scope": GDN,
         "not_op": "^%?gated_delta_rule_(fwd|bwd)"}, [QWEN]),
    "mamba_scope_ms_per_step": (
        {"stat": "ms_per_step", "scope": "(^|/)mamba(/|$)"}, [GRANITE]),
    "ssd_scope_ms_per_step": (
        {"stat": "ms_per_step", "scope": "(^|/)ssd(/|$)"}, [GRANITE]),
}

#: the program's table: instruction name -> (scope, also)
TABLE = {
    "while.1": ("", frozenset()),   # the step's own: attributed, no module
    "fusion.1": ("remat_0/moe", frozenset()),
    "ragged-dot-none.2": ("remat_0/moe", frozenset()),
    "fusion.3": ("optimizer", frozenset({"", "remat_0/moe/shared_expert"})),
    "fusion.4": ("remat_0/moe/router", frozenset()),
    "copy.5": (None, frozenset()),
    "fusion.6": ("optimizer", frozenset({""})),   # never runs in the slice
}


def _step(at, gather=30.0):
    """One train step's ops from ``at`` (ms): a loop of 80 whose body holds
    ``gather`` and 20, then 10, 6 and 4 — 100 of self time at ``gather`` 30
    (the loop's own: 80 - 30 - 20)."""
    def op(s, e, name):
        return ((at + s) * MS, (at + e) * MS, name)
    return [op(0, 80, "%while.1 = (s32[], f32[8]) while(%tuple.9)"),
            op(10, 10 + gather, "%fusion.1 = f32[8,8] fusion(f32[8,8])"),
            op(40, 60, "%ragged-dot-none.2 = f32[8] custom-call(f32[8])"),
            op(80, 90, "%fusion.3 = f32[8] fusion(f32[8])"),
            op(90, 96, "%fusion.4 = f32[8] fusion(%ragged-dot-none.2)"),
            op(96, 100, "%copy.5 = f32[8] copy(f32[8])")]


def _device(name, gather):
    """A chip that ran the train step four times, the first and the last
    cut by the slice's edges, and another program in between."""
    modules = [(0.0, 100 * MS, "jit_train_step(9)"),
               (100 * MS, 200 * MS, "jit_train_step(9)"),
               (200 * MS, 220 * MS, "jit_pred_step(3)"),
               (220 * MS, 320 * MS, "jit_train_step(9)"),
               (320 * MS, 400 * MS, "jit_train_step(9)")]
    ops = (_step(0, gather) + _step(100, gather) + _step(220, gather)
           # another program's op under a name the train step has too
           + [(200 * MS, 220 * MS, "%fusion.1 = f32[4] fusion(f32[4])")]
           + _step(320, gather)[:3])
    return xplane.Device(name, ops=ops, modules=modules).settle()


@pytest.fixture
def trace(monkeypatch):
    monkeypatch.setattr(trace_scope_time, "table", lambda: TABLE)
    return xplane.Trace([_device("/device:TPU:0", 30.0),
                         _device("/device:TPU:1", 20.0)],
                        [], (0.0, 400 * MS))


def _read(args, trace):
    return trace_scope_time.read(args, Reading(result=None, device={},
                                               trace=trace))


# Per step and chip: the loop's own 30 (40 on the second chip), the gather
# 30 (20), the grouped matmul 20, the mixed fusion 10, the router 6, the
# copy 4: 100 on both chips.
@pytest.mark.parametrize("args,expected", [
    ({"stat": "ms_per_step", "scope": MOE}, 25.0 + 20.0 + 6.0),
    (METRICS["moe_row_movement_ms_per_step"][0], 25.0),
    ({"stat": "ms_per_step", "scope": MOE, "not_op": "^%?ragged[-_]dot"},
     25.0 + 6.0),
    # unanchored, an op that consumes the matmul's result would go too
    ({"stat": "ms_per_step", "scope": MOE, "not_op": "ragged[-_]dot"}, 25.0),
    ({"stat": "pct_of_step", "scope": OPT}, 10.0),
    ({"stat": "pct_of_step", "scope": MOE}, 51.0),
    ({"stat": "unattributed_pct"}, 4.0),
    ({"stat": "mixed_pct"}, 10.0),
    # the empty path is attributed and matches no pattern
    ({"stat": "pct_of_step", "scope": ""}, 61.0),
    ({"stat": "ms_per_step", "scope": GDN}, 0.0),
    ({"stat": "pct_of_step", "scope": HEAD}, 0.0),
])
def test_time_falls_to_the_scope_that_made_the_op(trace, args, expected):
    assert _read(args, trace) == pytest.approx(expected)


def test_self_time_is_nested_and_only_whole_executions_count(trace):
    ops = trace_scope_time.self_ms_per_step(trace)
    assert {k: round(ms, 6) for k, (ms, _) in ops.items()} == {
        "while.1": 35.0, "fusion.1": 25.0, "ragged-dot-none.2": 20.0,
        "fusion.3": 10.0, "fusion.4": 6.0, "copy.5": 4.0}
    assert ops["fusion.3"][1].startswith("%fusion.3 = f32[8] fusion(")


def test_no_table_is_nothing_to_read(trace, monkeypatch):
    """A program that registered no train step, or one from before it had
    a table (the parent of PR 35, run with these files laid over it)."""
    monkeypatch.setattr(trace_scope_time, "table", lambda: None)
    assert _read({"stat": "unattributed_pct"}, trace) is None
    monkeypatch.undo()
    from analytics_zoo_tpu.core import trace as trace_lib
    monkeypatch.delattr(trace_lib, "op_scopes")
    assert trace_scope_time.table() is None
    assert _read({"stat": "ms_per_step", "scope": MOE},
                 xplane.Trace([], [], (0.0, 0.0))) is None


def test_a_platform_without_programs_reads_the_window(monkeypatch):
    """The CPU rehearsal's trace has no ``XLA Modules`` line: the metrics a
    cell must always report read the whole window there."""
    monkeypatch.setattr(trace_scope_time, "table", lambda: TABLE)
    dev = xplane.Device("host-xla", ops=_step(0)).settle()
    trace = xplane.Trace([dev], [], (0.0, 100 * MS))
    assert _read({"stat": "pct_of_step", "scope": OPT}, trace) == \
        pytest.approx(10.0)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_file_and_its_entry_say_what_is_read(name):
    args, cells = METRICS[name]
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "trace_scope_time" and spec["args"] == args
    assert len(spec["reads"]) > 40   # the scope it reads, in words
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert entry.get("workloads") == cells
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "train_samples_per_s_chip"
    for cell in (w["name"] for w in SPEC["workloads"]):
        resolved = manifest.cell(SPEC, cell)
        assert (name in {m.name for m in resolved.per_layer}) == (
            cells is None or cell in cells)


def test_the_traced_rehearsal_reports_the_four_every_cell_has():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, *SPEC["command"][1:], "--workload",
           "bert_base_fit_s512", "--seed", "35", "--seconds", "2",
           "--trace", "1", "--rehearse"]
    for _ in range(2):  # a window shorter than its warm epoch foretold
        proc = subprocess.run(cmd, env=env, cwd=REPO,  # leaves no slice
                              capture_output=True, text=True, timeout=600)
        if proc.returncode != 3:
            break
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    general = [n for n, (_, cells) in METRICS.items() if cells is None]
    assert set(general) <= set(got), sorted(got)
    assert all(0.0 <= got[n] <= 100.0 for n in general), got
    # the optimizer ran, the head and the loss ran, and most ops have a name
    assert got["optimizer_device_pct"] > 0 and got["head_loss_device_pct"] > 0
    assert got["scope_unattributed_pct"] < 50
    assert not set(METRICS) - set(general) & set(got)
