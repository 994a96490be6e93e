"""The flash backward kernels' four metrics (``flash_bwd_roofline_pct``,
``full_flash_bwd_s16k_roofline_pct``, ``swa_flash_bwd_roofline_pct``,
``swa_flash_bwd_ms_per_step``): the numbers in each metric's file held to
the arithmetic it states, from the cell's configuration and traffic files —
five matmuls over the pairs the forward's two cover, and q, k, v, out, g
read and dq, dk, dv written once — and the patterns held to the op names
the program gives its four attention kernels."""

import importlib
import inspect
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

SPEC = manifest.load(REPO)
QWEN, TRINITY = "qwen3next_ep16_fit_s8192", "trinity_mini_ep8_fit_s16384"
FULL, BAND = "^%?flash_attention_bwd", "^%?flash_attention_window_bwd"

# metric -> cell, the forward metric over the same pairs, pattern, the
# least time the chip could take (ms, at 197 TFLOP/s)
ROOFLINES = {
    "flash_bwd_roofline_pct": (QWEN, "flash_fwd_roofline_pct", FULL, 13.95),
    "full_flash_bwd_s16k_roofline_pct": (
        TRINITY, "full_flash_fwd_s16k_roofline_pct", FULL, 27.91),
    "swa_flash_bwd_roofline_pct": (
        TRINITY, "swa_flash_fwd_roofline_pct", BAND, 26.16),
}


def _entry(metric):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _forward_work(metric):
    """FLOPs and bytes of the forward over the same pairs, from the cell's
    files by its family's function."""
    from benchmark.families import afmoe, qwen3_next
    cell = manifest.cell(SPEC, ROOFLINES[metric][0])
    work = {"flash_bwd_roofline_pct": qwen3_next.flash_fwd_work,
            "full_flash_bwd_s16k_roofline_pct": afmoe.full_flash_fwd_work,
            "swa_flash_bwd_roofline_pct": afmoe.window_flash_fwd_work}
    return work[metric](cell.config, cell.traffic)


@pytest.mark.parametrize("metric", list(ROOFLINES))
def test_flops_are_five_matmuls_where_the_forward_counts_two(metric):
    entry = _entry(metric)
    assert entry["reader"] == "trace_kernel_roofline"
    args = entry["args"]
    forward = _forward_work(metric)
    # the forward metric's file holds the same forward number
    assert _entry(ROOFLINES[metric][1])["args"]["flops_per_step"] \
        == forward["flops"]
    assert args["flops_per_step"] == 2.5 * forward["flops"]
    for number in (args["flops_per_step"], forward["flops"]):
        assert str(int(number)) in args["work"]
    # FLOP-bound, and the time the issue worked out
    least = args["flops_per_step"] / 197e12
    assert least > args["bytes_per_step"] / 819e9
    assert abs(1e3 * least - ROOFLINES[metric][3]) < 0.01


def test_flops_from_the_cells_own_sizes():
    """... and the same numbers from the shapes, not through the family."""
    cell = manifest.cell(SPEC, QWEN)
    m, t = cell.config["model"], cell.traffic["seq_len"]
    layers = m["n_layers"] // m["full_attention_interval"]
    assert 5 * t * t * m["head_dim"] * m["num_heads"] \
        * cell.traffic["global_batch"] * layers == 2748779069440 \
        == _entry("flash_bwd_roofline_pct")["args"]["flops_per_step"]
    cell = manifest.cell(SPEC, TRINITY)
    t, window = cell.traffic["seq_len"], 2048
    assert (t, cell.traffic["global_batch"]) == (16384, 1)
    triangle = t * (t + 1) // 2
    band = sum(min(i + 1, window) for i in range(t))
    assert (triangle, band) == (134225920, 31458304)
    heads, dim = 32, 128
    assert 5 * 2 * triangle * dim * heads == 5497893683200 == _entry(
        "full_flash_bwd_s16k_roofline_pct")["args"]["flops_per_step"]
    assert 5 * 2 * band * dim * heads * 4 == 5154128527360 == _entry(
        "swa_flash_bwd_roofline_pct")["args"]["flops_per_step"]


@pytest.mark.parametrize("metric", list(ROOFLINES))
def test_bytes_are_five_reads_and_three_writes(metric):
    """q, out, g, dq at the query heads and k, v, dk, dv at the kv heads,
    each once: twice what the forward moves (q, out | k, v)."""
    args = _entry(metric)["args"]
    forward = _forward_work(metric)
    assert args["bytes_per_step"] == 2 * forward["bytes"]
    assert str(int(forward["bytes"])) in args["work"]


def test_the_time_metric_reads_the_banded_backward():
    assert _entry("swa_flash_bwd_ms_per_step") == {
        "reader": "trace_ops_matching",
        "args": {"stat": "ms_per_execution", "pattern": BAND}}


def test_the_entries_are_kernels_metrics_of_their_cells():
    entries = {e["name"]: e for e in SPEC["per_layer"]}
    cells = dict({m: v[0] for m, v in ROOFLINES.items()},
                 swa_flash_bwd_ms_per_step=TRINITY)
    for name, cell in cells.items():
        unit, better = ("ms", "lower") if name.endswith("ms_per_step") \
            else ("%", "higher")
        assert entries[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "train_samples_per_s_chip", "workloads": [cell]}


def test_no_pattern_matches_another_kernels_op():
    """Four kernels, four names; each pattern finds its own op in an HLO
    line and in a bare name, and none of the other three."""
    flash = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")
    names = set(re.findall(r'"(flash_attention_\w+)"',
                           inspect.getsource(flash._fwd_call)
                           + inspect.getsource(flash._bwd_call)))
    assert names == {"flash_attention_fwd", "flash_attention_window_fwd",
                     "flash_attention_bwd", "flash_attention_window_bwd"}
    patterns = {
        "flash_attention_fwd":
            _entry("flash_fwd_roofline_pct")["args"]["pattern"],
        "flash_attention_window_fwd":
            _entry("swa_flash_fwd_roofline_pct")["args"]["pattern"],
        "flash_attention_bwd": FULL,
        "flash_attention_window_bwd": BAND,
    }
    for metric, (_, _, pattern, _) in ROOFLINES.items():
        assert _entry(metric)["args"]["pattern"] == pattern
    assert _entry("full_flash_fwd_s16k_roofline_pct")["args"]["pattern"] \
        == patterns["flash_attention_fwd"]
    for name, pattern in patterns.items():
        rx = re.compile(pattern)
        for op in names:
            hits = [bool(rx.search(form)) for form in (
                op, "%" + op, f"%{op}.3 = (bf16[32,16384,128]")]
            assert hits == [op == name] * 3, (pattern, op)
        assert not rx.search("%fusion." + name)
