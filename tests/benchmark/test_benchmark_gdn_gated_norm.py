"""``gdn_gated_norm_roofline_pct``: the numbers in the metric's file held to
the arithmetic they state, from the cell's configuration and traffic files —
o and z read and y written once forward, o, z and dy read and do and dz
written once backward, a layer's value heads at every position — and the
pattern held to the op names the program gives its two kernels and apart
from the delta rule's and the way in's."""

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
CELL = "qwen3next_ep16_fit_s8192"
METRIC = "gdn_gated_norm_roofline_pct"
FORWARD_FLOPS, BACKWARD_FLOPS = 9, 24   # an element, as the file counts them


def _file(metric=METRIC):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _sizes():
    """Elements of o (and of z) a layer, bytes an element, layers."""
    cell = manifest.cell(SPEC, CELL)
    m, traffic = cell.config["model"], cell.traffic
    columns = m["linear_num_v_heads"] * m["linear_v_head_dim"]
    layers = m["n_layers"] - m["n_layers"] // m["full_attention_interval"]
    item = {"bfloat16": 2, "float32": 4}[m["dtype"]]
    return (columns * traffic["seq_len"] * traffic["global_batch"], item,
            layers)


def test_bytes_are_one_pass_each_way():
    elements, item, layers = _sizes()
    assert (elements, layers) == (67108864, 3)
    array = elements * item
    forward, backward = 3 * array, 5 * array
    assert (array, forward, backward) == (134217728, 402653184, 671088640)
    entry = _file()
    assert entry["reader"] == "trace_kernel_roofline"
    args = entry["args"]
    assert args["bytes_per_step"] == (forward + backward) * layers \
        == 3221225472.0
    for number in (elements, array, forward, backward, forward + backward):
        assert str(number) in args["work"]


def test_flops_are_the_elementwise_work_both_ways():
    elements, _, layers = _sizes()
    a_layer = (FORWARD_FLOPS + BACKWARD_FLOPS) * elements
    assert a_layer == 2214592512
    args = _file()["args"]
    assert args["flops_per_step"] == a_layer * layers == 6643777536.0
    assert str(a_layer) in args["work"]
    assert f"{FORWARD_FLOPS} an element forward" in args["work"]
    assert f"{BACKWARD_FLOPS} backward" in args["work"]


def test_the_kernels_are_bytes_bound_under_the_chips_peaks():
    args = _file()["args"]
    by_bytes = args["bytes_per_step"] / 819e9
    assert by_bytes > 100 * args["flops_per_step"] / 197e12
    assert abs(1e3 * by_bytes - 3.93) < 0.01
    assert "3.93 ms" in args["work"]
    # six forward calls and three backward at their bytes: the ceiling
    elements, item, layers = _sizes()
    moved = layers * elements * item * (2 * 3 + 5)
    assert abs(100 * args["bytes_per_step"] / moved - 73) < 0.5
    assert "~73%" in args["work"]


def test_the_file_proposes_a_linear_attention_metric_of_the_cell():
    """The metric waited in its file (``proposed``) for a benchmark change
    to admit it, and ``BENCHMARK.json``'s entry is what the file proposed,
    for the cell alone: the fifth metric of the layer whose names
    ``test_benchmark_gdn_qkv_conv.py`` lists.  A cell tried by its files'
    names (``<config>+<traffic>``) reads it too."""
    want = {"unit": "%", "better": "higher", "source": "device_trace",
            "layer": "linear attention",
            "moves": "train_samples_per_s_chip"}
    assert _file()["proposed"] == want
    entry = next(e for e in SPEC["per_layer"] if e["name"] == METRIC)
    assert entry == dict(want, name=METRIC, workloads=[CELL])
    assert METRIC in {m.name for m in manifest.cell(SPEC, CELL).per_layer}
    tried_as = "qwen3_next_80b_a3b_ep16+fit_lm_tokens_s8192_b2"
    cell = manifest.cell(SPEC, tried_as)
    tried = next(m for m in cell.per_layer if m.name == METRIC)
    assert (tried.reader, tried.layer, tried.moves) == (
        "trace_kernel_roofline", "linear attention",
        "train_samples_per_s_chip")


def test_the_pattern_names_the_kernels_the_program_calls():
    kernels = importlib.import_module("analytics_zoo_tpu.ops.gdn_gated_norm")
    names = set(re.findall(r'name="(\w+)"', inspect.getsource(kernels)))
    assert names == {"gdn_gated_norm_fwd", "gdn_gated_norm_bwd"}
    rx = re.compile(_file()["args"]["pattern"])
    for name in names:
        for form in (name, "%" + name, f"%{name}.6 = bf16[2,8192,4096]"):
            assert rx.search(form), form
        assert not rx.search("%fusion." + name)


@pytest.mark.parametrize("other,op", [
    ("gdn_kernel_roofline_pct", "gated_delta_rule_fwd"),
    ("gdn_kernel_roofline_pct", "gated_delta_rule_bwd"),
    ("gdn_qkv_conv_roofline_pct", "gdn_qkv_conv_fwd"),
    ("gdn_qkv_conv_roofline_pct", "gdn_qkv_conv_bwd")])
def test_no_pattern_takes_in_another_kernels_op(other, op):
    """The delta rule's and the way in's patterns do not read the new
    kernels (their counts of bytes stay true), nor the new pattern theirs;
    ``gdn_outside_kernels_ms_per_step`` keeps taking out the delta rule's
    two only, so the new kernels' time stays inside it."""
    mine = re.compile(_file()["args"]["pattern"])
    theirs = re.compile(_file(other)["args"]["pattern"])
    for form in (op, "%" + op + ".3"):
        assert theirs.search(form) and not mine.search(form)
    for name in ("gdn_gated_norm_fwd", "%gdn_gated_norm_bwd.5"):
        assert not theirs.search(name)
    outside = _file("gdn_outside_kernels_ms_per_step")["args"]
    assert not re.search(outside["not_op"], "%gdn_gated_norm_fwd.6")
    assert re.search(outside["scope"], "remat_0/gdn/norm/gdn_gated_norm_fwd")
