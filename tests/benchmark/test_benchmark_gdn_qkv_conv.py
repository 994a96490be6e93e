"""``gdn_qkv_conv_roofline_pct``: the numbers in the metric's file held to
the arithmetic they state, from the cell's configuration and traffic files —
the conv's columns read and q, k, v written once forward, dq, dk, dv and the
columns read and their gradient written once backward, four taps a pass —
and the pattern held to the op names the program gives its two kernels and
apart from the delta rule's."""

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
METRIC = "gdn_qkv_conv_roofline_pct"


def _file(metric=METRIC):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _sizes():
    """Elements the conv passes a layer, bytes an element, taps, layers."""
    cell = manifest.cell(SPEC, CELL)
    m, traffic = cell.config["model"], cell.traffic
    columns = 2 * m["linear_num_k_heads"] * m["linear_k_head_dim"] \
        + m["linear_num_v_heads"] * m["linear_v_head_dim"]
    layers = m["n_layers"] - m["n_layers"] // m["full_attention_interval"]
    item = {"bfloat16": 2, "float32": 4}[m["dtype"]]
    taps = m.get("linear_conv_kernel", 4)
    return (columns * traffic["seq_len"] * traffic["global_batch"], item,
            taps, layers)


def test_bytes_are_one_pass_each_way():
    elements, item, _, layers = _sizes()
    assert (elements, layers) == (134217728, 3)
    array = elements * item
    forward, backward = 2 * array, 3 * array
    assert (array, forward + backward) == (268435456, 1342177280)
    entry = _file()
    assert entry["reader"] == "trace_kernel_roofline"
    args = entry["args"]
    assert args["bytes_per_step"] == (forward + backward) * layers \
        == 4026531840.0
    for number in (elements, array, forward + backward):
        assert str(number) in args["work"]


def test_flops_are_the_taps_forward_and_for_both_gradients():
    elements, _, taps, layers = _sizes()
    a_pass = 2 * taps * elements
    assert a_pass == 1073741824
    args = _file()["args"]
    assert args["flops_per_step"] == 3 * a_pass * layers == 9663676416.0
    for number in (a_pass, 3 * a_pass):
        assert str(number) in args["work"]


def test_the_kernels_are_bytes_bound_under_the_chips_peaks():
    args = _file()["args"]
    by_bytes = args["bytes_per_step"] / 819e9
    assert by_bytes > 100 * args["flops_per_step"] / 197e12
    assert abs(1e3 * by_bytes - 4.92) < 0.01
    assert "4.92 ms" in args["work"]


def test_the_entry_is_a_linear_attention_metric_of_the_cell():
    entry = next(e for e in SPEC["per_layer"] if e["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "linear attention",
        "moves": "train_samples_per_s_chip", "workloads": [CELL]}
    # the layer's other metrics stand as they stood
    names = [e["name"] for e in SPEC["per_layer"]
             if e["layer"] == "linear attention"]
    assert names == ["gdn_kernel_roofline_pct", "gdn_scope_ms_per_step",
                     "gdn_outside_kernels_ms_per_step", METRIC,
                     "gdn_gated_norm_roofline_pct"]


def test_the_pattern_names_the_kernels_the_program_calls():
    kernels = importlib.import_module("analytics_zoo_tpu.ops.gdn_qkv_conv")
    names = set(re.findall(r'name="(\w+)"', inspect.getsource(kernels)))
    assert names == {"gdn_qkv_conv_fwd", "gdn_qkv_conv_bwd"}
    rx = re.compile(_file()["args"]["pattern"])
    for name in names:
        for form in (name, "%" + name, f"%{name}.6 = (bf16[2,8192,2048]"):
            assert rx.search(form), form
        assert not rx.search("%fusion." + name)


@pytest.mark.parametrize("other,op", [
    ("gdn_kernel_roofline_pct", "gated_delta_rule_fwd"),
    ("gdn_kernel_roofline_pct", "gated_delta_rule_bwd"),
    ("flash_fwd_roofline_pct", "flash_attention_fwd"),
    ("flash_bwd_roofline_pct", "flash_attention_bwd")])
def test_no_pattern_takes_in_another_kernels_op(other, op):
    """The delta rule's pattern does not read the new kernels (its count of
    bytes stays true), nor the new pattern the delta rule's or flash
    attention's; ``gdn_outside_kernels_ms_per_step`` keeps taking out the
    delta rule's two only, so the new kernels' time stays inside it."""
    mine = re.compile(_file()["args"]["pattern"])
    theirs = re.compile(_file(other)["args"]["pattern"])
    for form in (op, "%" + op + ".3"):
        assert theirs.search(form) and not mine.search(form)
    for name in ("gdn_qkv_conv_fwd", "%gdn_qkv_conv_bwd.5"):
        assert not theirs.search(name)
    outside = _file("gdn_outside_kernels_ms_per_step")["args"]
    assert not re.search(outside["not_op"], "%gdn_qkv_conv_fwd.6")
    assert re.search(outside["scope"], "remat_0/gdn/conv/gdn_qkv_conv_fwd")
