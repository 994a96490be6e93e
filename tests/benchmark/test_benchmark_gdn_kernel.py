"""``gdn_kernel_roofline_pct``: the numbers in the metric's file held to
the arithmetic they state, from the cell's configuration and traffic files —
the recurrence's term of the family's ``flops_per_sample`` and q, k, v, o,
g, beta and their gradients moved once — and the entry that reads the two
delta-rule kernels by their op names."""

import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

SPEC = manifest.load(REPO)
CELL = "qwen3next_ep16_fit_s8192"
METRIC = "gdn_kernel_roofline_pct"


def _args():
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           METRIC + ".json")) as f:
        entry = json.load(f)
    assert entry["reader"] == "trace_kernel_roofline"
    return entry["args"]


def _sizes():
    cell = manifest.cell(SPEC, CELL)
    m, traffic = cell.config["model"], cell.traffic
    full = m["n_layers"] // m["full_attention_interval"]
    return m, traffic["global_batch"], traffic["seq_len"], \
        m["n_layers"] - full


def test_flops_are_the_recurrences_term_of_flops_per_sample():
    from benchmark.families import qwen3_next
    cell = manifest.cell(SPEC, CELL)
    m, batch, t, layers = _sizes()
    # forget, recall, write, read: four d_k x d_v products a value head and
    # position, forward and backward (3 x)
    want = 3 * 4 * 2 * m["linear_k_head_dim"] * m["linear_v_head_dim"] \
        * m["linear_num_v_heads"] * t * batch * layers
    assert want == 618475290624
    assert _args()["flops_per_step"] == want
    # ... which is what flops_per_sample counts beyond matmuls and attention
    attn = 3 * 2 * t * t * m["head_dim"] * m["num_heads"] \
        * (m["n_layers"] - layers)
    rest = 6.0 * qwen3_next.matmul_params_per_token(m) * t + attn
    whole = qwen3_next.flops_per_sample(cell.config, cell.traffic)
    assert abs((whole - rest) * batch - want) < 1e-6 * want


def test_bytes_are_every_operand_and_gradient_moved_once():
    m, batch, t, layers = _sizes()
    item = {"bfloat16": 2, "float32": 4}[m["dtype"]]
    keys = batch * t * m["linear_num_k_heads"] * m["linear_k_head_dim"] * item
    values = batch * t * m["linear_num_v_heads"] * m["linear_v_head_dim"] \
        * item
    gates = batch * t * m["linear_num_v_heads"] * 4          # g, beta: f32
    forward = 2 * keys + 2 * values + 2 * gates              # q k | v o
    backward = forward + values + 2 * keys + values + 2 * gates
    assert (forward, backward) == (406847488, 813694976)
    args = _args()
    assert args["bytes_per_step"] == (forward + backward) * layers
    for number in (forward, backward):
        assert str(number) in args["work"]
    # under the chip's peaks: bytes-bound, a few milliseconds of a step
    least = max(args["flops_per_step"] / 197e12,
                args["bytes_per_step"] / 819e9)
    assert least == args["bytes_per_step"] / 819e9
    assert 3e-3 < least < 6e-3


def test_the_entry_reads_both_kernels_by_name_in_the_cell():
    entry = next(e for e in SPEC["per_layer"] if e["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "linear attention",
        "moves": "train_samples_per_s_chip", "workloads": [CELL]}
    assert SPEC["per_layer"][-1]["name"] == METRIC           # appended last
    rx = re.compile(_args()["pattern"])
    for name in ("%gated_delta_rule_fwd.1 = (bf16[2,8192,4096]",
                 "gated_delta_rule_bwd", "%gated_delta_rule_fwd"):
        assert rx.search(name)
    for name in ("%flash_attention_fwd", "%ragged-dot-none.3",
                 "%fusion.gated_delta_rule_fwd"):
        assert not rx.search(name)


def test_the_pattern_names_the_kernels_the_program_calls():
    import importlib
    import inspect
    kernels = importlib.import_module(
        "analytics_zoo_tpu.ops.gated_delta_rule")
    names = set(re.findall(r'name="(gated_delta_rule_\w+)"',
                           inspect.getsource(kernels)))
    assert names == {"gated_delta_rule_fwd", "gated_delta_rule_bwd"}
    rx = re.compile(_args()["pattern"])
    assert all(rx.search("%" + n + ".2") for n in names)
