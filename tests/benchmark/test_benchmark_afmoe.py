"""The cell ``trinity_mini_ep8_fit_s16384`` and what came with it: the cell
rehearsed end to end through the driver's command (one process), the FLOPs
function and the three work functions held to their arithmetic and to the
numbers in the metric files, the entries ``BENCHMARK.json`` gained, and the
new metrics reported in the new cell and in no other."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

SPEC = manifest.load(REPO)
CELL = "trinity_mini_ep8_fit_s16384"
CONFIG = "trinity_mini_ep8"
MIX = "fit_lm_tokens_s16384_b1"
ROOFLINES = {"swa_flash_fwd_roofline_pct": "window_flash_fwd_work",
             "full_flash_fwd_s16k_roofline_pct": "full_flash_fwd_work",
             "afmoe_ragged_dot_roofline_pct": "ragged_dot_work"}
COUNTERS = {"afmoe_dropped_pairs_per_step", "afmoe_load_max_over_mean",
            "afmoe_expert_bias_abs_max", "afmoe_local_pair_share"}
NEW_METRICS = set(ROOFLINES) | COUNTERS | {"swa_flash_fwd_ms_per_step",
                                           "full_flash_fwd_ms_per_step"}
T, W, H, KV, D = 16384, 2048, 32, 4, 128


def _args(metric):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(SPEC, CELL)


@pytest.fixture(scope="module")
def rehearsal():
    """ONE traced rehearsal of the cell through the driver's command."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", CELL, "--seed",
         "3100000003", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1]), proc.stderr


def test_the_cell_rehearses_to_a_correct_line(rehearsal):
    line, stderr = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    steps = manifest.cell(SPEC, CELL, rehearse=True).traffic[
        "steps_per_epoch"]
    assert line["attempted"] > 0 and line["attempted"] % steps == 0
    # the float32 rehearsal sits on the reference (window 16 in a row of 64)
    found = re.search(r"differs from the float32 reference by ([\d.e+-]+)",
                      stderr)
    assert found and float(found.group(1)) < 1e-4
    overlay = manifest.cell(SPEC, CELL, rehearse=True)
    assert overlay.config["model"]["window"] < overlay.traffic["seq_len"]


def test_the_traced_rehearsal_reports_the_expert_layers_state(rehearsal):
    """The registry's series reach the line (the device-trace metrics need
    a TPU: their readers find nothing on the CPU and are left out)."""
    got = rehearsal[0]["metrics"]
    assert got["afmoe_dropped_pairs_per_step"]["value"] == 0.0
    assert 1.0 <= got["afmoe_load_max_over_mean"]["value"] < 4.0
    # a level of a bias that moves by at most 0.002 a step
    steps = rehearsal[0]["attempted"]
    assert 0.0 < got["afmoe_expert_bias_abs_max"]["value"] <= 0.002 * (
        steps + 4)
    model = manifest.cell(SPEC, CELL, rehearse=True).config["model"]
    # the share of the pairs that land on held experts: a half when the
    # router is balanced, more once training has taught it that only held
    # experts answer (PERF.md section 6, PR 31)
    assert model["experts_held"] / model["num_experts"] - 0.15 \
        < got["afmoe_local_pair_share"]["value"] <= 1.0
    assert NEW_METRICS & set(got) == COUNTERS
    assert "first_step_s" in got and "epoch_gap_ms" in got


def test_the_new_metrics_are_reported_in_the_new_cell_and_in_no_other():
    for w in SPEC["workloads"]:
        names = {m.name for m in manifest.cell(SPEC, w["name"]).per_layer}
        assert (NEW_METRICS <= names) == (w["name"] == CELL), w["name"]
        assert not (NEW_METRICS & names) or w["name"] == CELL
    # ... and the Qwen cell's kernels' and counters' entries stay its own
    names = {m.name for m in manifest.cell(SPEC, CELL).per_layer}
    assert not names & {"moe_local_pair_share", "moe_dropped_pairs_per_step",
                        "flash_fwd_roofline_pct", "ragged_dot_roofline_pct",
                        "gdn_scope_ms_per_step", "gdn_kernel_roofline_pct"}


def test_the_manifest_gained_one_configuration_and_one_cell(cell):
    assert manifest.problems(SPEC, REPO) == []
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    work = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, MIX, 1)
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == CONFIG] == [CELL]
    traffic = cell.traffic
    assert (traffic["job"], traffic["seq_len"], traffic["global_batch"],
            traffic["grad_accum"], traffic["sharding"],
            traffic["steps_per_epoch"], traffic["check_rows"],
            traffic["feed"]) == ("train_fit", T, 1, 1, "dp", 8, 1, {})
    assert traffic["trace_seconds"] == 4.0          # three steps of 1.33 s
    assert cell.config["family"] == "afmoe"
    for key in ("assumed", "not_built", "deployment", "published"):
        assert cell.config[key], key
    assert {"bias_rule", "gate_layout", "initialisers", "optimizer"} <= set(
        cell.config["assumed"])


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_each_new_entry_is_appended_for_the_cell_alone(metric):
    names = [e["name"] for e in SPEC["per_layer"]]
    entry = SPEC["per_layer"][names.index(metric)]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_per_s_chip"
    assert names.index(metric) > names.index("gdn_kernel_roofline_pct")
    assert entry["layer"] == ("expert layer" if metric in COUNTERS
                              else "kernels")
    if metric.endswith("_roofline_pct"):
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "%", "higher", "device_trace")


def test_flops_per_sample_is_the_issues_arithmetic(cell):
    from benchmark.families import afmoe
    m = afmoe._model(cell.config)
    assert afmoe.band_pairs(T, W) == T * W - W * (W - 1) // 2 == 31_458_304
    assert afmoe.band_pairs(T, T) == T * (T + 1) // 2 == 134_225_920
    assert afmoe.band_pairs(100, 2048) == 100 * 101 // 2
    assert afmoe.attention_pairs(m, T) == {"sliding": 4 * 31_458_304,
                                           "full": 134_225_920}
    attn = 2048 * H * 2 * D + 2 * 2048 * KV * D + H * D * 2048
    experts = 2048 * 128 + 3 * 2048 * 1024 + 8 * 16 / 128 * 3 * 2048 * 1024
    per_token = 5 * attn + 3 * 2048 * 6144 + 4 * experts + 25024 * 2048
    assert afmoe.matmul_params_per_token(m) == per_token == 276_692_992
    pairs = 4 * 31_458_304 + 134_225_920
    want = 6.0 * per_token * T + 3 * 2 * 2 * D * H * pairs
    assert afmoe.flops_per_sample(cell.config, cell.traffic) == want
    assert abs(want - 39.98e12) < 0.01e12           # ~40 TFLOP a step
    assert abs(6.0 * per_token * T - 27.2e12) < 0.1e12
    # the band, not the triangle, on the sliding layers: the triangle on
    # all five would count 1.65x the attention
    triangle = 3 * 2 * 2 * D * H * 5 * 134_225_920
    assert triangle / (want - 6.0 * per_token * T) > 1.6


@pytest.mark.parametrize("metric,work", sorted(ROOFLINES.items()))
def test_roofline_files_hold_what_the_familys_function_gives(metric, work,
                                                             cell):
    from benchmark.families import afmoe
    entry = _args(metric)
    assert entry["reader"] == "trace_kernel_roofline"
    args = entry["args"]
    want = getattr(afmoe, work)(cell.config, cell.traffic)
    assert args["flops_per_step"] == want["flops"]
    assert args["bytes_per_step"] == want["bytes"]
    assert work in args["work"] and CONFIG in args["work"]
    # under the chip's peaks the least time is ~10 ms of a step
    least = max(want["flops"] / 197e12, want["bytes"] / 819e9)
    assert 5e-3 < least < 20e-3


def test_the_work_functions_are_their_arithmetic(cell):
    from benchmark.families import afmoe
    rows = 2 * T * D * (H + KV + KV + H)             # q, k, v, out in bf16
    window = afmoe.window_flash_fwd_work(cell.config, cell.traffic)
    assert window == {"flops": 2 * 2 * D * H * 4 * 31_458_304,
                      "bytes": 4 * rows}
    full = afmoe.full_flash_fwd_work(cell.config, cell.traffic)
    assert full == {"flops": 2 * 2 * D * H * 134_225_920, "bytes": rows}
    ragged = afmoe.ragged_dot_work(cell.config, cell.traffic)
    pairs = T * 8 * 16 / 128                          # 16,384 a layer
    assert pairs == 16384
    assert ragged["flops"] == 4 * 3 * pairs * 2 * 3 * 2048 * 1024
    assert ragged["bytes"] == 4 * (3 * 16 * 3 * 2048 * 1024 * 2
                                   + 2 * pairs * (2 * 2048 + 3 * 1024) * 2)


def test_the_patterns_tell_the_two_kernels_apart():
    import importlib
    import inspect
    kernels = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")
    names = set(re.findall(r'"(flash_attention_\w*fwd)"',
                           inspect.getsource(kernels)))
    assert names == {"flash_attention_fwd", "flash_attention_window_fwd"}
    window = [re.compile(_args(m)["args"]["pattern"]) for m in (
        "swa_flash_fwd_roofline_pct", "swa_flash_fwd_ms_per_step")]
    full = [re.compile(_args(m)["args"]["pattern"]) for m in (
        "full_flash_fwd_s16k_roofline_pct", "full_flash_fwd_ms_per_step")]
    for rx in window:
        assert rx.search("%flash_attention_window_fwd.3 = (bf16[32,16384")
        assert not rx.search("%flash_attention_fwd.1")
    for rx in full:
        assert rx.search("%flash_attention_fwd.1 = (bf16[32,16384,128]")
        assert rx.search("flash_attention_fwd")
        assert not rx.search("%flash_attention_window_fwd.3")
    ragged = re.compile(_args("afmoe_ragged_dot_roofline_pct")["args"][
        "pattern"])
    assert ragged.search("%ragged-dot-none.3")
    assert not ragged.search("%ragged-dot-metadata.1")
    assert _args("afmoe_expert_bias_abs_max") == {
        "reader": "registry_hist",
        "args": {"series": "moe.expert_bias_abs_max", "stat": "mean"}}
