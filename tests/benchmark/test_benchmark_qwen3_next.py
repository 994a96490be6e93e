"""The cell ``qwen3next_ep16_fit_s8192`` and what came with it: the cell
rehearsed end to end through the driver's command, the per-kernel roofline
reader on the recorded synthetic trace, and the numbers in the roofline
metrics' files held to the family's own functions."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, window, xplane  # noqa: E402
from benchmark.layer_metrics import Reading, trace_kernel_roofline  # noqa: E402

SPEC = manifest.load(REPO)
CELL = "qwen3next_ep16_fit_s8192"
NEW_METRICS = {"moe_local_pair_share", "moe_load_max_over_mean",
               "moe_dropped_pairs_per_step", "pallas_ms_per_step",
               "flash_fwd_roofline_pct", "ragged_dot_roofline_pct"}


def _rehearse(trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", CELL, "--seed",
         "2700000003", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_cell_rehearses_to_a_correct_line_with_both_end_to_end_metrics():
    line = _rehearse(0)
    assert line["correct"] is True and line["failed"] == 0
    steps = manifest.cell(SPEC, CELL, rehearse=True).traffic[
        "steps_per_epoch"]
    assert line["attempted"] > 0 and line["attempted"] % steps == 0
    assert set(line["metrics"]) == {"train_samples_per_s_chip", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_rehearsal_reports_the_expert_layers_counters():
    """The registry counters reach the line (the device-trace metrics need
    a TPU: their readers find nothing on the CPU and are left out)."""
    line = _rehearse(1)
    assert line["correct"] is True
    got = line["metrics"]
    model = manifest.cell(SPEC, CELL, rehearse=True).config["model"]
    share = got["moe_local_pair_share"]["value"]
    assert abs(share - model["experts_held"] / model["num_experts"]) < 0.15
    assert got["moe_dropped_pairs_per_step"]["value"] == 0.0
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] < 4.0
    assert NEW_METRICS & set(got) == {
        "moe_local_pair_share", "moe_load_max_over_mean",
        "moe_dropped_pairs_per_step"}
    assert "first_step_s" in got and "epoch_gap_ms" in got


def test_the_new_metrics_are_reported_in_the_new_cell_and_in_no_other():
    for w in SPEC["workloads"]:
        names = {m.name for m in manifest.cell(SPEC, w["name"]).per_layer}
        assert (NEW_METRICS <= names) == (w["name"] == CELL), w["name"]
        assert not (NEW_METRICS & names) or w["name"] == CELL
    entry = next(c for c in SPEC["configs"]
                 if c["name"] == "qwen3_next_80b_a3b_ep16")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


@pytest.fixture(scope="module")
def reading():
    from jax.profiler import ProfileData
    path = os.path.join(REPO, "benchmark", "testdata",
                        "two_chips_synthetic.xplane.textproto")
    with open(path) as f:
        trace = xplane.from_profile(ProfileData.from_text_proto(f.read()))
    return Reading(result=window.Result(0, 0, [], {}),
                   device={"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 2}, trace=trace)


def test_kernel_roofline_is_least_time_over_the_matched_ops_time(reading):
    # fusion.2 runs [250,550) and [650,800) of every whole step: 450 us
    args = {"pattern": r"^%?fusion\.2", "flops_per_step": 197e12 * 90e-6,
            "bytes_per_step": 0.0}
    assert trace_kernel_roofline.read(args, reading) == pytest.approx(20.0)
    # the bytes bind when they take longer than the FLOPs
    args["bytes_per_step"] = 819e9 * 225e-6
    assert trace_kernel_roofline.read(args, reading) == pytest.approx(50.0)


def test_kernel_roofline_finds_nothing_where_there_is_nothing(reading):
    args = {"pattern": "^%?flash_attention_fwd", "flops_per_step": 1e12,
            "bytes_per_step": 1e9}
    assert trace_kernel_roofline.read(args, reading) is None  # no such op
    cpu = Reading(result=reading.result, trace=reading.trace,
                  device=dict(reading.device, platform="cpu"))
    args["pattern"] = "fusion"
    assert trace_kernel_roofline.read(args, cpu) is None
    assert trace_kernel_roofline.read(
        args, Reading(result=reading.result, device=reading.device)) is None
    with pytest.raises(KeyError):  # an unknown chip has no peak to share
        trace_kernel_roofline.read(args, Reading(
            result=reading.result, trace=reading.trace,
            device=dict(reading.device, kind="TPU v9")))


@pytest.mark.parametrize("metric,work", [
    ("flash_fwd_roofline_pct", "flash_fwd_work"),
    ("ragged_dot_roofline_pct", "ragged_dot_work")])
def test_roofline_files_hold_what_the_familys_function_gives(metric, work):
    from benchmark.families import qwen3_next
    cell = manifest.cell(SPEC, CELL)
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        args = json.load(f)["args"]
    want = getattr(qwen3_next, work)(cell.config, cell.traffic)
    assert args["flops_per_step"] == want["flops"]
    assert args["bytes_per_step"] == want["bytes"]
    assert work in args["work"]
    # under the chip's peaks the least time is a few milliseconds of a step
    least = max(want["flops"] / 197e12, want["bytes"] / 819e9)
    assert 1e-3 < least < 20e-3


def test_flash_work_is_the_causal_half_of_one_forward():
    from benchmark.families import qwen3_next
    cell = manifest.cell(SPEC, CELL)
    work = qwen3_next.flash_fwd_work(cell.config, cell.traffic)
    t, d, h, kv, b = 8192, 256, 16, 2, 2
    assert work["flops"] == 2 * (2 * t * t * d) * h * b / 2
    assert work["bytes"] == 2 * b * t * d * (h + kv + kv + h)
