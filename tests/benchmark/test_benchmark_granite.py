"""The cell ``granite4h_micro_fit_s8192`` and what came with it: the cell
rehearsed end to end through the driver's command (one process), the FLOPs
function and the work functions held to their arithmetic and to the numbers
in the metric files, the configuration's ``reduced`` / ``published`` / model
arguments held to each other and to the catalog row's widths, the entries
``BENCHMARK.json`` gained, and the new metrics reported in the new cell and
in no other."""

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
CELL = "granite4h_micro_fit_s8192"
CONFIG = "granite_4_0_h_micro_pp4"
MIX = "fit_lm_tokens_s8192_b1"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")
ROOFLINES = {"ssd_roofline_pct": "ssd_work",
             "granite_flash_fwd_roofline_pct": "flash_fwd_work",
             "granite_flash_bwd_roofline_pct": "flash_bwd_work"}
COUNTERS = {"ssm_padded_tokens_per_step", "ssm_chunk_decay_exponent_max",
            "ssm_state_abs_max"}
NEW_METRICS = set(ROOFLINES) | COUNTERS | {"ssd_scope_ms_per_step"}
STATE_SPACE = COUNTERS | {"ssd_scope_ms_per_step", "ssd_roofline_pct"}
T, H, P, N, HEADS, KV, D = 8192, 64, 64, 128, 32, 8, 64
PAIRS = T * (T + 1) // 2


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
         "3300000003", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1]), proc.stderr


def test_the_cell_rehearses_to_a_correct_line(rehearsal):
    line, stderr = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    overlay = manifest.cell(SPEC, CELL, rehearse=True)
    steps = overlay.traffic["steps_per_epoch"]
    assert line["attempted"] > 0 and line["attempted"] % steps == 0
    # the float32 rehearsal sits on the token-by-token reference
    found = re.search(r"differs from the float32 reference by ([\d.e+-]+)",
                      stderr)
    assert found and float(found.group(1)) < 1e-4
    # several chunks and a padded one: 64 = 24 + 24 + 16 (+ 8)
    seq, chunk = overlay.traffic["seq_len"], overlay.config["model"]["chunk"]
    assert seq > 2 * chunk and seq % chunk


def test_the_traced_rehearsal_reports_the_scans_state(rehearsal):
    """The registry's series reach the line (the device-trace metrics need
    a TPU: their readers find nothing on the CPU and are left out)."""
    got = rehearsal[0]["metrics"]
    overlay = manifest.cell(SPEC, CELL, rehearse=True)
    seq, chunk = overlay.traffic["seq_len"], overlay.config["model"]["chunk"]
    mamba = overlay.config["model"]["layer_types"].count("mamba")
    assert got["ssm_padded_tokens_per_step"]["value"] == (-seq % chunk) * mamba
    # dt up to softplus(.) of a few tenths, A up to 16, 24 positions
    assert 0.0 < got["ssm_chunk_decay_exponent_max"]["value"] < 16 * 24
    assert 0.0 < got["ssm_state_abs_max"]["value"] < 10.0
    # the device-trace metrics' readers find nothing on the CPU, a
    # module's time excepted: the scan's scope reads here too
    assert NEW_METRICS & set(got) == COUNTERS | {"ssd_scope_ms_per_step"}
    assert got["ssd_scope_ms_per_step"]["value"] > 0
    assert "first_step_s" in got and "epoch_gap_ms" in got


def test_the_new_metrics_are_reported_in_the_new_cell_and_in_no_other():
    for w in SPEC["workloads"]:
        names = {m.name for m in manifest.cell(SPEC, w["name"]).per_layer}
        assert (NEW_METRICS <= names) == (w["name"] == CELL), w["name"]
        assert not (NEW_METRICS & names) or w["name"] == CELL
    # ... and the other decoders' kernels' and counters' entries stay theirs
    names = {m.name for m in manifest.cell(SPEC, CELL).per_layer}
    assert not names & {"moe_local_pair_share", "flash_fwd_roofline_pct",
                        "flash_bwd_roofline_pct", "gdn_scope_ms_per_step",
                        "gdn_kernel_roofline_pct", "afmoe_local_pair_share",
                        "full_flash_fwd_s16k_roofline_pct"}
    # every metric without a list is read here as in every cell
    unlisted = {e["name"] for e in SPEC["per_layer"] if "workloads" not in e}
    assert unlisted <= names and {"step_device_ms", "train_mfu_pct",
                                  "hbm_peak_gb"} <= unlisted


def test_the_manifest_gained_one_configuration_and_one_cell(cell):
    assert manifest.problems(SPEC, REPO) == []
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert entry["source"] == cell.config["source"] == SOURCE
    work = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, MIX, 1)
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == CONFIG] == [CELL]
    traffic = cell.traffic
    assert (traffic["job"], traffic["seq_len"], traffic["global_batch"],
            traffic["grad_accum"], traffic["sharding"],
            traffic["steps_per_epoch"], traffic["check_rows"],
            traffic["feed"], traffic["trace_after_share"]) == (
        "train_fit", T, 1, 1, "dp", 8, 1, {}, 0.4)
    assert traffic["trace_seconds"] >= 3.0
    assert cell.config["family"] == "granite_hybrid"
    for key in ("assumed", "not_built", "deployment", "published"):
        assert cell.config[key], key
    assert {"source_of_equations", "model", "attention", "mamba",
            "initialisers", "optimizer", "recompute"} <= set(
        cell.config["assumed"])


def test_the_configuration_agrees_with_itself_and_with_the_catalog(cell):
    config = cell.config
    pub, m = config["published"], config["model"]
    for key, value in pub.items():
        assert config[key] == value or key in config["reduced"], key
    assert (pub["num_hidden_layers"], config["num_hidden_layers"],
            m["n_layers"]) == (40, 10, 10)
    assert (pub["vocab_size"], config["vocab_size"], m["vocab_size"]) == (
        100352, 12544, 12544)
    assert config["layer_types"] == pub["layer_types"]
    assert m["layer_types"] == pub["layer_types"][6:16]
    # the catalog row's widths, none of them cut
    assert (pub["hidden_size"], pub["mamba_n_heads"], pub["mamba_d_head"],
            pub["mamba_d_state"], pub["mamba_d_conv"], pub["mamba_n_groups"],
            pub["mamba_chunk_size"], pub["mamba_expand"],
            pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["shared_intermediate_size"]) == (
        2048, 64, 64, 128, 4, 1, 256, 2, 32, 8, 8192)
    assert (pub["embedding_multiplier"], pub["attention_multiplier"],
            pub["residual_multiplier"], pub["logits_scaling"],
            pub["tie_word_embeddings"]) == (12, 0.015625, 0.22, 8, True)
    assert (m["hidden_size"], m["mamba_heads"], m["mamba_head_dim"],
            m["mamba_state"], m["mamba_conv_kernel"], m["mamba_groups"],
            m["chunk"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["ff_units"]) == (2048, 64, 64, 128, 4, 1, 256, 32, 8, 64, 8192)
    assert (m["embedding_multiplier"], m["attention_multiplier"],
            m["residual_multiplier"], m["logits_scaling"],
            m["tie_embeddings"]) == (12, 0.015625, 0.22, 8, True)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert row["config"] == pub and row["source_url"] == SOURCE


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_each_new_entry_is_for_the_cell_alone(metric):
    entry = next(e for e in SPEC["per_layer"] if e["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_per_s_chip"
    assert entry["layer"] == ("state-space" if metric in STATE_SPACE
                              else "kernels")
    if metric.endswith("_roofline_pct"):
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "%", "higher", "device_trace")
    if metric in COUNTERS:
        assert entry["source"] == "program_counter"


def test_flops_per_sample_is_its_terms_written_out(cell):
    from benchmark.families import granite_hybrid as fam
    m = fam._model(cell.config)
    assert fam.causal_pairs(T) == PAIRS == 33_558_528
    mamba = 2048 * (4096 + 4352 + 64) + 4352 * 4 + 4096 * 2048
    attn = 2048 * HEADS * D + 2 * 2048 * KV * D + HEADS * D * 2048
    per_token = 9 * mamba + attn + 10 * 3 * 2048 * 8192 + 12544 * 2048
    # the issue's 771,883,008 and the conv's 4 multiply-adds a channel
    assert fam.matmul_params_per_token(m) == per_token == 772_039_680 \
        == 771_883_008 + 9 * 4352 * 4
    assert fam.recurrence_flops_per_token(m) == 2 * 2 * P * N * H * 9
    want = 6.0 * per_token * T + 3 * 2 * 2 * D * HEADS * PAIRS \
        + 3.0 * 2 * 2 * P * N * H * 9 * T
    assert fam.flops_per_sample(cell.config, cell.traffic) == want
    assert abs(want - 39.24e12) < 0.01e12           # ~39.2 TFLOP a step
    assert abs(3 * 2 * 2 * D * HEADS * PAIRS - 0.825e12) < 0.001e12
    assert 3 * 2 * 2 * P * N * H * 9 * T == 463_856_467_968


@pytest.mark.parametrize("metric,work", sorted(ROOFLINES.items()))
def test_roofline_files_hold_what_the_familys_function_gives(metric, work,
                                                             cell):
    from benchmark.families import granite_hybrid as fam
    entry = _args(metric)
    assert entry["reader"] == "trace_kernel_roofline"
    args = entry["args"]
    want = getattr(fam, work)(cell.config, cell.traffic)
    assert args["flops_per_step"] == want["flops"]
    assert args["bytes_per_step"] == want["bytes"]
    assert work in args["work"] and CONFIG in args["work"]
    # under the chip's peaks the least time is a few ms of a step
    least = max(want["flops"] / 197e12, want["bytes"] / 819e9)
    assert 1e-3 < least < 6e-3


def test_the_work_functions_are_their_arithmetic(cell):
    from benchmark.families import granite_hybrid as fam
    ssd = fam.ssd_work(cell.config, cell.traffic)
    forward = 2 * (4096 + 4096 + 128 + 128) + 4 * 64        # x y B C dt
    backward = forward + 2 * (4096 + 4096 + 128 + 128) + 4 * 64
    assert (forward, backward) == (17_152, 34_304)
    assert ssd == {"flops": 463_856_467_968.0,
                   "bytes": float((forward + backward) * T * 9)}
    assert ssd["bytes"] / 819e9 > ssd["flops"] / 197e12      # bytes-bound
    rows = 2 * T * D * (HEADS + KV + KV + HEADS)             # q, k, v, out
    fwd = fam.flash_fwd_work(cell.config, cell.traffic)
    assert fwd == {"flops": 2 * 2 * D * HEADS * PAIRS, "bytes": rows}
    assert fwd["flops"] == 274_911_461_376
    bwd = fam.flash_bwd_work(cell.config, cell.traffic)
    assert bwd == {"flops": 2.5 * fwd["flops"], "bytes": 2.0 * rows}


def test_the_patterns_read_the_scan_and_the_two_kernels():
    scope = _args("ssd_scope_ms_per_step")
    assert (scope["reader"], scope["args"]["stat"]) == (
        "trace_scope_time", "ms_per_step")
    rx = re.compile(scope["args"]["scope"])
    for path in ("remat_0/mamba/ssd", "remat_8/mamba/ssd/mamba2_ssd_fwd"):
        assert rx.search(path)
    for path in ("remat_0/mamba/in_proj", "remat_0/mamba/norm",
                 "remat_9/attn", "ssd_like"):
        assert not rx.search(path)
    rx = re.compile(_args("ssd_roofline_pct")["args"]["pattern"])
    assert rx.search("%mamba2_ssd_fwd.3 = (bf16[1,8192,64,64]")
    assert rx.search("mamba2_ssd_bwd")
    # a chunk's [Q, Q] term of all heads, and the state of all heads
    assert rx.search("%convolution_convert_fusion = bf16[32,64,256,256]"
                     " fusion(f32[1,32,256,1,64,64] %bitcast.4929")
    assert rx.search("%fusion.1421 = f32[32,1,1,64,64,128] fusion("
                     "f32[32,256,64,64] %bitcast.5053")
    assert rx.search("%fusion.9 = f32[64,64,128] fusion(")
    assert not rx.search("%flash_attention_fwd.1 = (bf16[32,8192,128]")
    assert not rx.search("%fusion.7 = bf16[8192,8512] fusion(")
    fwd = re.compile(_args("granite_flash_fwd_roofline_pct")["args"][
        "pattern"])
    bwd = re.compile(_args("granite_flash_bwd_roofline_pct")["args"][
        "pattern"])
    assert fwd.search("%flash_attention_fwd.1") and not fwd.search(
        "%flash_attention_bwd.1")
    assert bwd.search("%flash_attention_bwd.1") and not bwd.search(
        "%flash_attention_fwd.1")
    assert not fwd.search("%flash_attention_window_fwd.3")
    assert _args("ssm_padded_tokens_per_step") == {
        "reader": "registry_counter_rate",
        "args": {"series": "ssm.tokens_padded", "per": "train.steps"}}
    for name in ("chunk_decay_exponent_max", "state_abs_max"):
        assert _args("ssm_" + name) == {
            "reader": "registry_hist",
            "args": {"series": "ssm." + name, "stat": "mean"}}
