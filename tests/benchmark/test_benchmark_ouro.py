"""The cell ``ouro26b_pp6_fit_s8192`` and what came with it: the cell
rehearsed end to end through the driver's command (one process), the FLOPs
function and the work functions held to their arithmetic and to the numbers
in the metric files, the new reader ``trace_scope_roofline``, the
configuration held to itself and to the catalog row, the entries
``BENCHMARK.json`` gained, and the new metrics reported in the new cell and
in no other."""

import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

SPEC = manifest.load(REPO)
CELL = "ouro26b_pp6_fit_s8192"
CONFIG = "ouro_2_6b_pp6"
MIX = "fit_lm_tokens_s8192_b1"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
LAYER = "looped stack and exit heads"
ROOFLINES = {"ouro_flash_fwd_roofline_pct": "flash_fwd_work",
             "ouro_flash_bwd_roofline_pct": "flash_bwd_work",
             "ouro_exit_head_roofline_pct": "exit_head_work"}
SCOPES = ("ouro_loop_scope_ms_per_step", "ouro_exit_scope_ms_per_step")
COUNTERS = {"ouro_loss_pass1": "loss_pass1", "ouro_loss_pass4": "loss_pass4",
            "ouro_expected_passes": "expected_passes"}
NEW_METRICS = set(ROOFLINES) | set(SCOPES) | set(COUNTERS)
T, H, HD, D, F, V, L, P = 8192, 16, 128, 2048, 5632, 49152, 8, 4
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
         "3900000003", "--seconds", "8", "--trace", "1", "--rehearse"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1]), proc.stderr


def test_the_cell_rehearses_to_a_correct_line(rehearsal):
    line, stderr = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    overlay = manifest.cell(SPEC, CELL, rehearse=True)
    assert line["attempted"] > 0 and \
        line["attempted"] % overlay.traffic["steps_per_epoch"] == 0
    # the float32 rehearsal sits on the reference, every pass
    found = re.search(r"differs from the float32 reference by ([\d.e+-]+)",
                      stderr)
    assert found and float(found.group(1)) < 1e-4
    m = overlay.config["model"]
    # four passes over two layers
    assert (m["total_ut_steps"], m["n_layers"]) == (4, 2)


def test_the_traced_rehearsal_reports_the_loop_and_the_exits(rehearsal):
    """The registry's series and the scopes reach the line (the rooflines
    need a TPU: their readers find nothing on the CPU)."""
    got = rehearsal[0]["metrics"]
    assert NEW_METRICS & set(got) == NEW_METRICS - set(ROOFLINES)
    # the loop is most of the step, the exits the rest of the model
    assert got["ouro_loop_scope_ms_per_step"]["value"] > \
        got["ouro_exit_scope_ms_per_step"]["value"] > 0
    assert 0.0 < got["ouro_loss_pass4"]["value"] < 6.0     # ln 128 = 4.85
    assert 0.0 < got["ouro_loss_pass1"]["value"] < 6.0
    assert 1.0 <= got["ouro_expected_passes"]["value"] <= 4.0
    assert "first_step_s" in got and "head_loss_device_pct" in got


def test_the_new_metrics_are_reported_in_the_new_cell_and_in_no_other():
    for w in SPEC["workloads"]:
        names = {m.name for m in manifest.cell(SPEC, w["name"]).per_layer}
        assert (NEW_METRICS <= names) == (w["name"] == CELL), w["name"]
        assert not (NEW_METRICS & names) or w["name"] == CELL
    names = {m.name for m in manifest.cell(SPEC, CELL).per_layer}
    assert not names & {"flash_fwd_roofline_pct", "mla_flash_fwd_roofline_pct",
                        "mtp_loss", "gdn_scope_ms_per_step"}
    # every metric without a list is read here as in every cell
    unlisted = {e["name"] for e in SPEC["per_layer"] if "workloads" not in e}
    assert unlisted <= names and {"step_device_ms", "train_mfu_pct",
                                  "hbm_peak_gb", "head_loss_device_pct"} \
        <= unlisted


def test_the_manifest_gained_one_configuration_and_one_cell(cell):
    assert manifest.problems(SPEC, REPO) == []
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cell.config["source"] == SOURCE
    work = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, MIX, 1)
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == CONFIG] == [CELL]
    traffic = cell.traffic
    assert (traffic["job"], traffic["seq_len"], traffic["global_batch"],
            traffic["grad_accum"], traffic["steps_per_epoch"],
            traffic["check_rows"]) == ("train_fit", T, 1, 1, 8, 1)
    assert (cell.config["family"], cell.config["loss"]) == ("ouro",
                                                            "model_loss")
    for key in ("assumed", "not_built", "deployment", "published",
                "reduced_note"):
        assert cell.config[key], key
    assert {"source_of_equations", "block", "attention", "loop", "exit",
            "exit_distribution", "loss", "initialisers", "optimizer",
            "recompute"} <= set(cell.config["assumed"])
    assert "0.05" in cell.config["assumed"]["loss"]
    for name in NEW_METRICS:
        entry = next(e for e in SPEC["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL], name


def test_the_configuration_agrees_with_itself_and_with_the_catalog(cell):
    config = cell.config
    pub, m = config["published"], config["model"]
    for key, value in pub.items():
        assert config[key] == value or key in config["reduced"], key
    assert (pub["num_hidden_layers"], config["num_hidden_layers"],
            m["n_layers"]) == (48, 8, 8)
    assert pub["total_ut_steps"] == m["total_ut_steps"] == 4
    for ours, theirs in [("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("ffn_units", "intermediate_size"),
                         ("vocab_size", "vocab_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps")]:
        assert m[ours] == pub[theirs], ours
    assert (pub["tie_word_embeddings"], pub["early_exit_threshold"],
            pub["use_sliding_window"]) == (False, 1, False)
    assert (m["dtype"], m["remat"], m["use_flash"], m["entropy_beta"]) == (
        "bfloat16", True, "auto", 0.05)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert row["config"] == pub and row["source_url"] == SOURCE


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_each_new_entry_is_for_the_cell_alone(metric):
    entry = next(e for e in SPEC["per_layer"] if e["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_per_s_chip"
    kernel = metric.startswith("ouro_flash")
    assert entry["layer"] == ("kernels" if kernel else LAYER)
    if metric in ROOFLINES:
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "%", "higher", "device_trace")
    elif metric in SCOPES:
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ms", "lower", "device_trace")
        assert _args(metric)["reader"] == "trace_scope_time"
    else:
        assert entry["source"] == "program_counter"
        assert _args(metric)["args"] == {"series": "ouro." + COUNTERS[metric],
                                         "stat": "mean"}


def test_flops_per_sample_is_its_terms_written_out(cell):
    from benchmark.families import ouro as fam
    m = fam._model(cell.config)
    block = 4 * D * D + 3 * D * F
    assert fam.block_params(m) == block == 51_380_224      # no norms
    assert fam.block_applications(m) == 32
    per_token = 32 * block + P * V * D + (P - 1) * D
    assert fam.matmul_params_per_token(m) == per_token
    assert 32 * block == 1_644_167_168 and P * V * D == 402_653_184
    attention = 3 * 2 * 2 * HD * H * PAIRS * 32
    assert 2 * 2 * HD * H * PAIRS == 274_911_461_376
    want = 6.0 * per_token * T + attention
    assert fam.flops_per_sample(cell.config, cell.traffic) == want
    assert abs(6.0 * 32 * block * T - 80.8e12) < 0.1e12     # the loop
    assert abs(6.0 * P * V * D * T - 19.8e12) < 0.1e12      # the heads
    assert abs(attention - 26.4e12) < 0.1e12
    assert abs(want - 127.0e12) < 0.1e12
    loop = 6.0 * 32 * block * T + attention
    assert 0.83 < loop / want < 0.85                        # the issue's 84%


@pytest.mark.parametrize("metric,work", sorted(ROOFLINES.items()))
def test_roofline_files_hold_what_the_familys_function_gives(metric, work,
                                                             cell):
    from benchmark.families import ouro as fam
    entry = _args(metric)
    args = entry["args"]
    want = getattr(fam, work)(cell.config, cell.traffic)
    assert args["flops_per_step"] == want["flops"]
    assert args["bytes_per_step"] == want["bytes"]
    assert work in args["work"] and CONFIG in args["work"]
    least = max(want["flops"] / 197e12, want["bytes"] / 819e9)
    assert want["flops"] / 197e12 > want["bytes"] / 819e9   # FLOP-bound
    assert 40e-3 < least < 140e-3
    assert entry["reader"] == ("trace_scope_roofline" if metric ==
                               "ouro_exit_head_roofline_pct"
                               else "trace_kernel_roofline")


def test_the_work_functions_are_their_arithmetic(cell):
    from benchmark.families import ouro as fam
    fwd = fam.flash_fwd_work(cell.config, cell.traffic)
    assert fwd == {"flops": 32 * 274_911_461_376.0,
                   "bytes": 32 * 134_217_728.0}
    assert 4 * T * H * HD * 2 == 134_217_728
    bwd = fam.flash_bwd_work(cell.config, cell.traffic)
    assert bwd == {"flops": 2.5 * fwd["flops"], "bytes": 2.0 * fwd["bytes"]}
    head = fam.exit_head_work(cell.config, cell.traffic)
    assert head == {"flops": 4.0 * 2 * T * D * V * P,
                    "bytes": 4.0 * T * D * 2 * P + 3 * D * V * 4}
    assert head["flops"] == 26_388_279_066_624


def test_the_patterns_and_series_are_the_ones_the_program_has():
    fwd = re.compile(_args("ouro_flash_fwd_roofline_pct")["args"]["pattern"])
    bwd = re.compile(_args("ouro_flash_bwd_roofline_pct")["args"]["pattern"])
    assert fwd.search("%flash_attention_fwd.1") and not fwd.search(
        "%flash_attention_bwd.1")
    assert bwd.search("%flash_attention_bwd.1") and not bwd.search(
        "%flash_attention_fwd.1")
    loop = re.compile(_args("ouro_loop_scope_ms_per_step")["args"]["scope"])
    exit_ = re.compile(_args("ouro_exit_scope_ms_per_step")["args"]["scope"])
    assert loop.search("loop/remat_3/attn/flash_attention_fwd")
    assert not loop.search("exit/head") and not exit_.search("loop/remat_0")
    assert exit_.search("exit/head") and exit_.search("exit/loss")
    assert exit_.search("exit/final_norm") and not exit_.search("exit_gate")
    assert _args("ouro_exit_head_roofline_pct")["args"]["scope"] == \
        _args("ouro_exit_scope_ms_per_step")["args"]["scope"]
    # the head's and the loss's time also lie inside head_loss_device_pct
    head_loss = re.compile(_args("head_loss_device_pct")["args"]["scope"])
    assert head_loss.search("exit/head") and head_loss.search("exit/loss")
    assert not head_loss.search("exit/final_norm")
    from analytics_zoo_tpu.models.ouro import level_keys
    assert {COUNTERS[m] for m in COUNTERS} <= set(level_keys(4))


def test_the_scope_roofline_reader(monkeypatch):
    from benchmark.layer_metrics import trace_scope_roofline, trace_scope_time
    args = {"scope": "(^|/)exit(/|$)", "flops_per_step": 197e12 * 0.05,
            "bytes_per_step": 819e9 * 0.01}
    tpu = SimpleNamespace(trace=object(), device={"platform": "tpu",
                                                  "kind": "TPU v5 lite"})
    monkeypatch.setattr(trace_scope_time, "read", lambda a, r: 100.0)
    assert abs(trace_scope_roofline.read(args, tpu) - 50.0) < 1e-9
    # off the chip, without a slice, or where nothing matched: nothing
    cpu = SimpleNamespace(trace=object(), device={"platform": "cpu"})
    assert trace_scope_roofline.read(args, cpu) is None
    assert trace_scope_roofline.read(
        args, SimpleNamespace(trace=None, device=tpu.device)) is None
    monkeypatch.setattr(trace_scope_time, "read", lambda a, r: None)
    assert trace_scope_roofline.read(args, tpu) is None
    monkeypatch.setattr(trace_scope_time, "read", lambda a, r: 0.0)
    assert trace_scope_roofline.read(args, tpu) is None
