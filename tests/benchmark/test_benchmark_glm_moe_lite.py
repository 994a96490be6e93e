"""The cell ``glm47_flash_ep8_fit_s8192`` and what came with it: the cell
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
CELL = "glm47_flash_ep8_fit_s8192"
CONFIG = "glm_4_7_flash_ep8"
MIX = "fit_lm_tokens_s8192_b1"
SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
ROOFLINES = {"mla_flash_fwd_roofline_pct": "flash_fwd_work",
             "mla_flash_bwd_roofline_pct": "flash_bwd_work",
             "glm_ragged_dot_roofline_pct": "ragged_dot_work"}
SCOPES = {"mla_scope_ms_per_step": "latent attention",
          "mla_outside_kernels_ms_per_step": "latent attention",
          "glm_moe_scope_ms_per_step": "expert layer",
          "glm_moe_row_movement_ms_per_step": "expert layer",
          "mtp_scope_ms_per_step": "multi-token prediction"}
COUNTERS = {"mtp_top1_hit_share": "multi-token prediction",
            "mtp_loss": "multi-token prediction",
            "mla_kv_latent_abs_max": "latent attention",
            "glm_local_pair_share": "expert layer",
            "glm_load_max_over_mean": "expert layer",
            "glm_dropped_pairs_per_step": "expert layer"}
NEW_METRICS = set(ROOFLINES) | set(SCOPES) | set(COUNTERS)
T, H, QK, V = 8192, 20, 192 + 64, 256
PAIRS = T * (T + 1) // 2
ATTN = 2048 * 768 + 768 * H * QK + 2048 * (512 + 64) + 512 * H * (192 + V) \
    + H * V * 2048
EXPERT = 3 * 2048 * 1536


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
         "3700000003", "--seconds", "4", "--trace", "1", "--rehearse"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1]), proc.stderr


def test_the_cell_rehearses_to_a_correct_line(rehearsal):
    line, stderr = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    overlay = manifest.cell(SPEC, CELL, rehearse=True)
    steps = overlay.traffic["steps_per_epoch"]
    assert line["attempted"] > 0 and line["attempted"] % steps == 0
    # the float32 rehearsal sits on the reference, both depths
    found = re.search(r"differs from the float32 reference by ([\d.e+-]+)",
                      stderr)
    assert found and float(found.group(1)) < 1e-4
    m = overlay.config["model"]
    assert m["num_heads"] == 5 and m["nope_dim"] + m["rope_dim"] == m["v_dim"]


def test_the_traced_rehearsal_reports_the_modules_series(rehearsal):
    """The registry's series and the scopes reach the line (the kernels'
    rooflines need a TPU: their reader finds nothing on the CPU)."""
    got = rehearsal[0]["metrics"]
    assert NEW_METRICS & set(got) == NEW_METRICS - set(ROOFLINES)
    assert 0.0 <= got["mtp_top1_hit_share"]["value"] <= 1.0
    assert 0.0 < got["mtp_loss"]["value"] < 6.0           # ln 128 = 4.85
    assert 1.0 < got["mla_kv_latent_abs_max"]["value"] < 24 ** 0.5
    assert got["glm_dropped_pairs_per_step"]["value"] == 0.0
    assert 0.0 < got["glm_local_pair_share"]["value"] <= 1.0
    assert got["glm_load_max_over_mean"]["value"] >= 1.0
    # the module's block is one of four attention and of three expert layers
    assert 0 < got["mtp_scope_ms_per_step"]["value"] \
        < got["mla_scope_ms_per_step"]["value"] \
        + got["glm_moe_scope_ms_per_step"]["value"]
    assert got["glm_moe_row_movement_ms_per_step"]["value"] \
        < got["glm_moe_scope_ms_per_step"]["value"]
    assert "first_step_s" in got and "head_loss_device_pct" in got


def test_the_new_metrics_are_reported_in_the_new_cell_and_in_no_other():
    for w in SPEC["workloads"]:
        names = {m.name for m in manifest.cell(SPEC, w["name"]).per_layer}
        assert (NEW_METRICS <= names) == (w["name"] == CELL), w["name"]
        assert not (NEW_METRICS & names) or w["name"] == CELL
    # ... and the other decoders' kernels' and counters' entries stay theirs
    names = {m.name for m in manifest.cell(SPEC, CELL).per_layer}
    assert not names & {"moe_local_pair_share", "flash_fwd_roofline_pct",
                        "moe_scope_ms_per_step", "afmoe_local_pair_share",
                        "granite_flash_fwd_roofline_pct",
                        "gdn_scope_ms_per_step"}
    # every metric without a list is read here as in every cell
    unlisted = {e["name"] for e in SPEC["per_layer"] if "workloads" not in e}
    assert unlisted <= names and {"step_device_ms", "train_mfu_pct",
                                  "hbm_peak_gb", "head_loss_device_pct"} \
        <= unlisted


def test_the_manifest_gained_one_configuration_and_one_cell(cell):
    assert manifest.problems(SPEC, REPO) == []
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
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
            traffic["feed"]) == ("train_fit", T, 1, 1, "dp", 8, 1, {})
    assert cell.config["family"] == "glm_moe_lite"
    assert cell.config["loss"] == "multi_token_crossentropy"
    for key in ("assumed", "not_built", "deployment", "published"):
        assert cell.config[key], key
    assert {"source_of_equations", "block", "latent_attention",
            "rotary_layout", "router", "bias_rule", "multi_token_prediction",
            "initialisers", "optimizer", "recompute"} <= set(
        cell.config["assumed"])


def test_the_configuration_agrees_with_itself_and_with_the_catalog(cell):
    config = cell.config
    pub, m = config["published"], config["model"]
    for key, value in pub.items():
        assert config[key] == value or key in config["reduced"], key
    assert (pub["num_hidden_layers"], config["num_hidden_layers"],
            m["n_layers"]) == (47, 5, 5)
    assert (pub["n_routed_experts"], config["n_routed_experts"],
            m["experts_held"], m["num_experts"], m["first_expert"]) == (
        64, 8, 8, 64, 0)
    assert (pub["vocab_size"], config["vocab_size"], m["vocab_size"]) == (
        154880, 19360, 19360) and 19360 * 8 == 154880
    # the catalog row's widths, none of them cut
    assert (pub["hidden_size"], pub["num_attention_heads"],
            pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
            pub["qk_rope_head_dim"], pub["v_head_dim"],
            pub["intermediate_size"], pub["moe_intermediate_size"],
            pub["num_experts_per_tok"], pub["n_shared_experts"]) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 4, 1)
    assert (pub["routed_scaling_factor"], pub["rope_theta"],
            pub["rms_norm_eps"], pub["first_k_dense_replace"],
            pub["num_nextn_predict_layers"], pub["n_group"],
            pub["topk_group"], pub["topk_method"], pub["norm_topk_prob"],
            pub["tie_word_embeddings"], pub["attention_bias"],
            pub["rope_scaling"]) == (
        1.8, 1000000, 1e-05, 1, 1, 1, 1, "noaux_tc", True, False, False,
        None)
    for ours, theirs in [
            ("hidden_size", "hidden_size"),
            ("num_heads", "num_attention_heads"),
            ("q_rank", "q_lora_rank"), ("kv_rank", "kv_lora_rank"),
            ("nope_dim", "qk_nope_head_dim"),
            ("rope_dim", "qk_rope_head_dim"), ("v_dim", "v_head_dim"),
            ("rope_theta", "rope_theta"),
            ("dense_units", "intermediate_size"),
            ("num_experts", "n_routed_experts"),
            ("top_k", "num_experts_per_tok"),
            ("moe_units", "moe_intermediate_size"),
            ("route_scale", "routed_scaling_factor"),
            ("num_dense_layers", "first_k_dense_replace"),
            ("mtp_layers", "num_nextn_predict_layers"),
            ("rms_eps", "rms_norm_eps")]:
        assert m[ours] == pub[theirs], ours
    assert m["shared_units"] == pub["n_shared_experts"] \
        * pub["moe_intermediate_size"]
    assert (m["dtype"], m["remat"], m["use_flash"]) == (
        "bfloat16", True, "auto")
    # the bias speed is the cell's own, with its reason; the model's default
    # is the report's
    from analytics_zoo_tpu.models import GlmMoeLite
    assert m["balance_coeff"] == 0.01 and "0.01" in config["assumed"][
        "bias_rule"] and GlmMoeLite().balance_coeff == 0.001
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-4.7-Flash")
        assert row["config"] == pub and row["source_url"] == SOURCE


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_each_new_entry_is_for_the_cell_alone(metric):
    entry = next(e for e in SPEC["per_layer"] if e["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_per_s_chip"
    layers = dict(SCOPES, **COUNTERS)
    assert entry["layer"] == layers.get(metric, "kernels")
    if metric in ROOFLINES:
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "%", "higher", "device_trace")
    if metric in SCOPES:
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ms", "lower", "device_trace")
        assert _args(metric)["reader"] == "trace_scope_time"
        assert "prediction module" in _args(metric)["reads"] \
            or metric == "mtp_scope_ms_per_step"
    if metric in COUNTERS:
        assert entry["source"] == "program_counter"


def test_flops_per_sample_is_its_terms_written_out(cell):
    from benchmark.families import glm_moe_lite as fam
    m = fam._model(cell.config)
    assert fam.causal_pairs(T) == PAIRS == 33_558_528
    assert fam.attention_params(m) == ATTN == 21_757_952    # no norms
    assert (fam.attention_layers(m), fam.expert_layers(m)) == (6, 5)
    # router, shared expert, and top-4 of 64 with 8 held: half an expert
    moe = 2048 * 64 + EXPERT + 4 * 8 / 64 * EXPERT
    per_token = 6 * ATTN + 3 * 2048 * 10240 + 5 * moe + 2 * 2048 * 2048 \
        + 2 * 19360 * 2048
    assert fam.matmul_params_per_token(m) == per_token
    assert abs(per_token - 352.6e6) < 0.1e6                 # the issue's
    attention = 3 * 2 * (QK + V) * H * PAIRS * 6
    want = 6.0 * per_token * T + attention
    assert fam.flops_per_sample(cell.config, cell.traffic) == want
    assert abs(6.0 * per_token * T - 17.33e12) < 0.01e12
    assert abs(attention - 12.37e12) < 0.01e12
    assert abs(want - 29.70e12) < 0.01e12                   # a step


@pytest.mark.parametrize("metric,work", sorted(ROOFLINES.items()))
def test_roofline_files_hold_what_the_familys_function_gives(metric, work,
                                                             cell):
    from benchmark.families import glm_moe_lite as fam
    entry = _args(metric)
    assert entry["reader"] == "trace_kernel_roofline"
    args = entry["args"]
    want = getattr(fam, work)(cell.config, cell.traffic)
    assert args["flops_per_step"] == want["flops"]
    assert args["bytes_per_step"] == want["bytes"]
    assert work in args["work"] and CONFIG in args["work"]
    # under the chip's peaks the least time is milliseconds of a step
    least = max(want["flops"] / 197e12, want["bytes"] / 819e9)
    assert 5e-3 < least < 60e-3


def test_the_work_functions_are_their_arithmetic(cell):
    from benchmark.families import glm_moe_lite as fam
    fwd = fam.flash_fwd_work(cell.config, cell.traffic)
    assert fwd == {"flops": 6.0 * 687_278_653_440,
                   "bytes": 6.0 * 4 * T * H * 256 * 2}
    assert fwd == {"flops": 4_123_671_920_640.0, "bytes": 2_013_265_920.0}
    assert 2 * (QK + V) * H * PAIRS == 687_278_653_440
    bwd = fam.flash_bwd_work(cell.config, cell.traffic)
    assert bwd == {"flops": 2.5 * fwd["flops"], "bytes": 2.0 * fwd["bytes"]}
    ragged = fam.ragged_dot_work(cell.config, cell.traffic)
    pairs = T * 4 * 8 / 64                                   # 4,096 a layer
    assert ragged["flops"] == 5 * pairs * EXPERT * 6 == 1_159_641_169_920
    weights = 3 * 8 * EXPERT * 2
    rows = 2 * pairs * (2 * 2048 + 3 * 1536) * 2
    assert ragged["bytes"] == 5 * (weights + rows)


def test_the_patterns_and_series_are_the_ones_the_program_has():
    fwd = re.compile(_args("mla_flash_fwd_roofline_pct")["args"]["pattern"])
    bwd = re.compile(_args("mla_flash_bwd_roofline_pct")["args"]["pattern"])
    assert fwd.search("%flash_attention_fwd.1") and not fwd.search(
        "%flash_attention_bwd.1")
    assert bwd.search("%flash_attention_bwd.1") and not bwd.search(
        "%flash_attention_fwd.1")
    assert not fwd.search("%flash_attention_window_fwd.3")
    ragged = re.compile(_args("glm_ragged_dot_roofline_pct")["args"][
        "pattern"])
    assert ragged.search("%ragged-dot-none.3") and not ragged.search(
        "%ragged-dot-metadata.3")
    # the scope metrics take the arguments of the accepted ones
    for ours, theirs in [("glm_moe_scope_ms_per_step", "moe_scope_ms_per_step"),
                         ("glm_moe_row_movement_ms_per_step",
                          "moe_row_movement_ms_per_step")]:
        assert _args(ours)["args"] == _args(theirs)["args"]
    attn = _args("mla_scope_ms_per_step")["args"]
    outside = _args("mla_outside_kernels_ms_per_step")["args"]
    assert outside == dict(attn, not_op="^%?flash_attention_(fwd|bwd)")
    scope = re.compile(attn["scope"])
    assert scope.search("remat_3/attn/flash_attention_fwd")
    assert scope.search("mtp/remat/attn") and not scope.search("remat_3/moe")
    mtp = re.compile(_args("mtp_scope_ms_per_step")["args"]["scope"])
    assert mtp.search("mtp/eh_proj") and mtp.search("mtp/remat/moe/router")
    assert not mtp.search("mtp_counters") and not mtp.search("head")
    # the counters' series are the ones the layers publish
    from analytics_zoo_tpu.models import glm_moe_lite as model
    from analytics_zoo_tpu.nn import attention
    assert _args("mtp_top1_hit_share")["args"] == {
        "series": "mtp." + model.MTP_COUNTER_KEYS[1],
        "per": "mtp." + model.MTP_COUNTER_KEYS[0]}
    assert _args("mtp_loss")["args"] == {
        "series": "mtp." + model.MTP_LEVEL_KEYS[0], "stat": "mean"}
    assert _args("mla_kv_latent_abs_max")["args"] == {
        "series": "mla." + attention.LATENT_LEVEL_KEYS[0], "stat": "mean"}
    for ours, theirs in [("glm_local_pair_share", "afmoe_local_pair_share"),
                         ("glm_load_max_over_mean",
                          "afmoe_load_max_over_mean"),
                         ("glm_dropped_pairs_per_step",
                          "afmoe_dropped_pairs_per_step")]:
        assert _args(ours) == _args(theirs)
