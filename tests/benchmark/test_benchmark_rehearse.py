"""Every job of the benchmark, rehearsed end to end at its tiny preset on
the CPU through the one command the driver runs: the shape of the last line
and exact counts, never a time.  And each family's plain float32 reference
against the system's own forward at a tiny size."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

SPEC = manifest.load(REPO)
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(*args, rehearse=True):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, *SPEC["command"][1:], *args]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=600)


def _line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("cell,trace,chips", [
    ("bert_base_fit_s512", 0, 1),
    ("bert_base_fit_s512", 1, 1),
    ("bert_base_fit_dp4", 1, 4),
    ("resnet50_fit_stream", 0, 1),
    ("resnet18_serve+closed_64x1row", 0, 1),
    ("resnet18_serve+closed_64x1row", 1, 1),
    ("resnet18_serve+open_poisson_rehearsal", 0, 1),
    ("resnet18_serve+open_bursts_rehearsal", 0, 1),
])
def test_a_rehearsed_cell_prints_the_contracts_last_line(cell, trace, chips):
    line = _line(_run("--workload", cell, "--seed", "3", "--seconds", "2",
                      "--trace", str(trace)))
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"} | (
        {"busy_s", "window_s"} if trace else set())
    resolved = manifest.cell(SPEC, cell, rehearse=True)
    wanted = resolved.per_layer if trace else resolved.end_to_end
    units = {m.name: m.unit for m in wanted}
    assert line["metrics"], "no metric in the line"
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert isinstance(m["value"], float) and np.isfinite(m["value"])
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        for rows in line["breakdown"].values():
            assert 0 < len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)
        assert "first_step_s" in line["metrics"]
    else:
        # every end-to-end metric of the cell is there, and none is 0 (a
        # cell tried from its files reports those its job has values for)
        assert set(line["metrics"]) == set(units) or "+" in cell
        assert {"setup_s", "serve_rows_per_s", "serve_p50_ms",
                "serve_p99_ms"} == set(line["metrics"]) or "+" not in cell
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_training_job_counts_whole_epochs_of_optimizer_steps():
    line = _line(_run("--workload", "bert_base_fit_s512", "--seed", "1",
                      "--seconds", "0.01", "--trace", "0"))
    steps = manifest.cell(SPEC, "bert_base_fit_s512",
                          rehearse=True).traffic["steps_per_epoch"]
    assert line["attempted"] == steps  # one epoch is the least it runs


def test_without_the_rehearsal_option_a_cpu_is_refused():
    proc = _run("--workload", "bert_base_fit_s512", "--seed", "0",
                "--seconds", "1", "--trace", "0", rehearse=False)
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line to mistake for a pass
    assert "not a TPU" in proc.stderr


def test_fewer_devices_than_the_cell_asks_for_is_refused():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload",
         "bert_base_fit_dp4", "--rehearse"], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "asks for 4 chip(s)" in proc.stderr


def test_without_the_program_beside_it_the_benchmark_fails_with_no_result(
        tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no system to measure: non-zero, and no result line."""
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload",
         "bert_base_fit_s512", "--rehearse"], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "analytics_zoo_tpu" in proc.stderr


def test_an_unknown_workload_fails_before_any_result():
    proc = _run("--workload", "no_such_cell")
    assert proc.returncode != 0 and proc.stdout == ""


@pytest.mark.parametrize("name", ["bert_base_fit_s512", "resnet50_fit_stream",
                                  "resnet18_serve+closed_64x1row"])
def test_reference_agrees_with_the_systems_float32_forward(name):
    """The plain reference is an independent implementation: at float32 the
    system's own forward must land on it to rounding, with statistics and
    gains moved off their initial 0 and 1 so that no term drops out."""
    import jax
    from benchmark.families import family
    cell = manifest.cell(SPEC, name, rehearse=True)
    config = manifest.merged(cell.config, {"model": {"dtype": "float32"}})
    fam = family(config)
    model = fam.build(config)
    x = fam.inputs(config, cell.traffic, 5, 4)
    variables = jax.jit(lambda r, a: model.init(r, a))(
        jax.random.PRNGKey(5), x)
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 10_000))
    variables = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.uniform(next(keys), a.shape)
        if a.ndim == 1 else a, variables)
    got = np.asarray(model.apply(variables, x, training=False)[0])
    ref = fam.reference(config, variables, x)
    assert got.shape == ref.shape
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-4


@pytest.mark.parametrize("norm,stem", [("nf", "space_to_depth"),
                                       ("batch", "space_to_depth")])
def test_resnet_reference_covers_the_open_tables_recipe(norm, stem):
    """``resnet50_nf_fit_stream`` of PERF.md's open table arrives as data
    only, so the reference must already know its recipe."""
    import jax
    from benchmark.families import resnet_uint8
    config = {"model": dict(depth=50, class_num=10, width=8,
                            dtype="float32", stem=stem, norm=norm)}
    traffic = {"image_size": 32, "pool_size": 4}
    model = resnet_uint8.build(config)
    x = resnet_uint8.inputs(config, traffic, 2, 4)
    variables = jax.jit(lambda r, a: model.init(r, a))(
        jax.random.PRNGKey(2), x)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 10_000))
    variables = jax.tree_util.tree_map(  # skip gains are scalars, off 0
        lambda a: a + 0.3 * jax.random.uniform(next(keys), a.shape)
        if a.ndim <= 1 else a, variables)
    got = np.asarray(model.apply(variables, x, training=False)[0])
    ref = resnet_uint8.reference(config, variables, x)
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-4
