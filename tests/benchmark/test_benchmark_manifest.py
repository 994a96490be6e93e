"""BENCHMARK.json and the data files behind it: every name resolves, every
name and unit keeps the driver's character rules, and the yardstick's
arithmetic (peaks, registry window, FLOPs functions) gives known answers."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, peaks, registry  # noqa: E402

SPEC = manifest.load(REPO)
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_manifest_keeps_the_contracts_static_rules():
    assert manifest.problems(SPEC, REPO) == []


@pytest.mark.parametrize("breach,expected", [
    (lambda m: m["workloads"][0].update(name="has space"), "is not a name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]],
     "ask for 4 chips"),
    (lambda m: m["workloads"][0].update(traffic="no_such_mix"),
     "does not exist"),
    (lambda m: m["per_layer"][0].update(why="x"), "keys"),
    (lambda m: m.update(extra=1), "top-level keys"),
])
def test_problems_names_a_breach(breach, expected):
    spec = json.loads(json.dumps(SPEC))
    breach(spec)
    found = manifest.problems(spec, REPO)
    assert any(expected in p for p in found), found


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_files_and_code_that_exist(name):
    for rehearse in (False, True):
        cell = manifest.cell(SPEC, name, rehearse=rehearse)
        for kind, key in (("families", cell.config["family"]),
                          ("jobs", cell.traffic["job"])):
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", kind, key + ".py")), (kind, key)
        readers = {m.reader for m in cell.end_to_end + cell.per_layer}
        for reader in readers:
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", "layer_metrics", reader + ".py")), reader
        names = [m.name for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer and all(
            m.moves in names for m in cell.per_layer)


def test_only_the_cross_chip_cell_asks_for_four_chips():
    assert [w["name"] for w in SPEC["workloads"] if w["chips"] == 4] \
        == ["bert_base_fit_dp4"]


def test_a_cell_can_be_tried_from_its_data_files_before_it_has_an_entry():
    """... with the metrics that wait for a benchmark PR too: a metric file
    without an entry in BENCHMARK.json proposes its own."""
    cell = manifest.cell(SPEC, "resnet18_serve+open_poisson_rehearsal")
    assert cell.traffic["job"] == "serve_open" and cell.chips == 1
    assert {"serve_rows_per_s", "serve_p50_ms", "serve_p99_ms"} <= {
        m.name for m in cell.end_to_end}
    queue_wait = next(m for m in cell.per_layer
                      if m.name == "srv_queue_wait_ms_p50")
    assert (queue_wait.unit, queue_wait.moves, queue_wait.reader) == (
        "ms", "serve_p50_ms", "registry_hist")
    assert manifest.cell(SPEC, "bert_base_mlm+fit_tokens_b128_dp4+4"
                         ).chips == 4
    with pytest.raises(KeyError, match="no workload"):
        manifest.cell(SPEC, "no_such_cell")


def test_rehearsal_overlays_change_sizes_and_nothing_else():
    real = manifest.cell(SPEC, "bert_base_fit_s512")
    tiny = manifest.cell(SPEC, "bert_base_fit_s512", rehearse=True)
    assert real.config["model"]["hidden_size"] == 768
    assert tiny.config["model"]["hidden_size"] == 64
    assert tiny.config["model"]["dtype"] == real.config["model"]["dtype"]
    assert tiny.traffic["job"] == real.traffic["job"]


def test_configurations_hold_the_published_widths():
    bert = manifest.cell(SPEC, "bert_base_fit_s512").config
    assert bert["reduced"] == [] and bert["model"] == {
        "vocab_size": 30522, "hidden_size": 768, "n_layers": 12,
        "n_heads": 12, "intermediate_mult": 4, "max_position": 512,
        "dropout": 0.0, "use_flash": False, "remat_attention": True,
        "dtype": "bfloat16"}
    pub = bert["published"]
    assert pub["intermediate_size"] == 4 * pub["hidden_size"]
    for name, depth in (("resnet50_fit_stream", 50),
                        ("resnet18_serve+closed_64x1row", 18)):
        model = manifest.cell(SPEC, name).config["model"]
        assert (model["depth"], model["width"], model["class_num"],
                model["stem"], model["norm"]) == (depth, 64, 1000, "conv",
                                                  "batch")


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_registry_window_subtracts_counters_and_histogram_buckets():
    from analytics_zoo_tpu.core import metrics
    reg = metrics.MetricsRegistry()
    h = reg.histogram("x.ms")
    c = reg.counter("x.n")
    for v in (0.2, 0.2, 40.0):
        h.observe(v)
    c.inc(5)
    before = reg.snapshot()
    for v in (3.0, 3.0, 3.0, 700.0):
        h.observe(v)
    c.inc(2)
    grew = registry.window(before, reg.snapshot())
    assert grew["x.n"] == 2
    assert grew["x.ms"]["count"] == 4
    assert grew["x.ms"]["sum"] == pytest.approx(709.0)
    # three of four in (2.5, 5]: the median interpolates inside that bucket
    p50 = registry.bucket_quantile(grew["x.ms"]["edges"],
                                   grew["x.ms"]["counts"], 0.5)
    assert 2.5 < p50 <= 5.0
    # ... and agrees with the program's own arithmetic, which it copies
    delta = metrics.snapshot_delta(before, reg.snapshot())
    assert p50 == pytest.approx(delta["x.ms"]["p50"], abs=1e-6)
    assert registry.bucket_quantile([1.0], [0, 0], 0.5) is None


def test_flops_functions_give_the_canonical_counts():
    from benchmark.families import bert_mlm, resnet_uint8
    # torchvision quotes 4.09e9 multiply-accumulates for ResNet-50 at 224
    # and 1.81e9 for ResNet-18
    assert resnet_uint8.forward_macs(50, 64, 1000, 224) == pytest.approx(
        4.089e9, rel=2e-3)
    assert resnet_uint8.forward_macs(18, 64, 1000, 224) == pytest.approx(
        1.814e9, rel=2e-3)
    cell = manifest.cell(SPEC, "resnet50_fit_stream")
    assert resnet_uint8.flops_per_sample(cell.config, cell.traffic) == \
        6 * resnet_uint8.forward_macs(50, 64, 1000, 224)
    # BERT-base at 512: 6 x 108.4 M matmul parameters + attention
    assert bert_mlm.flops_per_token(768, 12, 512, 30522) == 706_876_416
    cell = manifest.cell(SPEC, "bert_base_fit_s512")
    assert bert_mlm.flops_per_sample(cell.config, cell.traffic) == \
        512 * 706_876_416


def _sources():
    for base, _, files in os.walk(os.path.join(REPO, "benchmark")):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    yield os.path.relpath(path, REPO), fh.read()


def test_the_benchmark_imports_neither_bench_nor_chip_smoke():
    for path, text in _sources():
        assert not re.search(
            r"^\s*(import|from)\s+(bench|chip_smoke)\b", text, re.M), path


def test_no_code_of_the_harness_or_the_jobs_names_a_cell_config_or_metric():
    """Cells, configurations, traffic mixes and metrics are data: the code
    that runs them may not know one by name."""
    names = set(CELLS) | {c["name"] for c in SPEC["configs"]} \
        | {w["traffic"] for w in SPEC["workloads"]} \
        | {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    names.discard("setup_s")  # the contract's own name: problems() asks
    #                           that every cell reports it
    for path, text in _sources():
        if not path.startswith(("benchmark/harness", "benchmark/jobs",
                                "benchmark/run.py",
                                "benchmark/layer_metrics")):
            continue
        code = "\n".join(l for l in text.splitlines()
                         if re.match(r"\s*(if|elif|while)\b", l))
        hit = [n for n in names if re.search(r"[\"']%s[\"']" % re.escape(n),
                                             code)]
        assert not hit, (path, hit)


def test_no_device_or_topology_call_at_import_time():
    """Importing the benchmark's modules touches no backend (on-chip
    guide, section 2): tier-1's workers each import every test file."""
    import subprocess
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax._src.xla_bridge as xb\n"
        "import benchmark.harness.manifest, benchmark.harness.xplane, "
        "benchmark.harness.window, benchmark.harness.peaks, "
        "benchmark.harness.registry, benchmark.layer_metrics, "
        "benchmark.families.bert_mlm, benchmark.families.resnet_uint8, "
        "benchmark.jobs.train_fit, benchmark.jobs.serve_closed, "
        "benchmark.jobs.serve_open\n"
        "assert not xb._backends, list(xb._backends)\n" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


#: a manifest list indexed from its end: what a later PR appends would move
FROM_THE_END = re.compile(
    r"""\[\s*["'](configs|workloads|per_layer)["']\s*\]\s*\[\s*-""")


@pytest.mark.parametrize("subscript,index,found", [
    ('["configs"]', '[-1]["name"]', True),
    ("['workloads']", "[-1]", True),
    ('["per_layer"]', "[-3:]", True),
    ('["per_layer"]', "[-len(NEW_METRICS):]", True),
    ('[ "per_layer" ]', "[ -2 ]", True),
    ('["configs"]', "[0]", False),
    ('["per_layer"]', "[:-1]", False),
    ('["end_to_end"]', "[-1]", False),
])
def test_the_guard_knows_an_index_from_the_end(subscript, index, found):
    # written apart, so that this file's own lines hold no such index
    assert bool(FROM_THE_END.search("SPEC" + subscript + index)) == found


def test_no_test_pins_a_position_at_the_end_of_the_manifest():
    """A configuration, a cell or a metric is appended to ``BENCHMARK.json``
    by a later PR, which may not edit these files: a test finds its entries
    by name, never as the manifest's last."""
    folder = os.path.dirname(os.path.abspath(__file__))
    for f in sorted(os.listdir(folder)):
        if f.endswith(".py"):
            with open(os.path.join(folder, f)) as fh:
                hits = [n for n, line in enumerate(fh, 1)
                        if FROM_THE_END.search(line)]
            assert not hits, (f, hits)


@pytest.mark.parametrize("name", CELLS)
def test_a_rehearsal_opens_its_slice_with_the_window_and_a_chip_run_later(
        name):
    """On a CPU that a test suite loads, a rehearsal's window can close
    before a slice started at a share of its estimated length would open;
    a chip run's slice keeps its traffic's own start."""
    entry = next(w for w in SPEC["workloads"] if w["name"] == name)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           entry["traffic"] + ".json")) as f:
        own = json.load(f)
    assert manifest.cell(SPEC, name).traffic["trace_after_share"] \
        == own["trace_after_share"]
    tiny = manifest.cell(SPEC, name, rehearse=True).traffic
    assert tiny["trace_after_share"] == 0
    assert tiny["trace_seconds"] == own.get("rehearse", {}).get(
        "trace_seconds", own["trace_seconds"])
