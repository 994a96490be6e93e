"""Unified telemetry: metrics registry, end-to-end request tracing, and
training-loop instrumentation (ISSUE 3).

Covers: registry thread-safety under concurrent writers, histogram
bucket-edge semantics, the Prometheus exposition golden format, end-to-end
trace-id propagation through a live ClusterServing round trip, the
``/stats`` namespacing fix + flat back-compat view, the healthy-server
counter invariant, step-loop instrumentation (snapshot + SummaryWriter
mirror), heartbeat JSON payloads + supervisor aggregation, fault
arming/firing counted through the registry, and the instrumentation
overhead guard (slow).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import init_orca_context, metrics, trace
from analytics_zoo_tpu.core.metrics import MetricsRegistry
from analytics_zoo_tpu.serving import (ClusterServing, HTTPFrontend,
                                       InferenceModel, InputQueue,
                                       OutputQueue)


def _linear_model():
    init_orca_context("local")

    class M(nn.Module):
        def forward(self, scope, x):
            return scope.child(nn.Dense(3), x, name="fc")

    m = M()
    variables = m.init(__import__("jax").random.PRNGKey(0),
                       np.zeros((1, 4), np.float32))
    return InferenceModel(batch_buckets=(1, 4, 8)).load(m, variables)


# -- registry primitives ------------------------------------------------------

def test_counter_thread_safety_under_concurrent_writers():
    reg = MetricsRegistry()
    c = reg.counter("t.hits")
    h = reg.histogram("t.lat_ms")
    n_threads, n_iter = 8, 5000

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe(float(i % 100))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    snap = reg.snapshot()["t.lat_ms"]
    assert snap["count"] == n_threads * n_iter
    assert snap["sum"] == pytest.approx(
        n_threads * sum(range(100)) * (n_iter // 100))


def test_histogram_bucket_edges():
    """Prometheus ``le`` semantics: bucket i counts values <= edges[i];
    one overflow bucket catches the rest."""
    reg = MetricsRegistry()
    h = reg.histogram("t.edges", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):
        h.observe(v)
    assert h.counts == [2, 2, 1, 1]  # le=1, le=2, le=4, +Inf
    assert h.count == 6 and h.sum == pytest.approx(14.0)
    # quantiles interpolate within the winning bucket and stay ordered
    assert 0.0 <= h.percentile(0.25) <= h.percentile(0.75) <= 4.0
    # the exposition renders CUMULATIVE bucket counts
    text = reg.prometheus()
    assert 'zoo_t_edges_bucket{le="1"} 2' in text
    assert 'zoo_t_edges_bucket{le="2"} 4' in text
    assert 'zoo_t_edges_bucket{le="4"} 5' in text
    assert 'zoo_t_edges_bucket{le="+Inf"} 6' in text


def test_gauge_tracks_high_water_mark():
    reg = MetricsRegistry()
    g = reg.gauge("t.depth")
    g.add(3)
    g.add(2)
    g.add(-4)
    assert g.value == 1 and g.max == 5
    assert reg.snapshot()["t.depth"] == {"value": 1, "max": 5}


def test_metric_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("t.x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t.x")
    # type uniqueness is per NAME, not per (name, labels): a counter and
    # a histogram sharing a name would corrupt the exposition, which
    # renders all of a name's label series under one # TYPE line
    reg.inc("t.y")
    with pytest.raises(ValueError, match="already registered"):
        reg.observe("t.y", 1.0, route="a")
    reg.prometheus()  # still renders cleanly


def test_labels_create_distinct_series():
    reg = MetricsRegistry()
    reg.inc("t.req", route="/a")
    reg.inc("t.req", route="/a")
    reg.inc("t.req", route="/b")
    snap = reg.snapshot()
    assert snap["t.req{route=/a}"] == 2
    assert snap["t.req{route=/b}"] == 1


def test_prometheus_exposition_golden():
    """Byte-exact golden for the three metric kinds — scrapers parse this
    format mechanically, so it must not drift by accident."""
    reg = MetricsRegistry()
    reg.counter("app.requests").inc(3)
    reg.counter("app.requests", route="/x").inc(1)
    reg.gauge("app.depth").set(2)
    h = reg.histogram("app.lat_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    assert reg.prometheus() == (
        "# TYPE zoo_app_depth gauge\n"
        "zoo_app_depth 2\n"
        "zoo_app_depth_max 2\n"
        "# TYPE zoo_app_lat_ms histogram\n"
        'zoo_app_lat_ms_bucket{le="1"} 1\n'
        'zoo_app_lat_ms_bucket{le="10"} 2\n'
        'zoo_app_lat_ms_bucket{le="+Inf"} 3\n'
        "zoo_app_lat_ms_sum 55.5\n"
        "zoo_app_lat_ms_count 3\n"
        "# TYPE zoo_app_requests counter\n"
        "zoo_app_requests 3\n"
        'zoo_app_requests{route="/x"} 1\n')


def test_export_jsonl_and_flat_view(tmp_path):
    reg = MetricsRegistry()
    reg.inc("server.requests", 4)
    reg.gauge("server.queue_depth").set(7)
    reg.observe("server.lat_ms", 3.0)
    path = str(tmp_path / "metrics.jsonl")
    reg.export_jsonl(path)
    reg.export_jsonl(path)  # append-only: one record per call
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[0]["metrics"]["server.requests"] == 4
    assert lines[0]["wall"] <= lines[1]["wall"]
    flat = reg.flat(prefix="server.")
    # counters + gauge values only, prefix stripped, histograms excluded
    assert flat == {"requests": 4, "queue_depth": 7}


def test_reset_zeroes_in_place_keeping_handles():
    reg = MetricsRegistry()
    c = reg.counter("t.n")
    c.inc(5)
    reg.reset()
    assert c.value == 0
    c.inc()  # the old handle still feeds the same registered series
    assert reg.snapshot()["t.n"] == 1


def test_disabled_registry_drops_writes():
    reg = MetricsRegistry()
    c = reg.counter("t.n")
    reg.enabled = False
    c.inc()
    reg.observe("t.h", 1.0)
    reg.enabled = True
    assert reg.snapshot()["t.n"] == 0


# -- end-to-end tracing through live serving ---------------------------------

def test_trace_id_propagation_through_serving_round_trip():
    """One request's trace id is observable at the client, at the
    batcher, and in the reply's stage breakdown — the acceptance
    criterion's single-request correlation."""
    im = _linear_model()
    with ClusterServing(im, batch_size=4) as srv:
        inq = InputQueue(port=srv.port)
        outq = OutputQueue(input_queue=inq)
        uid = inq.enqueue("t", t=np.ones((4,), np.float32))
        tid = inq.trace_id(uid)
        assert tid is not None and len(tid) == 16
        out = outq.query(uid, timeout=30)
        assert out is not None
        recs = trace.find(tid)
        wheres = [r.where for r in recs]
        assert "server.batch" in wheres  # the batcher saw this id
        assert "client" in wheres        # the client closed it out
        client_rec = recs[wheres.index("client")]
        # reply stages: the server's breakdown rode the reply header
        for stage in ("client.total_ms", "server.queue_wait_ms",
                      "server.inference_ms", "server.batch_size"):
            assert stage in client_rec.stages, stage
        assert (client_rec.stages["client.total_ms"]
                >= client_rec.stages["server.inference_ms"] > 0)
        # and the latency landed in the registry histograms
        snap = metrics.get_registry().snapshot()
        assert snap["client.request_ms"]["count"] >= 1
        assert snap["server.inference_ms"]["count"] >= 1
        assert snap["server.queue_wait_ms"]["count"] >= 1
        inq.close()


def test_frontend_propagates_caller_trace_id():
    im = _linear_model()
    with ClusterServing(im, batch_size=4) as srv:
        with HTTPFrontend(srv.host, srv.port) as fe:
            url = f"http://{fe.host}:{fe.port}"
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"instances": [[1, 2, 3, 4]]}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Trace-Id": "cafe0123cafe0123"})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.headers.get("X-Trace-Id") == "cafe0123cafe0123"
            recs = trace.find("cafe0123cafe0123")
            assert {r.where for r in recs} >= {"server.batch", "client"}


# -- /metrics + /stats --------------------------------------------------------

def test_frontend_metrics_endpoint_serves_prometheus():
    """GET /metrics is valid text exposition covering serving, client,
    and frontend series in one scrape (acceptance criterion)."""
    im = _linear_model()
    with ClusterServing(im, batch_size=4) as srv:
        with HTTPFrontend(srv.host, srv.port) as fe:
            url = f"http://{fe.host}:{fe.port}"
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"instances": [[1, 2, 3, 4]]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30):
                pass
            with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
    for needle in ("# TYPE zoo_server_requests counter",
                   "zoo_server_requests 1",
                   "zoo_server_replies 1",
                   "# TYPE zoo_server_queue_wait_ms histogram",
                   "# TYPE zoo_client_request_ms histogram",
                   "zoo_client_request_ms_count 1",
                   "# TYPE zoo_frontend_requests counter",
                   "zoo_frontend_requests 1",
                   'zoo_frontend_request_ms_count{route="/predict"} 1'):
        assert needle in text, needle
    # every non-comment line is "<name>[{labels}] <number>"
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name[0].isalpha()


def test_stats_namespaced_and_flat_backcompat():
    """The /stats key-collision fix: frontend and client counters are
    namespaced (``frontend.*`` / ``client.*``); the flat old-name view
    rides along for existing dashboards."""
    im = _linear_model()
    with ClusterServing(im, batch_size=4) as srv:
        with HTTPFrontend(srv.host, srv.port) as fe:
            url = f"http://{fe.host}:{fe.port}"
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"instances": [[1, 2, 3, 4]]}).encode(),
                headers={"Content-Type": "application/json"})
            for _ in range(2):
                with urllib.request.urlopen(req, timeout=30):
                    pass
            with urllib.request.urlopen(url + "/stats", timeout=10) as r:
                stats = json.load(r)
    assert stats["frontend.requests"] == 2
    assert stats["client.retries"] == 0
    # per-route latency summaries ride along
    assert stats["frontend.request_ms{route=/predict}"]["count"] == 2
    # flat back-compat view: the pre-registry key names still work
    assert stats["requests"] == 2 and stats["timeouts"] == 0
    for key in ("reconnects", "resends", "retries"):
        assert key in stats


def test_server_stats_healthy_invariant():
    """The docstring-backed invariant from ``ClusterServing.stats()``:
    requests == replies + errors + pending — nothing silently dropped.
    Also: the queue-depth gauge recorded a high-water mark."""
    im = _linear_model()
    with ClusterServing(im, batch_size=4) as srv:
        inq = InputQueue(port=srv.port)
        outq = OutputQueue(input_queue=inq)
        uids = [inq.enqueue("t", t=np.full((4,), float(i), np.float32))
                for i in range(6)]
        for uid in uids:
            assert outq.query(uid, timeout=30) is not None
        s = srv.stats()
        inq.close()
    assert "requests == replies + errors + pending" in \
        ClusterServing.stats.__doc__
    assert s["requests"] == s["replies"] + s["errors"] + s["pending"] == 6
    assert s["pending"] == 0
    assert s["queue_depth_max"] >= 1  # at least one request was queued
    assert s["shed_batches"] == 0
    # stop() zeroes the occupancy gauge: a stopped server (or a successor
    # sharing the process registry) must not report phantom queue depth
    assert srv.stats()["queue_depth"] == 0


@pytest.mark.faults
def test_shed_counts_surface_per_batch():
    """Deadline shedding shows up in stats() as shed_batches (how many
    batches shed anything) next to the cumulative shed count, and in the
    ``server.shed_per_batch`` histogram."""
    from analytics_zoo_tpu.core import faults
    im = _linear_model()
    with ClusterServing(im, batch_size=4, batch_timeout_ms=1) as srv:
        inq = InputQueue(port=srv.port)
        outq = OutputQueue(input_queue=inq)
        with faults.get_registry().armed("serving.model_latency", times=1,
                                         delay=0.4):
            blocker = inq.enqueue("t", t=np.ones((4,), np.float32))
            time.sleep(0.1)  # batcher is now sleeping in the armed delay
            doomed = inq.enqueue("t", deadline=0.05,
                                 t=np.ones((4,), np.float32))
            with pytest.raises(RuntimeError, match="deadline exceeded"):
                outq.query(doomed, timeout=30)
            assert outq.query(blocker, timeout=30) is not None
        s = srv.stats()
        inq.close()
    assert s["shed"] == 1 and s["shed_batches"] == 1
    snap = metrics.get_registry().snapshot()
    assert snap["server.shed_per_batch"]["count"] == 1


# -- faults counted through the registry --------------------------------------

@pytest.mark.faults
def test_fault_arming_and_firing_counted_in_registry():
    """Resilience tests can assert injections via public metrics
    (``faults.armed`` / ``faults.fired{point=...}``) instead of the
    fault registry's private state."""
    from analytics_zoo_tpu.core import faults
    reg = faults.get_registry()
    with reg.armed("feed.stall", times=2):
        reg.fire("feed.stall")
        reg.fire("feed.stall")
        reg.fire("feed.stall")  # spec exhausted: does not fire
    snap = metrics.get_registry().snapshot()
    assert snap["faults.armed{point=feed.stall}"] == 1
    assert snap["faults.fired{point=feed.stall}"] == 2


# -- training-loop instrumentation -------------------------------------------

def _tiny_fit(log_dir=None, epochs=2, n=128, batch=32):
    from analytics_zoo_tpu.orca.learn import Estimator
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = rng.normal(size=(n, 1)).astype(np.float32)
    est = Estimator.from_keras(
        nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(1)]),
        loss="mse", learning_rate=1e-3, log_dir=log_dir)
    hist = est.fit((x, y), epochs=epochs, batch_size=batch, verbose=False)
    return est, hist


def test_fit_reports_step_time_and_data_wait_split(tmp_path):
    """Acceptance criterion: fit() reports step-time and the data-wait /
    compute split in BOTH the registry snapshot and the SummaryWriter
    scalars."""
    init_orca_context("local")
    est, hist = _tiny_fit(log_dir=str(tmp_path), epochs=2)
    steps = 2 * (128 // 32)
    snap = metrics.get_registry().snapshot()
    assert snap["train.data_wait_ms"]["count"] == steps
    assert snap["train.steps"] == steps
    assert snap["train.samples"] == steps * 32
    for tag in ("step_time_ms", "data_wait_ms", "compute_ms",
                "samples_per_sec", "throughput", "loss"):
        scalars = est.get_train_summary(tag)
        assert len(scalars) == 2, tag  # one point per epoch
    # the split adds up: step ≈ data_wait + compute, per epoch
    step = dict(est.get_train_summary("step_time_ms"))
    wait = dict(est.get_train_summary("data_wait_ms"))
    comp = dict(est.get_train_summary("compute_ms"))
    for ep in step:
        assert step[ep] == pytest.approx(wait[ep] + comp[ep], rel=1e-3,
                                         abs=1e-3)


def test_checkpoint_save_restore_durations_recorded(tmp_path):
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    rng = np.random.default_rng(0)
    est = Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                               learning_rate=1e-3,
                               model_dir=str(tmp_path / "ckpt"))
    est.fit((rng.normal(size=(64, 4)).astype(np.float32),
             rng.normal(size=(64, 1)).astype(np.float32)),
            epochs=1, batch_size=32, verbose=False)
    est.save()
    est.load()
    snap = metrics.get_registry().snapshot()
    assert snap["checkpoint.save_ms"]["count"] >= 1
    assert snap["checkpoint.restore_ms"]["count"] >= 1


def test_streaming_feed_load_latency_and_counters():
    from analytics_zoo_tpu.data.stream import StreamingDataFeed
    mesh = init_orca_context("local")

    def load(i, rng=None):
        return {"x": np.full((4,), float(i), np.float32)}

    feed = StreamingDataFeed(num_samples=32, load_sample=load,
                             batch_size=8, shuffle=False, num_workers=2)
    n = sum(1 for _ in feed.epoch(mesh, 0))
    assert n == 4
    snap = metrics.get_registry().snapshot()
    assert snap["feed.load_ms"]["count"] == 32


def test_automl_trial_timings_recorded():
    from analytics_zoo_tpu.automl.search import RandomSearchEngine
    from analytics_zoo_tpu.automl import hp

    eng = RandomSearchEngine(metric_mode="min")
    eng.run(lambda cfg, report: cfg["x"] * 2,
            {"x": hp.uniform(0.0, 1.0)}, n_trials=3)
    snap = metrics.get_registry().snapshot()
    assert snap["automl.trial_ms"]["count"] == 3
    assert snap["automl.trials{status=done}"] == 3


# -- heartbeat payloads + supervisor aggregation ------------------------------

def test_heartbeat_file_carries_json_status(tmp_path):
    from analytics_zoo_tpu.core import ZooConfig
    hb = tmp_path / "hb"
    init_orca_context("local", config=ZooConfig(
        heartbeat_file=str(hb), heartbeat_interval=0.0))
    _tiny_fit(epochs=1)
    payload = json.loads(hb.read_text())
    assert payload["step"] == 4
    assert "loss" in payload and "samples_per_sec" in payload
    assert payload["wall"] <= time.time()


def test_gang_status_aggregates_heartbeats(tmp_path, caplog):
    """The supervisor turns heartbeat JSON payloads into one periodic
    gang-status log line and a metrics_w<rank>.jsonl per worker."""
    import logging
    from analytics_zoo_tpu.core.launcher import _GangStatus

    class FakeProc:
        def poll(self):
            return None

    hb_files = []
    for rank in range(2):
        hb = tmp_path / f"hb_w{rank}"
        hb.write_text(json.dumps({"step": 10 + rank, "loss": 0.5,
                                  "samples_per_sec": 100.0,
                                  "wall": time.time()}))
        hb_files.append(str(hb))
    status = _GangStatus(interval=0.0, metrics_dir=str(tmp_path / "m"))
    procs = [FakeProc(), FakeProc()]
    with caplog.at_level(logging.INFO, logger="analytics_zoo_tpu"):
        status.maybe_emit(procs, hb_files, attempt=0)
        status.maybe_emit(procs, hb_files, attempt=0)
    lines = [r.message for r in caplog.records
             if "gang status" in r.message]
    assert lines and "step=10" in lines[0] and "step=11" in lines[0]
    for rank in range(2):
        recs = [json.loads(ln) for ln in
                (tmp_path / "m" / f"metrics_w{rank}.jsonl").open()]
        assert len(recs) == 2
        assert recs[0]["rank"] == rank and recs[0]["step"] == 10 + rank


def test_gang_status_tolerates_legacy_touch_files(tmp_path):
    from analytics_zoo_tpu.core.launcher import _read_heartbeat_payload
    hb = tmp_path / "hb"
    hb.write_text("")  # the supervisor's baseline touch
    assert _read_heartbeat_payload(str(hb)) == {}
    assert _read_heartbeat_payload(str(tmp_path / "missing")) == {}
    hb.write_text("{half a json")  # torn write from a dying worker
    assert _read_heartbeat_payload(str(hb)) == {}


# The series PR 22 judged "wrong source, not read": an asynchronous
# dispatch timed as a step, an MFU over an epoch wall with feed start-up in
# it, a probe program's all-reduce, and the dispatch half of a copy.  The
# step clocks, the MFU and the collective times are the benchmark's.

def _stream_fit(**est_kw):
    from analytics_zoo_tpu.data import StreamingDataFeed
    from analytics_zoo_tpu.orca.learn import Estimator
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 4)).astype(np.float32)
    ys = rng.normal(size=(64, 1)).astype(np.float32)
    feed = StreamingDataFeed(
        64, lambda i, rng=None: {"x": xs[i], "y": ys[i]}, batch_size=16,
        shuffle=False, num_workers=2, workers="thread")
    est = Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                               learning_rate=1e-3, profile=True, **est_kw)
    est.fit(feed, epochs=2, batch_size=16, verbose=False, prefetch=2)
    return est


@pytest.mark.parametrize("series, est_kw", [
    ("train.step_ms", {}),
    ("train.mfu", {}),
    ("train.comm_ms", {"grad_compression": "none"}),
    ("feed.h2d_ms", {}),
])
def test_fit_moves_no_wrong_source_series(series, est_kw):
    init_orca_context("local")
    est = _stream_fit(**est_kw)
    assert est.compile_count == 1
    snap = metrics.get_registry().snapshot()
    assert snap["train.steps"] == 8
    if series.startswith("train."):
        assert series not in snap  # nothing in the program makes it
    else:
        # a process-backend fit in this process may have made the handle
        # (test_stream_shm.py); a thread-backend fit must not feed it
        assert snap.get(series, {"count": 0})["count"] == 0


@pytest.mark.parametrize("key", ["flops_per_sample", "peak_flops"])
def test_profile_rejects_unknown_keys(key):
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    with pytest.raises(ValueError, match=key):
        Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                             profile={key: 1e9})


# -- overhead guard -----------------------------------------------------------

@pytest.mark.slow
def test_step_loop_instrumentation_overhead_under_5_percent():
    """Acceptance criterion: the per-step telemetry (a histogram
    observe + a span record + two counter incs + the heartbeat check)
    costs < 5% of a tiny model's step loop.  Best-of-5 epochs per mode to shave CPU
    scheduling noise; compiled executables are warmed first."""
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 4)).astype(np.float32)
    y = rng.normal(size=(2048, 1)).astype(np.float32)
    est = Estimator.from_keras(
        nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(1)]),
        loss="mse", learning_rate=1e-3)
    est.fit((x, y), epochs=1, batch_size=16, verbose=False)  # compile

    reg = metrics.get_registry()

    def best_epoch_time(repeats=5):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.monotonic()
            est.fit((x, y), epochs=1, batch_size=16, verbose=False)
            best = min(best, time.monotonic() - t0)
        return best

    try:
        reg.enabled = False
        t_off = best_epoch_time()
        reg.enabled = True
        t_on = best_epoch_time()
    finally:
        reg.enabled = True
    # 5% relative plus a 5 ms absolute floor: at 128 steps/epoch the
    # telemetry budget is ~40 µs/step, two orders above its real cost
    assert t_on <= t_off * 1.05 + 0.005, (t_on, t_off)


def test_metric_catalog_matches_code():
    """The docs/observability.md catalog must track the code: a series
    registered but undocumented (or documented but gone) fails here —
    the catalog drifted risk-free for four PRs before this guard."""
    import pathlib
    import subprocess
    import sys
    script = (pathlib.Path(__file__).resolve().parent.parent
              / "dev" / "check_metric_docs.py")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
