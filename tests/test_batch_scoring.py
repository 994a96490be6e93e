"""Offline batch scoring (ISSUE 13): the BatchScorer job engine —
journaled resumable shards through the ReplicaSet as klass="batch"
traffic, shadow validation against a pinned candidate version, and
zero-downtime promotion via ModelRegistry.promote().

Resilience coverage: shard-level fault injection (``batch.shard_fail``),
a HARD client kill (SIGKILL of a zoo-score subprocess mid-job) followed
by resume, a replica hard-kill mid-job, and crc rejection of corrupted
shard bytes — in every case the concatenated output must be row-for-row
identical to an uninterrupted run (zero lost, zero duplicated rows).

The ≥50k-row acceptance run (replica kill + client crash + resume +
concurrent-interactive p99 guard) is ``slow``-marked.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.core import metrics as metrics_lib
from analytics_zoo_tpu.core.faults import FaultRegistry, get_registry
from analytics_zoo_tpu.serving import (BatchJobError, BatchScorer,
                                       ClusterServing, ModelRegistry,
                                       ReplicaSet, read_output)
from analytics_zoo_tpu.serving.batch import _read_journal
from analytics_zoo_tpu.serving.client import RetryPolicy

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Model:
    """Multiplies by k; optional per-batch delay to stretch jobs."""

    def __init__(self, k: float = 2.0, delay: float = 0.0):
        self.k = k
        self.delay = delay

    def predict(self, x):
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(x) * self.k


def _fast_retry(**kw) -> RetryPolicy:
    kw.setdefault("max_attempts", 3)
    kw.setdefault("base_delay", 0.02)
    kw.setdefault("max_delay", 0.1)
    kw.setdefault("seed", 0)
    return RetryPolicy(**kw)


def _serve(model=None, faults=None, port=0, **kw) -> ClusterServing:
    kw.setdefault("batch_size", 8)
    kw.setdefault("batch_timeout_ms", 2)
    return ClusterServing(model or _Model(), port=port, faults=faults,
                          **kw).start()


def _rows(n, d=4, seed=0):
    return np.random.default_rng(seed).normal(
        size=(n, d)).astype(np.float32)


# -- the basic job ------------------------------------------------------------

def test_job_row_exact_through_two_replicas(tmp_path):
    """203 rows / shard 50 through a 2-replica pool: the journaled
    output is row-for-row the model's answer, the journal carries a
    verifiable crc per shard, and the batch.* counters add up."""
    rows = _rows(203)
    with _serve() as s1, _serve() as s2:
        rs = ReplicaSet([(s1.host, s1.port), (s2.host, s2.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=50,
                         max_inflight=8, retry=_fast_retry()) as sc:
            rep = sc.score(rows)
        rs.close()
    assert (rep.rows, rep.n_shards, rep.scored_shards) == (203, 5, 5)
    assert rep.resumed_shards == 0 and rep.promoted is None
    np.testing.assert_allclose(rep.output(), rows * 2.0, rtol=1e-6)
    entries = _read_journal(str(tmp_path / "job"))
    assert sorted(e["shard"] for e in entries) == list(range(5))
    # every journal entry's crc matches the bytes on disk
    from analytics_zoo_tpu.serving.batch import _crc32_file
    for e in entries:
        assert _crc32_file(str(tmp_path / "job" / e["file"])) \
            == e["crc32"]
    snap = metrics_lib.get_registry().snapshot()
    assert snap.get("batch.rows") == 203
    assert snap["batch.inflight"]["value"] == 0  # window fully drained


def test_read_output_names_missing_shards(tmp_path):
    rows = _rows(100)
    with _serve() as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=25,
                         retry=_fast_retry()) as sc:
            sc.score(rows)
        rs.close()
    # drop shard 1's journal line: the gap must be named, not glossed
    jpath = tmp_path / "job" / "journal.jsonl"
    lines = [l for l in jpath.read_text().splitlines()
             if json.loads(l)["shard"] != 1]
    jpath.write_text("\n".join(lines) + "\n")
    with pytest.raises(BatchJobError, match=r"missing shard\(s\) \[1\]"):
        read_output(str(tmp_path / "job"))


def test_shard_fail_injection_retries_and_recovers(tmp_path):
    rows = _rows(160)
    with _serve() as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=40,
                         retry=_fast_retry()) as sc:
            with get_registry().armed("batch.shard_fail", times=2):
                rep = sc.score(rows)
        rs.close()
    assert rep.retries == 2
    np.testing.assert_allclose(rep.output(), rows * 2.0, rtol=1e-6)
    assert metrics_lib.get_registry().snapshot().get("batch.retries") == 2


# -- crash + resume -----------------------------------------------------------

def test_abort_dumps_flight_record_then_resume_is_row_identical(
        tmp_path, monkeypatch):
    """Retries exhausted mid-job → BatchJobError + a ``batch_abort``
    flight record; a resume skips the journaled prefix and the final
    output equals an UNINTERRUPTED run of the same job, row for row."""
    monkeypatch.setenv("ZOO_FLIGHTREC_DIR", str(tmp_path / "rec"))
    rows = _rows(200)
    with _serve() as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        # the uninterrupted reference run
        with BatchScorer(rs, str(tmp_path / "ref"), shard_size=40,
                         retry=_fast_retry()) as ref_sc:
            want = ref_sc.score(rows).output()
        sc = BatchScorer(rs, str(tmp_path / "job"), shard_size=40,
                         retry=_fast_retry())
        with get_registry().armed("batch.shard_fail", times=100,
                                  after=2):
            with pytest.raises(BatchJobError, match="shard 2"):
                sc.score(rows)
        dumps = os.listdir(tmp_path / "rec")
        assert any(f.startswith("flightrec") for f in dumps), dumps
        rec = json.load(open(tmp_path / "rec" / sorted(dumps)[0]))
        assert rec["reason"] == "batch_abort"

        rep = sc.score(rows, resume=True)
        sc.close()
        rs.close()
    assert rep.resumed_shards == 2 and rep.scored_shards == 3
    got = rep.output()
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)  # row-for-row identical
    assert metrics_lib.get_registry().snapshot().get(
        "batch.resumed_shards") == 2


def test_hard_client_kill_then_resume_is_row_identical(tmp_path):
    """THE client-crash leg: a zoo-score subprocess is SIGKILLed
    mid-job; resuming the same job directory in-process re-scores only
    the unjournaled tail and the output matches an uninterrupted run
    row for row — zero lost, zero duplicated."""
    rows = _rows(400)
    np.save(tmp_path / "rows.npy", rows)
    model = _Model(delay=0.02)  # stretch the job so the kill lands mid-way
    with _serve(model) as srv:
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.batch",
             "--backend", f"{srv.host}:{srv.port}",
             "--input", str(tmp_path / "rows.npy"),
             "--out", str(tmp_path / "job"), "--shard-size", "40",
             "--max-inflight", "4"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        try:
            # wait for a partial journal (some, not all, of 10 shards)
            deadline = time.monotonic() + 120
            while True:
                n_done = len(_read_journal(str(tmp_path / "job")))
                if 1 <= n_done <= 8:
                    break
                assert proc.poll() is None, \
                    "job finished before the kill landed — slow it down"
                assert time.monotonic() < deadline
                time.sleep(0.02)
            os.kill(proc.pid, signal.SIGKILL)
        finally:
            proc.wait(timeout=30)
        model.delay = 0.0  # the resume leg can run at full speed
        rs = ReplicaSet([(srv.host, srv.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=40,
                         max_inflight=4, retry=_fast_retry()) as sc:
            rep = sc.score(rows, resume=True)
        rs.close()
    assert rep.resumed_shards >= 1      # the pre-kill prefix survived
    assert rep.scored_shards >= 1       # and the tail was re-scored
    assert rep.resumed_shards + rep.scored_shards == rep.n_shards == 10
    np.testing.assert_allclose(rep.output(), rows * 2.0, rtol=1e-6)


def test_resume_rejects_config_mismatch(tmp_path):
    rows = _rows(100)
    with _serve() as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=25,
                         retry=_fast_retry()) as sc:
            sc.score(rows)
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=50,
                         retry=_fast_retry()) as sc2:
            with pytest.raises(BatchJobError, match="config mismatch"):
                sc2.score(rows, resume=True)
        rs.close()


def test_resume_rescores_corrupted_shard(tmp_path):
    """Bit-rot in a journaled shard file must not be trusted: the crc
    check fails, the shard re-scores, and the output stays exact."""
    rows = _rows(120)
    with _serve() as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        sc = BatchScorer(rs, str(tmp_path / "job"), shard_size=40,
                         retry=_fast_retry())
        sc.score(rows)
        bad = tmp_path / "job" / "shard_00001.npz"
        blob = bytearray(bad.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        bad.write_bytes(bytes(blob))
        rep = sc.score(rows, resume=True)
        sc.close()
        rs.close()
    assert rep.resumed_shards == 2 and rep.scored_shards == 1
    np.testing.assert_allclose(rep.output(), rows * 2.0, rtol=1e-6)


# -- replica failure under a running job --------------------------------------

def test_replica_hard_kill_mid_job_zero_lost_rows(tmp_path):
    """2 replicas, one dies hard (``serving.replica_down``) while the
    job streams: the router fails the in-flight rows over and the job
    completes with every row scored exactly once."""
    rows = _rows(240)
    f1 = FaultRegistry()
    s1 = _serve(_Model(delay=0.005), faults=f1)
    s2 = _serve(_Model(delay=0.005))
    rs = ReplicaSet([(s1.host, s1.port), (s2.host, s2.port)],
                    retry=_fast_retry(max_attempts=4),
                    health_interval=0.08, health_timeout=0.5,
                    breaker_threshold=3, breaker_reset_s=0.2)
    try:
        sc = BatchScorer(rs, str(tmp_path / "job"), shard_size=30,
                         max_inflight=4,
                         retry=_fast_retry(max_attempts=4),
                         request_timeout=30.0)
        result = {}

        def run():
            result["report"] = sc.score(rows)

        t = threading.Thread(target=run)
        t.start()
        # kill replica 1 once the job is demonstrably in flight
        deadline = time.monotonic() + 60
        while len(_read_journal(str(tmp_path / "job"))) < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        f1.enable("serving.replica_down", times=1)
        t.join(timeout=120)
        assert not t.is_alive(), "job wedged after the replica kill"
        sc.close()
    finally:
        rs.close()
        s2.stop()
        s1.stop()
    rep = result["report"]
    assert rep.rows == 240 and rep.n_shards == 8
    np.testing.assert_allclose(rep.output(), rows * 2.0, rtol=1e-6)


def test_interactive_clients_see_zero_errors_while_a_job_streams(tmp_path):
    """Batch and interactive traffic share one 2-replica pool: while a
    journaled job streams ``klass="batch"`` rows through it, closed-loop
    interactive clients get every answer (zero client-visible errors,
    each the model's answer for its own row) and the job's output is
    row-for-row exact."""
    rows = _rows(600)
    with _serve(_Model(delay=0.002)) as s1, \
            _serve(_Model(delay=0.002)) as s2:
        rs = ReplicaSet([(s1.host, s1.port), (s2.host, s2.port)],
                        retry=_fast_retry(max_attempts=4))
        job_done = threading.Event()
        errors, served = [], []

        def interactive(i):
            x = rows[i]
            while not job_done.is_set():
                try:
                    out = rs.predict(x, deadline=15.0, klass="interactive")
                except Exception as e:  # noqa: BLE001 — the failure record
                    errors.append(f"{type(e).__name__}: {e}"[:200])
                    continue
                if out is None:
                    errors.append("timeout")
                elif not np.allclose(out, x * 2.0, rtol=1e-6):
                    errors.append(f"wrong answer for row {i}: {out}")
                else:
                    served.append(i)

        clients = [threading.Thread(target=interactive, args=(i,))
                   for i in range(3)]
        try:
            for t in clients:
                t.start()
            with BatchScorer(rs, str(tmp_path / "job"), shard_size=50,
                             max_inflight=4, retry=_fast_retry(),
                             request_timeout=30.0) as sc:
                rep = sc.score(rows)
        finally:
            job_done.set()
            for t in clients:
                t.join(timeout=30.0)
            rs.close()
        assert not any(t.is_alive() for t in clients)
    assert errors == [], errors[:5]
    assert len(served) > 0, "no interactive request ran beside the job"
    assert (rep.rows, rep.n_shards, rep.scored_shards) == (600, 12, 12)
    np.testing.assert_allclose(rep.output(), rows * 2.0, rtol=1e-6)


# -- shadow validation + promotion --------------------------------------------

def test_shadow_validation_promotes_identical_candidate(tmp_path):
    """Candidate == active → zero deltas → the gate passes and the
    candidate goes live through ModelRegistry.promote() (counted in
    registry.swaps), with interactive clients serving throughout."""
    rows = _rows(150)
    reg = ModelRegistry()
    reg.register("default", _Model(2.0))                     # v1 active
    reg.register("default", _Model(2.0), make_active=False)  # v2 shadow
    with ClusterServing(models=reg, batch_size=8,
                        batch_timeout_ms=2) as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=50,
                         retry=_fast_retry()) as sc:
            rep = sc.score(rows, shadow_version="v2",
                           promote_if=lambda d:
                               d["mismatch_rate"] == 0.0
                               and d["max_abs_delta"] < 1e-6,
                           registry=reg)
        assert rep.promoted == "v2"
        assert reg.active_version("default") == "v2"
        assert rep.deltas.rows == 150
        assert rep.deltas.max_abs_delta == 0.0
        # both versions' outputs were journaled
        np.testing.assert_allclose(
            read_output(str(tmp_path / "job"), key="y_shadow"),
            rows * 2.0, rtol=1e-6)
        # zero client-visible errors: the promoted version serves
        out = rs.predict(rows[0], deadline=10.0)
        assert out is not None
        rs.close()
    snap = metrics_lib.get_registry().snapshot()
    assert snap.get("registry.swaps") == 1


def test_shadow_validation_gate_rejects_drifted_candidate(tmp_path):
    """Candidate with different math → nonzero deltas → the gate holds
    and the active version stays put."""
    rows = _rows(120)
    reg = ModelRegistry()
    reg.register("default", _Model(2.0))
    reg.register("default", _Model(-2.0), make_active=False)  # drifted
    with ClusterServing(models=reg, batch_size=8,
                        batch_timeout_ms=2) as srv:
        rs = ReplicaSet([(srv.host, srv.port)])
        with BatchScorer(rs, str(tmp_path / "job"), shard_size=60,
                         retry=_fast_retry()) as sc:
            rep = sc.score(rows, shadow_version="v2",
                           promote_if=lambda d:
                               d["mismatch_rate"] == 0.0,
                           registry=reg)
        rs.close()
    assert rep.promoted is None
    assert reg.active_version("default") == "v1"
    assert rep.deltas.mismatch_rate > 0.0
    assert rep.deltas.max_abs_delta > 0.0


def test_promote_requires_loaded_version_and_is_idempotent():
    reg = ModelRegistry()
    reg.register("m", _Model(1.0), version="a")
    reg.register("m", _Model(1.0), version="b", make_active=False)
    with pytest.raises(KeyError):
        reg.promote("m", "zzz")
    assert reg.promote("m", "b") == "b"
    assert reg.active_version("m") == "b"
    # promoting the active version is a no-op (no extra swap counted)
    before = metrics_lib.get_registry().snapshot().get("registry.swaps")
    assert reg.promote("m", "b") == "b"
    assert metrics_lib.get_registry().snapshot().get(
        "registry.swaps") == before


# -- THE acceptance (slow) ----------------------------------------------------

@pytest.mark.slow
def test_acceptance_50k_job_survives_kill_and_crash_with_p99_guard(
        tmp_path):
    """ISSUE 13 acceptance: a 50k-row job through a 2-replica pool
    survives a mid-job replica hard-kill AND a client crash+resume with
    zero lost/duplicated rows, while concurrent interactive p99 stays
    within 1.5x its batch-free baseline (per-class admission)."""
    rows = _rows(50_000, d=4)
    f1 = FaultRegistry()
    s1 = _serve(_Model(), faults=f1)
    s2 = _serve(_Model())
    ports = (s1.port, s2.port)
    rs = ReplicaSet([(s1.host, p) for p in ports],
                    retry=_fast_retry(max_attempts=4),
                    health_interval=0.08, health_timeout=0.5,
                    breaker_threshold=3, breaker_reset_s=0.2)
    x1 = rows[0]

    def p99_of(samples):
        return float(np.percentile(np.asarray(samples), 99))

    def interactive(n, out):
        for _ in range(n):
            t0 = time.monotonic()
            r = rs.predict(x1, deadline=15.0, klass="interactive")
            assert r is not None
            out.append((time.monotonic() - t0) * 1000.0)

    try:
        # batch-free interactive baseline
        base = []
        interactive(300, base)
        baseline_p99 = p99_of(base)

        sc = BatchScorer(rs, str(tmp_path / "job"), shard_size=1000,
                         max_inflight=4,
                         retry=_fast_retry(max_attempts=4))
        state = {}
        lat = []
        stop = threading.Event()

        def closed_loop():
            while not stop.is_set():
                t0 = time.monotonic()
                r = rs.predict(x1, deadline=15.0, klass="interactive")
                assert r is not None
                lat.append((time.monotonic() - t0) * 1000.0)

        def run_job():
            try:
                sc.score(rows)
            except BatchJobError as e:
                state["abort"] = e  # the scripted client crash

        loader = threading.Thread(target=closed_loop)
        job = threading.Thread(target=run_job)
        loader.start()
        job.start()
        # phase 1: replica hard-kill once the job is under way
        deadline = time.monotonic() + 300
        while len(_read_journal(str(tmp_path / "job"))) < 5:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        f1.enable("serving.replica_down", times=1)
        # phase 2: scripted client crash a few shards later
        while len(_read_journal(str(tmp_path / "job"))) < 20:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        get_registry().enable("batch.shard_fail", times=100)
        job.join(timeout=300)
        assert not job.is_alive()
        get_registry().disable("batch.shard_fail")
        assert isinstance(state.get("abort"), BatchJobError)
        # resume to completion (one replica may still be down — fine)
        rep = sc.score(rows, resume=True)
        stop.set()
        loader.join(timeout=60)
        sc.close()
    finally:
        rs.close()
        s2.stop()
        s1.stop()
    assert rep.resumed_shards >= 20
    assert rep.resumed_shards + rep.scored_shards == rep.n_shards == 50
    out = rep.output()
    assert out.shape == rows.shape  # zero lost / duplicated rows
    np.testing.assert_allclose(out, rows * 2.0, rtol=1e-6)
    assert lat, "no interactive samples under batch load"
    assert p99_of(lat) <= 1.5 * max(baseline_p99, 5.0), \
        (p99_of(lat), baseline_p99)
