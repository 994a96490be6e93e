"""High-availability serving (ISSUE 5): replicated backends behind the
ReplicaSet router — health-checked routing, circuit breakers, failover,
hedged reads, graceful drain, and THE acceptance scenario: hard-kill a
replica under sustained load with zero client-visible failures, then a
rolling restart that drops nothing.

Determinism: faults come from per-server private FaultRegistry
instances (or the scoped global registry), retry policies are seeded,
and no injected delay exceeds 0.5 s.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.core import metrics as metrics_lib
from analytics_zoo_tpu.core import trace as trace_lib
from analytics_zoo_tpu.core.faults import FaultRegistry, get_registry
from analytics_zoo_tpu.serving import (CircuitBreaker, ClusterServing,
                                       HTTPFrontend, InputQueue,
                                       OutputQueue, ReplicaSet)
from analytics_zoo_tpu.serving.client import RetryPolicy

pytestmark = pytest.mark.faults


class _Model:
    """Doubles its input; counts the rows it actually ran."""

    def __init__(self, delay: float = 0.0):
        self.delay = delay
        self.calls = []
        self._lock = threading.Lock()

    def predict(self, x):
        if self.delay:
            time.sleep(self.delay)
        with self._lock:
            self.calls.append(np.asarray(x).shape[0])
        return np.asarray(x) * 2.0

    @property
    def rows_seen(self) -> int:
        with self._lock:
            return sum(self.calls)


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


def _restart_on_port(model, port, faults=None, timeout=15.0, **kw):
    """Start a replacement server on a just-released port (the OS may
    need a beat to free it)."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            return _serve(model, faults=faults, port=port, **kw)
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.05)


# -- circuit breaker (pure unit) ----------------------------------------------

def test_breaker_opens_after_threshold_and_recloses():
    b = CircuitBreaker(threshold=3, reset_s=0.1)
    assert b.state == "closed" and b.allow()
    b.record_failure(); b.record_failure()
    assert b.state == "closed" and b.allow()  # under threshold
    b.record_failure()
    assert b.state == "open" and b.opens == 1
    assert not b.allow()                      # open: fail fast
    time.sleep(0.12)
    assert b.allow()                          # reset elapsed: half-open probe
    assert b.state == "half-open"
    b.record_success()
    assert b.state == "closed" and b.allow()
    assert b.consecutive_failures == 0


def test_breaker_failed_probe_reopens_with_backoff():
    b = CircuitBreaker(threshold=1, reset_s=0.05, backoff_factor=2.0,
                       max_reset_s=1.0)
    b.record_failure()
    assert b.state == "open"
    time.sleep(0.06)
    assert b.allow()                          # half-open probe
    b.record_failure()                        # probe failed
    assert b.state == "open" and b.opens == 2
    assert b._timeout == pytest.approx(0.1)   # grew 2x
    assert not b.allow()                      # new window not elapsed
    time.sleep(0.11)
    assert b.allow()
    b.record_success()
    assert b.state == "closed" and b._timeout == pytest.approx(0.05)


def test_breaker_half_open_probe_budget_is_rate_limited():
    b = CircuitBreaker(threshold=1, reset_s=0.1)
    b.record_failure()
    time.sleep(0.11)
    assert b.allow()          # the transition probe
    assert not b.allow()      # second caller inside the window: rejected


# -- health pings -------------------------------------------------------------

def test_ping_round_trip_carries_state_and_depth():
    with _serve() as srv:
        iq = InputQueue(srv.host, srv.port, retry=_fast_retry())
        pong = iq.conn.ping(timeout=2.0)
        assert pong and pong.get("pong") is True
        assert pong["state"] == "serving"
        assert "queue_depth" in pong
        assert srv.stats()["pings"] == 1
        # pings never touch the request invariant
        s = srv.stats()
        assert s["requests"] == s["replies"] == s["errors"] == 0
        iq.close()


def test_health_fail_fault_swallows_the_pong():
    faults = get_registry()
    with _serve() as srv:
        iq = InputQueue(srv.host, srv.port, retry=_fast_retry())
        with faults.armed("serving.health_fail", times=1):
            assert iq.conn.ping(timeout=0.4) is None  # probe lost
        assert faults.fired("serving.health_fail") == 1
        assert iq.conn.ping(timeout=2.0) is not None  # next probe lands
        iq.close()


def test_wedged_assembly_fails_the_ping_by_timeout():
    """The reason pings ride the queue: an armed assembly-stage latency
    (the wedged-but-connected backend) delays the pong past the probe
    timeout even though the socket is perfectly healthy."""
    private = FaultRegistry()
    with _serve(faults=private) as srv:
        iq = InputQueue(srv.host, srv.port, retry=_fast_retry())
        assert iq.conn.ping(timeout=2.0) is not None  # healthy baseline
        private.enable("serving.model_latency", times=1, delay=0.4)
        assert iq.conn.ping(timeout=0.15) is None     # wedged: no pong
        iq.close()


# -- drain + admission control ------------------------------------------------

def test_drain_rejects_new_work_retryably_and_finishes_in_flight():
    model = _Model(delay=0.2)
    srv = _serve(model, batch_size=1, batch_timeout_ms=1)
    iq = InputQueue(srv.host, srv.port, retry=_fast_retry(max_attempts=2))
    oq = OutputQueue(input_queue=iq)
    x = np.arange(4, dtype=np.float32)
    uid_in = iq.enqueue("in-flight", t=x)
    time.sleep(0.05)  # the request reaches the pipeline
    assert srv.drain(wait=False)
    assert srv.state == "draining"
    # a health pong reports the drain BEFORE any rejection happens
    assert iq.conn.ping(timeout=2.0)["state"] == "draining"
    uid_new = iq.enqueue("late", t=x)
    with pytest.raises(RuntimeError, match="draining"):
        oq.query(uid_new, timeout=10.0)
    # the admitted request still completes, and drain(wait) observes it
    assert srv.drain(wait=True, timeout=10.0)
    np.testing.assert_allclose(oq.query(uid_in, timeout=10.0), x * 2.0)
    s = srv.stats()
    assert s["draining_rejected"] >= 1
    assert s["requests"] == s["replies"] + s["errors"]
    srv.stop()
    iq.close()


def test_admission_queue_limit_rejects_retryably():
    private = FaultRegistry()
    model = _Model()
    with _serve(model, batch_size=1, batch_timeout_ms=1,
                admission_queue_limit=1, faults=private) as srv:
        iq = InputQueue(srv.host, srv.port,
                        retry=_fast_retry(max_attempts=1))
        oq = OutputQueue(input_queue=iq)
        x = np.arange(4, dtype=np.float32)
        # wedge assembly so the queue actually builds depth
        private.enable("serving.model_latency", times=1, delay=0.4)
        uid_a = iq.enqueue("a", t=x)      # popped, wedged in assembly
        time.sleep(0.05)
        uid_b = iq.enqueue("b", t=x)      # sits in the queue (depth 1)
        time.sleep(0.05)
        uid_c = iq.enqueue("c", t=x)      # over the soft cap
        with pytest.raises(RuntimeError, match="queue full"):
            oq.query(uid_c, timeout=10.0)
        assert oq.query(uid_a, timeout=10.0) is not None
        assert oq.query(uid_b, timeout=10.0) is not None
        assert srv.stats()["admission_rejected"] >= 1
        iq.close()


def test_admission_rejects_unattainable_deadline():
    """A request whose whole budget is below the observed queue wait is
    rejected at the door — not queued, not inferred, not shed later."""
    private = FaultRegistry()
    model = _Model()
    with _serve(model, batch_size=1, batch_timeout_ms=1,
                faults=private) as srv:
        iq = InputQueue(srv.host, srv.port,
                        retry=_fast_retry(max_attempts=1))
        oq = OutputQueue(input_queue=iq)
        x = np.arange(4, dtype=np.float32)
        private.enable("serving.model_latency", times=3, delay=0.3)
        uid_a = iq.enqueue("a", t=x)          # wedges assembly
        time.sleep(0.02)
        uid_b = iq.enqueue("b", t=x)          # waits ~0.3s -> EWMA rises
        rows_before = model.rows_seen
        # wait until B was assembled (EWMA now reflects its queue wait)
        deadline = time.monotonic() + 5
        while model.rows_seen < rows_before + 1 and \
                time.monotonic() < deadline:
            time.sleep(0.02)
        uid_c = iq.enqueue("c", t=x)          # keeps queue depth >= 1
        uid_d = iq.enqueue("doomed", deadline=0.01, t=x)
        with pytest.raises(RuntimeError, match="deadline unattainable"):
            oq.query(uid_d, timeout=10.0)
        for uid in (uid_a, uid_b, uid_c):
            assert oq.query(uid, timeout=10.0) is not None
        s = srv.stats()
        assert s["admission_rejected"] == 1
        assert s["requests"] == s["replies"] + s["errors"]
        iq.close()


# -- replica set: routing + health --------------------------------------------

def _replica_set(servers, **kw):
    kw.setdefault("retry", _fast_retry())
    kw.setdefault("health_interval", 0.08)
    kw.setdefault("health_timeout", 0.5)
    kw.setdefault("breaker_reset_s", 0.25)
    return ReplicaSet([(s.host, s.port) for s in servers], **kw)


def test_replica_set_routes_and_both_replicas_serve():
    m1, m2 = _Model(delay=0.03), _Model(delay=0.03)
    s1, s2 = _serve(m1, batch_size=1, batch_timeout_ms=1), \
        _serve(m2, batch_size=1, batch_timeout_ms=1)
    rs = _replica_set([s1, s2])
    errors = []

    def client(i):
        x = np.full((4,), float(i), np.float32)
        for _ in range(6):
            try:
                np.testing.assert_allclose(rs.predict(x, timeout=15.0),
                                           x * 2.0)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

    try:
        # concurrent clients: least-pending routing only spreads load
        # when requests overlap (a serial loop correctly pins the
        # emptiest — i.e. always the same — replica)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[:3]
        assert m1.rows_seen > 0 and m2.rows_seen > 0
        assert m1.rows_seen + m2.rows_seen == 24
        hz = rs.healthz()
        assert hz["status"] == "ok"
        assert all(v["available"] for v in hz["replicas"].values())
    finally:
        rs.close()
        s1.stop()
        s2.stop()


def test_health_checker_ejects_wedged_replica_and_readmits_it():
    """Arm assembly latency on one replica: its pongs stop arriving, the
    health checker ejects it, traffic flows to the sibling with zero
    failures, and the first pong after the wedge clears re-admits it."""
    private = FaultRegistry()
    m1, m2 = _Model(), _Model()
    s1 = _serve(m1, faults=private)
    s2 = _serve(m2)
    rs = _replica_set([s1, s2], health_timeout=0.15)
    name1 = f"{s1.host}:{s1.port}"
    try:
        x = np.arange(4, dtype=np.float32)
        assert rs.predict(x, timeout=10.0) is not None
        private.enable("serving.model_latency", times=5, delay=0.4)
        deadline = time.monotonic() + 10
        while rs.healthz()["replicas"][name1]["healthy"]:
            assert time.monotonic() < deadline, "replica never ejected"
            time.sleep(0.02)
        # ejected: every request is served by the sibling, none fail
        for _ in range(6):
            assert rs.predict(x, timeout=10.0) is not None
        snap = metrics_lib.get_registry().snapshot()
        assert snap[f"router.health_ejections{{replica={name1}}}"] >= 1
        # charges exhaust -> pongs flow again -> re-admitted
        deadline = time.monotonic() + 15
        while not rs.healthz()["replicas"][name1]["healthy"]:
            assert time.monotonic() < deadline, "replica never re-admitted"
            time.sleep(0.05)
    finally:
        rs.close()
        s1.stop()
        s2.stop()


def test_hedged_read_wins_on_a_slow_replica():
    """A deadline'd request that has waited ``hedge_ms`` is re-enqueued
    on the second replica; the fast replica's answer wins."""
    # pin the pick order: least-pending ties break on the name STRING,
    # so give the slow model the lexicographically smaller address
    ports = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    ports.sort(key=lambda p: f"127.0.0.1:{p}")
    slow, fast = _Model(delay=0.4), _Model()
    s1 = _serve(slow, port=ports[0], batch_size=1, batch_timeout_ms=1)
    s2 = _serve(fast, port=ports[1], batch_size=1, batch_timeout_ms=1)
    rs = _replica_set([s1, s2], hedge_ms=50.0, start_health=False)
    try:
        x = np.arange(4, dtype=np.float32)
        tid = trace_lib.new_trace_id()
        t0 = time.monotonic()
        out = rs.predict(x, deadline=5.0, trace_id=tid, timeout=10.0)
        elapsed = time.monotonic() - t0
        np.testing.assert_allclose(out, x * 2.0)
        assert fast.rows_seen >= 1          # the hedge replica answered
        assert elapsed < 0.35, elapsed      # won before the slow reply
        # the slow replica WAS picked first: its model is still inside
        # the 0.4s sleep at win time, so poll for its (duplicate) call
        deadline = time.monotonic() + 5
        while slow.rows_seen < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert slow.rows_seen >= 1
        snap = metrics_lib.get_registry().snapshot()
        assert snap["router.hedges"] >= 1
        assert snap["router.hedge_wins"] >= 1
        # the trace names the replica that actually served it
        router_recs = [r for r in trace_lib.find(tid)
                       if r.where == "router"]
        assert router_recs, "router trace record missing"
        assert router_recs[-1].stages["router.replica"] == \
            f"{s2.host}:{s2.port}"
    finally:
        rs.close()
        s1.stop()
        s2.stop()


# -- client replay cap (satellite) --------------------------------------------

def test_replay_cap_fails_uid_visibly_instead_of_looping_forever():
    """A backend that drops the connection on every delivery would make
    ``_replay_inflight`` resend the same frame on every reconnect,
    forever.  The cap (RetryPolicy.max_attempts) fails the uid with a
    visible error reply and surfaces ``client.replayed``."""
    faults = get_registry()
    model = _Model()
    with _serve(model) as srv:
        retry = _fast_retry(max_attempts=3)
        iq = InputQueue(srv.host, srv.port, retry=retry)
        oq = OutputQueue(input_queue=iq)
        with faults.armed("serving.conn_drop"):  # drop EVERY frame
            uid = iq.enqueue("t", t=np.ones(4, np.float32))
            with pytest.raises(RuntimeError,
                               match="replay budget exhausted"):
                oq.query(uid, timeout=30.0)
        assert iq.conn.stats["replayed"] == retry.max_attempts
        snap = metrics_lib.get_registry().snapshot()
        assert snap["client.replayed"] == retry.max_attempts
        # the connection itself is still usable afterwards
        uid2 = iq.enqueue("after", t=np.ones(4, np.float32))
        assert oq.query(uid2, timeout=20.0) is not None
        iq.close()


# -- shutdown races (satellite) -----------------------------------------------

def test_stop_during_client_reconnect_terminates_bounded():
    """``ClusterServing.stop()`` racing a client mid-``reconnect()``:
    every query thread terminates within a bounded time — served, an
    explicit error, or a timeout — and the server's counter invariant
    holds."""
    model = _Model(delay=0.2)
    faults = get_registry()
    srv = _serve(model, batch_size=1, batch_timeout_ms=1)
    iq = InputQueue(srv.host, srv.port, retry=_fast_retry())
    oq = OutputQueue(input_queue=iq)
    x = np.arange(4, dtype=np.float32)
    uids = [iq.enqueue(f"r{i}", t=x) for i in range(3)]
    # the NEXT frame the server sees kills this connection: the client
    # enters its reconnect path while we stop() the server underneath
    faults.enable("serving.conn_drop", times=1)
    iq.enqueue("dropper", t=x)
    outcomes = {}

    def q(uid):
        try:
            outcomes[uid] = ("ok", oq.query(uid, timeout=10.0))
        except (RuntimeError, OSError) as e:
            outcomes[uid] = ("error", str(e))

    threads = [threading.Thread(target=q, args=(u,)) for u in uids]
    for t in threads:
        t.start()
    time.sleep(0.05)
    srv.stop()
    for t in threads:
        t.join(timeout=20)
    faults.disable("serving.conn_drop")  # the charge may be unspent
    assert not any(t.is_alive() for t in threads), "hung query() calls"
    assert len(outcomes) == 3, outcomes
    s = srv.stats()
    assert s["pending"] == 0
    assert s["requests"] == s["replies"] + s["errors"]
    iq.close()


def test_frontend_close_with_hedged_request_in_flight_is_bounded():
    """``HTTPFrontend.close()`` while a hedged request is outstanding on
    BOTH replicas: the in-flight predict raises promptly instead of
    waiting out its timeout, and close() itself returns."""
    slow1, slow2 = _Model(delay=1.0), _Model(delay=1.0)
    s1 = _serve(slow1, batch_size=1, batch_timeout_ms=1)
    s2 = _serve(slow2, batch_size=1, batch_timeout_ms=1)
    rs = _replica_set([s1, s2], hedge_ms=30.0, start_health=False)
    fe = HTTPFrontend(router=rs).start()
    outcome = {}

    def call():
        try:
            outcome["result"] = fe.predict(
                np.arange(4, dtype=np.float32), deadline=8.0)
        except OSError as e:
            outcome["error"] = str(e)

    t = threading.Thread(target=call)
    t.start()
    time.sleep(0.3)  # request sent; hedge_ms elapsed -> hedge launched
    t0 = time.monotonic()
    fe.close()
    close_s = time.monotonic() - t0
    t.join(timeout=5)
    assert not t.is_alive(), "predict hung past close()"
    assert close_s < 3.0, close_s
    assert "error" in outcome and "closed" in outcome["error"], outcome
    s1.stop()
    s2.stop()


# -- THE acceptance test ------------------------------------------------------

def test_ha_acceptance_replica_kill_and_rolling_restart_zero_failures():
    """ISSUE 5 acceptance: 2 replicas behind the router under sustained
    load; hard-kill one (``serving.replica_down``) → ZERO client-visible
    failures, the dead replica's circuit opens and re-closes when it
    returns; then a scripted rolling restart (drain → stop → start, one
    replica at a time) completes with 0 dropped requests, ``/healthz``
    reflecting the state transitions throughout."""
    f1 = FaultRegistry()
    servers = [_serve(_Model(), faults=f1), _serve(_Model())]
    names = [f"{s.host}:{s.port}" for s in servers]
    ports = [s.port for s in servers]
    rs = ReplicaSet([(s.host, s.port) for s in servers],
                    retry=_fast_retry(max_attempts=4),
                    health_interval=0.08, health_timeout=0.5,
                    breaker_threshold=3, breaker_reset_s=0.2)
    fe = HTTPFrontend(router=rs).start()
    url = f"http://{fe.host}:{fe.port}/healthz"

    stop_load = threading.Event()
    failures, served = [], []
    hz_samples = []

    def load(i):
        x = np.full((4,), float(i), np.float32)
        while not stop_load.is_set():
            try:
                out = fe.predict(x, deadline=15.0)
            except Exception as e:  # noqa: BLE001 — the failure record
                failures.append(f"{type(e).__name__}: {e}")
                continue
            if out is None:
                failures.append("timeout")
            else:
                served.append(1)

    def poll_healthz():
        while not stop_load.is_set():
            try:
                with urllib.request.urlopen(url, timeout=5) as r:
                    hz_samples.append(json.load(r))
            except urllib.error.HTTPError as e:
                hz_samples.append(json.load(e))
            except OSError:
                pass
            time.sleep(0.04)

    threads = [threading.Thread(target=load, args=(i,)) for i in range(4)]
    poller = threading.Thread(target=poll_healthz)
    for t in threads + [poller]:
        t.start()
    try:
        deadline = time.monotonic() + 10     # steady state, both serving
        while not served and not failures:
            assert time.monotonic() < deadline, "no request was served"
            time.sleep(0.01)
        n_steady = len(served)
        assert n_steady > 0 and not failures

        # ---- phase 1: hard-kill replica 0 under load --------------------
        f1.enable("serving.replica_down", times=1)
        deadline = time.monotonic() + 10
        while not servers[0]._stop.is_set():
            assert time.monotonic() < deadline, "kill fault never fired"
            time.sleep(0.01)
        # load keeps flowing degraded until the router has noticed: the
        # replica is reported unavailable and its circuit has opened
        # (breaker) — the dead replica costs nothing
        opens = f"router.breaker_opens{{replica={names[0]}}}"
        deadline = time.monotonic() + 10
        while True:
            rep = rs.healthz()["replicas"][names[0]]
            snap = metrics_lib.get_registry().snapshot()
            if not rep["available"] and snap.get(opens, 0) >= 1:
                break
            assert time.monotonic() < deadline, \
                f"killed replica never ejected: {rep}, opens=" \
                f"{snap.get(opens, 0)}"
            time.sleep(0.02)

        # ---- replica returns: circuit re-closes, health re-admits -------
        servers[0] = _restart_on_port(_Model(), ports[0])
        deadline = time.monotonic() + 20
        while True:
            rep = rs.healthz()["replicas"][names[0]]
            if rep["available"] and rep["breaker"] == "closed":
                break
            assert time.monotonic() < deadline, \
                f"replica never re-admitted: {rep}"
            time.sleep(0.05)

        # ---- phase 2: rolling restart under load ------------------------
        for i, _ in enumerate(servers):
            srv = servers[i]
            assert srv.drain(timeout=15.0), "drain never settled"
            srv.stop()
            servers[i] = _restart_on_port(_Model(), ports[i])
            deadline = time.monotonic() + 20
            while True:
                rep = rs.healthz()["replicas"][names[i]]
                if rep["available"] and rep["breaker"] == "closed":
                    break
                assert time.monotonic() < deadline, \
                    f"replica {names[i]} never returned: {rep}"
                time.sleep(0.05)
        time.sleep(0.3)                      # post-restart steady state
    finally:
        stop_load.set()
        for t in threads + [poller]:
            t.join(timeout=20)
        fe.stop()
        for s in servers:
            s.stop()
    assert not any(t.is_alive() for t in threads + [poller])

    # ZERO client-visible failures across kill + rolling restart
    assert failures == [], failures[:5]
    assert len(served) > n_steady            # load really ran throughout
    # /healthz reflected the transitions: degraded (or down) while a
    # replica was out, ok at the end, and the drain state was observable
    statuses = [h["status"] for h in hz_samples]
    assert "degraded" in statuses or "down" in statuses
    assert statuses[-1] == "ok", statuses[-10:]
    seen_states = {rep["state"] for h in hz_samples
                   for rep in h["replicas"].values()}
    assert "draining" in seen_states or "stopped" in seen_states, \
        seen_states
    # both final replicas took traffic after the restarts
    assert all(s.stats()["replies"] > 0 for s in servers)
