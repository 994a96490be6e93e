"""The load generator of the serving jobs: one child process of the
benchmark, which never touches the chip (the parent starts it with
``JAX_PLATFORMS=cpu``).  It talks to the server through the public client
only (``InputQueue.enqueue`` / ``OutputQueue.query``), one thread (closed
loop) or a sender and a receiver (open loop) per connection, checks every
reply against the float32 reference of its pool row, and leaves one row per
request in ``result.<child>.npy``:

    t_due, t_send, t_recv, ok, blocked_s, turnaround_s

all times ``time.monotonic()`` (one clock for every process of the host).
``blocked_s`` is how long ``query`` blocked: the public client claims replies
by uuid, so this generator claims them in the order sent, and a reply that
was already waiting shows as a ``query`` that returned at once.
``turnaround_s`` is reply received -> next request sent (closed loop; NaN
where no request followed).  Usage: ``serve_client.py <spec.json>``.
"""

from __future__ import annotations

import collections
import json
import os
import queue
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from analytics_zoo_tpu.serving import InputQueue, OutputQueue  # noqa: E402

NAN = float("nan")


class Shared:
    """What the threads of this child share: the spec, the pool and its
    references, the window once the parent has fixed it, the records."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.rows = np.load(os.path.join(spec["dir"], "pool.npy"),
                            mmap_mode="r")
        self.ref = np.load(os.path.join(spec["dir"], "reference.npy"))
        self.scale = float(np.max(np.abs(self.ref)))
        self.window = None          # (t_begin, t_end)
        self.go = threading.Event()
        self.warmed = threading.Barrier(spec["connections"] + 1)
        self.lock = threading.Lock()
        self.records = []
        self.errors = collections.Counter()

    def check(self, out, j: int) -> bool:
        if out is None:
            self.errors["timeout"] += 1
            return False
        got = np.asarray(out, np.float32).reshape(-1)
        err = float(np.max(np.abs(got - self.ref[j]))) / self.scale \
            if got.shape == self.ref[j].shape else NAN
        if not err <= self.spec["tolerance"]:  # NaN fails too
            self.errors["wrong row"] += 1
            return False
        return True

    def query(self, outq, uid: str):
        try:
            return outq.query(uid, timeout=self.spec["query_timeout_s"])
        except (RuntimeError, OSError) as e:
            self.errors[str(e)[:80]] += 1
            return None


def closed_loop(sh: Shared, conn: int) -> None:
    """``in_flight`` requests outstanding on one connection; each reply
    claimed releases the next request."""
    spec = sh.spec
    inq = InputQueue(port=spec["port"])
    outq = OutputQueue(input_queue=inq)
    rng = np.random.default_rng([spec["seed"], spec["child"], conn])
    pending = collections.deque()
    records = []

    def send() -> None:
        j = int(rng.integers(len(sh.rows)))
        t = time.monotonic()
        pending.append((inq.enqueue("bench", t=sh.rows[j]), j, t))

    for _ in range(spec["in_flight"]):
        send()
    answered = 0
    while pending:
        uid, j, t_send = pending.popleft()
        t_query = time.monotonic()
        out = sh.query(outq, uid)
        t_recv = time.monotonic()
        ok = sh.check(out, j)
        turnaround = NAN
        if sh.window is None or t_recv < sh.window[1]:
            send()
            turnaround = time.monotonic() - t_recv
        records.append((t_send, t_send, t_recv, ok, t_recv - t_query,
                        turnaround))
        answered += 1
        if answered == spec["in_flight"]:
            sh.warmed.wait()  # one whole round answered: ready
    inq.close()
    with sh.lock:
        sh.records.extend(records)


def schedule(spec: dict, conn: int, t_begin: float, t_end: float
             ) -> np.ndarray:
    """This connection's due times, from the seed: its share of a Poisson
    process of ``rate_per_s``, or of bursts of ``burst_size`` requests every
    ``burst_every_ms``; from ``lead_in_s`` before the window to its end."""
    arrival = spec["arrival"]
    conns = spec["total_connections"]
    start = t_begin - arrival["lead_in_s"]
    rng = np.random.default_rng([spec["seed"], spec["child"], conn, 7])
    if arrival["process"] == "poisson":
        rate = arrival["rate_per_s"] / conns
        n = int((t_end - start) * rate * 1.5) + 16
        due = start + np.cumsum(rng.exponential(1.0 / rate, n))
    else:  # bursts: every connection sends its share at each burst time
        share = max(1, round(arrival["burst_size"] / conns))
        ticks = np.arange(start, t_end, arrival["burst_every_ms"] / 1e3)
        due = np.repeat(ticks, share)
    return due[due < t_end]


def open_loop(sh: Shared, conn: int) -> None:
    """Requests sent when they are due, whatever the server does; a
    receiver claims the replies in the order sent."""
    spec = sh.spec
    inq = InputQueue(port=spec["port"])
    outq = OutputQueue(input_queue=inq)
    rng = np.random.default_rng([spec["seed"], spec["child"], conn])
    for _ in range(spec["warm_requests"]):  # the path, once, before ready
        j = int(rng.integers(len(sh.rows)))
        sh.check(sh.query(outq, inq.enqueue("bench", t=sh.rows[j])), j)
    sh.warmed.wait()
    sh.go.wait()
    sent: queue.Queue = queue.Queue()
    records = []

    def receive() -> None:
        while (item := sent.get()) is not None:
            uid, j, t_due, t_send = item
            t_query = time.monotonic()
            out = sh.query(outq, uid)
            t_recv = time.monotonic()
            records.append((t_due, t_send, t_recv, sh.check(out, j),
                            t_recv - t_query, NAN))

    receiver = threading.Thread(target=receive, name=f"recv-{conn}")
    receiver.start()
    for t_due in schedule(spec, conn, *sh.window):
        wait = t_due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        j = int(rng.integers(len(sh.rows)))
        t_send = time.monotonic()
        sent.put((inq.enqueue("bench", t=sh.rows[j]), j, float(t_due),
                  t_send))
    sent.put(None)
    receiver.join()
    inq.close()
    with sh.lock:
        sh.records.extend(records)


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    sh = Shared(spec)
    loop = {"closed": closed_loop, "open": open_loop}[spec["mode"]]
    threads = [threading.Thread(target=loop, args=(sh, c), name=f"conn-{c}")
               for c in range(spec["connections"])]
    for t in threads:
        t.start()
    sh.warmed.wait()
    tag = spec["child"]
    open(os.path.join(spec["dir"], f"ready.{tag}"), "w").close()
    window_file = os.path.join(spec["dir"], "window.json")
    while not os.path.exists(window_file):
        time.sleep(0.005)
    with open(window_file) as f:
        w = json.load(f)
    sh.window = (w["t_begin"], w["t_end"])
    sh.go.set()
    for t in threads:
        t.join()
    np.save(os.path.join(spec["dir"], f"result.{tag}.npy"),
            np.asarray(sh.records, np.float64).reshape(-1, 6))
    with open(os.path.join(spec["dir"], f"errors.{tag}.json"), "w") as f:
        json.dump(sh.errors, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
