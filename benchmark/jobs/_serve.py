"""What the serving jobs share: ``ClusterServing`` in this process (it holds
the chip), the load generator in child processes that never touch it, and a
window fixed on the clock every process of the host shares.

Set-up: context, the model from its family, its weights on the device from
the seed, the float32 reference of every pool row, ``InferenceModel.warm``
of the configuration's buckets (``first_step_s``), the bare host->device
probe, the server, the children connected and one round answered.  Load runs
before and after the window, so the window is steady state; the registry and
the server's counters are read at its two ends.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.core import (init_orca_context, metrics,
                                    stop_orca_context)
from analytics_zoo_tpu.serving import (ClusterServing, InferenceModel,
                                       enable_aot_cache)

from benchmark.families import family
from benchmark.harness import registry, window

CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "serve_client.py")
#: how long the children may take to start, connect and get a round answered
READY_TIMEOUT_S = 180.0


def _sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.05))


def run(run: window.Run, mode: str) -> window.Result:
    config, traffic = run.cell.config, run.cell.traffic
    fam = family(config)
    serving = config["serving"]
    problems = []

    enable_aot_cache()
    init_orca_context("local")
    model = fam.build(config)
    pool = fam.inputs(config, traffic, run.seed, traffic["pool_size"])
    variables = jax.jit(lambda r, x: model.init(r, x))(
        jax.random.PRNGKey(run.seed), pool[:1])
    reference = fam.reference(config, variables, pool)
    im = InferenceModel(batch_buckets=serving["batch_buckets"]).load(
        model, variables, dtype=jnp.dtype(serving["dtype"]))
    t0 = time.perf_counter()
    im.warm([pool.shape[1:]], dtype=pool.dtype,
            buckets=serving["batch_buckets"])
    first_step_s = time.perf_counter() - t0
    compiles_warm = im.compile_count
    link = window.probe_link((serving["batch_size"],) + pool.shape[1:],
                             pool.dtype, run.seed)

    tmp = tempfile.mkdtemp(prefix="bench-serve-")
    children, logs = [], []
    srv = ClusterServing(im, batch_size=serving["batch_size"],
                         batch_timeout_ms=serving["batch_timeout_ms"]).start()
    try:
        np.save(os.path.join(tmp, "pool.npy"), pool)
        np.save(os.path.join(tmp, "reference.npy"),
                reference.reshape(len(pool), -1))
        procs = traffic["processes"]
        per_child = traffic["connections"] // procs
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for child in range(procs):
            spec = dict(
                dir=tmp, port=srv.port, seed=run.seed, child=child,
                mode=mode, connections=per_child,
                total_connections=per_child * procs,
                in_flight=traffic.get("in_flight_per_connection", 0),
                warm_requests=traffic.get("warm_requests", 0),
                arrival=traffic.get("arrival"), tolerance=fam.TOLERANCE,
                query_timeout_s=traffic["query_timeout_s"])
            path = os.path.join(tmp, f"spec.{child}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            log = open(os.path.join(tmp, f"log.{child}.txt"), "w")
            logs.append(log)
            children.append(subprocess.Popen(
                [sys.executable, CLIENT, path], env=env, stdout=log,
                stderr=subprocess.STDOUT))

        deadline = time.monotonic() + READY_TIMEOUT_S
        while not all(os.path.exists(os.path.join(tmp, f"ready.{c}"))
                      for c in range(procs)):
            if time.monotonic() > deadline or any(
                    p.poll() is not None for p in children):
                raise RuntimeError("the load generator did not get ready:\n"
                                   + _logs(tmp, procs))
            time.sleep(0.02)

        t_begin = time.monotonic() + traffic["lead_in_s"]
        t_end = t_begin + run.seconds
        with open(os.path.join(tmp, "window.tmp"), "w") as f:
            json.dump({"t_begin": t_begin, "t_end": t_end}, f)
        os.rename(os.path.join(tmp, "window.tmp"),
                  os.path.join(tmp, "window.json"))
        reg = metrics.get_registry()
        _sleep_until(t_begin)
        before = reg.snapshot()
        setup_s = time.perf_counter() - run.process_start
        slice_ = None
        if run.trace:
            slice_ = window.TraceSlice(
                after=traffic["trace_after_share"] * run.seconds,
                seconds=traffic["trace_seconds"]).start()
        _sleep_until(t_end)
        grew = registry.window(before, reg.snapshot())
        trace_dir = slice_.finish() if slice_ else None

        for p in children:
            p.wait(timeout=traffic["query_timeout_s"] + 60.0)
        if any(p.returncode != 0 for p in children):
            raise RuntimeError("a load generator failed:\n"
                               + _logs(tmp, procs))
        stats = srv.stats()
        rows = np.concatenate([np.load(os.path.join(tmp, f"result.{c}.npy"))
                               for c in range(procs)])
        errors: dict = {}
        for c in range(procs):
            with open(os.path.join(tmp, f"errors.{c}.json")) as f:
                for k, v in json.load(f).items():
                    errors[k] = errors.get(k, 0) + v
    finally:
        for p in children:
            if p.poll() is None:
                p.kill()
            p.wait()
        for log in logs:
            log.close()
        srv.stop()
        shutil.rmtree(tmp, ignore_errors=True)
        stop_orca_context()

    t_due, t_send, t_recv, ok, blocked, turnaround = rows.T
    sent_in = (t_send >= t_begin) & (t_send < t_end)
    answered_in = sent_in & (t_recv <= t_end) & (ok > 0)
    received_in = (t_recv >= t_begin) & (t_recv <= t_end) & (ok > 0)
    latency_ms = 1e3 * (t_recv - t_due)[answered_in]
    late_ms = 1e3 * (t_send - t_due)[sent_in]
    turn_ms = 1e3 * turnaround[sent_in & ~np.isnan(turnaround)]
    failed = int((sent_in & ~(ok > 0)).sum())

    if errors:
        problems.append(f"replies failed: {errors}")
    if stats["requests"] != stats["replies"] + stats["errors"] \
            or stats["pending"]:
        problems.append(f"server counters do not add up: {stats}")
    if compiles_warm != len(serving["batch_buckets"]) \
            or im.compile_count != compiles_warm:
        problems.append(f"{compiles_warm} compiles at warm(), "
                        f"{im.compile_count - compiles_warm} after; expected "
                        f"{len(serving['batch_buckets'])} and 0")
    if len(latency_ms) < 100:
        problems.append(f"only {len(latency_ms)} requests sent and answered "
                        "in the window: no tail to speak of")

    pct = lambda a, q: float(np.percentile(a, q)) if len(a) else None
    values = {
        "setup_s": setup_s,
        "rows_per_s": float(received_in.sum()) / run.seconds,
        "latency_ms_p50": pct(latency_ms, 50),
        "latency_ms_p99": pct(latency_ms, 99),
        "first_step_s": first_step_s,
        "h2d_mb_per_s": link,
        "gen_turnaround_ms_p99": pct(turn_ms, 99),
        "gen_late_ms_p99": pct(late_ms, 99),
        "claimed_waiting_share": float(
            (blocked[sent_in] < 2e-4).mean()) if sent_in.any() else None,
    }
    print(f"benchmark: {len(latency_ms)} requests sent and answered in the "
          f"window; {values['claimed_waiting_share']:.1%} of replies were "
          "already waiting when claimed (the public client claims by uuid, "
          "in the order sent)" if sent_in.any() else "benchmark: no request "
          "was sent in the window", file=sys.stderr)
    return window.Result(
        attempted=int(sent_in.sum()), failed=failed, problems=problems,
        values={k: v for k, v in values.items() if v is not None},
        registry=grew, window_s=run.seconds, trace_dir=trace_dir)


def _logs(tmp: str, procs: int) -> str:
    out = []
    for c in range(procs):
        path = os.path.join(tmp, f"log.{c}.txt")
        if os.path.exists(path):
            with open(path) as f:
                out.append(f"--- child {c}\n" + f.read()[-2000:])
    return "\n".join(out)
