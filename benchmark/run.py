"""Run one cell of BENCHMARK.json once, in this process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearse]

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``).  ``--trace 0`` gives the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler slice of the
window.  Anything that fails before the result exits non-zero and prints no
result: a platform other than ``tpu`` (unless ``--rehearse``, which runs the
``rehearse`` overlay of the cell's data files at a tiny size on whatever JAX
finds, for the tests), or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402  (imports no JAX)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX finds")
    args = ap.parse_args(argv)

    spec = manifest.load(ROOT)
    cell = manifest.cell(spec, args.workload, rehearse=args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(spec["run_seconds"])
    if args.rehearse and cell.chips > 1 and \
            "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    # the compile cache sits where JAX_COMPILATION_CACHE_DIR says, else at a
    # fixed path inside this checkout; the program's configure_compile_cache
    # keeps a directory that is already placed
    import jax
    from benchmark.harness import window, xplane
    from benchmark.layer_metrics import Reading, read
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))

    device = window.device_info()
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"benchmark: JAX found platform {device['platform']!r}, not a "
              "TPU (--rehearse runs the tiny rehearsal)", file=sys.stderr)
        return 2
    if device["count"] < cell.chips:
        print(f"benchmark: {args.workload} asks for {cell.chips} chip(s), "
              f"JAX found {device['count']}", file=sys.stderr)
        return 2

    memory = window.MemoryWatch().start()
    job = importlib.import_module(f"benchmark.jobs.{cell.traffic['job']}")
    result = job.run(window.Run(cell=cell, seed=args.seed, seconds=seconds,
                                trace=bool(args.trace),
                                process_start=PROCESS_START))

    reading = Reading(result=result, device=device)
    line = {"correct": not result.problems,
            "attempted": result.attempted, "failed": result.failed}
    device["memory_peak_bytes"] = memory.peak()
    result.values["hbm_peak_gb"] = device["memory_peak_bytes"] / 1e9 or None
    if args.trace:
        path = result.trace_dir and xplane.find(result.trace_dir)
        if path is None:
            print("benchmark: the profiler slice left no .xplane.pb",
                  file=sys.stderr)
            return 3
        t0 = time.perf_counter()
        reading.trace = xplane.load(path)
        print(f"benchmark: {os.path.getsize(path) / 1e6:.1f} MB of trace "
              f"read in {time.perf_counter() - t0:.1f} s", file=sys.stderr)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:  # for looking at a trace by hand
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                keep, f"{args.workload}.xplane.pb"))
        shutil.rmtree(result.trace_dir, ignore_errors=True)
        busy = xplane.busy_seconds(reading.trace)
        device["busy_s"] = sum(busy) / max(1, len(busy))
        device["window_s"] = xplane.window_seconds(reading.trace)
        line["breakdown"] = {"device_ops": xplane.top_ops(reading.trace),
                             "idle_gaps": xplane.idle_gaps(reading.trace)}
        if not device["busy_s"] > 0:
            result.problems.append("no operation ran on the device in the "
                                   "traced slice")
            line["correct"] = False
    def values(wanted):
        found = ((m, read(m, reading)) for m in wanted)
        return {m.name: {"value": v, "unit": m.unit}
                for m, v in found if v is not None}

    metrics = values(cell.end_to_end)
    if args.trace:
        # a per-layer metric is reported where the metric it moves is (for
        # a cell tried from its files: where that metric found a value)
        metrics = values(m for m in cell.per_layer if m.moves in metrics)
    line["metrics"] = metrics
    line["device"] = device
    for p in result.problems:
        print(f"benchmark: not correct: {p}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
