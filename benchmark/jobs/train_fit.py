"""``Estimator.fit`` over a ``StreamingDataFeed`` for the length of the
window.

Set-up: context and mesh, the model from its family, the system's forward on
the initial parameters against the family's float32 reference, the bare
host->device probe, a one-step ``fit`` (initialises, compiles the train
step: ``first_step_s``), and one whole epoch (compiles what an epoch's end
runs, and measures how long an epoch takes).  The window is then ONE
``fit()`` call of as many whole epochs as fit the asked seconds, timed from
the call to ``block_until_ready`` of everything it left on the device: feed
start-up and epoch boundaries are inside, compilation is not.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import numpy as np

from analytics_zoo_tpu.core import (init_orca_context, metrics,
                                    stop_orca_context)
from analytics_zoo_tpu.data import shard_batch
from analytics_zoo_tpu.data.stream import StreamingDataFeed
from analytics_zoo_tpu.orca.learn import Estimator
from analytics_zoo_tpu.serving import enable_aot_cache

from benchmark.families import family
from benchmark.harness import registry, window


def run(run: window.Run) -> window.Result:
    config, traffic = run.cell.config, run.cell.traffic
    fam = family(config)
    problems = []

    enable_aot_cache()  # every program, however quick to compile, is kept
    mesh = init_orca_context("local", mesh_shape=traffic.get("mesh_shape"))
    chips = mesh.devices.size
    global_batch = traffic["global_batch"]
    steps_per_epoch = traffic["steps_per_epoch"]
    load_sample = fam.loader(config, traffic, run.seed)

    def traced_load(i, rng=None):
        with window.annotate("feed.load_sample"):
            return load_sample(i, rng)

    def feed(steps: int) -> StreamingDataFeed:
        return StreamingDataFeed(
            num_samples=steps * global_batch,
            load_sample=traced_load if run.trace else load_sample,
            batch_size=global_batch, shuffle=False, seed=run.seed,
            **traffic.get("feed", {}))

    est = Estimator.from_keras(
        fam.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"],
        grad_accum=traffic["grad_accum"], seed=run.seed,
        sharding=traffic.get("sharding", "dp"),
        profile=True)  # counts the train step's compiles; traces nothing

    # the system's forward on the initial parameters against the reference
    x = fam.inputs(config, traffic, run.seed, traffic["check_rows"])
    got = np.asarray(est.predict(x, batch_size=len(x)), np.float32)
    ref = fam.reference(config, est.get_model(), x)
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    if not err <= fam.TOLERANCE:  # NaN fails too
        problems.append(f"forward differs from the float32 reference by "
                        f"{err:.3g} of its range (tolerance "
                        f"{fam.TOLERANCE})")
    del got, ref

    shape, dtype = fam.batch_spec(config, traffic)
    placed = shard_batch({"x": np.zeros(shape, dtype)}, mesh)
    batch_devices = len({s.device.id for s in placed["x"].addressable_shards})
    if batch_devices != chips:
        problems.append(f"a batch lands on {batch_devices} device(s) of "
                        f"the mesh's {chips}")
    del placed
    link = window.probe_link(shape, dtype, run.seed)

    def fit(steps: int, epochs: int):
        t0 = time.perf_counter()
        hist = est.fit(feed(steps), epochs=epochs, batch_size=global_batch,
                       verbose=False)
        # fit() read every epoch's loss; wait for the last update too
        jax.block_until_ready(jax.live_arrays())
        return hist["loss"], time.perf_counter() - t0

    _, first_step_s = fit(1, 1)
    warm_losses, epoch_s = fit(steps_per_epoch, 1)
    compiles_warm = est.compile_count
    epochs = max(1, round(run.seconds / epoch_s))

    reg = metrics.get_registry()
    slice_ = None
    if run.trace:
        slice_ = window.TraceSlice(
            after=traffic["trace_after_share"] * epochs * epoch_s,
            seconds=traffic["trace_seconds"]).start()
    before = reg.snapshot()
    setup_s = time.perf_counter() - run.process_start
    losses, wall = fit(steps_per_epoch, epochs)
    grew = registry.window(before, reg.snapshot())
    trace_dir = slice_.finish() if slice_ else None

    steps = int(grew.get("train.steps", 0))
    bad_epochs = sum(1 for l in losses if not math.isfinite(l))
    if steps != epochs * steps_per_epoch:
        problems.append(f"{steps} optimizer steps in the window, not "
                        f"{epochs} x {steps_per_epoch}")
    if bad_epochs or not all(map(math.isfinite, warm_losses)):
        problems.append(f"non-finite loss: {warm_losses + losses}")
    elif not losses[-1] < warm_losses[0]:
        problems.append("loss did not fall on the repeating data: "
                        f"{warm_losses + losses}")
    if compiles_warm != 1 or est.compile_count != 1:
        problems.append(f"train step compiled {compiles_warm} time(s) in "
                        f"set-up and {est.compile_count - compiles_warm} in "
                        "the window; expected 1 and 0")

    per_chip = steps * global_batch / wall / chips
    flops = fam.flops_per_sample(config, traffic)
    values = {
        "setup_s": setup_s,
        "samples_per_s_chip": per_chip,
        "first_step_s": first_step_s,
        "h2d_mb_per_s": link,
        "flops_per_sample": flops,
        "flops_per_step_chip": flops * global_batch / chips,
        "step_wall_ms": 1000.0 * wall / max(1, steps),
    }
    print(f"benchmark: forward differs from the float32 reference by "
          f"{err:.3g} of its range (tolerance {fam.TOLERANCE}); {epochs} "
          f"epoch(s) of {steps_per_epoch} steps in {wall:.2f} s; epoch "
          f"losses {warm_losses + losses}", file=sys.stderr)
    stop_orca_context()
    return window.Result(
        attempted=epochs * steps_per_epoch,
        failed=bad_epochs * steps_per_epoch + max(
            0, epochs * steps_per_epoch - steps),
        problems=problems,
        values={k: v for k, v in values.items() if v is not None},
        registry=grew, window_s=wall,
        trace_dir=trace_dir)
