"""Fast RN50 resident-step timer for perf iteration (dev tool).

Mirrors bench.py's resnet50 resident phase exactly (same model, batch,
space-to-depth stem, uint8 normalize-on-device) but skips streaming /
host-feed phases, so one A/B costs ~60s instead of minutes.  Knobs via
env so two variants can run back-to-back:

  RN50_BATCH=128     per-chip batch
  RN50_STEPS=20      steps per timed scan
  RN50_REPEATS=5     timed repeats (prints each; best is the signal)
  RN50_VARIANT=...   free-form tag echoed in the output line
  RN50_STEM=space_to_depth|conv
  RN50_NORM=bn|nf    bn (default) = classic exact-BN ResNet-50;
                     nf = normalizer-free (ScaledWSConv + SkipInit)

Usage: python dev/rn50_step.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.core import init_orca_context
    from analytics_zoo_tpu.data import as_feed
    from analytics_zoo_tpu.models import ResNet
    from analytics_zoo_tpu.orca.learn import Estimator

    size, classes = 224, 1000
    batch = int(os.environ.get("RN50_BATCH", "128"))
    steps = int(os.environ.get("RN50_STEPS", "20"))
    repeats = int(os.environ.get("RN50_REPEATS", "5"))
    variant = os.environ.get("RN50_VARIANT", "base")
    stem = os.environ.get("RN50_STEM", "space_to_depth")
    norm = os.environ.get("RN50_NORM", "bn")

    class TrainNet(nn.Module):
        def __init__(self):
            super().__init__()
            kw = {}
            if norm != "bn":
                kw["norm"] = norm
            self.net = ResNet(depth=50, class_num=classes,
                              dtype="bfloat16", stem=stem, **kw)

        def forward(self, scope, x):
            x = (x.astype(jnp.bfloat16) - 127.0) * (1.0 / 64.0)
            return scope.child(self.net, x, name="resnet")

    mesh = init_orca_context("local")
    n_chips = jax.device_count()
    global_batch = batch * n_chips

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (global_batch, size, size, 3),
                        dtype=np.uint8)
    labels = rng.integers(0, classes, global_batch).astype(np.int32)

    est = Estimator.from_keras(TrainNet(),
                               loss="sparse_categorical_crossentropy",
                               optimizer="sgd", learning_rate=0.1)
    b0 = next(as_feed((imgs, labels), global_batch, shuffle=False)
              .epoch(mesh, 0))
    est._ensure_initialized(b0["x"])

    est._ts, warm = est._multi_step(est._ts, b0, steps)
    _ = float(warm[-1])

    dts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        est._ts, losses = est._multi_step(est._ts, b0, steps)
        _ = float(losses[-1])
        dts.append((time.perf_counter() - t0) / steps)
    best = min(dts)
    ips = global_batch / best
    # canonical fwd estimate; MFU here is for RELATIVE comparison only
    mfu = ips * 3 * 8.023e9 / (197e12 * n_chips)
    print(f"[{variant}] step_ms={[round(1e3 * d, 2) for d in dts]} "
          f"best={1e3 * best:.2f}ms ips={ips:.0f} mfu~{mfu:.4f}")


if __name__ == "__main__":
    main()
