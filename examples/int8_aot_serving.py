"""Calibrated-int8 + AOT-artifact serving example (reference: the
OpenVINO INT8 quickstart — calibrate → save IR → load IR → serve).

Trains a small CNN classifier, calibrates static int8 activation scales
from a representative batch, serves it int8 (Dense matmuls and Conv2D
convolutions run int8 x int8 -> int32 on the MXU), then demonstrates the
OpenVINO-IR analog: ``save_executables`` writes per-shape compiled-
computation artifacts that a RESTARTED process loads without re-tracing
(and, with ``enable_aot_cache``, without re-running the XLA compile).

Run:  python examples/int8_aot_serving.py
"""

from __future__ import annotations

# allow `python examples/<script>.py` straight from a checkout (the
# CI harness sets PYTHONPATH; a user following the README should not
# need to): put the repo root ahead of the script's own directory
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.core import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.orca.learn import Estimator
    from analytics_zoo_tpu.serving import InferenceModel, enable_aot_cache

    init_orca_context("local")
    try:
        # JAX_COMPILATION_CACHE_DIR, else the fixed <checkout>/.jax_cache:
        # a cache directory that moves between runs never hits
        enable_aot_cache()

        # 1. train a small CNN (class signal: bright channel per class)
        rng = np.random.default_rng(0)
        x = rng.normal(0, 1, (256, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 3, 256).astype(np.int32)
        for i in range(len(x)):
            x[i, :, :, y[i]] += 2.0
        model = nn.Sequential([
            nn.Conv2D(16, 3, activation="relu"),
            nn.Conv2D(32, 3, strides=2, activation="relu"),
            nn.GlobalAveragePooling2D(),
            nn.Dense(3)])
        est = Estimator.from_keras(model,
                                   loss="sparse_categorical_crossentropy",
                                   optimizer="adam", learning_rate=3e-3)
        est.fit((x, y), epochs=3, batch_size=32, verbose=False)
        variables = est.get_model()

        # 2. calibrated int8 serving: one float pass over a
        # representative batch freezes the activation scales
        f32 = InferenceModel().load(model, variables)
        q = InferenceModel().load(model, variables, dtype="int8",
                                  calibrate=x[:64])
        out_f32 = np.asarray(f32.predict(x[:64]))
        out_q = np.asarray(q.predict(x[:64]))
        agree = float(np.mean(out_q.argmax(1) == out_f32.argmax(1)))
        print(f"int8 vs f32 top-1 agreement: {agree:.2%} "
              f"({len(q._quant_ctx.amax)} calibrated layers)")

        # 3. the OpenVINO-IR analog: serialize the compiled computations,
        # reload them in a "restarted" server without the cold compile
        aot_dir = tempfile.mkdtemp(prefix="zoo_aot_exec_")
        n = q.save_executables(aot_dir)
        restarted = InferenceModel().load(model, variables, dtype="int8",
                                          calibrate=x[:64])
        loaded = restarted.load_executables(aot_dir)
        # the reload path is what this example guards: a serialization
        # or fingerprint regression must fail here, not silently fall
        # back to a fresh compile
        assert n >= 1 and loaded == n, (n, loaded)
        t0 = time.perf_counter()
        out_r = np.asarray(restarted.predict(x[:64]))
        dt = time.perf_counter() - t0
        np.testing.assert_allclose(out_r, out_q, rtol=1e-5)
        print(f"AOT artifacts: saved {n}, loaded {loaded}; restarted "
              f"first predict {dt * 1e3:.0f} ms (no re-trace), outputs "
              f"identical")
    finally:
        stop_orca_context()


if __name__ == "__main__":
    main()
