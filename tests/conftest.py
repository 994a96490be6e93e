"""Test fixtures: an 8-device CPU "cluster in a box".

Reference test strategy (SURVEY.md §4): the universal trick was ``local[N]``
Spark + Ray local mode so real all-reduce code paths run as processes on one
machine.  The TPU-native analog is an 8-device virtual CPU mesh — real XLA
collectives (psum/all_gather/ppermute) execute, no hardware needed.

Env vars must be set before jax initializes its backends, hence at import.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# The persistent compilation cache (core/context.py places it in the
# checkout) stays OFF for the suite, here and in every child process the
# tests spawn: the tier-1 run's time and steadiness must not depend on what
# an earlier run left on disk.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_context():
    """Each test starts with no global context."""
    from analytics_zoo_tpu.core import stop_orca_context
    stop_orca_context()
    yield
    stop_orca_context()


@pytest.fixture(autouse=True)
def _telemetry_reset():
    """Each test reads a zeroed metrics registry and trace ring: the
    registry is process-global and tests assert absolute counts.
    ``reset()`` zeroes values in place, so handles cached by long-lived
    objects (a module-scoped server fixture) stay valid."""
    from analytics_zoo_tpu.core import metrics, trace
    metrics.get_registry().reset()
    metrics.get_registry().enabled = True
    trace.reset()
    trace.enabled = True
    yield


@pytest.fixture(autouse=True)
def _fault_registry_disarmed():
    """Suite hygiene: a test that arms a fault-injection point must disarm
    it (use ``registry.armed(...)`` — it always does).  A leaked armed
    fault fails the test that leaked it, not the innocent test 200 ids
    later that trips over it."""
    yield
    from analytics_zoo_tpu.core import faults
    reg = faults.get_registry()
    storms = reg.running_schedules()
    if storms:
        # ISSUE 14: a leaked chaos storm keeps ARMING points from its
        # background thread, so stop the storms before the armed-point
        # sweep below (each stop() disarms its own points).
        names = reg.schedule_state()
        for storm in storms:
            try:
                storm.stop()
            except Exception:  # noqa: BLE001 — hygiene must not mask
                pass
        reg.reset()
        pytest.fail(f"test leaked running chaos schedule(s): {names} "
                    "(use the ChaosSchedule context manager or call "
                    "stop() in teardown)")
    leaked = reg.armed_points()
    if leaked:
        reg.reset()  # disarm so subsequent tests run clean
        pytest.fail(f"test leaked armed fault injection points: {leaked} "
                    "(arm with registry.armed(...) or disable() in "
                    "teardown)")


@pytest.fixture(autouse=True)
def _no_leaked_controllers():
    """Suite hygiene (ISSUE 12): a test that starts a ServingController
    must stop it (``controller.close()`` / the context manager).  A
    leaked supervision thread keeps ticking against the shared metrics
    registry and can scale replicas during LATER tests — fail the test
    that leaked it, after stopping the thread so the rest of the suite
    runs clean."""
    yield
    from analytics_zoo_tpu.serving import controller as controller_lib
    leaked = controller_lib.live_controllers()
    if leaked:
        for c in leaked:
            c.stop()
        pytest.fail("test leaked running ServingController thread(s): "
                    f"{leaked} (call controller.close() or use it as a "
                    "context manager)")


@pytest.fixture(autouse=True, scope="module")
def _bound_accumulated_state():
    """Full-suite hygiene: 360+ tests in one process accumulate jit
    executables and native-side state; unbounded growth intermittently
    aborts the interpreter deep into the run (observed as 'Fatal Python
    error: Aborted' inside a trace).  Clearing jax's caches per MODULE
    bounds it at the cost of some recompiles."""
    yield
    import gc
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
