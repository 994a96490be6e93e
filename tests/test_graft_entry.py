"""Driver-contract tests: the __graft_entry__ surface the harness invokes.

The driver compile-checks ``entry()`` single-chip and runs
``dryrun_multichip(8)`` bare; these tests keep both paths green in CI
(the bare-subprocess re-exec path is additionally exercised by invoking
the module exactly as the driver does)."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_entry_compiles_and_runs():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (4, 2)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("n", [4, 8])
def test_dryrun_local_parallel_modes(n):
    # conftest provides 8 CPU devices; exercises dp/tp/sp/pp/ep/fsdp math
    # at two device counts in-process
    import __graft_entry__ as g
    g._dryrun_local(n)


def test_dryrun_bare_subprocess_self_provisions():
    """The driver's exact invocation: bare process, no test env."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "JAX_"))}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # keep CI off the real chip
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "all parallel modes ok" in proc.stdout
