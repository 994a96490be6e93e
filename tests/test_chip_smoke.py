"""chip_smoke.py, rehearsed: the script the driver runs on the chip keeps its
contract — control flow at a tiny size on the CPU, refusal of a platform
that is not a TPU, a non-zero exit when a phase fails."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _smoke(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("chips,phases", [
    (1, ["device", "link", "kernel", "kernel", "kernel", "train", "serve",
         "cache"]),
    (4, ["device", "multichip", "multichip", "multichip", "cache"]),
])
def test_rehearsal_passes_and_reports_the_platform_it_used(chips, phases):
    proc = _smoke("--rehearse", "--chips", str(chips))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    # every earlier line names the device; --chips 4 ran no other phase
    assert [l["phase"] for l in lines[:-1]] == phases
    assert all(l["platform"] == "cpu" and "device_kind" in l
               for l in lines[:-1])


def test_without_the_rehearsal_option_a_cpu_is_refused():
    proc = _smoke()
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line to mistake for a pass
    assert "not a TPU" in proc.stderr


def test_a_phase_that_raises_fails_the_run(monkeypatch):
    import chip_smoke

    def broken(self):
        raise RuntimeError("link phase broke")

    monkeypatch.setattr(chip_smoke.Smoke, "phase_link", broken)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--rehearse"])
    with pytest.raises(RuntimeError, match="link phase broke"):
        chip_smoke.main()
