"""Unit tests for bench.py's parent-side retry/variance harness.

The measurement children need the real chip; the PARENT's logic —
record parsing, the rel_spread over-spread retry, skip records — is pure
control flow and testable with a faked ``subprocess.run``.
"""

import json
import sys
import types

sys.path.insert(0, __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__))))

import bench


def _fake_proc(record: dict, rc: int = 0) -> types.SimpleNamespace:
    return types.SimpleNamespace(returncode=rc,
                                 stdout=json.dumps(record) + "\n",
                                 stderr="")


def _record(value: float, spread: float) -> dict:
    return {"metric": "bert_base_train_tokens_per_sec_per_chip",
            "value": value, "unit": "tokens/s/chip", "vs_baseline": 1.0,
            "detail": {"rel_spread": spread}}


def _run(monkeypatch, capsys, procs, attempts):
    calls = iter(procs)
    monkeypatch.setattr(bench.subprocess, "run",
                        lambda *a, **k: next(calls))
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    rc = bench._run_child("bert", attempts=attempts)
    out = [json.loads(l) for l in
           capsys.readouterr().out.strip().splitlines()]
    assert len(out) == 1
    return rc, out[0]


def test_clean_window_passes_through(monkeypatch, capsys):
    rc, rec = _run(monkeypatch, capsys,
                   [_fake_proc(_record(100.0, 0.02))], attempts=3)
    assert rc == 0
    assert rec["value"] == 100.0
    assert "contended" not in rec["detail"]


def test_contended_window_retries_then_clean(monkeypatch, capsys):
    rc, rec = _run(monkeypatch, capsys,
                   [_fake_proc(_record(80.0, 0.30)),
                    _fake_proc(_record(100.0, 0.03))], attempts=3)
    assert rc == 0
    assert rec["value"] == 100.0
    assert "contended" not in rec["detail"]


def test_never_settles_emits_best_with_contended_flag(monkeypatch, capsys):
    rc, rec = _run(monkeypatch, capsys,
                   [_fake_proc(_record(80.0, 0.30)),
                    _fake_proc(_record(120.0, 0.25)),
                    _fake_proc(_record(90.0, 0.20))], attempts=3)
    assert rc == 0
    assert rec["value"] == 120.0  # best contended attempt, not the last
    assert rec["detail"]["contended"] is True


def test_contended_then_hard_failures_is_a_failure(monkeypatch, capsys):
    """An over-spread measurement followed by children that crash is a
    failure: the parent emits the skip record and a non-zero code, never
    the earlier record under rc=0."""
    rc, rec = _run(monkeypatch, capsys,
                   [_fake_proc(_record(95.0, 0.30)),
                    _fake_proc({}, rc=1), _fake_proc({}, rc=1)],
                   attempts=3)
    assert rc == 1
    assert rec["metric"] == "bert_skipped"
    assert "rc=1" in rec["detail"]["skipped"]


def test_exhausted_failures_emit_skip_record(monkeypatch, capsys):
    rc, rec = _run(monkeypatch, capsys,
                   [_fake_proc({}, rc=1), _fake_proc({}, rc=1)],
                   attempts=2)
    assert rc == 1
    assert rec["metric"] == "bert_skipped"
    assert "skipped" in rec["detail"]


def test_mfu_configs_print_last():
    """The driver records only the stdout TAIL: the acceptance-bar
    records (resnet50, bert) must be the final lines of the matrix."""
    assert bench.CONFIGS[-2:] == ("resnet50", "bert")
