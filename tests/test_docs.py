"""Docs are executable: every bare ```python block in docs/*.md runs
(VERDICT r2 #10 — per-subsystem pages with runnable snippets,
import-checked in CI).  Blocks within one file share a namespace and run
in order; illustrative snippets that need external files/servers are
fenced as ```python no-run and excluded."""

import pathlib
import re

import pytest

DOCS = sorted((pathlib.Path(__file__).parent.parent / "docs").glob("*.md"))
_BLOCK = re.compile(r"```python\n(.*?)```", re.S)


@pytest.mark.parametrize("doc", DOCS, ids=[d.name for d in DOCS])
def test_doc_snippets_execute(doc):
    blocks = _BLOCK.findall(doc.read_text())
    if not blocks:
        pytest.skip("no python blocks")
    ns: dict = {}
    for i, code in enumerate(blocks):
        try:
            exec(compile(code, f"{doc.name}[block {i}]", "exec"), ns)
        except Exception as e:
            pytest.fail(f"{doc.name} block {i} failed: {e}")


def test_docs_quote_no_deleted_benchmark():
    """The trainer is measured in one place, ``benchmark/``: what a user
    reads first (the README, ``docs/``) sends them to no second one.
    The records (CHANGES.md, PERF.md, ROADMAP.md, BASELINE.md, PARITY.md,
    benchmark/README.md) may name what was deleted."""
    readme = pathlib.Path(__file__).parent.parent / "README.md"
    for page in [readme, *DOCS]:
        text = page.read_text()
        for gone in ("bench.py", "BENCH_r0"):
            assert gone not in text, f"{page.name} names {gone}"
