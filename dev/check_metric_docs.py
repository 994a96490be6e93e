#!/usr/bin/env python
"""CI guard: the metric AND span catalogs in docs/observability.md match
the code.

The metric catalog drifted risk-free through four PRs — nothing failed
when a new series was registered but never documented, or a documented
series was renamed away.  This checker closes the loop without importing
(or running) anything; since ISSUE 9 it guards the SPAN catalog the same
way, so span naming can't drift undocumented either:

- **metrics, code side**: every metric name registered through the
  ``core/metrics.py`` registry is found by scanning ``analytics_zoo_tpu``
  sources for ``counter("...")`` / ``gauge("...")`` /
  ``histogram("...")`` / ``inc("...")`` / ``observe("...")`` /
  ``set_gauge("...")`` string literals, PLUS the known dynamic
  registration sites (``"client." + key`` over the client's stats dict,
  ``"server." + k`` over the server's counters dict, ``"frontend." +
  key`` over ``_FRONTEND_COUNTERS``, ``"moe." + key`` over the expert
  layer's ``COUNTER_KEYS`` and ``LEVEL_KEYS``, ``"ssm." + key`` over the
  state-space mixer's, ``"mla." + key`` over latent attention's level and
  ``"mtp." + key`` over the multi-token prediction module's) whose key
  sets are extracted from the same files;
- **spans, code side**: every span name recorded through ``core/trace.py``
  — the second argument of ``trace.record(...)`` / ``trace_lib.record``
  call sites and the first argument of ``trace.span("...")`` /
  ``.child("...")`` — as string literals (span names are a closed
  vocabulary by design; build one from a variable and this guard can't
  see it, so don't);
- **docs side**: the first column of the catalog tables (rows starting
  with ``| `` + a backtick), splitting ``a / b`` cells — metric rows
  from the "## Metric catalog" section, span rows from the
  "## Span catalog" section.

Exit 1 (with a readable diff) when code and catalog disagree in either
direction, for either vocabulary.  Wired into the test suite
(``tests/test_observability.py::test_metric_catalog_matches_code``).
"""

from __future__ import annotations

import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "analytics_zoo_tpu"
DOC = REPO / "docs" / "observability.md"

#: registry write/handle calls whose first argument is the series name
_LITERAL = re.compile(
    r'\.(?:counter|gauge|histogram|inc|observe|set_gauge)\(\s*'
    r'"([a-z0-9_.]+)"')

#: span-producing calls: record(<expr>, "name", ...) / span("name") /
#: sp.child("name").  The record() first argument never contains a
#: comma at this call depth (a bare name, attribute, or subscript).
_SPAN_RECORD = re.compile(
    r'\.record\(\s*\n?\s*[^,()]+,\s*\n?\s*"([a-z0-9_.]+)"', re.S)
_SPAN_CTX = re.compile(r'\.(?:span|child)\(\s*"([a-z0-9_.]+)"')

#: dynamic registration sites: (file, metric prefix, regex whose group 1
#: holds the key set as quoted strings)
_DYNAMIC = [
    ("serving/client.py", "client.",
     re.compile(r"CONN_STATS_KEYS = \(([^)]*)\)", re.S)),
    ("serving/server.py", "server.",
     re.compile(r"self\._counters = \{([^}]*)\}", re.S)),
    ("serving/http_frontend.py", "frontend.",
     re.compile(r"_FRONTEND_COUNTERS = \(([^)]*)\)", re.S)),
    # device-side counters: kept in the layer's state, published by the
    # Estimator at the epoch's read-back under "moe." + key
    ("parallel/moe.py", "moe.",
     re.compile(r"COUNTER_KEYS = \(([^)]*)\)", re.S)),
    # ... and the levels kept beside them (a bias-balanced router)
    ("parallel/moe.py", "moe.",
     re.compile(r"LEVEL_KEYS = \(([^)]*)\)", re.S)),
    # the state-space mixer's counters and levels, the same way
    ("nn/state_space.py", "ssm.",
     re.compile(r"COUNTER_KEYS = \(([^)]*)\)", re.S)),
    ("nn/state_space.py", "ssm.",
     re.compile(r"LEVEL_KEYS = \(([^)]*)\)", re.S)),
    # latent attention's level and the prediction module's counters
    ("nn/attention.py", "mla.",
     re.compile(r"LATENT_LEVEL_KEYS = \(([^)]*)\)", re.S)),
    ("models/glm_moe_lite.py", "mtp.",
     re.compile(r"MTP_COUNTER_KEYS = \(([^)]*)\)", re.S)),
    ("models/glm_moe_lite.py", "mtp.",
     re.compile(r"MTP_LEVEL_KEYS = \(([^)]*)\)", re.S)),
]

_KEY = re.compile(r'"([a-z0-9_]+)"')

#: catalog table rows: | `name` \| `a` / `b` | type | ...
_DOC_ROW = re.compile(r"^\|\s*(`[^|]*`)\s*\|", re.M)
_DOC_NAME = re.compile(r"`([a-z0-9_.]+)`")


def code_metrics() -> set:
    names: set = set()
    for py in sorted(PKG.rglob("*.py")):
        text = py.read_text()
        names.update(_LITERAL.findall(text))
    for rel, prefix, pattern in _DYNAMIC:
        text = (PKG / rel).read_text()
        m = pattern.search(text)
        if not m:
            print(f"check_metric_docs: dynamic-site pattern for {rel} "
                  f"no longer matches — update _DYNAMIC", file=sys.stderr)
            sys.exit(2)
        names.update(prefix + k for k in _KEY.findall(m.group(1)))
    # "client." + key literals are covered by _DYNAMIC; a bare prefix
    # fragment like "client." itself is not a series
    return {n for n in names if not n.endswith(".")}


def code_spans() -> set:
    names: set = set()
    for py in sorted(PKG.rglob("*.py")):
        text = py.read_text()
        names.update(_SPAN_RECORD.findall(text))
        names.update(_SPAN_CTX.findall(text))
    return names


def _doc_section(heading: str) -> str:
    text = DOC.read_text()
    m = re.search(rf"\n(#{{2,3}}) {re.escape(heading)}\n", text)
    if m is None:
        print(f"check_metric_docs: docs/observability.md has no "
              f"'{heading}' section", file=sys.stderr)
        sys.exit(2)
    body = text[m.end():]
    # the section runs until the next heading of the same-or-higher level
    nxt = re.search(rf"\n#{{2,{len(m.group(1))}}} ", body)
    return body if nxt is None else body[:nxt.start()]


def documented(heading: str) -> set:
    names: set = set()
    for cell in _DOC_ROW.findall(_doc_section(heading)):
        names.update(_DOC_NAME.findall(cell))
    return names


def _diff(kind: str, code: set, docs: set) -> bool:
    undocumented = sorted(code - docs)
    stale = sorted(docs - code)
    if undocumented:
        print(f"{kind} in code but MISSING from the docs/observability.md "
              "catalog:")
        for n in undocumented:
            print(f"  - {n}")
    if stale:
        print(f"{kind} documented in docs/observability.md but no longer "
              "in analytics_zoo_tpu/:")
        for n in stale:
            print(f"  - {n}")
    return bool(undocumented or stale)


def main() -> int:
    bad = _diff("metrics", code_metrics(), documented("Metric catalog"))
    bad = _diff("span names", code_spans(),
                documented("Span catalog")) or bad
    if bad:
        return 1
    print(f"metric catalog in sync: {len(code_metrics())} series; "
          f"span catalog in sync: {len(code_spans())} names")
    return 0


if __name__ == "__main__":
    sys.exit(main())
