"""A module's time on the device: the ops of the slice named by the scope
that made them.

The program hands out a table of its own compiled train step
(``analytics_zoo_tpu.core.trace.op_scopes("train_step")``: instruction name
-> ``(scope, also)``, parsed from the executable's HLO text); a device
event's name is the whole HLO instruction and starts with that instruction
name.  Here the two are joined.  Over the WHOLE executions of the dominant
program in the slice (``xplane.executions``; where a platform records no
programs, the CPU rehearsal, over the window), every op's SELF time
(``Device.own``: its span less the spans nested in it) is summed by
instruction name and divided by the count of executions, mean over the
chips.  ``stat``:

``ms_per_step``       the time of the ops whose scope matches ``scope`` (a
                      regex searched in the path) and not ``not_scope``, and
                      whose event name does not match ``not_op``
``pct_of_step``       the same over the summed self time of every op in
                      those executions, in percent: all scopes and the
                      unattributed add up to 100
``unattributed_pct``  the share of the ops the table gives no scope or does
                      not hold.  An op whose scope is the EMPTY path (the
                      step's own arithmetic, outside every module) is
                      attributed and matches no pattern
``mixed_pct``         the share of the ops (fusions) that hold instructions
                      from both sides of the ``optimizer`` scope: of their
                      named scopes, ``scope`` and ``also``, one lies under
                      ``optimizer`` and one does not.  The empty path takes
                      no side

A number whenever the trace has a device and the program a table, 0.0 where
nothing matches; None when the program registered no table (or is a program
from before it had one).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Tuple

from benchmark.harness import xplane

HEAD = re.compile(r"^%?([\w.\-]+)")
OPTIMIZER = re.compile(r"(^|/)optimizer(/|$)")

#: the last trace reduced and its ``{instruction: (ms a step, event name)}``:
#: ten metrics read one slice
_reduced: Tuple[object, Dict[str, Tuple[float, str]]] = (None, {})


def table():
    """The program's table of its train step's device ops, if it has one
    (a program from before PR 35 has no ``op_scopes``)."""
    from analytics_zoo_tpu.core import trace
    op_scopes = getattr(trace, "op_scopes", None)
    return op_scopes("train_step") if op_scopes else None


def self_ms_per_step(trace) -> Dict[str, Tuple[float, str]]:
    """``{instruction name: (self ms per execution, the event's name)}``."""
    global _reduced
    if _reduced[0] is trace:
        return _reduced[1]
    per_dev = []
    for d in trace.devices:
        runs = xplane.executions(d) or [(trace.window[0], trace.window[1],
                                         "")]
        starts = [r[0] for r in runs]
        own: Dict[str, Tuple[float, str]] = {}
        for (s, e, name), t in zip(d.ops, d.own):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > runs[i][1]:
                continue  # outside every whole execution
            head = HEAD.match(name)
            key = head.group(1) if head else name
            own[key] = (own.get(key, (0.0, name))[0] + t / 1e6 / len(runs),
                        name)
        per_dev.append(own)
    out: Dict[str, Tuple[float, str]] = {}
    for own in per_dev:
        for key, (ms, name) in own.items():
            out[key] = (out.get(key, (0.0, name))[0] + ms / len(per_dev),
                        name)
    _reduced = (trace, out)
    return out


def _mixed(scope: Optional[str], also) -> bool:
    sides = {bool(OPTIMIZER.search(s)) for s in (scope, *also) if s}
    return len(sides) == 2


def read(args, reading) -> Optional[float]:
    if reading.trace is None or not reading.trace.devices:
        return None
    scopes = table()
    if scopes is None:
        return None
    ops = self_ms_per_step(reading.trace)
    total = sum(ms for ms, _ in ops.values())
    stat = args["stat"]
    wanted = re.compile(args.get("scope", ""))
    not_scope = re.compile(args["not_scope"]) if "not_scope" in args else None
    not_op = re.compile(args["not_op"]) if "not_op" in args else None
    hit = 0.0
    for key, (ms, name) in ops.items():
        scope, also = scopes.get(key, (None, ()))
        if stat == "unattributed_pct":
            hit += ms if scope is None else 0.0
        elif stat == "mixed_pct":
            hit += ms if _mixed(scope, also) else 0.0
        elif (scope and wanted.search(scope)
              and not (not_scope and not_scope.search(scope))
              and not (not_op and not_op.search(name))):
            hit += ms
    if stat == "ms_per_step":
        return hit
    return 100.0 * hit / total if total > 0 else 0.0
