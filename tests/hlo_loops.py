"""Where a compiled program's collectives sit: inside a ``while`` body (they
run once an iteration) or outside it (once a call).  Reads the text of
``jax.stages.Compiled.as_text()``; shared by the CPU-mesh cases of
``test_scaleout.py`` and the described-TPU cases of ``test_tpu_compile.py``."""

import re

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_CALLEE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+)")
_WHILE_BODY = re.compile(r"\bwhile\(.*?body=%?([\w.\-]+)")
_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def collectives(hlo_text):
    """``(in_loop, outside)``: for each, ``{op name: [result types]}`` of
    the collectives in (the computations reachable from) a while body, and
    of all the others."""
    computations, current = {}, None
    for line in hlo_text.splitlines():
        head = None if line.startswith(" ") else _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(1), [])
        elif current is not None:
            current.append(line)

    looped = set()

    def reach(name):
        if name in looped or name not in computations:
            return
        looped.add(name)
        for line in computations[name]:
            for callee in _CALLEE.findall(line):
                reach(callee)

    for body in _WHILE_BODY.findall(hlo_text):
        reach(body)

    in_loop, outside = {}, {}
    for name, lines in computations.items():
        into = in_loop if name in looped else outside
        for line in lines:
            m = _COLLECTIVE.search(line)
            if m:
                into.setdefault(m.group(2), []).append(m.group(1))
    return in_loop, outside
