"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps, op time
and collective time.  Nothing here but ``jax.profiler.ProfileData`` reads the
file, and nothing here imports the program.

What a TPU trace holds (looked at by hand on a v5e, PR 22): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per
executed HLO op (its name the whole HLO instruction; 460,000 events in one
second of BERT-base training), whose line ``Async XLA Ops`` carries each
asynchronous op from its start to its done (copies, slices, collectives) and
whose line ``XLA Modules`` carries one event per executed program
(``jit_train_step(<hash>)``); and a plane ``/host:CPU`` with one line per host thread, carrying
JAX's own TraceMe events and every ``jax.profiler.TraceAnnotation`` of the
benchmark.  All planes share one clock, in nanoseconds from the start of the
profile.  Control-flow ops (``while``, ``conditional``) span the ops of their
bodies on the same line, so an op's time is its SELF time (its span less the
spans nested in it) and busy time is the union of the LEAF events.

On a platform with no device plane (the CPU rehearsal) the host events that
carry an ``hlo_op`` stat stand in, as one device called ``host-xla``, so that
the same code runs end to end in the tests; run.py never prints such a number
without the platform beside it.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Span = Tuple[float, float, str]  # start ns, end ns, name

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: ops that move data between chips (XLA's HLO names, sync and async forms)
COLLECTIVE = (r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
              r"collective-permute|collective-broadcast)")
#: gaps shorter than this sit between two back-to-back ops; they count as
#: idle time but are not worth a name
MIN_NAMED_GAP_NS = 50_000.0
#: at most this many gaps (the longest) are matched against host events
MAX_NAMED_GAPS = 500
#: host events shorter than this cannot explain a gap worth a name
MIN_HOST_EVENT_NS = 10_000.0
#: a breakdown's names are cut to this many characters
MAX_LABEL = 160


@dataclass
class Device:
    name: str
    ops: List[Span] = field(default_factory=list)
    async_ops: List[Span] = field(default_factory=list)
    modules: List[Span] = field(default_factory=list)
    #: worked out once by ``settle()``: each op's self time, the ops with
    #: nothing nested in them, and the union of those (the busy intervals)
    own: List[float] = field(default_factory=list)
    leaves: List[Span] = field(default_factory=list)
    busy: List[Tuple[float, float]] = field(default_factory=list)

    def settle(self) -> "Device":
        self.ops.sort()
        self.modules.sort()
        self.own, leaf = self_times(self.ops)
        self.leaves = [sp for sp, is_leaf in zip(self.ops, leaf) if is_leaf]
        self.busy = union((s, e) for s, e, _ in self.leaves)
        return self


@dataclass
class Trace:
    devices: List[Device]
    host: List[Span]
    #: [first device event's start, last device event's end], over all chips
    window: Tuple[float, float]


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _spans(line) -> List[Span]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events if e.duration_ns > 0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> Trace:
    devices: List[Device] = []
    host: List[Span] = []
    host_planes = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(_spans(line))
                elif line.name == ASYNC_LINE:
                    dev.async_ops.extend(_spans(line))
                elif line.name == MODULES_LINE:
                    dev.modules.extend(_spans(line))
            if dev.ops:
                devices.append(dev.settle())
        elif plane.name.startswith("/host:"):
            host_planes.append(plane)
            for line in plane.lines:
                host.extend(sp for sp in _spans(line)
                            if sp[1] - sp[0] >= MIN_HOST_EVENT_NS)
    if not devices:
        # no chip in the trace (the CPU rehearsal): the host's XLA ops stand
        # in as one device
        host_xla = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in host_planes for line in plane.lines
            for e in line.events
            if e.duration_ns > 0 and any(k == "hlo_op" for k, _ in e.stats))
        if host_xla:
            devices = [Device("host-xla", ops=host_xla).settle()]
    host.sort()
    if not devices:
        return Trace([], host, (0.0, 0.0))
    devices.sort(key=lambda d: d.name)
    t0 = min(d.ops[0][0] for d in devices)
    t1 = max(max(e for _, e, _ in d.ops) for d in devices)
    return Trace(devices, host, (t0, t1))


# -- intervals ----------------------------------------------------------------

def union(spans: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The spans merged into disjoint, sorted intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The part of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out: List[Tuple[float, float]] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(spans: Sequence[Span]) -> Tuple[List[float], List[bool]]:
    """For events of ONE line, sorted by start: each event's span less the
    spans nested directly in it, and whether it is a leaf (nothing nested)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    own = [spans[i][1] - spans[i][0] for i in range(len(spans))]
    leaf = [True] * len(spans)
    stack: List[int] = []
    for i in order:
        s, e, _ = spans[i]
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][1]:
            own[stack[-1]] -= e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return own, leaf


# -- reductions ---------------------------------------------------------------

def busy_seconds(trace: Trace) -> List[float]:
    """Per chip: seconds in which an op ran."""
    return [length(d.busy) / 1e9 for d in trace.devices]


def window_seconds(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_pct(trace: Trace) -> Optional[float]:
    """1 - busy/window on the chip that idles most, in percent."""
    w = window_seconds(trace)
    if not trace.devices or w <= 0:
        return None
    return 100.0 * (1.0 - min(busy_seconds(trace)) / w)


def executions(dev: Device, programs: str = "dominant") -> List[Span]:
    """The programs a chip executed WHOLE inside the slice (``XLA Modules``
    events; the first and the last are cut by the slice's edges and left
    out): all of them, or those of the program that took most time."""
    whole = dev.modules[1:-1] if len(dev.modules) >= 3 else dev.modules
    if programs == "all" or not whole:
        return list(whole)
    total: Dict[str, float] = {}
    for s, e, name in whole:
        total[name] = total.get(name, 0.0) + e - s
    top = max(total, key=total.get)
    return [m for m in whole if m[2] == top]


def busy_ms_per_execution(trace: Trace, programs: str = "dominant"
                          ) -> Optional[float]:
    """Device-busy milliseconds per executed program, mean over the chips:
    busy time inside the whole executions of the slice over their count
    ("dominant": the train step; "all": every serving batch)."""
    per_dev = []
    for d in trace.devices:
        runs = executions(d, programs)
        if not runs:
            continue
        inside = union((s, e) for s, e, _ in runs)
        covered = length(d.busy) - length(subtract(d.busy, inside))
        per_dev.append(covered / len(runs) / 1e6)
    return float(np.mean(per_dev)) if per_dev else None


def matching(trace: Trace, pattern: str = COLLECTIVE) -> Optional[dict]:
    """Time of the ops whose name matches, per whole execution of the
    dominant program, on the chip where it is largest: total milliseconds
    (an asynchronous op counts from its start to its done, as the ``Async
    XLA Ops`` line has it), the part of them during which no other op ran
    on that chip ("exposed"), and the count of executions."""
    rx = re.compile(pattern)
    best = None
    for d in trace.devices:
        runs = executions(d) or [(trace.window[0], trace.window[1], "")]
        inside = union((s, e) for s, e, _ in runs)
        hit = union((s, e) for s, e, n in d.leaves + d.async_ops
                    if rx.search(n))
        hit = subtract(hit, subtract(hit, inside))  # the part inside runs
        rest = union((s, e) for s, e, n in d.leaves if not rx.search(n))
        row = {"total_ms": length(hit) / 1e6 / len(runs),
               "exposed_ms": length(subtract(hit, rest)) / 1e6 / len(runs),
               "executions": len(runs), "events": len(hit),
               "device": d.name}
        if best is None or row["total_ms"] > best["total_ms"]:
            best = row
    return best


def label(name: str) -> str:
    """An event's name made short enough for a breakdown: on a TPU it is
    the whole HLO instruction, so the layouts (``{...}``) go, and what is
    left is cut to MAX_LABEL characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:MAX_LABEL]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """``[name, seconds]`` of the ops with most self time, seconds a mean
    over the chips."""
    total: Dict[str, float] = {}
    for d in trace.devices:
        for (_, _, name), t in zip(d.ops, d.own):
            total[name] = total.get(name, 0.0) + t
    k = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[label(name), t / 1e9 / k] for name, t in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """``[what the host was doing, seconds]`` for the idle time of the chip
    that idles most.  A gap takes the name of the shortest host event that
    covers at least half of it (the most specific thing the host was in),
    else of the host event that overlaps it most; ``bench:`` names are the
    benchmark's own annotations."""
    if not trace.devices:
        return []
    worst = min(trace.devices, key=lambda d: length(d.busy))
    gaps = subtract([trace.window], worst.busy)
    small = [g for g in gaps if g[1] - g[0] < MIN_NAMED_GAP_NS]
    big = sorted((g for g in gaps if g[1] - g[0] >= MIN_NAMED_GAP_NS),
                 key=lambda g: g[0] - g[1])
    total: Dict[str, float] = {}
    if small:
        total["gaps under %d us, between ops" % (MIN_NAMED_GAP_NS / 1e3)] \
            = length(small)
    if len(big) > MAX_NAMED_GAPS:
        total["gaps beyond the %d longest" % MAX_NAMED_GAPS] = \
            length(big[MAX_NAMED_GAPS:])
        big = big[:MAX_NAMED_GAPS]
    starts = np.array([h[0] for h in trace.host])
    ends = np.array([h[1] for h in trace.host])
    for g0, g1 in big:
        what = "no host event"
        if len(starts):
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            covering = np.flatnonzero(overlap >= 0.5 * (g1 - g0))
            if len(covering):
                i = covering[np.argmin((ends - starts)[covering])]
                what = trace.host[i][2]
            elif overlap.max() > 0:
                what = trace.host[int(overlap.argmax())][2]
        total[what] = total.get(what, 0.0) + g1 - g0
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[label(name), t / 1e9] for name, t in ranked]


def describe(path: str, limit: int = 4) -> str:
    """Planes and lines with their event counts, and the first events with
    their stats: what to read before writing a reader against a new kind of
    trace."""
    from jax.profiler import ProfileData
    out = [f"{path}: {os.path.getsize(path)} bytes"]
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            names = {e.name for e in events}
            busy = sum(e.duration_ns for e in events)
            out.append(f"  LINE {line.name!r}: {len(events)} events, "
                       f"{len(names)} names of {sum(map(len, names))} "
                       f"characters, {busy / 1e6:.3f} ms summed")
            for e in events[:limit]:
                stats = {k: (v if not isinstance(v, str) else v[:60])
                         for k, v in e.stats}
                out.append(f"    {e.name[:120]!r} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
