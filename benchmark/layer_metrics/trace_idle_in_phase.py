"""How much of the traced window the device idled while the program was in
one of its named phases: on the chip that idles most, the length of idle
(the window less the union of its op intervals) that lies inside the union
of the host spans named ``phase``, or outside the union of all those named
in ``outside``, over the window, in percent.  Phases of one thread are
disjoint, so its ``phase`` shares and the ``outside`` share of all of them
add up to ``trace_busy``'s ``idle_pct``; a phase of another thread overlaps
them and is read beside them.  The profiler records a span only if it both
starts and ends inside the slice, so a phase the slice does not hold whole
reads 0 and idle under it counts as outside (as all idle does for a program
that names no phases)."""

from benchmark.harness import xplane


def read(args, reading):
    trace = reading.trace
    if trace is None or not trace.devices or trace.window[1] <= trace.window[0]:
        return None
    names = set(args.get("outside") or [args["phase"]])
    worst = min(trace.devices, key=lambda d: xplane.length(d.busy))
    idle = xplane.subtract([trace.window], worst.busy)
    spans = xplane.union((s, e) for s, e, n in trace.host if n in names)
    outside = xplane.length(xplane.subtract(idle, spans))
    part = outside if "outside" in args else xplane.length(idle) - outside
    return 100.0 * part / (trace.window[1] - trace.window[0])
