"""From the device trace of the slice: ``idle_pct`` (1 - union of op
intervals over the traced window, on the chip that idles most) or
``busy_ms_per_execution`` (device-busy time inside the executed programs
over their count; ``programs`` "dominant" = the one program that took most
time, "all" = every program)."""

from benchmark.harness import xplane


def read(args, reading):
    if reading.trace is None or not reading.trace.devices:
        return None
    if args["stat"] == "idle_pct":
        return xplane.idle_pct(reading.trace)
    return xplane.busy_ms_per_execution(reading.trace,
                                        args.get("programs", "dominant"))
