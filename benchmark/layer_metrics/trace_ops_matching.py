"""From the device trace of the slice, the ops whose XLA name matches
``pattern`` (default: the collectives), on the chip where they take
longest: ``ms_per_execution`` (their time per executed train step) or
``exposed_pct`` (the share of that time during which no other op ran on
the chip)."""

from benchmark.harness import xplane


def read(args, reading):
    if reading.trace is None or not reading.trace.devices:
        return None
    row = xplane.matching(reading.trace,
                          args.get("pattern", xplane.COLLECTIVE))
    if row is None or not row["events"]:
        return None
    if args["stat"] == "ms_per_execution":
        return row["total_ms"]
    return 100.0 * row["exposed_ms"] / row["total_ms"]
