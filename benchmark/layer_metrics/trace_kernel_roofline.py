"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the kernel must do a step — the larger of
``flops_per_step`` over the published bf16 peak and ``bytes_per_step`` over
the published HBM bandwidth — over the time per executed train step of the
ops whose XLA name matches ``pattern``, from the device trace (on the chip
where they take longest).  The operations and bytes are numbers in the
metric's file, worked out by a function of the cell's family from the
mathematics (no recomputation, no padding, no masked block), so a kernel
that runs twice a step for a block's recomputation reads at most 50%."""

from benchmark.harness import peaks, xplane


def read(args, reading):
    if reading.trace is None or not reading.trace.devices or \
            reading.device["platform"] != "tpu":
        return None
    row = xplane.matching(reading.trace, args["pattern"])
    if row is None or not row["events"] or not row["total_ms"] > 0:
        return None
    peak = peaks.peaks(reading.device["kind"])
    least_s = max(args["flops_per_step"] / peak["bf16_flops"],
                  args["bytes_per_step"] / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (row["total_ms"] / 1e3)
