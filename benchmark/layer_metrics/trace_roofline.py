"""Utilisation of the time the device is busy: the family's FLOPs per
executed program and chip (``flops_key``) over the chip's published bf16
peak, over the device-busy time per execution from the trace, in percent.
It differs from the model FLOP/s utilisation by the idle share."""

from benchmark.harness import peaks, xplane


def read(args, reading):
    flops = reading.result.values.get(args["flops_key"])
    if reading.trace is None or flops is None or \
            reading.device["platform"] != "tpu":
        return None
    busy_ms = xplane.busy_ms_per_execution(reading.trace)
    if not busy_ms:
        return None
    peak = peaks.peaks(reading.device["kind"])["bf16_flops"]
    return 100.0 * flops / peak / (busy_ms / 1e3)
