"""Model FLOP/s utilisation: the job's rate per chip (``rate_key``) times
the family's FLOPs per unit (``flops_key``) over the chip's published bf16
peak, in percent.  Only a TPU has a peak to divide by."""

from benchmark.harness import peaks


def read(args, reading):
    values = reading.result.values
    if reading.device["platform"] != "tpu" or \
            args["rate_key"] not in values or args["flops_key"] not in values:
        return None
    peak = peaks.peaks(reading.device["kind"])["bf16_flops"]
    return 100.0 * values[args["rate_key"]] * values[args["flops_key"]] / peak
