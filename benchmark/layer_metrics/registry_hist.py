"""A statistic of one of the program's registry histograms over the window:
``p50`` / ``p99`` (bucket-interpolated), ``mean``, or ``sum_pct_of_window``
(the histogram's sum of milliseconds as a share of the window)."""

from benchmark.harness import registry


def read(args, reading):
    hist = reading.result.registry.get(args["series"])
    if not isinstance(hist, dict) or not hist["count"]:
        return None
    stat = args["stat"]
    if stat == "mean":
        return hist["sum"] / hist["count"]
    if stat == "sum_pct_of_window":
        return 100.0 * hist["sum"] / (1000.0 * reading.result.window_s)
    return registry.bucket_quantile(hist["edges"], hist["counts"],
                                    {"p50": 0.5, "p99": 0.99}[stat])
