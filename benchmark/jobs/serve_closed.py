"""Closed loop against ``ClusterServing``: a fixed number of single-row
requests in flight, each reply releasing the next (an upstream job that
keeps a bounded number of records outstanding).  The server runs at the
capacity it has; throughput and the latency at that depth are the result."""

from benchmark.harness import window
from benchmark.jobs import _serve


def run(run: window.Run) -> window.Result:
    return _serve.run(run, "closed")
