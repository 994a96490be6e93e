"""Open loop against ``ClusterServing``: requests sent on a schedule drawn
from the seed (Poisson, or bursts), whatever the server does (independent
users).  Latency counts from the time a request was DUE, so a stall charges
the requests behind it; how late the generator itself ran is reported
(``gen_late_ms_p99``).  Used by no cell yet: PERF.md's open table lists the
open-loop cells, which need a traffic file and a ``workloads`` entry only."""

from benchmark.harness import window
from benchmark.jobs import _serve


def run(run: window.Run) -> window.Result:
    return _serve.run(run, "open")
