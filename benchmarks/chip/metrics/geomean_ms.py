"""geomean_ms: geometric mean of the latency of every query answered in the
window, the basis of TPC-H's Power@Size."""

import math


def read(run):
    ms = [q.latency_s * 1e3 for q in run.done()]
    return math.exp(sum(math.log(x) for x in ms) / len(ms)) if ms else None
