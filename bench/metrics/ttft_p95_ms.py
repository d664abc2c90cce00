"""95th percentile time to first token over every request that arrived in
the window, from its arrival stamp; one that never got a token counts as
missing (and the percentile with it, where it falls there)."""
from bench.record import p95


def read(run):
    return p95([1e3 * (r.times[0] - r.arrival) if r.times else float("inf")
                for r in run.requests if run.in_window(r.arrival)])
