"""95th percentile gap between consecutive output tokens of one request,
over every such gap inside the window, all requests together."""
from bench.record import p95


def read(run):
    return p95([1e3 * (b - a) for r in run.requests
                for a, b in zip(r.times, r.times[1:])
                if run.in_window(a) and run.in_window(b)])
