"""95th percentile wait from arrival to the start of the request's prefill,
over requests that arrived in the window: the scheduler's admission queue.
(The result's ``started_s`` is stamped after the prefill, where it equals
the time of the first token, so the prefill span's start is read.)"""
from bench.record import p95


def read(run):
    start = {s["args"]["rid"]: s["t0"] for s in run.spans
             if s["name"] == "serve/prefill"}
    return p95([1e3 * (start[r.rid] - r.arrival) for r in run.requests
                if r.rid in start and run.in_window(r.arrival)])
