"""Output tokens stamped inside the window over the window's length."""


def read(run):
    n = sum(run.in_window(t) for r in run.requests for t in r.times)
    return n / run.seconds if n else None
