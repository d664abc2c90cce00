"""Mean host time the scheduler spends on its own work in a step that
decodes: each window ``serve/step`` that holds a ``serve/decode_step``, less
the parts of it its ``serve/prefill``, ``serve/decode_step`` and
``serve/idle`` spans cover. What is left is admission, the token upload,
the row inserts and the harvest."""
import bisect

COVERED = ("serve/prefill", "serve/decode_step", "serve/idle")


def _own_s(t0: float, t1: float, kids: list) -> float:
    """Length of ``[t0, t1)`` less the union of ``kids``, sorted by start."""
    covered, cursor = 0.0, t0
    for a, b, _ in kids:
        a, b = max(a, cursor), min(b, t1)
        if b > a:
            covered += b - a
            cursor = b
    return t1 - t0 - covered


def read(run):
    kids = sorted((s["t0"], s["t1"], s["name"]) for s in run.spans
                  if s["name"] in COVERED)
    starts = [k[0] for k in kids]
    own = []
    for step in run.window_spans("serve/step"):
        t0, t1 = step["t0"], step["t1"]
        inner = kids[bisect.bisect_left(starts, t0):
                     bisect.bisect_left(starts, t1)]
        if any(name == "serve/decode_step" for _, _, name in inner):
            own.append(_own_s(t0, t1, inner))
    return 1e3 * sum(own) / len(own) if own else None
