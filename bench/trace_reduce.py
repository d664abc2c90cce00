"""From a profiler trace to the device's busy time, idle gaps and collectives.

:func:`load` flattens the JAX profiler's ``.xplane.pb`` into plain events
``{"plane", "line", "name", "start_ns", "dur_ns"}``: the operations on each
TPU's ``XLA Ops`` line, and the host's annotations whose names start with
one of :data:`HOST_PREFIXES` (the benchmark's window marker and the
program's spans). :func:`reduce` works on that list alone, so a recorded
excerpt tests it without a chip.

Busy time is the union of a device's operation intervals inside the
``bench/window`` annotation, averaged over the devices that ran anything.
An idle gap is a stretch of that window with no operation on the first
device; it is charged to the innermost host span open at its midpoint.
A collective is exposed where it runs while no other operation does.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW = "bench/window"
HOST_PREFIXES = ("bench/", "serve/", "train/")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
TOP = 10
NESTING = 64          # host spans searched back for the innermost one


def load(trace_dir: str) -> list[dict]:
    """Device operations and host spans from the newest trace under
    ``trace_dir``."""
    from jax._src.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = [p for p in data.planes if p.name.startswith("/host:")] + \
        [p for p in data.planes if p.name.startswith("/device:TPU")]
    out: list[dict] = []
    lo, hi = float("-inf"), float("inf")
    for plane in planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIXES):
                    continue
                t, d = ev.start_ns, ev.duration_ns
                if device and not (t < hi and t + d > lo):
                    continue            # outside the window: never read
                if ev.name == WINDOW:
                    lo, hi = t, t + d
                # an op's name is its HLO text; keep what precedes " = "
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name.split(" = ")[0],
                            "start_ns": t, "dur_ns": d})
    return out


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _subtract(a_set, b_set) -> float:
    """Length of the union ``a_set`` not covered by the union ``b_set``."""
    total, j = 0.0, 0
    for a0, a1 in a_set:
        cur = a0
        while j < len(b_set) and b_set[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_set) and b_set[k][0] < a1:
            b0, b1 = b_set[k]
            if b0 > cur:
                total += b0 - cur
            cur = max(cur, b1)
            k += 1
        if cur < a1:
            total += a1 - cur
    return total


def reduce(events: list[dict]) -> dict | None:
    """Busy and idle time, top operations and idle gaps in the window.

    Returns None where the trace holds no window or no device operation."""
    win = [e for e in events if e["name"] == WINDOW]
    if not win:
        return None
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    ops: dict[str, list] = defaultdict(list)
    for e in events:
        if e["line"] != "XLA Ops":
            continue
        a, b = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if b > a:
            ops[e["plane"]].append((a, b, e["name"]))
    if not ops:
        return None
    planes = sorted(ops)
    busy, exposed, by_name = [], [], defaultdict(float)
    for pl in planes:
        busy.append(_length(_union([(a, b) for a, b, _ in ops[pl]])))
        coll = [(a, b) for a, b, n in ops[pl] if n.startswith(COLLECTIVES)]
        other = [(a, b) for a, b, n in ops[pl]
                 if not n.startswith(COLLECTIVES)]
        exposed.append(_subtract(_union(coll), _union(other)))
        for a, b, n in ops[pl]:
            by_name[n] += (b - a) / len(planes)

    host = sorted(((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events if e["line"] != "XLA Ops"
                   and e["name"] != WINDOW))
    starts = [h[0] for h in host]

    def doing(t: float) -> str:
        """The innermost host span open at ``t``: the latest to start."""
        i = bisect.bisect_right(starts, t) - 1
        for a, b, name in reversed(host[max(0, i - NESTING):i + 1]):
            if a <= t < b:
                return name
        return "no host span"

    gaps: dict[str, float] = defaultdict(float)
    cursor = w0
    for a, b in _union([(a, b) for a, b, _ in ops[planes[0]]]) + [[w1, w1]]:
        if a > cursor:
            gaps[doing((cursor + a) / 2)] += a - cursor
        cursor = max(cursor, b)
    ns = 1e-9
    window_s = (w1 - w0) * ns
    busy_s = sum(busy) / len(busy) * ns
    top = lambda d: [[k, v * ns] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "collective_exposed_s": sum(exposed) / len(exposed) * ns,
            "devices": len(planes),
            "device_ops": top(by_name), "idle_gaps": top(gaps)}


def excerpt(events: list[dict], ms: float) -> list[dict]:
    """The first ``ms`` of the window: a trace small enough to keep."""
    win = next(e for e in events if e["name"] == WINDOW)
    w0, w1 = win["start_ns"], win["start_ns"] + ms * 1e6
    out = [dict(win, dur_ns=ms * 1e6)]
    out += [e for e in events if e is not win
            and e["start_ns"] < w1 and e["start_ns"] + e["dur_ns"] > w0]
    return out
