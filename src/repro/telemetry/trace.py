"""Span tracer exporting Chrome/Perfetto trace-event JSON.

Lightweight and dependency-free: a :class:`Tracer` records begin/end ("B"/
"E") duration events and instant ("i") events into an in-process buffer;
``dump(path)`` writes the standard trace-event container
(``{"traceEvents": [...], "displayTimeUnit": "ms"}``) that chrome://tracing
and https://ui.perfetto.dev load directly.

Nesting is tracked with a ``contextvars`` stack, so spans opened in
``async``/generator code attribute to the right parent, and each OS thread
gets its own lane (``tid``) — the checkpoint writer thread's spans land in
their own track. Every span also enters a ``jax.profiler.TraceAnnotation``
of the same name (unless ``jax_annotations=False``), so an XLA/Perfetto
device profile carries the paper's phase names next to the HLO ops they
bracket.

``validate_trace_events`` is the schema half the tests and
``scripts/check_metrics_schema.py`` share: per-thread monotonic ``ts`` and
strictly matched (LIFO, same-name) B/E pairs.
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import os
import threading
import time

import jax.profiler

_SPAN_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_span_stack", default=())


class Tracer:
    """Collects trace events; thread-safe; one per process by default."""

    def __init__(self, process_name: str = "repro",
                 jax_annotations: bool = True):
        self.process_name = process_name
        self.jax_annotations = jax_annotations
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._t0 = time.perf_counter()
        self._pid = os.getpid()

    # -- core ----------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _emit(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Context manager recording one B/E pair (plus a jax.profiler
        annotation when enabled). ``args`` become the event's ``args`` dict
        and must be JSON-serializable."""
        tid = threading.get_ident()
        stack = _SPAN_STACK.get()
        token = _SPAN_STACK.set(stack + (name,))
        ev = {"ph": "B", "name": name, "cat": "repro", "ts": self._now_us(),
              "pid": self._pid, "tid": tid}
        if args:
            ev["args"] = args
        if stack:
            ev.setdefault("args", {})["parent"] = stack[-1]
        self._emit(ev)
        ann = (jax.profiler.TraceAnnotation(name) if self.jax_annotations
               else contextlib.nullcontext())
        try:
            with ann:
                yield self
        finally:
            self._emit({"ph": "E", "name": name, "cat": "repro",
                        "ts": self._now_us(), "pid": self._pid, "tid": tid})
            _SPAN_STACK.reset(token)

    def instant(self, name: str, **args) -> None:
        ev = {"ph": "i", "name": name, "cat": "repro", "s": "t",
              "ts": self._now_us(), "pid": self._pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def current_span(self) -> str | None:
        stack = _SPAN_STACK.get()
        return stack[-1] if stack else None

    # -- export --------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
        self._t0 = time.perf_counter()

    def dump(self, path: str) -> dict:
        """Write the Chrome trace-event JSON container; returns it."""
        meta = [{"ph": "M", "name": "process_name", "pid": self._pid,
                 "tid": 0, "ts": 0,
                 "args": {"name": self.process_name}}]
        doc = {"traceEvents": meta + self.events(),
               "displayTimeUnit": "ms"}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return doc


def validate_trace_events(events: list[dict]) -> list[str]:
    """Schema check for a trace-event list; returns problems ([] = valid).

    Enforced invariants (the ones Perfetto silently mis-renders when
    broken): every event has a known ``ph`` and numeric ``ts`` (metadata
    "M" events excepted), ``ts`` is non-decreasing per (pid, tid) lane, and
    B/E events form matched LIFO pairs with identical names per lane.
    """
    problems: list[str] = []
    last_ts: dict[tuple, float] = {}
    stacks: dict[tuple, list[tuple[str, float]]] = {}
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph not in ("B", "E", "i", "I", "X", "M", "C"):
            problems.append(f"event {i}: unknown ph {ph!r}")
            continue
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i}: non-numeric ts {ts!r}")
            continue
        lane = (ev.get("pid"), ev.get("tid"))
        if ts < last_ts.get(lane, float("-inf")):
            problems.append(
                f"event {i}: ts {ts} decreases on lane {lane} "
                f"(prev {last_ts[lane]})")
        last_ts[lane] = ts
        if ph == "B":
            stacks.setdefault(lane, []).append((ev.get("name", ""), ts))
        elif ph == "E":
            stack = stacks.get(lane) or []
            if not stack:
                problems.append(f"event {i}: E {ev.get('name')!r} with no "
                                f"open B on lane {lane}")
                continue
            name, b_ts = stack.pop()
            if ev.get("name") != name:
                problems.append(
                    f"event {i}: E {ev.get('name')!r} closes B {name!r} "
                    f"on lane {lane} (not LIFO-matched)")
            if ts < b_ts:
                problems.append(f"event {i}: E.ts {ts} < B.ts {b_ts} "
                                f"for span {name!r}")
    for lane, stack in stacks.items():
        for name, _ in stack:
            problems.append(f"unclosed span {name!r} on lane {lane}")
    return problems


# ---------------------------------------------------------------------------
# process-global tracer
# ---------------------------------------------------------------------------
_default = Tracer()


def get_tracer() -> Tracer:
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer (tests); returns the previous one."""
    global _default
    prev, _default = _default, tracer
    return prev


def span(name: str, **args):
    """``with telemetry.span("train/step"): ...`` on the global tracer."""
    return _default.span(name, **args)


def dump_trace(path: str) -> dict:
    """Export the global tracer's buffer as Chrome trace-event JSON."""
    return _default.dump(path)
