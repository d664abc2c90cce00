"""What one run leaves for the metric readers, and the arithmetic they share.

Every time is the host's ``time.perf_counter()`` in seconds. The window is
``[t_open, t_close)``; set-up is everything from the process's start to
``t_open``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts the executables JAX builds or loads while it is on."""

    def __init__(self) -> None:
        self.on = False
        self.count = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_kw) -> None:
        if self.on and event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Request:
    arrival: float
    prompt_len: int
    times: list             # each output token's host stamp
    reason: str             # "length" when it finished, else why not
    tokens: Any = None      # the served tokens
    prompt: Any = None
    rid: int = -1           # the scheduler's request id


@dataclasses.dataclass
class Run:
    spec: dict              # the configuration file
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    requests: list          # Request, every one submitted for the window
    spans: list             # {"name", "t0", "t1", "args"} from the tracer
    peak: dict              # the device's row of peaks.json
    trace: dict | None = None   # trace_reduce.reduce() of the traced window

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def window_spans(self, name: str) -> list[dict]:
        return [s for s in self.spans
                if s["name"] == name and self.in_window(s["t0"])]


def p95(values: list[float]) -> float | None:
    """Nearest-rank 95th percentile; None for no values or a missing one."""
    if not values:
        return None
    v = sorted(values)[math.ceil(0.95 * len(values)) - 1]
    return None if math.isinf(v) else v


def mean_span_ms(run: Run, name: str) -> float | None:
    spans = run.window_spans(name)
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)


def serve_mfu(run: Run) -> float | None:
    """Model FLOPs of the prefills and decoded tokens in the window over
    the time in their spans at the chip's peak, in percent; the counts are
    the configuration's family's."""
    from bench import cells
    spec = run.spec
    prefills = run.window_spans("serve/prefill")
    decodes = run.window_spans("serve/decode_step")
    busy = sum(s["t1"] - s["t0"] for s in prefills + decodes)
    if not busy:
        return None
    fam = cells.family(spec)
    work = sum(fam.prefill_flops(spec, s["args"]["prompt_len"])
               for s in prefills)
    for r in run.requests:
        # token 0 comes from the prefill; token i from the decode step fed
        # token i-1 at position prompt_len + i - 1
        for i, t in enumerate(r.times[1:], start=1):
            if run.in_window(t):
                work += fam.decode_flops(spec, r.prompt_len + i - 1)
    return 100.0 * work / (busy * run.peak["bf16_flops_per_s"])
