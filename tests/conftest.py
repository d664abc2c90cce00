"""Shared test utilities.

NOTE: XLA_FLAGS / device-count forcing is deliberately NOT set here — smoke
tests run on the single real CPU device. Multi-device tests spawn
subprocesses (helpers below) that set --xla_force_host_platform_device_count
before importing jax.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, devices: int = 8, timeout: int = 900):
    """Run ``code`` in a fresh python with N host devices; returns stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode}):\n--- stdout ---\n"
            f"{proc.stdout[-4000:]}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess


class FakeDev:
    """Stand-in for a jax Device: the topology helpers only read ``.id``."""

    def __init__(self, id_):
        self.id = id_


def fake_mesh(shape, names):
    """Mesh stand-in (``axis_names`` + object ndarray of FakeDevs) for
    topology/sharding unit tests that never touch real devices."""
    import numpy as np
    n = int(np.prod(shape))
    devs = np.array([FakeDev(i) for i in range(n)],
                    dtype=object).reshape(shape)

    class _M:
        axis_names = names
        devices = devs

    return _M()


# spans a Scheduler.step() opens; each must lie inside a serve/step
STEP_SPANS = ("serve/idle", "serve/prefill", "serve/insert", "serve/migrate",
              "serve/decode_step", "serve/decode_launch",
              "serve/decode_fetch")


def closed_spans(events: list[dict]) -> list[dict]:
    """A Tracer's B/E events as spans ``{name, t0, t1, args, parent}``
    (``t0``/``t1`` in the tracer's microseconds, ``parent`` the enclosing
    span or None), in the order they opened."""
    out, stacks = [], {}
    for ev in events:
        stack = stacks.setdefault(ev.get("tid"), [])
        if ev["ph"] == "B":
            sp = {"name": ev["name"], "t0": ev["ts"], "t1": None,
                  "args": ev.get("args", {}),
                  "parent": stack[-1] if stack else None}
            out.append(sp)
            stack.append(sp)
        elif ev["ph"] == "E":
            stack.pop()["t1"] = ev["ts"]
    return out


def check_serve_spans(events: list[dict], first_token_s: dict, *,
                      batched: bool, clock_t0: float | None = None) -> dict:
    """Assert the scheduler's span tree over a Tracer's events.

    ``first_token_s`` maps each admitted request id to its first token's
    stamp. ``clock_t0`` (the tracer's zero on ``perf_counter``) also checks
    that each stamp lies between its prefill's end and its step's end.
    Returns the spans by name."""
    from repro.telemetry import validate_trace_events
    assert validate_trace_events(events) == []
    spans = closed_spans(events)
    by_name: dict[str, list] = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp)

    def root(sp):
        while sp["parent"] is not None:
            sp = sp["parent"]
        return sp

    for sp in by_name.get("serve/step", []):
        assert sp["parent"] is None, sp["parent"]["name"]
    for sp in spans:
        if sp["name"] in STEP_SPANS:
            assert root(sp)["name"] == "serve/step", sp["name"]
    steps = by_name["serve/decode_step"]
    assert steps
    for ds in steps:
        assert ds["parent"]["name"] == "serve/step"
        kids = [sp for sp in spans if sp["parent"] is ds]
        assert [k["name"] for k in kids] == ["serve/decode_launch",
                                            "serve/decode_fetch"]
        launch, fetch = kids
        assert ds["t0"] <= launch["t0"] <= launch["t1"] <= fetch["t0"] \
            <= fetch["t1"] <= ds["t1"]
    for rid, stamp in first_token_s.items():
        (pre,) = [sp for sp in by_name["serve/prefill"]
                  if sp["args"]["rid"] == rid]
        step = root(pre)
        assert pre["parent"] is step
        if batched:
            (ins,) = [sp for sp in by_name["serve/insert"]
                      if sp["args"]["rid"] == rid]
            assert ins["parent"] is step and pre["t1"] <= ins["t0"]
        if clock_t0 is not None:
            at = (stamp - clock_t0) * 1e6
            assert pre["t1"] <= at <= step["t1"], (rid, pre, at, step)
    return by_name


@pytest.fixture(scope="session")
def serve_spans():
    return check_serve_spans
