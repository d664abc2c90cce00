"""The host loop's per-layer metrics, ``launch_ms`` and ``sched_ms``, read
from the serve scheduler's spans: on hand-built runs with known times, and
on a whole smoke-cell run of the benchmark on the CPU.
"""
from __future__ import annotations

import time
from pathlib import Path

import jax
import pytest

from bench import cells, record, run as R
from bench.tests.smoke import smoke_cell

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _read(metric: str, run: record.Run):
    return cells.reader(BENCH, metric)(run)


def _sp(name: str, t0: float, t1: float) -> dict:
    return {"name": name, "t0": t0, "t1": t1, "args": {}}


def _step(t0, t1, *kids) -> list[dict]:
    return [_sp("serve/step", t0, t1)] + [_sp(*k) for k in kids]


def _run(spans: list[dict]) -> record.Run:
    return record.Run(spec={}, seconds=10.0, setup_s=1.0, t_open=0.0,
                      t_close=10.0, requests=[], spans=spans, peak={})


SPANS = (
    # before the window opens: not read
    _step(-1.0, -0.5, ("serve/decode_step", -0.9, -0.6),
          ("serve/decode_launch", -0.9, -0.8),
          ("serve/decode_fetch", -0.8, -0.6))
    # an admission: prefill and insert, then the decode and its children;
    # own work 0.100 - 0.020 - 0.060 = 0.020 s (the insert is the
    # scheduler's own)
    + _step(1.0, 1.1, ("serve/prefill", 1.0, 1.02),
            ("serve/insert", 1.02, 1.025),
            ("serve/decode_step", 1.03, 1.09),
            ("serve/decode_launch", 1.03, 1.031),
            ("serve/decode_fetch", 1.031, 1.09))
    # a nap toward an arrival, its prefill, then the decode:
    # 0.200 - 0.050 - 0.020 - 0.100 = 0.030 s
    + _step(2.0, 2.2, ("serve/idle", 2.0, 2.05),
            ("serve/prefill", 2.05, 2.07),
            ("serve/decode_step", 2.08, 2.18),
            ("serve/decode_launch", 2.08, 2.083),
            ("serve/decode_fetch", 2.083, 2.18))
    # nothing to decode: a nap alone is no decode step
    + _step(3.0, 3.05, ("serve/idle", 3.0, 3.05))
    # after the window closes: not read
    + _step(10.5, 10.7, ("serve/decode_step", 10.5, 10.6),
            ("serve/decode_launch", 10.5, 10.55),
            ("serve/decode_fetch", 10.55, 10.6))
)


@pytest.mark.parametrize("cell", ["chat", "offline"])
def test_readers_on_hand_built_spans(cell):
    run = _run(SPANS)
    assert _read(f"launch_ms.{cell}", run) == pytest.approx(2.0)
    assert _read(f"sched_ms.{cell}", run) == pytest.approx(25.0)


def test_readers_give_none_where_no_step_decodes():
    """A run whose steps never decode, or a program that marks no step
    (as one without these spans), has nothing to read: None, not 0."""
    naps = _run(_step(3.0, 3.05, ("serve/idle", 3.0, 3.05)))
    assert _read("sched_ms", naps) is None
    assert _read("launch_ms", naps) is None
    unmarked = _run([_sp("serve/decode_step", 1.0, 1.07),
                     _sp("serve/prefill", 2.0, 2.02)])
    assert _read("sched_ms", unmarked) is None
    assert _read("launch_ms", unmarked) is None


@pytest.mark.parametrize("name", ["zamba2-1.2b.chat", "mamba2-780m.offline"])
def test_smoke_run_reads_launch_and_sched(name):
    """A whole run, tracing off: the returned ``Run`` holds the spans both
    readers read."""
    cell = smoke_cell(name)
    _, run = R.run_cell(cell, seed=2**31 + 9, seconds=1.0, trace=False,
                        devices=jax.devices(), t_start=time.perf_counter(),
                        device_kind="TPU v5 lite",
                        compiles=record.CompileCounter())
    suffix = name.rsplit(".", 1)[1]
    launch = _read(f"launch_ms.{suffix}", run)
    sched = _read(f"sched_ms.{suffix}", run)
    assert launch is not None and sched is not None
    assert launch > 0 and sched > 0
