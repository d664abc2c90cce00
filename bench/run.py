#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload zamba2-1.2b.chat --seed 7 \
        --seconds 40 --trace 0

The cell (configuration, traffic mix, chips) comes from ``BENCHMARK.json``
at the root of the checkout; its pieces are found by name (``cells.py``).
Set-up makes the weights from ``--seed``, builds the program and warms
every shape the mix will use; the window then runs for ``--seconds``.
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window and the result holds the
per-layer metrics, the device's busy time and a breakdown.

Earlier lines name the device, the peak memory, the resolved algorithms
and the compilations inside the window (0 when set-up warmed every
shape). The last lines of stderr give each number ``correct`` compared
with its limit; the last line of stdout is the result, one JSON object.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                     # noqa: E402
import dataclasses                                  # noqa: E402
import json                                         # noqa: E402
import os                                           # noqa: E402
import shutil                                       # noqa: E402
import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp

from bench import cells, record, trace_reduce       # noqa: E402
from bench.flops import peak                        # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell and the run's settings and devices."""

    cell: cells.Cell
    seed: int
    seconds: float
    trace_dir: str | None
    devices: list
    peak: dict
    compiles: record.CompileCounter
    t_start: float
    log: object
    control: bool = False        # also read the fp8 control's gap

    def memory_peak(self) -> int | None:
        """Peak bytes in use on the fullest chip of the cell."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices[:self.cell.chips]]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at a
    fixed directory in the checkout; every program goes in it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def metrics_of(cell: cells.Cell, run: record.Run, trace: bool) -> dict:
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.reader(cell.bench, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float, device_kind: str,
             compiles: record.CompileCounter, control: bool = False,
             excerpt_ms: float = 0.0) -> tuple[dict, record.Run]:
    """Set up, measure and check one cell: the result's fields, and the
    run's record.

    ``control`` also reads the fp8 control's gap on the same sample;
    ``excerpt_ms`` keeps that much of the window's trace as plain events
    (``result["excerpt"]``). Both serve calibration, not the benchmark."""
    trace_dir = None
    if trace:
        trace_dir = str(TRACE_DIR / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace_dir=trace_dir,
                  devices=devices, peak=peak(device_kind), compiles=compiles,
                  t_start=t_start, log=log, control=control)
    compiles.count = 0
    run, outcome = cells.driver(cell).run(ctx)
    log(f"setup_s {run.setup_s:.3f}; compilations inside the window: "
        f"{compiles.count}; peak memory {outcome['memory_peak_bytes']} B")
    excerpt = None
    if trace_dir is not None:
        events = trace_reduce.load(trace_dir)
        run.trace = trace_reduce.reduce(events)
        if excerpt_ms:
            excerpt = trace_reduce.excerpt(events, excerpt_ms)
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = {"correct": bool(outcome["correct"]),
              "attempted": outcome["attempted"],
              "failed": outcome["failed"],
              "metrics": metrics_of(cell, run, trace)}
    result["device"] = {"memory_peak_bytes": outcome["memory_peak_bytes"]}
    if run.trace is not None:
        result["device"].update(busy_s=run.trace["busy_s"],
                                window_s=run.trace["window_s"])
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    if excerpt is not None:
        result["excerpt"] = excerpt
    result["checks"] = outcome["checks"]
    return result, run


def device_fields(devices: list, measured: dict) -> dict:
    """The result's ``device``: as JAX names it, and what the run read."""
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), **measured}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
        return 2
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    from repro.tuning.policy import Policy, set_default_policy
    set_default_policy(Policy())   # "auto" from the cost model, no table
    compiles = record.CompileCounter()
    result, _ = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      t_start=T_START, device_kind=dev.device_kind,
                      compiles=compiles)
    result["device"] = device_fields(devices, result["device"])
    log(f"correct: {result['correct']}; attempted {result['attempted']}, "
        f"failed {result['failed']}")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
