"""Shared benchmark utilities: timing, subprocess multi-device runs, and the
environment metadata stamp every BENCH_*.json carries (the CI trend job only
diffs artifacts whose stamps match — like with like)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
RESULTS = os.path.join(REPO, "results")


_META_PREFIX = "BENCH_META "
# Appended to every device child: the child owns the devices, so it is the
# one that reports them. A parent that touched the backend would hold an
# accelerator, and the children that need it would then fail or hang.
_META_CODE = (
    "\nimport json as _json, jax as _jax\n"
    f"print({_META_PREFIX!r} + _json.dumps({{"
    "'jax_version': _jax.__version__, 'backend': _jax.default_backend(), "
    "'device_count': _jax.device_count(), "
    "'device_kind': _jax.devices()[0].device_kind}))\n")
_child_meta: dict = {}


def bench_metadata() -> dict:
    """jax version / backend / device identity of this benchmark run: the
    stamp of the last device child this process ran, else (benchmarks that
    run in-process) this process's own devices."""
    if _child_meta:
        return dict(_child_meta)
    import jax
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
    }


def write_bench_json(path: str, payload: dict) -> None:
    """Persist one BENCH_*.json with the metadata stamp injected."""
    payload = dict(payload)
    payload.setdefault("meta", bench_metadata())
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


def time_us(fn, *, warmup: int = 3, iters: int = 20) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def run_multidevice(code: str, devices: int, timeout: int = 1200) -> str:
    """Run code in a subprocess with N forced host devices; returns its
    stdout without the metadata line, which :func:`bench_metadata` keeps."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code + _META_CODE], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-3000:]}")
    lines = []
    for line in proc.stdout.splitlines(keepends=True):
        if line.startswith(_META_PREFIX):
            _child_meta.update(json.loads(line[len(_META_PREFIX):]))
        else:
            lines.append(line)
    return "".join(lines)


def emit(rows: list[tuple]) -> list[tuple]:
    """Print the CSV rows AND publish them into the metrics registry
    (``bench/<name>_us`` gauges), so ``results/metrics.json`` carries the
    same numbers the BENCH_*.json artifacts do."""
    from repro import telemetry
    reg = telemetry.get_registry()
    for name, us, derived in rows:
        print(f"{name},{us if us is not None else ''},{derived}")
        if us is not None:
            reg.gauge(f"bench/{name}_us").set(float(us))
    return rows


def telemetry_artifacts(name: str) -> None:
    """Persist this process's telemetry: the global tracer's span buffer to
    ``results/trace_<name>.json`` (Chrome/Perfetto trace-event JSON) and the
    global registry snapshot merged into ``results/metrics.json`` (stamped
    with the same metadata BENCH_*.json carries, so the trend job matches
    like with like)."""
    from repro import telemetry
    os.makedirs(RESULTS, exist_ok=True)
    telemetry.dump_trace(os.path.join(RESULTS, f"trace_{name}.json"))
    telemetry.get_registry().dump(os.path.join(RESULTS, "metrics.json"),
                                  meta=bench_metadata())
