#!/usr/bin/env python3
"""Readings that set a cell's limits and rate, several runs in one process.

    python3 bench/calibrate.py --workload zamba2-1.2b.chat --seconds 10 \
        --seeds 11,12,13 --control       # program and fp8-control gaps
    python3 bench/calibrate.py --workload zamba2-1.2b.chat --seconds 20 \
        --seeds 5 --rates 4,6,8          # open-loop sweep for the knee
    python3 bench/calibrate.py --workload mamba2-780m.offline --seconds 10 \
        --seeds 5 --rows 96,128          # what fits the chip's memory
    python3 bench/calibrate.py --workload zamba2-1.2b.chat --seconds 3 \
        --seeds 5 --trace --excerpt-ms 30 --out trace_excerpt.json

Each run is ``bench/run.py``'s whole run (set-up, window, check) on a
fresh engine; the compile cache makes every run after the first cheap.
One JSON line per run goes to stdout and, with ``--out``, to a file.
The benchmark itself never runs this. It needs the TPU, like run.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import run as R                          # noqa: E402
from bench import cells, record                     # noqa: E402


def backlog(run_rec, points: int = 8) -> list[int]:
    """Requests arrived but not yet started, at ``points`` even times over
    the window: a backlog that climbs to the close marks an overload."""
    starts = {s["args"]["rid"]: s["t0"] for s in run_rec.spans
              if s["name"] == "serve/prefill"}
    out = []
    for k in range(1, points + 1):
        t = run_rec.t_open + k * run_rec.seconds / points
        out.append(sum(r.arrival <= t and starts.get(r.rid, 1e30) > t
                       for r in run_rec.requests))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--rows", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--check", type=int, default=1,
                    help="0 skips the reference check (rate sweeps)")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--excerpt-ms", type=float, default=0.0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        R.log(f"needs a TPU; JAX found {devices[0].platform}")
        return 2
    R.log(f"compile cache {R.enable_compile_cache()}")
    from repro.tuning.policy import Policy, set_default_policy
    set_default_policy(Policy())
    base = cells.load_cell(args.workload)
    compiles = record.CompileCounter()
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    rows_list = [int(r) for r in args.rows.split(",") if r] or [None]
    lines = []
    for rate, rows in [(a, b) for a in rates for b in rows_list]:
        mix = dict(base.traffic)
        if rows is not None:
            mix["rows"] = rows
        if rate is not None:
            mix["arrivals"] = {"process": "poisson", "rate_per_s": rate}
        if not args.check:
            mix["check_requests"] = 0
        cell = dataclasses.replace(base, traffic=mix)
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            res, run = R.run_cell(
                cell, seed=seed, seconds=args.seconds, trace=args.trace,
                devices=devices, t_start=t0,
                device_kind=devices[0].device_kind, compiles=compiles,
                control=args.control, excerpt_ms=args.excerpt_ms)
            res.update(seed=seed, rate=rate, rows=mix["rows"],
                       backlog=backlog(run),
                       compiles_in_window=compiles.count,
                       run_s=time.perf_counter() - t0)
            print(json.dumps({k: v for k, v in res.items()
                              if k != "excerpt"}), flush=True)
            lines.append(json.dumps(res))
            if args.out:
                args.out.write_text("\n".join(lines) + "\n")
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
