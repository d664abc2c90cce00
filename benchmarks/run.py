"""Benchmark harness entry: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [bench] [--only fig7,...]``
    Prints ``name,us_per_call,derived`` CSV rows per benchmark.

``python benchmarks/run.py tune [--p 16 --p-local 4 ...]``
    Runs the repro.tuning sweep: measures (or, on CPU containers,
    deterministically simulates) every collective algorithm across message
    sizes, persists ``results/tuning_table.json`` — which
    ``allgather(..., algorithm="auto")`` and ``grad_sync="auto"`` then
    resolve through — and writes the Fig. 9-style measured-vs-modeled
    report to ``BENCH_tuning.json``.

``python benchmarks/run.py overlap``
    Eager vs double-buffered-prefetch FSDP train pipeline (DESIGN.md §5):
    wall-clock step time / tokens per second on an 8-device subprocess plus
    the simulated exposed-communication split; writes
    ``BENCH_overlap.json`` and fails if the prefetched pipeline does not
    reduce exposed communication (or breaks exact equality).

``python benchmarks/run.py serve_traffic``
    Continuous batching vs lockstep waves under Poisson and bursty request
    traces on a deterministic virtual clock (DESIGN.md §9): per-request
    p50/p99 latency and SLO goodput of the Scheduler vs the wave baseline;
    writes ``BENCH_serve_traffic.json`` and fails unless continuous
    batching wins p99 latency AND SLO goodput on every trace.

``python benchmarks/run.py multipod``
    The ('pod','data') sharding-domain proof (DESIGN.md §6): compiled-HLO
    non-local byte/message comparison of the locality train-FSDP and
    serve-combine paths vs the flat XLA paths on multi-pod meshes, plus
    layout-equivalence numerics; writes ``BENCH_multipod.json`` and fails
    unless the locality paths move strictly fewer inter-pod bytes AND
    messages.

``python benchmarks/run.py fleet``
    Fleet-controller chaos mini-soak (DESIGN.md §11): seeded kills,
    preemptions and stragglers on a 12-device pod-aligned run; trends the
    controller's decision latency and failure-to-resumed recovery
    wall-clock; writes ``BENCH_fleet.json`` and fails unless the run
    converges to healthy.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

if __package__ in (None, ""):                     # `python benchmarks/run.py`
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _REPO)
    sys.path.insert(1, os.path.join(_REPO, "src"))
    __package__ = "benchmarks"

from . import (checkpoint_bench, collective_hlo_audit, fig3_pingpong,
               fig7_model_scaling, fig8_model_datasize, fig9_measured,
               overlap, roofline, serve_combine)

BENCHES = {
    "checkpoint": checkpoint_bench,
    "fig3": fig3_pingpong,
    "fig7": fig7_model_scaling,
    "fig8": fig8_model_datasize,
    "fig9": fig9_measured,
    "hlo_audit": collective_hlo_audit,
    "overlap": overlap,
    "roofline": roofline,
    "serve_combine": serve_combine,
}


def run_benches(only: str | None) -> None:
    from repro import telemetry
    from .common import telemetry_artifacts
    names = only.split(",") if only else list(BENCHES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            with telemetry.span(f"bench/{name}"):
                BENCHES[name].main()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    telemetry_artifacts("bench")
    if failed:
        print(f"FAILED benchmarks: {failed}", file=sys.stderr)
        sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd")
    bench = sub.add_parser("bench", help="run the figure benchmarks (default)")
    bench.add_argument("--only", default=None,
                       help="comma-separated subset of " + ",".join(BENCHES))
    sub.add_parser("tune", help="run the collective tuning sweep",
                   add_help=False)
    sub.add_parser("overlap", help="eager vs prefetched pipeline benchmark")
    sub.add_parser("multipod", help="('pod','data') non-local traffic proof")
    sub.add_parser("serve_traffic",
                   help="continuous batching vs lockstep waves")
    sub.add_parser("fleet", help="fleet-controller chaos mini-soak")
    # default to `bench` for backward compatibility: `run.py --only fig7`
    argv = sys.argv[1:]
    if argv[:1] == ["tune"]:
        from repro.tuning import sweep
        sweep.main(argv[1:])
        return
    if argv[:1] == ["overlap"]:
        from repro import telemetry
        from .common import telemetry_artifacts
        print("name,us_per_call,derived")
        try:
            with telemetry.span("bench/overlap"):
                overlap.main()
        finally:                   # keep artifacts from failed gate runs
            telemetry_artifacts("overlap")
        return
    if argv[:1] == ["serve_traffic"]:
        from repro import telemetry
        from . import serve_traffic
        from .common import telemetry_artifacts
        print("name,us_per_call,derived")
        try:
            with telemetry.span("bench/serve_traffic"):
                serve_traffic.main()
        finally:                   # keep artifacts from failed gate runs
            telemetry_artifacts("serve_traffic")
        return
    if argv[:1] == ["multipod"]:
        from repro import telemetry
        from . import multipod
        from .common import telemetry_artifacts
        print("name,us_per_call,derived")
        try:
            with telemetry.span("bench/multipod"):
                multipod.main()
        finally:                   # keep artifacts from failed gate runs
            telemetry_artifacts("multipod")
        return
    if argv[:1] == ["fleet"]:
        from repro import telemetry
        from . import fleet_bench
        from .common import telemetry_artifacts
        print("name,us_per_call,derived")
        try:
            with telemetry.span("bench/fleet"):
                fleet_bench.main()
        finally:                   # keep artifacts from failed gate runs
            telemetry_artifacts("fleet")
        return
    if argv[:1] != ["bench"] and any(a.startswith("--only") for a in argv):
        argv = ["bench"] + argv
    args = ap.parse_args(argv or ["bench"])
    run_benches(getattr(args, "only", None))


if __name__ == "__main__":
    main()
