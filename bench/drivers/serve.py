"""Serve a traffic mix through ``Engine``/``Scheduler`` and time it.

Set-up: weights from the seed, the engine, and one warm-up request at
every prompt length the mix can draw (each length is its own prefill
program), so nothing compiles in the window. An open loop then submits
every request of the window with its arrival stamp and steps the
scheduler until the window closes, and on until each of them has finished
(at most ``DRAIN_S`` past the close). A backlog is queued whole; the
window opens once every row is busy, closes on the clock, and runs on
only until ``check_requests`` of its requests have finished (a short
traced window may close before any has). A traced run's window is at
most ``TRACE_S`` long and the profiler stops at its close.

Afterwards the peak memory is read, the engine is dropped, and the plain
reference checks a sample of the finished requests.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import correct, record, traffic, weights as W

DRAIN_S = 60.0
WARM_SEED = 12345              # warm-up prompts: fixed, not the run's seed
# A traced run measures at most this long: a v5e decode step of these
# models runs thousands of device ops, and 50 s of them took ~170 s to
# write and read back, too close to a run's 360 s.
TRACE_S = 20.0


def program_config(spec: dict):
    """The program's ModelConfig holding every size the file states."""
    from repro import configs
    base = configs.get(spec["arch"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name", "dtype",
                                                           "param_dtype"}
    cfg = dataclasses.replace(
        base, dtype=W.DTYPES[spec["dtype"]],
        **{k: v for k, v in spec.items() if k in fields})
    return cfg if cfg.name == spec["name"] else dataclasses.replace(
        cfg, name=spec["name"])


def _warm(eng, mix: dict, vocab: int) -> None:
    from repro.serve import Request
    rng = np.random.default_rng(WARM_SEED)
    for n in traffic.lengths(mix["prompt"]):
        eng.submit(Request(tokens=rng.integers(0, vocab, n, dtype=np.int32),
                           max_new=2))
    eng.drain()


def _spans(tracer, base: float) -> list[dict]:
    """Closed spans of the tracer, in perf_counter seconds."""
    opened: dict = {}
    out = []
    for ev in tracer.events():
        lane = ev["tid"]
        if ev["ph"] == "B":
            opened.setdefault(lane, []).append(ev)
        elif ev["ph"] == "E":
            b = opened[lane].pop()
            out.append({"name": b["name"], "t0": base + b["ts"] / 1e6,
                        "t1": base + ev["ts"] / 1e6,
                        "args": b.get("args", {})})
    return out


def run(ctx) -> tuple[record.Run, dict]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import telemetry
    from repro.launch.mesh import mesh_from_devices
    from repro.serve import Engine, Request, ServeSpec

    cell, seed = ctx.cell, ctx.seed
    spec, mix = cell.config, cell.traffic
    traced = ctx.trace_dir is not None
    seconds = min(ctx.seconds, TRACE_S) if traced else ctx.seconds
    cfg = program_config(spec)
    mesh = mesh_from_devices(ctx.devices[:cell.chips])
    tracer = telemetry.Tracer(jax_annotations=traced)
    backlog = mix["arrivals"]["process"] == "backlog"
    with jax.set_mesh(mesh):
        params = W.make(spec, seed, cfg.padded_vocab,
                        sharding=NamedSharding(mesh, P()))
        eng = Engine(cfg, mesh, params,
                     ServeSpec(batch=mix["rows"], cache_len=mix["cache_len"],
                               page_len=mix["page_len"]),
                     comm_telemetry=False, tracer=tracer,
                     registry=telemetry.MetricsRegistry())
        ctx.log(f"engine: combine={eng.combine.algorithm} rows={mix['rows']}"
                f" cache_len={mix['cache_len']}")
        _warm(eng, mix, spec["vocab_size"])
        items = traffic.generate(mix, seed, seconds, spec["vocab_size"])
        if traced:
            jax.profiler.start_trace(ctx.trace_dir)
        if backlog:
            rids = [eng.submit(Request(tokens=it.prompt, max_new=it.max_new))
                    for it in items]
            while (len(eng.scheduler.active) < mix["rows"]
                   and eng.scheduler.queue):
                eng.step()
            t_open = time.perf_counter()
        else:
            t_open = time.perf_counter() + 0.05
            rids = [eng.submit(Request(tokens=it.prompt, max_new=it.max_new,
                                       arrival_s=t_open + it.at_s))
                    for it in items]
        while time.perf_counter() < t_open:
            pass
        tracer.clear()
        base = time.perf_counter()
        ctx.compiles.on = True
        t_close = t_open + seconds
        with jax.profiler.TraceAnnotation("bench/window"):
            while time.perf_counter() < t_close:
                eng.step()
        ctx.compiles.on = False
        if traced:
            jax.profiler.stop_trace()
        sched = eng.scheduler
        limit = time.perf_counter() + DRAIN_S
        if backlog:              # enough finished answers for the check
            while (sum(r in sched.results for r in rids)
                   < mix["check_requests"] and sched.active
                   and time.perf_counter() < limit):
                eng.step()
        else:                    # every request that arrived in the window
            while (sched.queue or sched.active) and \
                    time.perf_counter() < limit:
                eng.step()
        t_drained = time.perf_counter()
        peak_bytes = ctx.memory_peak()
        reqs = []
        for rid, it in zip(rids, items):
            res = sched.results.get(rid)
            act = sched.active.get(rid)
            times = (res.token_times_s if res else
                     list(act.times) if act else [])
            toks = (res.tokens if res else
                    np.asarray(act.tokens, np.int32) if act else
                    np.zeros(0, np.int32))
            reason = res.finish_reason if res else (
                "running" if act else "queued")
            arrival = (res.arrival_s if res else
                       act.req.arrival_s if act else t_open + it.at_s)
            reqs.append(record.Request(arrival=arrival,
                                       prompt_len=len(it.prompt),
                                       times=times,
                                       reason=reason, tokens=toks,
                                       prompt=it.prompt, rid=rid))
        spans = _spans(tracer, base)
        del eng, sched
        gc.collect()              # engine <-> scheduler cycle holds the cache

        run_rec = record.Run(spec=spec, seconds=seconds,
                             setup_s=t_open - ctx.t_start, t_open=t_open,
                             t_close=t_close, requests=reqs, spans=spans,
                             peak=ctx.peak)
        if backlog:
            # due in the window: what was served in it
            counted = [r for r in reqs if r.times and r.times[0] < t_close]
            failed = [r for r in counted
                      if r.reason not in ("length", "running")]
        else:
            counted = [r for r in reqs if run_rec.in_window(r.arrival)]
            failed = [r for r in counted if r.reason != "length"]
        t_check = time.perf_counter()
        ok, checks = correct.check_serve(params, spec, counted, cell.limits,
                                         mix["check_requests"], seed,
                                         mix["cache_len"],
                                         control=ctx.control)
        ctx.log(f"drain after the window {t_drained - t_close:.1f} s; "
                f"reference check {time.perf_counter() - t_check:.1f} s")
    outcome = {"correct": ok and not failed, "attempted": len(counted),
               "failed": len(failed), "checks": checks,
               "memory_peak_bytes": peak_bytes}
    return run_rec, outcome
