"""serve-traffic: continuous batching vs lockstep waves under a request trace.

Spawns an 8-device ('pod','data') subprocess, drives the Scheduler with a
deterministic ``StepClock`` over two arrival traces — Poisson and bursty —
and compares it against the wave baseline (collect whatever has arrived,
run one lockstep ``generate`` to the longest decode budget in the wave,
repeat). Requests carry heterogeneous decode budgets, so the wave baseline
suffers the two classic lockstep pathologies the continuous engine was
built to remove: late arrivals wait out the whole wave, and short requests
are head-of-line blocked behind the longest request in their wave.

Both sides run the same model on the same mesh under the same virtual
pricing (one tick per decode step, ``PREFILL_COST`` per prefill — the wave
gets its prefill batched for free at the same flat cost). Latencies are
exact functions of the trace and the schedule, not of CI-runner noise.

Reports per-request p50/p99 latency (ticks), makespan, and SLO goodput
(tokens from requests finishing within ``SLO_FACTOR`` x their own no-queue
latency, per tick). Wall seconds come from one measured conversion factor
(median decode-step wall time) applied to the virtual makespan. Writes
``BENCH_serve_traffic.json`` and fails unless continuous batching beats
the wave baseline on p99 latency AND SLO goodput on every trace.
"""
from __future__ import annotations

import json
import os
import sys

if __package__ in (None, ""):          # `python benchmarks/serve_traffic.py`
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _REPO)
    sys.path.insert(1, os.path.join(_REPO, "src"))
    __package__ = "benchmarks"

from .common import REPO, emit, run_multidevice, write_bench_json

OUT = os.path.join(REPO, "BENCH_serve_traffic.json")

DEVICES = 8

CODE = r"""
from repro.launch.mesh import make_mesh
import json, time, warnings
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro import configs
from repro.models import transformer
from repro.serve import Engine, Request, ServeSpec, StepClock

B, S, CL, PAGE = 8, 6, 32, 8
PREFILL_COST = 0.5      # vs 1.0 per decode step
SLO_FACTOR = 3.0        # SLO = 3x the request's own no-queue latency
N_REQ = 24

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
spec = ServeSpec(batch=B, cache_len=CL, page_len=PAGE)

rng = np.random.default_rng(0)
PROMPTS = rng.integers(0, cfg.vocab_size, (N_REQ, S), dtype=np.int32)
# heterogeneous decode budgets: the wave baseline locksteps every request
# to the longest budget in its wave
MAX_NEW = rng.integers(4, 17, N_REQ)


def trace_poisson(rng, mean_gap=2.0):
    # staggered single arrivals: the regime continuous batching exists for
    gaps = rng.exponential(mean_gap, N_REQ)
    return np.cumsum(gaps) - gaps[0]


def trace_bursty(rng, group=12, gap=24.0):
    # bursts larger than the batch: the tail of each burst spills into a
    # second wave while continuous admission backfills rows as they free
    return np.asarray([gap * (i // group) for i in range(N_REQ)])


def run_continuous(arrivals):
    clock = StepClock(decode_cost=1.0, prefill_cost=PREFILL_COST)
    eng = Engine(cfg, mesh, params, spec, clock=clock)
    rid_of = {}
    for i in range(N_REQ):
        rid_of[eng.submit(Request(tokens=PROMPTS[i],
                                  max_new=int(MAX_NEW[i]),
                                  home_pod=i % 2,
                                  arrival_s=float(arrivals[i])))] = i
    t0 = time.perf_counter()
    results = eng.drain()
    wall = time.perf_counter() - t0
    st = eng.scheduler.stats()
    lat = np.zeros(N_REQ)
    for rid, r in results.items():
        lat[rid_of[rid]] = r.finished_s - r.arrival_s
    assert all(r.finish_reason == "length" for r in results.values())
    return lat, clock.t, wall / max(st["steps"], 1), st


def run_wave(arrivals):
    # the lockstep baseline: at each wave start, take whatever has arrived
    # (up to B), prefill once (batched, flat PREFILL_COST — generous), then
    # decode max(max_new in wave) lockstep steps; late arrivals wait out
    # the whole wave and short requests wait for the longest.
    eng = Engine(cfg, mesh, params, spec)
    order = np.argsort(arrivals, kind="stable")
    pending = [(int(i), float(arrivals[i])) for i in order]
    t, lat = 0.0, np.zeros(N_REQ)
    while pending:
        t = max(t, pending[0][1])
        wave = [iv for iv in pending if iv[1] <= t][:B]
        pending = [iv for iv in pending if iv not in wave]
        steps = max(int(MAX_NEW[i]) for i, _ in wave)
        prompts = np.zeros((B, S), np.int32)
        for row, (i, _) in enumerate(wave):
            prompts[row] = PROMPTS[i]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            eng.generate(prompts, steps)
        t += PREFILL_COST + steps
        for i, arr in wave:
            lat[i] = t - arr
    return lat, t


def summarize(lat, makespan):
    slo = SLO_FACTOR * (PREFILL_COST + MAX_NEW)   # per-request SLO
    within = lat <= slo
    return {
        "p50_latency_ticks": float(np.percentile(lat, 50)),
        "p99_latency_ticks": float(np.percentile(lat, 99)),
        "mean_latency_ticks": float(lat.mean()),
        "makespan_ticks": float(makespan),
        "requests_in_slo": int(within.sum()),
        "slo_goodput_tokens_per_tick":
            float(MAX_NEW[within].sum() / makespan),
    }


out = {"n_requests": N_REQ, "batch": B, "slo_factor": SLO_FACTOR,
       "prefill_cost": PREFILL_COST, "total_tokens": int(MAX_NEW.sum()),
       "traces": {}}
step_s = None
for name, arrivals in (("poisson", trace_poisson(np.random.default_rng(1))),
                       ("bursty", trace_bursty(np.random.default_rng(2)))):
    c_lat, c_make, c_step_s, c_stats = run_continuous(arrivals)
    w_lat, w_make = run_wave(arrivals)
    step_s = c_step_s if step_s is None else min(step_s, c_step_s)
    cell = {"continuous": summarize(c_lat, c_make),
            "wave": summarize(w_lat, w_make)}
    cell["continuous"]["migrations"] = c_stats["migrations"]
    cell["continuous"]["decode_steps"] = c_stats["steps"]
    # ledger: every stamped comm label must reconcile vs its compiled HLO
    comm = c_stats.get("comm", {})
    cell["continuous"]["comm_labels_matched"] = sum(
        1 for rec in comm.values() if rec.get("match"))
    assert all(rec.get("match") for rec in comm.values()), comm
    out["traces"][name] = cell

# the measured wall conversion: virtual ticks -> seconds via the decode-step
# wall time of the continuous runs
out["decode_step_s"] = step_s
for name, cell in out["traces"].items():
    for side in ("continuous", "wave"):
        mk = cell[side]["makespan_ticks"]
        cell[side]["tokens_per_s"] = float(MAX_NEW.sum() / (mk * step_s))

print("TRAFFIC_OK" + json.dumps(out))
"""


def main() -> None:
    stdout = run_multidevice(CODE, DEVICES, timeout=2400)
    marker = "TRAFFIC_OK"
    line = next(ln for ln in stdout.splitlines() if ln.startswith(marker))
    res = json.loads(line[len(marker):])

    rows = []
    gates = {}
    for name, cell in res["traces"].items():
        cont, wave = cell["continuous"], cell["wave"]
        gates[name] = {
            "p99_improves": cont["p99_latency_ticks"] < wave["p99_latency_ticks"],
            "slo_goodput_improves":
                cont["slo_goodput_tokens_per_tick"]
                > wave["slo_goodput_tokens_per_tick"],
        }
        rows.append((f"serve_traffic/{name}/continuous_p99", None,
                     f"{cont['p99_latency_ticks']:.1f} ticks"))
        rows.append((f"serve_traffic/{name}/wave_p99", None,
                     f"{wave['p99_latency_ticks']:.1f} ticks"))
        rows.append((f"serve_traffic/{name}/slo_goodput", None,
                     f"{cont['slo_goodput_tokens_per_tick']:.3f} vs "
                     f"{wave['slo_goodput_tokens_per_tick']:.3f} tok/tick"))
    res["gates"] = gates
    write_bench_json(OUT, res)
    emit(rows)

    for name, g in gates.items():
        assert g["p99_improves"], (
            f"continuous batching lost on p99 latency for the {name} trace: "
            f"{res['traces'][name]}")
        assert g["slo_goodput_improves"], (
            f"continuous batching lost on SLO goodput for the {name} trace: "
            f"{res['traces'][name]}")
    print(f"serve_traffic: gates passed for {list(gates)} -> {OUT}")


if __name__ == "__main__":
    main()
