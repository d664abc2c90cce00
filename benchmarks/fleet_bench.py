"""fleet: chaos mini-soak benchmark — decision latency + recovery wall-clock.

A seeded kill + preemption + straggler schedule hits a 12-device
(3x4 pod-aligned) flat-psum run under the :class:`repro.fleet
.FleetController`; the run must converge to ``complete``/healthy, and the
controller's overheads become the trended numbers:

* ``decision_latency_s`` — mean wall-clock of one ``FleetPolicy.decide``
  round trip including signal assembly (the per-step tick tax);
* ``recovery_wall_s`` — mean wall-clock from a failure (kill / drain) to
  the next episode's trainer standing on the committed step (rebuild +
  resharding restore + recompile).

Writes ``BENCH_fleet.json`` (trended via ``scripts/bench_trend.py
--pattern BENCH_fleet.json``); the subprocess dumps its own registry into
``results/metrics.json`` so the ``fleet/*`` counter invariants are
checkable by ``scripts/check_metrics_schema.py``.
"""
from __future__ import annotations

import json
import os

from .common import REPO, RESULTS, emit, run_multidevice, write_bench_json

OUT = os.path.join(REPO, "BENCH_fleet.json")
DEVICES = 12

SOAK_CODE = r"""
import dataclasses, json, os, tempfile
import jax, jax.numpy as jnp
from repro import configs, telemetry
from repro.fleet import (ChaosSchedule, ChaosSpec, FleetController,
                         FleetPolicy, PolicyConfig)
from repro.train import Trainer, TrainerConfig

STEPS = 8
ckdir = tempfile.mkdtemp(prefix="fleet_bench_")
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          d_model=96, d_ff=192, vocab_size=384,
                          dtype=jnp.float32)
tcfg = TrainerConfig(steps=STEPS, seq_len=32, global_batch=24, ckpt_every=2,
                     keep_last=6, log_every=100, grad_sync="flat_psum",
                     fsdp=False, lr=3e-3, comm_telemetry=False,
                     ckpt_dir=ckdir)

def make_trainer(mesh):
    return Trainer(cfg, mesh, tcfg, log=lambda s: None)

chaos = ChaosSchedule(ChaosSpec(steps=STEPS, seed=1, kills=1, preempts=1,
                                straggles=1, first_step=3, delay_s=0.2))
policy = FleetPolicy(PolicyConfig(max_retries=6, max_shrinks=0,
                                  straggler_high=99))
fc = FleetController(make_trainer, pod_size=4, devices=12, chaos=chaos,
                     policy=policy, log=lambda s: None)
report = fc.run()
assert report.status == "complete", report.status
assert chaos.pending() == {"kills": [], "preempts": []}, chaos.pending()

reg = telemetry.get_registry()
snap = reg.snapshot()
lat = snap["histograms"].get("fleet/decision_latency_s", {})
rec = snap["histograms"].get("fleet/recovery_s", {})
out = {
    "status": report.status,
    "steps": report.steps,
    "episodes": len(report.episodes),
    "final_layout": list(report.final_layout),
    "decisions": snap["counters"].get("fleet/decisions", 0),
    "decision_latency_s": lat.get("mean"),
    "decision_latency_max_s": lat.get("max"),
    "recovery_wall_s": rec.get("mean"),
    "recoveries": rec.get("count", 0),
    "healthy": snap["gauges"].get("fleet/healthy"),
}
print("RESULT " + json.dumps(out))
results = os.environ.get("FLEET_BENCH_RESULTS")
if results:
    # this subprocess owns the fleet/* counters — persist them itself so
    # results/metrics.json carries what the schema checker reconciles
    os.makedirs(results, exist_ok=True)
    meta = {"jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind}
    reg.dump(os.path.join(results, "metrics.json"), meta=meta)
    telemetry.dump_trace(os.path.join(results, "trace_fleet_soak.json"))
"""


def main() -> list[tuple]:
    os.environ["FLEET_BENCH_RESULTS"] = RESULTS
    stdout = run_multidevice(SOAK_CODE, DEVICES, timeout=1500)
    line = [ln for ln in stdout.splitlines() if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["status"] == "complete", res
    assert res["healthy"] == 1.0, res
    assert res["decision_latency_s"] is not None, res
    assert res["recoveries"] >= 2 and res["recovery_wall_s"] is not None, res

    write_bench_json(OUT, {"fleet": res})
    return emit([
        ("fleet/decision_latency", res["decision_latency_s"] * 1e6,
         f"mean_s={res['decision_latency_s']:.2e} "
         f"max_s={res['decision_latency_max_s']:.2e} "
         f"decisions={res['decisions']}"),
        ("fleet/recovery_wall", res["recovery_wall_s"] * 1e6,
         f"mean_s={res['recovery_wall_s']:.3f} "
         f"recoveries={res['recoveries']}"),
        ("fleet/soak", None,
         f"status={res['status']} episodes={res['episodes']} "
         f"steps={res['steps']} layout={tuple(res['final_layout'])} "
         f"healthy={res['healthy']}"),
    ])


if __name__ == "__main__":
    main()
