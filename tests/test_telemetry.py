"""repro.telemetry: span tracer, metrics registry, comm reconciliation.

Fast units run in the parent process (tracer nesting + trace-event schema,
registry semantics, the comm predicted-vs-actual ledger, TelemetryEvent
string back-compat, StepMonitor edge cases, the check_metrics_schema CI
gate). The slow end-to-end test runs a 2-pod (2x4 ('pod','data')) train +
decode in an 8-device subprocess and asserts the acceptance contract: the
runtime-accumulated inter-pod bytes/msgs equal the compile-time
``collective_stats`` prediction EXACTLY for both the locality and the
flat-XLA paths, the locality artifacts carry the pod-crossing permute
schedule, and the run's trace dump is valid Perfetto trace-event JSON.
"""
import json
import os
import sys
import threading

import pytest

from conftest import fake_mesh

from repro.runtime import StepMonitor
from repro.telemetry import (CommReport, MetricsRegistry, TelemetryEvent,
                             Tracer, dp_group_map, validate_trace_events)

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_valid_events():
    tr = Tracer(jax_annotations=False)
    with tr.span("outer", step=1):
        assert tr.current_span() == "outer"
        with tr.span("inner"):
            assert tr.current_span() == "inner"
            tr.instant("marker", note="x")
        assert tr.current_span() == "outer"
    assert tr.current_span() is None
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["B", "B", "i", "E", "E"]
    assert evs[0]["name"] == "outer" and evs[0]["args"] == {"step": 1}
    # the inner span records its parent
    assert evs[1]["args"]["parent"] == "outer"
    assert validate_trace_events(evs) == []


def test_tracer_thread_lanes():
    tr = Tracer(jax_annotations=False)

    def worker():
        with tr.span("thread-span"):
            pass

    with tr.span("main-span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    evs = tr.events()
    tids = {e["tid"] for e in evs}
    assert len(tids) == 2          # each OS thread gets its own lane
    assert validate_trace_events(evs) == []


def test_tracer_dump_is_chrome_trace_container(tmp_path):
    tr = Tracer(jax_annotations=False)
    with tr.span("a"):
        pass
    path = tmp_path / "trace.json"
    doc = tr.dump(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == doc
    assert on_disk["displayTimeUnit"] == "ms"
    evs = on_disk["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    assert validate_trace_events(evs) == []


def test_validate_trace_events_rejects_malformed():
    lane = {"pid": 1, "tid": 1}
    # unknown phase
    assert validate_trace_events([{"ph": "Z", "ts": 0, **lane}])
    # non-numeric ts
    assert validate_trace_events([{"ph": "B", "name": "a", "ts": "0", **lane}])
    # decreasing ts on one lane
    bad = [{"ph": "B", "name": "a", "ts": 5.0, **lane},
           {"ph": "E", "name": "a", "ts": 1.0, **lane}]
    assert any("decreases" in p or "E.ts" in p
               for p in validate_trace_events(bad))
    # E with no open B
    assert any("no open B" in p for p in validate_trace_events(
        [{"ph": "E", "name": "a", "ts": 0.0, **lane}]))
    # non-LIFO close
    bad = [{"ph": "B", "name": "a", "ts": 0.0, **lane},
           {"ph": "B", "name": "b", "ts": 1.0, **lane},
           {"ph": "E", "name": "a", "ts": 2.0, **lane},
           {"ph": "E", "name": "b", "ts": 3.0, **lane}]
    assert any("not LIFO" in p for p in validate_trace_events(bad))
    # unclosed span
    assert any("unclosed" in p for p in validate_trace_events(
        [{"ph": "B", "name": "a", "ts": 0.0, **lane}]))


def test_span_closes_on_exception():
    tr = Tracer(jax_annotations=False)
    with pytest.raises(RuntimeError):
        with tr.span("failing"):
            raise RuntimeError("boom")
    assert validate_trace_events(tr.events()) == []
    assert tr.current_span() is None


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.count("steps")
    reg.count("steps", 2)
    assert reg.counter("steps").value == 3
    with pytest.raises(ValueError):
        reg.counter("steps").inc(-1)
    reg.gauge("loss").set(2.5)
    for v in [1.0, 2.0, 3.0, 4.0]:
        reg.observe("dt", v)
    snap = reg.snapshot()
    assert snap["counters"]["steps"] == 3
    assert snap["gauges"]["loss"] == 2.5
    h = snap["histograms"]["dt"]
    assert h["count"] == 4 and h["total"] == 10.0 and h["mean"] == 2.5
    assert h["min"] == 1.0 and h["max"] == 4.0
    # histogram means are mirrored as gauges for the trend gate
    assert snap["gauges"]["dt_mean"] == 2.5


def test_registry_dump_merges_sections(tmp_path):
    path = str(tmp_path / "metrics.json")
    r1 = MetricsRegistry()
    r1.gauge("a").set(1.0)
    r1.dump(path, meta={"backend": "cpu"})
    r2 = MetricsRegistry()
    r2.gauge("b").set(2.0)
    merged = r2.dump(path)            # merge=True default; meta preserved
    assert merged["gauges"]["a"] == 1.0 and merged["gauges"]["b"] == 2.0
    assert merged["meta"] == {"backend": "cpu"}
    on_disk = json.loads(open(path).read())
    assert on_disk == merged


def _report(nl_bytes=100.0, nl_msgs=4.0, **kw):
    return CommReport(label="t", nonlocal_bytes=nl_bytes,
                      nonlocal_msgs=nl_msgs, **kw)


def test_comm_ledger_reconciles_exactly():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        reg.record_comm("t")          # unstamped path is the bug this catches
    reg.attach_comm_report("t", _report(permute_edges_nonlocal=2))
    for _ in range(5):
        reg.record_comm("t")
    rec = reg.reconcile("t")
    assert rec["invocations"] == 5 and rec["match"]
    assert rec["predicted_nonlocal_bytes"] == 500.0
    assert rec["actual_nonlocal_bytes"] == 500.0
    assert rec["predicted_nonlocal_msgs"] == 20.0
    snap = reg.snapshot()["comm"]["t"]
    assert snap["comm_nonlocal_bytes_per_step"] == 100.0
    assert snap["report"]["has_locality_schedule"] is True


def test_comm_ledger_detects_drift():
    reg = MetricsRegistry()
    reg.attach_comm_report("t", _report())
    reg.record_comm("t", 3)
    # simulate a step path that executed outside the accounting
    reg._comm["t"].actual_nonlocal_bytes += 100.0
    assert not reg.reconcile("t")["match"]


def test_comm_ledger_archives_on_reattach():
    reg = MetricsRegistry()
    reg.attach_comm_report("t", _report(100.0))
    reg.record_comm("t", 2)
    reg.attach_comm_report("t", _report(50.0))       # elastic rebuild
    reg.record_comm("t")
    snap = reg.snapshot()
    assert snap["comm"]["t"]["invocations"] == 1
    archived = snap["comm_archive"]["t"]
    assert len(archived) == 1 and archived[0]["invocations"] == 2
    assert archived[0]["actual_nonlocal_bytes"] == 200.0
    assert reg.reconcile_all()["t"]["match"]


# ---------------------------------------------------------------------------
# structured events: string back-compat
# ---------------------------------------------------------------------------

def test_telemetry_event_is_a_string():
    ev = TelemetryEvent("straggler: step took 9.000s", kind="straggler",
                        step=7, attrs={"dt": 9.0})
    assert isinstance(ev, str)
    assert "straggler" in ev                       # substring matching
    assert ev.startswith("straggler:")             # prefix matching
    assert ev == "straggler: step took 9.000s"     # equality with plain str
    assert ev.kind == "straggler" and ev.step == 7
    d = ev.asdict()
    assert d["message"] == str(ev) and d["attrs"] == {"dt": 9.0}
    assert d["t"] > 0
    assert "TelemetryEvent" in repr(ev)


# ---------------------------------------------------------------------------
# StepMonitor edge cases
# ---------------------------------------------------------------------------

def test_step_monitor_warmup_zero_does_not_flag_normal_steps():
    # historical bug: warmup=0 seeded the EWMA as alpha*dt, so every
    # subsequent NORMAL step satisfied dt > k*(alpha*dt) and was flagged
    m = StepMonitor(k=3.0, warmup=0)
    events = []
    for dt in [1.0, 1.0, 1.0, 1.0]:
        events.extend(m.record(dt))
    assert not any(e.kind == "straggler" for e in events)
    assert m.ewma == pytest.approx(1.0)
    # a genuine straggler is still caught
    events = m.record(10.0)
    assert sum(e.kind == "straggler" for e in events) == 1


def test_step_monitor_ewma_seeds_from_first_sample():
    m = StepMonitor(k=3.0, warmup=3, alpha=0.5)
    m.record(2.0)
    assert m.ewma == 2.0               # seeded, not blended against 0
    m.record(4.0)
    assert m.ewma == pytest.approx(3.0)


def test_step_monitor_collective_event_dedup():
    m = StepMonitor(k=3.0, warmup=0)
    evs = m.record(1.0, algorithm="locality")
    assert [e.kind for e in evs] == ["collective"]
    assert evs[0].attrs == {"algorithm": "locality", "previous": None}
    # repeats stay silent; a change (elastic re-resolution) re-fires
    assert m.record(1.0, algorithm="locality") == []
    evs = m.record(1.0, algorithm="flat_psum")
    assert [e.kind for e in evs] == ["collective"]
    assert evs[0].attrs["previous"] == "locality"


def test_step_monitor_returns_structured_string_events():
    m = StepMonitor(k=3.0, warmup=1)
    m.record(1.0)
    m.record(1.0)
    (ev,) = m.record(50.0)
    assert isinstance(ev, TelemetryEvent) and isinstance(ev, str)
    assert ev.kind == "straggler" and "straggler" in ev
    assert ev.attrs["dt"] == 50.0 and ev.attrs["k"] == 3.0


# ---------------------------------------------------------------------------
# dp_group_map (the DP-domain grouping behind CommReport.dp_bytes)
# ---------------------------------------------------------------------------

def test_dp_group_map_groups_tp_peers_together():
    mesh = fake_mesh((2, 2, 2), ("pod", "data", "model"))
    m = dp_group_map(mesh, ("pod", "data"))
    assert m is not None and len(m) == 8
    # devices 0 and 1 differ only in 'model' position: same DP coordinate
    assert m[0] == m[1]
    # device 2 sits at a different 'data' position, 4 at a different 'pod'
    assert m[0] != m[2] and m[0] != m[4]
    assert len(set(m.values())) == 4   # 2 pods x 2 data rows


def test_dp_group_map_none_when_no_dp_width():
    assert dp_group_map(fake_mesh((1, 1, 4), ("pod", "data", "model")),
                        ("pod", "data")) is None
    assert dp_group_map(fake_mesh((4,), ("model",)), ("data",)) is None


# ---------------------------------------------------------------------------
# check_metrics_schema (the CI gate script)
# ---------------------------------------------------------------------------

def _schema():
    sys.path.insert(0, SCRIPTS)
    try:
        import check_metrics_schema
    finally:
        sys.path.remove(SCRIPTS)
    return check_metrics_schema


def test_check_metrics_schema_accepts_real_artifacts(tmp_path):
    schema = _schema()
    reg = MetricsRegistry()
    reg.count("steps", 3)
    reg.observe("dt", 0.5)
    reg.attach_comm_report("t", _report())
    reg.record_comm("t", 3)
    mpath = str(tmp_path / "metrics.json")
    reg.dump(mpath)
    tr = Tracer(jax_annotations=False)
    with tr.span("a"):
        pass
    tpath = str(tmp_path / "trace_x.json")
    tr.dump(tpath)
    assert schema.main([mpath, tpath]) == 0


def test_check_metrics_schema_fails_on_comm_mismatch(tmp_path):
    schema = _schema()
    reg = MetricsRegistry()
    reg.attach_comm_report("t", _report())
    reg.record_comm("t", 2)
    reg._comm["t"].actual_nonlocal_msgs += 1.0      # drift
    mpath = str(tmp_path / "metrics.json")
    reg.dump(mpath)
    assert schema.main([mpath]) == 1


def test_check_metrics_schema_fails_on_bad_trace(tmp_path):
    schema = _schema()
    tpath = str(tmp_path / "trace_bad.json")
    with open(tpath, "w") as f:
        json.dump({"traceEvents": [
            {"ph": "B", "name": "a", "ts": 0.0, "pid": 1, "tid": 1}]}, f)
    assert schema.main([tpath]) == 1                # unclosed span
    empty = str(tmp_path / "trace_empty.json")
    with open(empty, "w") as f:
        json.dump({"traceEvents": []}, f)
    assert schema.main([empty]) == 1                # no spans at all
    missing = str(tmp_path / "nope.json")
    assert schema.main([missing]) == 1


# ---------------------------------------------------------------------------
# end-to-end: 2-pod train + decode with exact comm reconciliation
# ---------------------------------------------------------------------------

E2E_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, json, os, shutil
import jax, jax.numpy as jnp, numpy as np
from repro import configs, telemetry
from repro.serve.engine import Engine
from repro.serve.spec import ServeSpec
from repro.train import Trainer, TrainerConfig
from repro.models import transformer

telemetry.set_tracer(telemetry.Tracer())
telemetry.set_registry(telemetry.MetricsRegistry())
tracer = telemetry.get_tracer()
registry = telemetry.get_registry()

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
shutil.rmtree("/tmp/repro_ckpt_telemetry", ignore_errors=True)

# --- train: locality FSDP (the paper path) ---------------------------------
tcfg = TrainerConfig(steps=4, seq_len=16, global_batch=8, ckpt_every=2,
                     ckpt_dir="/tmp/repro_ckpt_telemetry", log_every=100,
                     grad_sync="locality", fsdp=True)
tr = Trainer(cfg, mesh, tcfg, log=lambda s: None)
assert tr.comm_report is not None, "AOT comm stamping failed on locality path"
rep = tr.comm_report
assert rep.nonlocal_bytes > 0 and rep.nonlocal_msgs > 0, rep
assert rep.has_locality_schedule, (
    "locality train path lost its pod-crossing permute schedule", rep)
assert rep.dp_bytes > 0, rep
tr.run()
rec = registry.reconcile(tr.comm_label)
assert rec["invocations"] == 4, rec
assert rec["match"], ("train/locality reconciliation failed", rec)
assert rec["actual_nonlocal_bytes"] == 4 * rep.nonlocal_bytes, rec
assert rec["actual_nonlocal_msgs"] == 4 * rep.nonlocal_msgs, rec
assert registry.counter("train/steps").value == 4
assert registry.histogram("train/step_time_s").count == 4
assert registry.counter("checkpoint/saves").value >= 2

# --- train: flat XLA baseline (reconciliation must hold there too) ---------
tcfg_x = dataclasses.replace(tcfg, grad_sync="xla", fsdp=False,
                             ckpt_dir="/tmp/repro_ckpt_telemetry_x")
shutil.rmtree(tcfg_x.ckpt_dir, ignore_errors=True)
tr_x = Trainer(cfg, mesh, tcfg_x, log=lambda s: None)
assert tr_x.comm_report is not None, "AOT comm stamping failed on xla path"
assert tr_x.comm_label != tr.comm_label
tr_x.run()
rec_x = registry.reconcile(tr_x.comm_label)
assert rec_x["invocations"] == 4 and rec_x["match"], rec_x

# --- serve: locality vs flat-XLA decode combine over ('pod','data') --------
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.random.default_rng(0).integers(
    0, cfg.vocab_size, (1, 8)).astype(np.int32)
NEW = 5
engines = {}
for alg in ("locality", "xla"):
    eng = Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=64,
                                              combine=alg))
    assert eng.comm_report is not None, f"decode comm stamping failed ({alg})"
    eng.generate(prompts, NEW)
    st = eng.stats()
    r = eng.comm_report
    assert st["decode_steps"] == NEW
    assert st["nonlocal_bytes"] == NEW * r.nonlocal_bytes, st
    assert st["nonlocal_msgs"] == NEW * r.nonlocal_msgs, st
    srec = st["comm"]["reconcile"]
    assert srec["invocations"] == NEW and srec["match"], (alg, srec)
    engines[alg] = eng
loc, xla = engines["locality"], engines["xla"]
assert loc.combine.algorithm == "locality"
assert loc.comm_report.has_locality_schedule, loc.comm_report
assert loc.comm_report.nonlocal_bytes > 0
assert loc.stats()["combine_bytes"] == NEW * loc.comm_report.dp_bytes
assert xla.stats()["combine_steps"] == 0

# --- artifacts: Perfetto trace + metrics snapshot --------------------------
os.makedirs("results", exist_ok=True)
doc = tracer.dump("results/trace_telemetry_e2e.json")
problems = telemetry.validate_trace_events(doc["traceEvents"])
assert problems == [], problems[:5]
names = {e.get("name") for e in doc["traceEvents"]}
for want in ("train/build", "train/compile", "train/step", "train/step_fn",
             "train/data", "checkpoint/save", "checkpoint/write",
             "serve/build", "serve/compile", "serve/prefill",
             "serve/decode_step"):
    assert want in names, (want, sorted(names))
snap = registry.dump("results/metrics.json")
assert all(rec["match"] for rec in registry.reconcile_all().values())
assert snap["gauges"]["train/compile_time_s"] > 0
assert snap["gauges"]["train/step_time_s_mean"] > 0
assert snap["gauges"]["serve/decode_step_s_mean"] > 0
print("TELEMETRY_E2E_OK",
      int(rep.nonlocal_bytes), int(loc.comm_report.nonlocal_bytes))
"""


@pytest.mark.slow
def test_telemetry_end_to_end_two_pods(subproc):
    out = subproc(E2E_CODE, devices=8, timeout=1800)
    assert "TELEMETRY_E2E_OK" in out
