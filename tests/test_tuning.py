"""repro.tuning: cache round-trip/versioning, policy crossovers + hysteresis,
simulated-measurement vs closed-form model agreement, and algorithm="auto"
equivalence inside shard_map (subprocess)."""
import json
import os

import pytest

from repro.core import autotune
from repro.tuning import cache as tcache
from repro.tuning import measure as tmeasure
from repro.tuning import policy as tpolicy
from repro.tuning import sweep as tsweep
from repro.tuning.cache import Entry, SchemaVersionError, TuningCache, bucket_bytes

FP = "sim:lassen"


def _entry(bucket, costs, collective="allgather", p=16, pl=4):
    return Entry(collective=collective, p=p, p_local=pl, dtype="float32",
                 bucket=bucket, costs=costs, source="simulated")


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def test_cache_round_trip_atomic(tmp_path):
    cache = TuningCache()
    cache.put(FP, _entry(1024, {"bruck": 1e-5, "ring": 2e-5}))
    cache.put(FP, _entry(4096, {"bruck": 3e-5, "ring": 2.5e-5}))
    path = tmp_path / "table.json"
    cache.save(str(path))
    # atomic write leaves no temp droppings
    assert [p.name for p in tmp_path.iterdir()] == ["table.json"]
    loaded = TuningCache.load(str(path))
    assert len(loaded) == 2
    e = loaded.get(FP, 16, 4, "allgather", "float32", 4096)
    assert e is not None and e.best == "ring" and e.costs == {
        "bruck": 3e-5, "ring": 2.5e-5}
    # group returns buckets ascending
    assert [e.bucket for e in loaded.group(FP, 16, 4, "allgather", "float32")] \
        == [1024, 4096]


def test_cache_rejects_future_schema(tmp_path):
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"schema_version": 99, "entries": {}}))
    with pytest.raises(SchemaVersionError):
        TuningCache.load(str(path))
    path.write_text(json.dumps({"entries": {}}))          # missing version
    with pytest.raises(SchemaVersionError):
        TuningCache.load(str(path))


def test_cache_migrates_v1(tmp_path):
    key = tcache.make_key(FP, 16, 4, "allgather", "float32", 1024)
    v1 = {"schema_version": 1,
          "entries": {key: {"collective": "allgather", "p": 16, "p_local": 4,
                            "dtype": "float32", "bucket": 1024,
                            "costs": {"bruck": 1e-5}}}}   # v1: no "source"
    path = tmp_path / "t.json"
    path.write_text(json.dumps(v1))
    loaded = TuningCache.load(str(path))
    assert loaded.entries[key].source == "measured"


def test_bucket_bytes():
    assert bucket_bytes(1) == 1
    assert bucket_bytes(1000) == 1024
    assert bucket_bytes(1024) == 1024
    assert bucket_bytes(1025) == 2048


# ---------------------------------------------------------------------------
# staleness (generation stamping, schema v3)
# ---------------------------------------------------------------------------
def test_cache_migrates_v2_adds_generation(tmp_path):
    key = tcache.make_key(FP, 16, 4, "allgather", "float32", 1024)
    v2 = {"schema_version": 2,
          "entries": {key: {"collective": "allgather", "p": 16, "p_local": 4,
                            "dtype": "float32", "bucket": 1024,
                            "costs": {"bruck": 1e-5},
                            "source": "simulated"}}}   # v2: no "generation"
    path = tmp_path / "t.json"
    path.write_text(json.dumps(v2))
    loaded = TuningCache.load(str(path))
    assert loaded.entries[key].generation == 0
    assert loaded.max_generation() == 0


def test_stale_keys_and_policy_surface():
    cache = TuningCache()
    for bucket, gen in ((1024, 1), (4096, 3), (16384, 5)):
        e = _entry(bucket, {"bruck": 1e-5, "ring": 2e-5})
        e.generation = gen
        cache.put(FP, e)
    assert cache.max_generation() == 5
    stale = cache.stale_keys(2)            # age >= 2 sweeps behind gen 5
    assert len(stale) == 2 and all("b1024" in k or "b4096" in k
                                   for k in stale)
    assert cache.stale_keys(10) == []
    with pytest.raises(ValueError):
        cache.stale_keys(0)
    pol = tpolicy.Policy(cache, fingerprint=FP)
    assert pol.stale_buckets(2) == stale
    assert tpolicy.Policy(None).stale_buckets(2) == []


def test_sweep_generation_stamp_and_stale_refresh():
    c1, r1 = tsweep.run_sweep(8, 2, sizes=(256,), collectives=("allgather",),
                              mode="simulated", machine="lassen")
    assert r1["generation"] == 1
    assert all(e.generation == 1 for e in c1)
    # everything fresh: a stale_after sweep measures nothing new
    c2, r2 = tsweep.run_sweep(8, 2, sizes=(256,), collectives=("allgather",),
                              mode="simulated", machine="lassen",
                              existing=c1, stale_after=3)
    assert len(c2) == 0 and r2["stale_skipped"] == 1 and r2["generation"] == 2
    # age the cell out: a later sweep pushed the table generation far ahead
    # (simulated by a fresh unrelated entry), so the same sweep re-measures
    fresh = _entry(1 << 30, {"bruck": 1e-5}, p=8, pl=2)
    fresh.generation = 6
    c1.put(FP, fresh)
    c3, r3 = tsweep.run_sweep(8, 2, sizes=(256,), collectives=("allgather",),
                              mode="simulated", machine="lassen",
                              existing=c1, stale_after=3)
    assert len(c3) == 1 and r3["stale_skipped"] == 0
    assert next(iter(c3)).generation == r3["generation"] == 7


def test_sweep_includes_overlap_cells(tmp_path):
    from repro.tuning.measure import OVERLAP_INTENSITY_OCTAVES
    cache, report = tsweep.run_sweep(
        16, 4, sizes=(4096,), collectives=("overlap",),
        mode="simulated", machine="lassen")
    colls = {e.collective for e in cache}
    assert colls == {f"overlap:i{k}" for k in OVERLAP_INTENSITY_OCTAVES}
    assert all(set(e.costs) == {"eager", "prefetch"} for e in cache)
    # the whole table (with overlap cells) round-trips the schema gate
    table = tmp_path / "tab.json"
    rep = tmp_path / "rep.json"
    tsweep.write_outputs(cache, report, table_path=str(table),
                         report_path=str(rep))
    import subprocess, sys, os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts",
                                      "check_tuning_schema.py"), str(table)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    # BENCH report carries the metadata stamp for the trend job
    meta = json.loads(rep.read_text())["meta"]
    assert {"jax_version", "backend", "device_count"} <= set(meta)


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------
def test_policy_crossover_monotone_in_bytes():
    cache, _ = tsweep.run_sweep(16, 4, mode="simulated", machine="lassen")
    pol = tpolicy.Policy(cache, fingerprint=FP, machine="lassen")
    table = pol.crossover_table("allgather", 16, 4, "float32")
    assert table, "sweep produced no crossover table"
    buckets = [b for b, _, _ in table]
    assert buckets == sorted(buckets) and len(set(buckets)) == len(buckets)
    # selection is piecewise-constant: walking sizes upward, the chosen
    # algorithm changes only at bucket boundaries and matches the table
    prev_alg, changes = None, 0
    for nbytes in [2 ** k for k in range(4, 24)]:
        sel = pol.select("allgather", 16, 4, nbytes)
        assert sel.source == "table"
        if prev_alg is not None and sel.algorithm != prev_alg:
            changes += 1
        prev_alg = sel.algorithm
    assert changes <= len(set(a for _, a, _ in table))


def test_policy_hysteresis_suppresses_flapping():
    cache = TuningCache()
    # ring "wins" the middle bucket by only 5% — inside the 10% band the
    # incumbent (bruck) must be kept; at 2x it must switch.
    cache.put(FP, _entry(1024, {"bruck": 1.0e-5, "ring": 2.0e-5}))
    cache.put(FP, _entry(4096, {"bruck": 2.0e-5, "ring": 1.9e-5}))
    cache.put(FP, _entry(16384, {"bruck": 4.0e-5, "ring": 2.0e-5}))
    pol = tpolicy.Policy(cache, fingerprint=FP, hysteresis=0.10)
    assert pol.select("allgather", 16, 4, 1024).algorithm == "bruck"
    assert pol.select("allgather", 16, 4, 4096).algorithm == "bruck"   # held
    assert pol.select("allgather", 16, 4, 16384).algorithm == "ring"   # clear


def test_policy_model_fallback_matches_autotune():
    tpolicy.set_default_policy(tpolicy.Policy(None, machine="tpu_v5e"))
    try:
        for nbytes in (256, 1 << 16, 1 << 22):
            got = tpolicy.resolve("allgather", 16, 4, nbytes)
            want = autotune.pick_allgather(16, 4, nbytes, "tpu_v5e",
                                           use_table=False)
            assert got == want, (nbytes, got, want)
    finally:
        tpolicy.set_default_policy(None)


# ---------------------------------------------------------------------------
# measured (simulated executor) vs closed-form model
# ---------------------------------------------------------------------------
def test_simulated_measurement_tracks_model():
    """On the simulated machine the round-priced schedules must stay within
    a small factor of the closed forms (they differ by final-round effects,
    not orders of magnitude), and winner agreement must be high."""
    for nbytes in (256, 4096, 1 << 18):
        modeled = autotune.model_costs(16, 4, nbytes, "lassen")
        for alg in ("bruck", "ring"):
            sim = tmeasure.simulate("allgather", alg, 16, 4, nbytes, "lassen")
            ratio = sim / modeled[alg]
            assert 0.3 < ratio < 3.0, (alg, nbytes, ratio)
    _, report = tsweep.run_sweep(16, 4, mode="simulated", machine="lassen")
    assert report["winner_agreement"]["fraction"] >= 0.5


def test_sweep_outputs(tmp_path):
    cache, report = tsweep.run_sweep(
        8, 2, sizes=(256, 4096), collectives=("allgather",),
        mode="simulated", machine="quartz")
    table = tmp_path / "tab.json"
    rep = tmp_path / "rep.json"
    tsweep.write_outputs(cache, report, table_path=str(table),
                         report_path=str(rep))
    assert TuningCache.load(str(table)).entries
    r = json.loads(rep.read_text())
    assert r["fingerprint"] == "sim:quartz"
    assert r["topology"] == {"p": 8, "p_local": 2, "n_regions": 4}
    assert all(c["measured_winner"] in c["measured_s"] for c in r["cells"])


# ---------------------------------------------------------------------------
# wiring
# ---------------------------------------------------------------------------
def test_monitor_logs_algorithm_changes():
    from repro.runtime import StepMonitor
    m = StepMonitor(k=3.0, warmup=1)
    ev = m.record(1.0, algorithm="locality_bruck")
    assert any("locality_bruck" in e for e in ev)
    assert not m.record(1.0, algorithm="locality_bruck")   # unchanged: quiet
    ev = m.record(1.0, algorithm="ring")
    assert any("ring" in e for e in ev)


def test_serve_combine_resolution_single_device():
    import jax
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import resolve_cache_combine
    mesh = make_mesh((1,), ("data",))
    cfg = configs.get_smoke("llama3.2-3b")
    choice = resolve_cache_combine(cfg, mesh, batch=4, cache_len=64)
    assert choice.algorithm == "none"       # no sequence sharding on 1 chip


GRAD_SYNC_AUTO_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, dataclasses, shutil
from repro import configs
from repro.train import Trainer, TrainerConfig

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh)
shutil.rmtree("/tmp/repro_ckpt_auto", ignore_errors=True)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
tcfg = TrainerConfig(steps=4, seq_len=32, global_batch=8, ckpt_every=100,
                     ckpt_dir="/tmp/repro_ckpt_auto", log_every=100,
                     grad_sync="auto")
logs = []
tr = Trainer(cfg, mesh, tcfg, log=logs.append)
assert tr.artifacts.grad_sync in ("locality", "flat_psum"), tr.artifacts
assert tr.artifacts.grad_algorithm in ("locality", "xla")
assert tr.artifacts.grad_sync_source in ("table", "model")
assert any("grad_sync=auto ->" in l for l in logs), logs
out = tr.run()
assert any(e.startswith("collective:") for e in tr.events), tr.events
assert out["steps"] == 4
print("GRAD_SYNC_AUTO_OK", tr.artifacts.grad_sync,
      tr.artifacts.grad_algorithm, tr.artifacts.grad_sync_source)
"""


@pytest.mark.slow
def test_trainer_grad_sync_auto(subproc):
    assert "GRAD_SYNC_AUTO_OK" in subproc(GRAD_SYNC_AUTO_CODE, devices=8)


AUTO_EQUIV_CODE = r"""
from repro.launch.mesh import make_mesh
import os, tempfile
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
from repro.tuning import sweep
from repro.tuning.policy import default_policy, set_default_policy

tmp = tempfile.mkdtemp()
cache, _ = sweep.run_sweep(16, 4, mode="simulated", machine="lassen")
path = os.path.join(tmp, "table.json")
cache.save(path)
os.environ["REPRO_TUNING_TABLE"] = path
set_default_policy(None)                      # rediscover from env

pol = default_policy()
mesh = make_mesh((4, 4), ("pod", "local"))
for n in (3, 16384):
    sel = pol.select("allgather", 16, 4, n * 4)
    assert sel.source == "table", sel
    x = jnp.arange(16 * n, dtype=jnp.float32).reshape(16, n)
    def run(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(("pod","local")),
                                     out_specs=P(("pod","local"))))(x)
    auto = run(lambda s: C.allgather(s, "pod", "local", algorithm="auto",
                                     tiled=True))
    explicit = run(lambda s, a=sel.algorithm: C.allgather(
        s, "pod", "local", algorithm=a, tiled=True))
    truth = run(lambda s: jax.lax.all_gather(s, ("pod","local"), tiled=True))
    assert np.array_equal(np.asarray(auto), np.asarray(explicit)), sel
    assert np.allclose(np.asarray(auto), np.asarray(truth)), sel
ar = run = None
print("AUTO_EQUIV_OK")
"""


@pytest.mark.slow
def test_allgather_auto_equivalence_in_shard_map(subproc):
    assert "AUTO_EQUIV_OK" in subproc(AUTO_EQUIV_CODE, devices=16)
