"""Trainer integration (subprocess, 8 devices): learning, checkpoint
restart, fault recovery, straggler detection."""
import pytest

from repro.runtime import StepMonitor

pytestmark = pytest.mark.slow      # multi-device subprocess suite

TRAINER_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, shutil, dataclasses
from repro import configs
from repro.train import Trainer, TrainerConfig
from repro.runtime import FaultInjector

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh)
shutil.rmtree("/tmp/repro_ckpt_pytest", ignore_errors=True)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
tcfg = TrainerConfig(steps=40, seq_len=32, global_batch=8, ckpt_every=10,
                     ckpt_dir="/tmp/repro_ckpt_pytest", log_every=100,
                     grad_sync="locality", lr=3e-3)
tr = Trainer(cfg, mesh, tcfg, fault_injector=FaultInjector(fail_at_steps=(13,)),
             log=lambda s: None)
out = tr.run()
assert out["steps"] == 40
assert any("injected failure" in e for e in out["events"])
assert any("restored checkpoint at step 10" in e for e in out["events"])
first = tr.metrics_history[0]["loss"]; last = tr.metrics_history[-1]["loss"]
assert last < first - 0.5, (first, last)

# cold restart resumes from the newest checkpoint
tr2 = Trainer(cfg, mesh, dataclasses.replace(tcfg, steps=45),
              log=lambda s: None)
assert tr2.step == 40
out2 = tr2.run()
assert out2["steps"] == 45
print("TRAINER_OK", first, last)
"""

ELASTIC_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, shutil, dataclasses
from repro import configs
from repro.train import Trainer, TrainerConfig

shutil.rmtree("/tmp/repro_ckpt_elastic", ignore_errors=True)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
mesh8 = make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh8)
tcfg = TrainerConfig(steps=10, seq_len=32, global_batch=8, ckpt_every=10,
                     ckpt_dir="/tmp/repro_ckpt_elastic", log_every=100,
                     grad_sync="locality")
tr = Trainer(cfg, mesh8, tcfg, log=lambda s: None)
tr.run()
l8 = tr.metrics_history[-1]["loss"]

# elastic restart on a SMALLER mesh (lost a pod: 8 -> 4 chips)
mesh4 = make_mesh((2, 2), ("data", "model"))
jax.set_mesh(mesh4)
tr2 = Trainer(cfg, mesh4, dataclasses.replace(tcfg, steps=14),
              log=lambda s: None)
assert tr2.step == 10       # restored across mesh shapes
out = tr2.run()
assert out["steps"] == 14
print("ELASTIC_OK")
"""


def test_trainer_learning_and_recovery(subproc):
    assert "TRAINER_OK" in subproc(TRAINER_CODE, devices=8)


def test_elastic_restart_smaller_mesh(subproc):
    assert "ELASTIC_OK" in subproc(ELASTIC_CODE, devices=8)


def test_straggler_monitor_unit():
    m = StepMonitor(k=3.0, warmup=2)
    events = []
    for dt in [1.0, 1.0, 1.0, 1.0, 1.0, 10.0, 1.0]:
        events.extend(m.record(dt))
    assert any("straggler" in e for e in events)
    assert sum("straggler" in e for e in events) == 1
