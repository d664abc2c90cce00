"""Elastic fleet operations, end to end (subprocess, 8/12 devices).

The contract under test (DESIGN.md §10):

* kill-and-resume: a hard mid-run kill (``ProcessKilled`` — BaseException,
  no recovery path may swallow it) loses at most the steps since the last
  commit; restarting on a *different* pod layout of the same DP size
  ((2,4) → (4,2) → flat) replays the remaining loss trajectory **bitwise**
  (``grad_sync="flat_psum"`` compiles to one psum over the concatenated
  axes, and every layout reshapes the same device order → identical
  replica groups);
* resharding restart across *pod counts*: the step-4 checkpoint written on
  (2,4)/fsdp=False restores onto (3,4)/fsdp=True — q=3, Algorithm-2
  territory — with **bitwise-identical state** (full-leaf digests match)
  and a loss trajectory that tracks the baseline (the DP=12 reduction
  order differs, so the tail is allclose, not bitwise);
* graceful preemption: the signal triggers one final blocking save and a
  clean drain (status "preempted"); the restart resumes exactly there and
  the joint trajectory is bitwise-identical to an uninterrupted run;
* serve drain/restore: ``Engine.drain(checkpoint_dir=...)`` suspends every
  in-flight request (KV state included) and a fresh engine's ``resume``
  replays them to the *same tokens* the uninterrupted engine produces —
  on both the batch-sharded and the sequence-sharded (locality-combine)
  layouts.
"""
import os
import re

import numpy as np
import pytest

pytestmark = pytest.mark.slow


# ---------------------------------------------------------------------------
# leg 1 (8 devices): baseline + kill/resume across layouts + preemption
# ---------------------------------------------------------------------------
BITWISE_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, os
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.checkpoint import committed_step, restore_checkpoint
from repro.faults import ProcessKilled
from repro.runtime import FaultInjector, PreemptionSignal
from repro.train import Trainer, TrainerConfig

CKDIR = os.environ["ELASTIC_CKDIR"]
# dims divisible by every composite span used across the legs (8 and 12)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          d_model=96, d_ff=192, vocab_size=384,
                          dtype=jnp.float32)
def tcfg(ckpt_dir, **kw):
    base = dict(steps=8, seq_len=32, global_batch=24, ckpt_every=2,
                keep_last=4, log_every=100, grad_sync="flat_psum",
                fsdp=False, lr=3e-3, comm_telemetry=False)
    base.update(kw)
    return TrainerConfig(ckpt_dir=ckpt_dir, **base)

def losses(tr):
    return [m["loss"] for m in tr.metrics_history]

def hexes(ls):
    return " ".join(float(l).hex() for l in ls)

def mesh(shape):
    m = make_mesh(shape, ("pod", "data"))
    jax.set_mesh(m)
    return m

# --- baseline: uninterrupted (2,4) run --------------------------------
tr = Trainer(cfg, mesh((2, 4)), tcfg(CKDIR + "/base"), log=lambda s: None)
out = tr.run()
assert out["status"] == "complete", out["status"]
base = losses(tr)
print("BASE", hexes(base))

# --- hard kill at step 5 on (2,4): commits at 2 and 4 survive ---------
kdir = CKDIR + "/kill"
tr = Trainer(cfg, mesh((2, 4)), tcfg(kdir),
             fault_injector=FaultInjector(kill_at_steps=(5,)),
             log=lambda s: None)
try:
    tr.run()
except ProcessKilled as e:
    print("KILLED", tr.step, e)
else:
    raise AssertionError("kill did not fire")
tr.ckpt.wait()   # quiesce the async writer: the in-process "kill" leaves
                 # it alive, and committed_step below must not race it
assert committed_step(kdir) == 4, committed_step(kdir)

# --- resume the killed run on (4,2): auto-restore, bitwise tail -------
tr = Trainer(cfg, mesh((4, 2)), tcfg(kdir), log=lambda s: None)
assert tr.step == 4, tr.step
out = tr.fit(resume="auto")
assert out["status"] == "complete" and out["steps"] == 8, out
r42 = losses(tr)
assert hexes(r42) == hexes(base[4:]), (r42, base[4:])
print("RESUME42_BITWISE_OK")

# --- rollback-resume the same dir on flat(8): explicit step, bitwise --
tr = Trainer(cfg, mesh((1, 8)), tcfg(kdir), log=lambda s: None)
out = tr.fit(resume=4)
assert out["steps"] == 8, out
rflat = losses(tr)
assert hexes(rflat) == hexes(base[4:]), (rflat, base[4:])
print("RESUMEFLAT_BITWISE_OK")

# step 4 must still be on disk for the 12-device resharding leg, and its
# full-leaf digests are the cross-layout bitwise ground truth
import hashlib
m24 = mesh((2, 4))
s, tree = restore_checkpoint(kdir, tr.artifacts.abstract_state,
                             step=4, shardings=tr.artifacts.state_shardings)
assert s == 4
import jax.tree_util as jtu
for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
    h = hashlib.sha256(np.ascontiguousarray(
        jax.device_get(leaf)).tobytes()).hexdigest()
    print("DIGEST4", jtu.keystr(path), h)

# --- graceful preemption at step 3, restart resumes exactly there -----
pdir = CKDIR + "/preempt"
tr = Trainer(cfg, mesh((2, 4)), tcfg(pdir),
             preemption=PreemptionSignal(at_steps=(3,)), log=lambda s: None)
out = tr.run()
assert out["status"] == "preempted" and out["steps"] == 3, out
assert any(e.kind == "preemption" for e in out["events"])
assert committed_step(pdir) == 3, committed_step(pdir)
pre = losses(tr)
tr = Trainer(cfg, mesh((2, 4)), tcfg(pdir), log=lambda s: None)
assert tr.step == 3, tr.step
out = tr.fit(resume="auto")
assert out["status"] == "complete" and out["steps"] == 8, out
assert hexes(pre + losses(tr)) == hexes(base), (pre, losses(tr), base)
print("PREEMPT_BITWISE_OK")
"""


# ---------------------------------------------------------------------------
# leg 2 (12 devices): reshard the step-4 checkpoint onto q=3 pods + FSDP
# ---------------------------------------------------------------------------
RESHARD_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, hashlib, os
import jax, jax.numpy as jnp, numpy as np
import jax.tree_util as jtu
from repro import configs
from repro.train import Trainer, TrainerConfig

CKDIR = os.environ["ELASTIC_CKDIR"]
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          d_model=96, d_ff=192, vocab_size=384,
                          dtype=jnp.float32)
mesh = make_mesh((3, 4), ("pod", "data"))
jax.set_mesh(mesh)
tcfg = TrainerConfig(steps=8, seq_len=32, global_batch=24, ckpt_every=100,
                     keep_last=4, log_every=100, grad_sync="locality",
                     fsdp=True, lr=3e-3, comm_telemetry=False,
                     ckpt_dir=CKDIR + "/kill")
tr = Trainer(cfg, mesh, tcfg, log=lambda s: None)
out = tr.fit(resume=4)           # explicit rollback to the killed commit
assert out["steps"] == 8, out

# the restored-then-resaved state is sharded (3,4)+FSDP now; digest the
# assembled full leaves of the ORIGINAL step-4 restore for the driver
from repro.checkpoint import restore_checkpoint
s, tree = restore_checkpoint(CKDIR + "/kill", tr.artifacts.abstract_state,
                             step=4, shardings=tr.artifacts.state_shardings)
assert s == 4
for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
    assert leaf.sharding.mesh.shape.get("pod") == 3, leaf.sharding
    h = hashlib.sha256(np.ascontiguousarray(
        jax.device_get(leaf)).tobytes()).hexdigest()
    print("DIGEST4", jtu.keystr(path), h)
for m in tr.metrics_history:
    print("RLOSS", float(m["loss"]).hex())
print("RESHARD12_OK")
"""


# ---------------------------------------------------------------------------
# leg 3 (8 devices): serve graceful drain -> fresh-engine resume
# ---------------------------------------------------------------------------
SERVE_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, os
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.checkpoint import read_manifest
from repro.serve import Engine, Request, ServeSpec, StepClock

CKDIR = os.environ["ELASTIC_CKDIR"]
mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          dtype=jnp.float32)
from repro.models import transformer
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)

# --- batch-sharded continuous batching --------------------------------
B, S = 8, 6
spec = ServeSpec(batch=B, cache_len=32, page_len=8)
prompts = rng.integers(0, cfg.vocab_size, (B, S), np.int32)
budgets = [2] + [6] * (B - 1)        # rid 0 finishes BEFORE the suspend

def submit_all(eng):
    return [eng.submit(Request(tokens=prompts[i], max_new=budgets[i],
                               arrival_s=0.0)) for i in range(B)]

eng0 = Engine(cfg, mesh, params, spec, clock=StepClock())
rids = submit_all(eng0)
ref = eng0.drain()

ckdir = CKDIR + "/serve_batch"
eng1 = Engine(cfg, mesh, params, spec, clock=StepClock())
submit_all(eng1)
eng1.step(); eng1.step()
partial = eng1.drain(checkpoint_dir=ckdir)
assert set(partial) == {0}, set(partial)      # only rid 0 already done
assert np.array_equal(partial[0].tokens, ref[0].tokens)

step, manifest = read_manifest(ckdir)
assert manifest["extra"]["kind"] == "serve_suspend"
assert len(manifest["extra"]["active"]) == B - 1

eng2 = Engine(cfg, mesh, params, spec, clock=StepClock())
assert eng2.resume(ckdir) == B - 1
res = eng2.drain()
for rid in rids[1:]:
    assert np.array_equal(ref[rid].tokens, res[rid].tokens), \
        (rid, ref[rid].tokens, res[rid].tokens)
print("SERVE_BATCH_RESUME_OK")

# --- sequence-sharded (locality combine): active + queued replay ------
cfg1 = dataclasses.replace(cfg, n_layers=1)
params1 = transformer.init_params(jax.random.PRNGKey(0), cfg1)
spec1 = ServeSpec(batch=1, cache_len=32, combine="locality")
p0 = rng.integers(0, cfg1.vocab_size, 6, np.int32)
p1 = rng.integers(0, cfg1.vocab_size, 5, np.int32)

def submit_two(eng):
    a = eng.submit(Request(tokens=p0, max_new=5, arrival_s=0.0))
    b = eng.submit(Request(tokens=p1, max_new=4, arrival_s=0.0))
    return a, b

eng0 = Engine(cfg1, mesh, params1, spec1, clock=StepClock())
r0, r1 = submit_two(eng0)
ref = eng0.drain()

ckdir = CKDIR + "/serve_seq"
eng1 = Engine(cfg1, mesh, params1, spec1, clock=StepClock())
submit_two(eng1)
eng1.step(); eng1.step()             # r0 mid-decode, r1 still queued
eng1.drain(checkpoint_dir=ckdir)
_, manifest = read_manifest(ckdir)
assert len(manifest["extra"]["active"]) == 1
assert len(manifest["extra"]["queued"]) == 1

eng2 = Engine(cfg1, mesh, params1, spec1, clock=StepClock())
assert eng2.resume(ckdir) == 2
res = eng2.drain()
for rid in (r0, r1):
    assert np.array_equal(ref[rid].tokens, res[rid].tokens), \
        (rid, ref[rid].tokens, res[rid].tokens)
print("SERVE_SEQ_RESUME_OK")
"""


def _hex_losses(out: str, tag: str) -> list[float]:
    for line in out.splitlines():
        if line.startswith(tag + " "):
            return [float.fromhex(h) for h in line.split()[1:]]
    raise AssertionError(f"no {tag} line in:\n{out}")


def _digests(out: str) -> dict[str, str]:
    return dict(re.findall(r"^DIGEST4 (\S+) ([0-9a-f]{64})$", out, re.M))


def test_kill_resume_reshard_bitwise(subproc, tmp_path):
    """The full elastic matrix: kill on (2,4) → bitwise resume on (4,2)
    and flat(8); preemption → bitwise resume; the same checkpoint
    resharded onto 12 devices / q=3 pods with bitwise state and a
    tracking loss tail."""
    os.environ["ELASTIC_CKDIR"] = str(tmp_path)
    out8 = subproc(BITWISE_CODE, devices=8, timeout=1800)
    for marker in ("KILLED 5", "RESUME42_BITWISE_OK",
                   "RESUMEFLAT_BITWISE_OK", "PREEMPT_BITWISE_OK"):
        assert marker in out8, out8

    out12 = subproc(RESHARD_CODE, devices=12, timeout=1800)
    assert "RESHARD12_OK" in out12, out12

    # bitwise state across pod counts: every restored leaf's full-array
    # digest matches between the (2,4) and the (3,4)+FSDP restore
    d8, d12 = _digests(out8), _digests(out12)
    assert d8 and set(d8) == set(d12), (set(d8) ^ set(d12))
    mismatch = {k for k in d8 if d8[k] != d12[k]}
    assert not mismatch, mismatch

    # the resumed q=3 trajectory tracks the baseline: first loss is the
    # same forward on bitwise-identical state (ulp-level difference from
    # the DP=12 reduction order), the tail stays close
    base = _hex_losses(out8, "BASE")
    rloss = [float.fromhex(m.group(1))
             for m in re.finditer(r"^RLOSS (\S+)$", out12, re.M)]
    assert len(rloss) == 4, rloss
    np.testing.assert_allclose(rloss[0], base[4], rtol=1e-5)
    np.testing.assert_allclose(rloss, base[4:], rtol=5e-3, atol=1e-3)


def test_serve_drain_checkpoint_resume(subproc, tmp_path):
    """Engine.drain(checkpoint_dir=...) + fresh-engine resume replays
    every unfinished request to the uninterrupted engine's exact tokens
    (batch-sharded and sequence-sharded layouts)."""
    os.environ["ELASTIC_CKDIR"] = str(tmp_path)
    out = subproc(SERVE_CODE, devices=8, timeout=1800)
    assert "SERVE_BATCH_RESUME_OK" in out, out
    assert "SERVE_SEQ_RESUME_OK" in out, out
