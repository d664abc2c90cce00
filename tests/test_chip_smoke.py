"""chip_smoke.py's phases at the smoke preset on the CPU.

The script itself runs only on a TPU; these tests drive the same phase
functions on CPU devices so a change that breaks the chip run fails here
first: the one-chip serve and train phases on the one CPU device, the
four-chip phase on four forced host devices (with the Pallas decode-stats
kernel interpreted), and the refusal to run without a TPU.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)


def test_one_chip_phases(tmp_path):
    import jax
    import chip_smoke as cs
    from repro import configs
    from repro.launch.mesh import mesh_from_devices

    cfg = configs.get_smoke(cs.ARCH)
    mesh = mesh_from_devices(jax.devices()[:1])
    serve = cs.serve_phase(cfg, mesh, prompt_lens=(32, 64, 96, 128),
                           max_new=8)
    assert serve["requests"] == 4 and serve["decode_steps"] == 7
    assert serve["prefill_calls"] == 4 and serve["compile_s"] > 0
    assert serve["logit_rel_err"] <= cs.SERVE_TOL
    train = cs.train_phase(cfg, mesh, seq_len=64, global_batch=2, steps=3,
                           ckpt_dir=tmp_path / "ckpt")
    assert train["steps"] == 3 and len(train["step_s"]) == 3
    assert train["compile_s"] > 0
    assert not (tmp_path / "ckpt").exists()     # the phase cleans up


def test_serve_check_catches_a_wrong_cache():
    """The tolerance separates: logits one position early fail it."""
    import jax
    import numpy as np
    import chip_smoke as cs
    from repro import configs
    from repro.launch.mesh import mesh_from_devices
    from repro.serve import Engine, Request, ServeSpec

    cfg = configs.get_smoke(cs.ARCH)
    mesh = mesh_from_devices(jax.devices()[:1])
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 64,
                                               dtype=np.int32)
    with jax.set_mesh(mesh):
        eng = Engine(cfg, mesh, cs.init_params(cfg, mesh, 0),
                     ServeSpec(batch=1, cache_len=96))
        rid = eng.submit(Request(tokens=prompt, max_new=4))
        res = eng.drain()[rid]
        seq = np.concatenate([prompt, res.tokens[:-1]])
        ref = cs.reference_last_logits(eng.params, cfg, [seq, seq[:-1]])
    got = np.asarray(eng.scheduler.last_logits, np.float32)[0, 0,
                                                            :cfg.vocab_size]
    assert cs.rel_err(got, ref[0]) <= cs.SERVE_TOL
    assert cs.rel_err(got, ref[1]) > cs.SERVE_TOL


FOUR_CHIP_CODE = r"""
import sys
sys.path.insert(0, %r)
import jax
import chip_smoke as cs
from repro import configs
from repro.launch.mesh import mesh_from_devices

cfg = configs.get_smoke(cs.ARCH)
mesh = mesh_from_devices(jax.devices())
assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"pod": 2, "data": 2}
out = cs.four_chip_phase(cfg, cfg, mesh, seq_len=64, global_batch=8, steps=3,
                         prompt_len=64, cache_len=256, decode_steps=4,
                         fused_stats="pallas_interpret")
assert out["fused_stats"] == "pallas_interpret", out
assert len(out["decode_rel_errs"]) == 4, out
print("FOUR_CHIP_OK")
""" % REPO


@pytest.mark.slow
def test_four_chip_phase(subproc):
    assert "FOUR_CHIP_OK" in subproc(FOUR_CHIP_CODE, devices=4, timeout=900)


# Two faults in the locality path, each in its own module's view of the
# collectives so the XLA path it is compared with stays sound:
#  * grad sync with no inter-pod exchange: the pod allreduces sum over
#    'data' only, and the FSDP gather's transpose keeps only the cotangent
#    rows of the caller's own pod (forward values unchanged);
#  * decode combine that drops shard 0's partial stats (o, l) — the shard
#    holding the prompt.
FAULTS_CODE = r"""
import sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from jax import lax
import chip_smoke as cs
import repro.core.collectives as C
import repro.serve.engine as E
import repro.train.step as S
from repro import configs
from repro.launch.mesh import mesh_from_devices


class Override:
    def __init__(self, mod, **over):
        self._mod, self._over = mod, over

    def __getattr__(self, k):
        return self._over[k] if k in self._over else getattr(self._mod, k)


def allreduce_no_pod(x, outer, local=(), **kw):
    axes = tuple(a for a in C._tup(outer) + C._tup(local) if a != "pod")
    return lax.psum(x, axes) if axes else x


def allgather_no_pod_grad(x, outer, local=(), **kw):
    outer, local = C._tup(outer), C._tup(local)
    f = lambda v: C.allgather(v, outer, local, **kw)
    if "pod" not in outer:
        return f(x)

    @jax.custom_vjp
    def g(v):
        return f(v)

    def bwd(v, ct):
        n = ct.shape[0]
        pod = jnp.arange(n) // (n // C._size(outer + local)) // C._size(local)
        own = (pod == lax.axis_index("pod")).reshape((n,) + (1,) * (ct.ndim - 1))
        return jax.vjp(f, v)[1](jnp.where(own, ct, 0))

    g.defvjp(lambda v: (f(v), v), bwd)
    return g(x)


def finish_without_shard0(o, l, pending, **kw):
    axes = pending.meta.outer + pending.meta.local
    keep = (lax.axis_index(axes) != 0).astype(o.dtype)
    return C.logsumexp_combine_finish(o * keep, l * keep, pending, **kw)


S.C = Override(C, allreduce=allreduce_no_pod, allgather=allgather_no_pod_grad)
E.C = Override(C, logsumexp_combine_finish=finish_without_shard0)
cfg = configs.get_smoke(cs.ARCH)
try:
    cs.four_chip_phase(cfg, cfg, mesh_from_devices(jax.devices()),
                       seq_len=64, global_batch=8, steps=3, prompt_len=64,
                       cache_len=256, decode_steps=4,
                       fused_stats="pallas_interpret")
except cs.SmokeFailure as e:
    print("CAUGHT", e)
""" % REPO


@pytest.mark.slow
def test_four_chip_check_catches_faults(subproc):
    """Both four-chip limits fail a locality path with a planted fault."""
    out = subproc(FAULTS_CODE, devices=4, timeout=900)
    assert "CAUGHT" in out, out[-2000:]
    assert "train losses disagree" in out, out[-2000:]
    assert "decode logits disagree" in out, out[-2000:]


CACHE_CODE = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
got = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == got, got
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
print("CACHE_DIR=" + got + " DEFAULT=" + str(DEFAULT_DIR))
"""


def test_compile_cache_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and receives the entries; without it
    the cache is the fixed <repo>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", CACHE_CODE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"CACHE_DIR={tmp_path} " in proc.stdout
    assert any(tmp_path.iterdir())
    from repro.launch.compile_cache import DEFAULT_DIR
    assert DEFAULT_DIR == Path(REPO) / ".jax_cache"


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_without_a_tpu():
    proc = _run_script(REPO, SCRIPT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_script_fails_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
