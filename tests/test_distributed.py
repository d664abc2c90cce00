"""Multi-device tests (subprocess: 8-16 forced host devices).

Covers: JAX collectives == lax ground truth, reduce-scatter transpose,
paper-mode grad sync == GSPMD grad sync, and the DMA allgather kernel under
the TPU interpret backend.
"""
import pytest

pytestmark = pytest.mark.slow      # multi-device subprocess suite

COLLECTIVES_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C

mesh = make_mesh((4, 4), ("pod", "local"))
x = jnp.arange(16 * 3, dtype=jnp.float32).reshape(16, 3)
def run(fn, arr=None):
    arr = x if arr is None else arr
    f = jax.shard_map(fn, mesh=mesh, in_specs=P(("pod","local")),
                      out_specs=P(("pod","local")))
    return jax.jit(f)(arr)

truth = run(lambda s: jax.lax.all_gather(s, ("pod","local"), tiled=True))
for name in ["bruck","ring","hierarchical","multilane","locality_bruck","xla"]:
    out = run(lambda s, n=name: C.allgather(s, "pod", "local", algorithm=n, tiled=True))
    assert np.allclose(out, truth), name

truthr = run(lambda s: jax.lax.psum(s, ("pod","local")))
for alg in [("locality","rhd"),("locality","rd"),("locality","psum")]:
    out = run(lambda s, a=alg: C.allreduce(s, "pod", "local", algorithm=a[0],
                                           outer_algorithm=a[1]))
    assert np.allclose(out, truthr), alg

xx = jnp.arange(16*32*2, dtype=jnp.float32).reshape(16*32, 2)
t2 = run(lambda s: jax.lax.psum_scatter(s, ("pod","local"),
                                        scatter_dimension=0, tiled=True), xx)
for name in ["bruck","locality_bruck","multilane","hierarchical","ring"]:
    out = run(lambda s, n=name: C.reduce_scatter(s, "pod", "local", algorithm=n), xx)
    assert np.allclose(out, t2), name

for alg in ["locality_bruck", "xla"]:
    def loss(s, a=alg):
        g = C.allgather(s, "pod", "local", algorithm=a, tiled=True)
        return (g ** 2).sum()
    g = run(jax.grad(loss))
    assert np.allclose(np.asarray(g), 32 * np.asarray(x)), alg
print("COLLECTIVES_OK")
"""

NONPOWER_COLLECTIVES_CODE = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
from repro.core.hlo_analysis import op_payloads

# q in {3, 5, 6} outer regions — Algorithm 2's allgatherv adaptation
# (partial final-round payloads) plus the non-power allreduce structures
# (Bruck-transpose RS for "rhd", fold/unfold for "rd" and max/min).
for r, pl in [(3, 2), (3, 4), (5, 2), (5, 3), (6, 2), (6, 4)]:
    p = r * pl
    devs = np.asarray(jax.devices()[:p]).reshape(r, pl)
    mesh = jax.sharding.Mesh(devs, ("pod", "local"))
    x = jnp.arange(p * 3, dtype=jnp.float32).reshape(p, 3) * 0.37 - 4.2

    def run(fn, arr=None):
        arr = x if arr is None else arr
        f = jax.shard_map(fn, mesh=mesh, in_specs=P(("pod", "local")),
                          out_specs=P(("pod", "local")), check_vma=False)
        return jax.jit(f)(arr)

    truth = run(lambda s: jax.lax.all_gather(s, ("pod", "local"), tiled=True))
    for name in ["bruck", "ring", "hierarchical", "multilane",
                 "locality_bruck", "xla"]:
        out = run(lambda s, n=name: C.allgather(s, "pod", "local",
                                                algorithm=n, tiled=True))
        assert np.allclose(out, truth), (name, r, pl)

    truthr = run(lambda s: jax.lax.psum(s, ("pod", "local")))
    for oa in ("rhd", "rd", "psum"):
        out = run(lambda s, a=oa: C.allreduce(s, "pod", "local",
                                              algorithm="locality",
                                              outer_algorithm=a))
        assert np.allclose(out, truthr, atol=1e-4), (oa, r, pl)
    for op, lref in (("max", jax.lax.pmax), ("min", jax.lax.pmin)):
        t = run(lambda s, f=lref: f(s, ("pod", "local")))
        o = run(lambda s, o_=op: C.allreduce(s, "pod", "local",
                                             algorithm="locality", op=o_))
        assert np.array_equal(np.asarray(o), np.asarray(t)), (op, r, pl)

    xx = jnp.arange(p * p * 2, dtype=jnp.float32).reshape(p * p, 2)
    t2 = run(lambda s: jax.lax.psum_scatter(s, ("pod", "local"),
                                            scatter_dimension=0, tiled=True),
             xx)
    out = run(lambda s: C.reduce_scatter(s, "pod", "local",
                                         algorithm="locality_bruck"), xx)
    assert np.allclose(out, t2, atol=1e-4), ("rs", r, pl)

    def loss(s):
        g = C.allgather(s, "pod", "local", algorithm="locality_bruck",
                        tiled=True)
        return (g ** 2).sum()
    g = run(jax.grad(loss))
    assert np.allclose(np.asarray(g), 2 * p * np.asarray(x)), (r, pl)

# the psum fallback is GONE: a non-power locality allreduce lowers to
# ppermutes/psum-scatters only — zero all-reduce ops in the compiled HLO
devs = np.asarray(jax.devices()[:6]).reshape(3, 2)
mesh = jax.sharding.Mesh(devs, ("pod", "local"))
x = jnp.zeros((24, 2), jnp.float32)
for kw in (dict(op="sum"), dict(op="max"), dict(op="sum",
                                                outer_algorithm="rd")):
    f = jax.jit(jax.shard_map(
        lambda s, k=kw: C.allreduce(s, "pod", "local", algorithm="locality",
                                    **k),
        mesh=mesh, in_specs=P(("pod", "local")),
        out_specs=P(("pod", "local")), check_vma=False))
    hlo = f.lower(x).compile().as_text()
    assert not op_payloads(hlo, "all-reduce"), (kw, "psum fallback resurfaced")
print("NONPOWER_OK")
"""


GRAD_SYNC_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np, dataclasses
from repro import configs
from repro.train.step import make_train_step, init_state, custom_batch_specs
from repro.data import SyntheticLM

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=0)
bspec = custom_batch_specs(cfg, 8, 32)
states, losses = {}, {}
for mode in ["xla", "locality", "flat_psum"]:
    art = make_train_step(cfg, mesh, grad_sync=mode, shape=bspec, donate=False)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    state2, metrics = art.step_fn(state, batch)
    states[mode] = state2
    losses[mode] = float(metrics["loss"])
assert abs(losses["xla"] - losses["locality"]) < 1e-3, losses
p_x = jax.tree.leaves(states["xla"].params)
p_l = jax.tree.leaves(states["locality"].params)
err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
          for a, b in zip(p_x, p_l))
assert err < 5e-3, err
print("GRAD_SYNC_OK", losses["xla"])
"""

DMA_KERNEL_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels.dma_allgather.ops import dma_locality_allgather

mesh = make_mesh((2, 4), ("r", "l"))
jax.set_mesh(mesh)
x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)
def run(fn):
    f = jax.shard_map(fn, mesh=mesh, in_specs=P(("r","l")),
                      out_specs=P(("r","l")), check_vma=False)
    return jax.jit(f)(x)
truth = run(lambda s: jax.lax.all_gather(s, ("r","l")))
for alg in ["bruck", "locality_bruck", "multilane", "ring"]:
    out = run(lambda s, a=alg: dma_locality_allgather(
        s, "r", "l", mesh, algorithm=a, interpret=True))
    assert np.allclose(np.asarray(out), np.asarray(truth)), alg
print("DMA_OK")
"""

SEQ_SHARD_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np, dataclasses
from repro import configs
from repro.train.step import make_train_step, init_state, custom_batch_specs
from repro.data import SyntheticLM

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=0)
bspec = custom_batch_specs(cfg, 8, 32)
losses = {}
for fsdp in (False, True):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=fsdp,
                          seq_shard=fsdp, shape=bspec, donate=False)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    _, metrics = art.step_fn(state, batch)
    losses[fsdp] = float(metrics["loss"])
assert abs(losses[False] - losses[True]) < 1e-3, losses
print("FSDP_OK")
"""


def test_collectives_vs_ground_truth(subproc):
    assert "COLLECTIVES_OK" in subproc(COLLECTIVES_CODE, devices=16)


def test_collectives_nonpower_regions(subproc):
    """q ∈ {3, 5, 6} outer regions: every collective matches the lax ground
    truth and the non-power locality allreduce compiles without any
    all-reduce (the old silent psum fallback)."""
    assert "NONPOWER_OK" in subproc(NONPOWER_COLLECTIVES_CODE, devices=24,
                                    timeout=1800)


def test_grad_sync_modes_agree(subproc):
    assert "GRAD_SYNC_OK" in subproc(GRAD_SYNC_CODE, devices=8)


def test_dma_allgather_kernel(subproc):
    assert "DMA_OK" in subproc(DMA_KERNEL_CODE, devices=8)


def test_fsdp_seq_shard_agree(subproc):
    assert "FSDP_OK" in subproc(SEQ_SHARD_CODE, devices=8)
