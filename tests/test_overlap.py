"""Overlap pipeline (DESIGN.md §5): split start/finish collectives, the
double-buffered FSDP train pipeline, and the issue-order audit.

Guarantee layers:
  * bit-identity: ``allgather_finish(allgather_start(x))`` equals the eager
    ``locality_bruck_allgather`` — forward AND vjp (the transposed
    reduce-scatter schedule) — across dense / non-power / TP-mixed mesh
    layouts (exact ``np.array_equal``, no tolerance);
  * pipeline exactness: eager (prefetch_depth=0) and prefetched (1, 2)
    train steps produce bitwise-identical losses and updated params on
    dense and windowed-ring plans, and on a TP-mixed mesh;
  * issue order: in the lowered (trace-order) module, the prefetched
    variant shows the next gather's collective-permutes BEFORE the previous
    layer's consumer dot — the dataflow freedom XLA's latency-hiding
    scheduler needs;
  * serve: the fused-stats kernel path ("pallas_interpret") matches the jnp
    region path on a sequence-sharded decode step.
"""
import pytest

from hypothesis import given, settings, strategies as st

SPLIT_BIT_IDENTICAL_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C

CASES = [((4, 4), ("pod", "local")),    # dense power-of-two regions
         ((2, 4), ("pod", "local")),
         ((8, 2), ("pod", "local")),    # many regions, small locality
         ((3, 4), ("pod", "local")),    # non-power regions: one partial-free
                                        # round (active = 3)
         ((5, 3), ("pod", "local")),    # wrapped final round, partial payload
         ((6, 2), ("pod", "local")),    # three rounds, final one partial
         ((2, 2, 4), ("pod", "data", "model"))]   # TP-mixed (gather on 2 axes)

for shape, names in CASES:
    n = 1
    for s in shape:
        n *= s
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    mesh = jax.sharding.Mesh(devs, names)
    ag_axes = names[:2] if len(names) > 2 else names
    p = 1
    for n, s in zip(names, shape):
        if n in ag_axes:
            p *= s
    in_spec = P(ag_axes)

    x = jnp.arange(p * 6, dtype=jnp.float32).reshape(p * 2, 3) * 0.37 - 4.2

    def run(fn, arr):
        f = jax.shard_map(fn, mesh=mesh, in_specs=P(ag_axes),
                          out_specs=P(ag_axes), check_vma=False,
                          axis_names=set(mesh.axis_names))
        return jax.jit(f)(arr)

    for tiled in (False, True):
        eager = run(lambda s, t=tiled: C.locality_bruck_allgather(
            s, ag_axes[0], ag_axes[1:], tiled=t), x)
        split = run(lambda s, t=tiled: C.allgather_finish(
            C.allgather_start(s, ag_axes[0], ag_axes[1:], tiled=t)), x)
        assert np.array_equal(np.asarray(eager), np.asarray(split)), \
            (shape, tiled)

    # the transposed (reduce-scatter) schedule: vjp outputs bit-identical
    big = jnp.arange(p * p * 2, dtype=jnp.float32).reshape(p * p, 2) * 0.11

    def rs(fn, arr):
        def g(s):
            primal = jnp.zeros((s.shape[0] // p,) + s.shape[1:], s.dtype) \
                + s.reshape(-1)[0] * 0
            _, vjp = jax.vjp(fn, primal)
            (out,) = vjp(s)
            return out
        f = jax.shard_map(g, mesh=mesh, in_specs=P(ag_axes),
                          out_specs=P(ag_axes), check_vma=False,
                          axis_names=set(mesh.axis_names))
        return jax.jit(f)(arr)

    t_eager = rs(lambda v: C.locality_bruck_allgather(
        v, ag_axes[0], ag_axes[1:], tiled=True), big)
    t_split = rs(lambda v: C.allgather_finish(C.allgather_start(
        v, ag_axes[0], ag_axes[1:], tiled=True)), big)
    assert np.array_equal(np.asarray(t_eager), np.asarray(t_split)), shape
    # and both match the lax ground truth
    truth = run(lambda s: jax.lax.psum_scatter(
        s, ag_axes, scatter_dimension=0, tiled=True), big)
    assert np.allclose(np.asarray(t_eager), np.asarray(truth)), shape

# single-axis degenerate split (the FSDP gather over 'data' only)
mesh = make_mesh((8,), ("data",))
x = jnp.arange(8 * 5, dtype=jnp.float32).reshape(8, 5)
def run1(fn):
    f = jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)
    return jax.jit(f)(x)
eager = run1(lambda s: C.bruck_allgather(s, ("data",), tiled=True))
split = run1(lambda s: C.allgather_finish(C.allgather_start(
    s, (), ("data",), tiled=True)))
assert np.array_equal(np.asarray(eager), np.asarray(split))
print("SPLIT_BITWISE_OK")
"""


@pytest.mark.slow
def test_split_transpose_bit_identical(subproc):
    assert "SPLIT_BITWISE_OK" in subproc(SPLIT_BIT_IDENTICAL_CODE,
                                         devices=16)


PROPERTY_CODE_TMPL = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C

r, pl, rows, cols = %d, %d, %d, %d
p = r * pl
mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:p]).reshape(r, pl),
                         ("pod", "local"))
x = (jnp.arange(p * rows * p * cols, dtype=jnp.float32)
     .reshape(p * rows * p, cols) * 0.173 - 7.0)

def rs(fn):
    def g(s):
        primal = jnp.zeros((s.shape[0] // p,) + s.shape[1:], s.dtype) \
            + s.reshape(-1)[0] * 0
        _, vjp = jax.vjp(fn, primal)
        (out,) = vjp(s)
        return out
    f = jax.shard_map(g, mesh=mesh, in_specs=P(("pod", "local")),
                      out_specs=P(("pod", "local")), check_vma=False)
    return jax.jit(f)(x)

t_eager = rs(lambda v: C.locality_bruck_allgather(v, "pod", "local",
                                                  tiled=True))
t_split = rs(lambda v: C.allgather_finish(
    C.allgather_start(v, "pod", "local", tiled=True)))
assert np.array_equal(np.asarray(t_eager), np.asarray(t_split))
print("PROP_OK")
"""


@pytest.mark.slow
@pytest.mark.hypothesis
@settings(max_examples=5, deadline=None)
@given(st.sampled_from([(2, 4), (4, 2), (2, 8), (4, 4), (8, 2),
                        (3, 2), (5, 2), (6, 2), (3, 4), (5, 3)]),
       st.integers(1, 3), st.integers(1, 4))
def test_split_transpose_property(subproc, layout, rows, cols):
    """Transposed split schedule == eager transpose for arbitrary payloads
    (non-power region counts q ∈ {3, 5, 6} included via the layout pool —
    the allgatherv adaptation's partial rounds transpose exactly)."""
    r, pl = layout
    code = PROPERTY_CODE_TMPL % (r, pl, rows, cols)
    assert "PROP_OK" in subproc(code, devices=16)


ISSUE_ORDER_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import re
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C

mesh = make_mesh((2, 4), ("pod", "data"))
p = 8

def prefetched(s0, s1, x):
    p0 = C.allgather_start(s0, "pod", "data", tiled=True)
    w0 = C.allgather_finish(p0)
    p1 = C.allgather_start(s1, "pod", "data", tiled=True)  # issued early
    y = jnp.tanh(x @ w0)                                   # layer-0 consumer
    w1 = C.allgather_finish(p1)
    return y @ w1

def eager(s0, s1, x):
    w0 = C.locality_bruck_allgather(s0, "pod", "data", tiled=True)
    y = jnp.tanh(x @ w0)
    w1 = C.locality_bruck_allgather(s1, "pod", "data", tiled=True)
    return y @ w1

def lowered(fn):
    f = jax.shard_map(fn, mesh=mesh,
                      in_specs=(P(("pod", "data")), P(("pod", "data")), P()),
                      out_specs=P(), check_vma=False)
    # per-shard (2, 16) -> gathered weights (16, 16); x (4, 16)
    s = jnp.zeros((p * 2, 16)); xx = jnp.zeros((4, p * 2))
    return jax.jit(f).lower(s, s, xx).as_text()

def permutes_before_first_dot(txt):
    perm = [m.start() for m in re.finditer(r"collective.permute", txt)]
    dots = [m.start() for m in re.finditer(r"\bdot", txt)]
    assert perm and dots, (len(perm), len(dots))
    return sum(1 for q in perm if q < dots[0]), len(perm)

pre_before, pre_total = permutes_before_first_dot(lowered(prefetched))
eag_before, eag_total = permutes_before_first_dot(lowered(eager))
# both variants run the same two gathers in total...
assert pre_total == eag_total, (pre_total, eag_total)
# ...but the prefetched trace issues the SECOND gather's non-local rounds
# before the first layer's consumer dot; the eager trace cannot
assert pre_before > eag_before, (pre_before, eag_before)
print("ORDER_OK", pre_before, eag_before, pre_total)
"""


@pytest.mark.slow
def test_prefetched_gather_issued_before_consumer(subproc):
    assert "ORDER_OK" in subproc(ISSUE_ORDER_CODE, devices=8)


TRAIN_EXACT_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np, dataclasses
from repro import configs
from repro.train.step import make_train_step, init_state, custom_batch_specs
from repro.data import SyntheticLM

def one_step(cfg, mesh, depth):
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                       seed=0)
    bspec = custom_batch_specs(cfg, 8, 32)
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          shape=bspec, donate=False, prefetch_depth=depth)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    state2, metrics = art.step_fn(state, batch)
    return art, float(metrics["loss"]), state2

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
for arch in ("llama3.2-3b", "gemma2-9b"):       # dense + windowed-ring plan
    cfg = dataclasses.replace(configs.get_smoke(arch), n_layers=4)
    outs = {}
    for depth in (0, 1, 2):
        art, loss, st = one_step(cfg, mesh, depth)
        assert art.prefetch_depth == depth, (arch, depth, art)
        outs[depth] = (loss, st)
    for d in (1, 2):
        assert outs[0][0] == outs[d][0], (arch, d, outs[0][0], outs[d][0])
        pa = jax.tree.leaves(outs[0][1].params)
        pb = jax.tree.leaves(outs[d][1].params)
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(pa, pb)), (arch, d)

# TP-mixed: the pipeline runs inside the partially-manual region (DP axes
# manual, 'model' auto) and stays exact
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
losses = {}
for depth in (0, 1):
    art, loss, _ = one_step(cfg, mesh, depth)
    losses[depth] = loss
    assert art.prefetch_depth == depth, art
assert losses[0] == losses[1], losses

# "auto" resolves through the tuning policy's overlap term
mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                   seed=0)
art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                      shape=custom_batch_specs(cfg, 8, 32), donate=False,
                      prefetch_depth="auto")
assert art.prefetch_source in ("model", "table", "dispatch"), art
assert art.prefetch_depth in (0, 1), art
# on the host-CPU harness there is no wire to hide: the measured-dispatch
# guard must resolve "auto" to the eager schedule (depth 0)
if jax.default_backend() == "cpu":
    assert art.prefetch_depth == 0, art
print("TRAIN_EXACT_OK")
"""


@pytest.mark.slow
def test_train_prefetch_exact(subproc):
    assert "TRAIN_EXACT_OK" in subproc(TRAIN_EXACT_CODE, devices=8,
                                       timeout=1800)


SERVE_FUSED_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models import transformer
from repro.serve.engine import make_serve_fns
from repro.serve.spec import ServeSpec

mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          dtype=jnp.float32)
B, CL = 1, 64
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.random.default_rng(0).integers(
    0, cfg.vocab_size, (B, 8)).astype(np.int32)

outs = {}
for impl in ("jnp", "pallas_interpret"):
    art = make_serve_fns(cfg, mesh, ServeSpec(batch=B, cache_len=CL,
                                          combine="locality",
                                          fused_stats=impl))
    assert art.fused_stats == impl, art.fused_stats
    logits, cache = art.prefill_fn(params, {"tokens": jnp.asarray(prompts)})
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    logits, _ = art.decode_fn(params, cache, tok)
    outs[impl] = np.asarray(logits)
np.testing.assert_allclose(outs["jnp"], outs["pallas_interpret"],
                           atol=1e-4, rtol=1e-4)
# "auto" resolves to jnp on CPU backends (the kernel would only interpret)
art = make_serve_fns(cfg, mesh, ServeSpec(batch=B, cache_len=CL,
                                          combine="locality"))
assert art.fused_stats == "jnp", art.fused_stats
print("SERVE_FUSED_OK")
"""


@pytest.mark.slow
def test_serve_fused_stats_matches_jnp(subproc):
    assert "SERVE_FUSED_OK" in subproc(SERVE_FUSED_CODE, devices=8,
                                       timeout=1800)


# ---------------------------------------------------------------------------
# fast (single-device) coverage — runs in --smoke mode
# ---------------------------------------------------------------------------
def test_overlap_cost_model_properties():
    from repro.core import cost_model as cm
    m = cm.MACHINES["lassen"]
    for p, pl in ((16, 4), (8, 2), (12, 4), (16, 1), (4, 4),
                  (6, 2), (10, 2), (15, 3), (24, 4)):
        for nbytes in (64, 4096, 1 << 20):
            t_sl, t_nl, t_fl = cm.locality_bruck_phase_split(p, pl, nbytes, m)
            assert t_sl >= 0 and t_nl >= 0 and t_fl >= 0
            for flops in (0.0, 1e9, 1e15):
                oc = cm.overlap_model(p, pl, nbytes, flops, m)
                # prefetch never exposes more than eager; hidden is bounded
                # by the start chain
                assert oc.exposed_prefetch <= oc.exposed_eager + 1e-18
                assert 0.0 <= oc.hidden <= t_sl + t_nl + 1e-18
                assert oc.exposed_nonlocal_prefetch <= \
                    oc.exposed_nonlocal_eager + 1e-18
            # a huge compute window hides the whole start chain
            oc = cm.overlap_model(p, pl, nbytes, 1e30, m)
            assert abs(oc.exposed_prefetch - t_fl) < 1e-18


def test_overlap_intensity_octaves():
    from repro.tuning.measure import overlap_collective, overlap_intensity
    assert overlap_collective(1.0) == "overlap:i0"
    assert overlap_collective(100.0) == "overlap:i7"
    assert overlap_collective(128.0) == "overlap:i7"
    assert overlap_collective(129.0) == "overlap:i8"
    assert overlap_intensity("overlap:i7") == 128.0


def test_policy_selects_overlap():
    from repro.tuning.policy import Policy
    pol = Policy(None, machine="tpu_v5e")
    # no compute window: nothing to hide -> eager (tie broken to eager)
    sel = pol.select_overlap(16, 4, 1 << 20, flops=0.0)
    assert sel.algorithm == "eager" and sel.source == "model"
    # a realistic FSDP layer window -> prefetch wins
    sel = pol.select_overlap(16, 4, 1 << 20, flops=1e12)
    assert sel.algorithm == "prefetch" and sel.source == "model"
    # single device: trivially eager
    assert pol.select_overlap(1, 1, 1024, flops=1e9).algorithm == "eager"


def test_pending_collective_is_pytree():
    import jax
    import jax.numpy as jnp
    from repro.core.collectives import PendingCollective, _SplitMeta
    pend = PendingCollective((jnp.ones(3), jnp.zeros(2)),
                             _SplitMeta("allgather", "pending", ("pod",),
                                        ("data",), True, (3,), 2, 2))
    leaves, treedef = jax.tree.flatten(pend)
    assert len(leaves) == 2
    back = jax.tree.unflatten(treedef, leaves)
    assert back.meta == pend.meta
    doubled = jax.tree.map(lambda t: t * 2, pend)
    assert float(doubled.arrays[0][0]) == 2.0
