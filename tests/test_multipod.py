"""The ('pod','data') sharding domain: unit + integration coverage.

Unit layer (single device, fake meshes): composite FSDP param specs and
their (outer, local) gather geometry, the serve cache's sequence-shard
candidate resolution, the combine geometry on multi-pod meshes, the
overlap policy's measured-dispatch guard, and the bench_trend
median-of-K baseline.

Integration layer (8-device subprocess, marked slow): pod-aware FSDP
train step vs the 'data'-only layout (loss bitwise-identical — the gather
is pure data movement), and the ('pod','data') sequence-sharded decode
(greedy tokens exactly equal across locality/XLA/legacy layouts).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import fake_mesh as _fake_mesh
from jax.sharding import PartitionSpec as P

from repro.train.sharding import (fsdp_dim, fsdp_leaf_axes,
                                  gather_outer_local, param_specs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# train sharding geometry
# ---------------------------------------------------------------------------
def _abstract():
    import jax
    sds = jax.ShapeDtypeStruct
    f32 = np.float32
    return {
        "blocks": {"slot0": {"attn": {
            "wq": sds((3, 64, 32), f32),       # divisible by 8 -> composite
            "wo": sds((3, 32, 64), f32),
            "bias": sds((3, 64), f32),         # replicated by name rule
        }}},
        "embed": sds((512, 64), f32),
        "head": sds((12, 512), f32),           # 12 % 8 != 0, 12 % 4 == 0
    }


def test_param_specs_composite_fsdp_axes():
    mesh = _fake_mesh((2, 4), ("pod", "data"))
    specs = param_specs(_abstract(), mesh, fsdp=True)
    wq = specs["blocks"]["slot0"]["attn"]["wq"]
    assert wq == P(None, ("pod", "data"), None)
    assert fsdp_dim(wq) == 1
    assert fsdp_leaf_axes(wq) == "pod,data"
    # dim divisible intra-pod only: falls back to 'data' (pods replicate)
    head = specs["head"]
    assert head == P("data", None) or head == P(("data",), None), head
    assert fsdp_leaf_axes(head) == "data"
    # replicated-by-name leaves stay replicated
    bias = specs["blocks"]["slot0"]["attn"]["bias"]
    assert fsdp_dim(bias) == -1 and fsdp_leaf_axes(bias) == ""


def test_param_specs_forced_data_only():
    mesh = _fake_mesh((2, 4), ("pod", "data"))
    specs = param_specs(_abstract(), mesh, fsdp=True, fsdp_axes=("data",))
    wq = specs["blocks"]["slot0"]["attn"]["wq"]
    assert wq == P(None, "data", None)
    assert fsdp_leaf_axes(wq) == "data"


def test_string_axes_mean_one_axis():
    # a bare "data" must behave as ("data",), not be iterated char-by-char
    # (which would silently disable FSDP / the sequence sharding)
    mesh = _fake_mesh((2, 4), ("pod", "data"))
    specs = param_specs(_abstract(), mesh, fsdp=True, fsdp_axes="data")
    assert specs["blocks"]["slot0"]["attn"]["wq"] == P(None, "data", None)
    from repro.serve.engine import _cache_layout
    _, cand = _cache_layout(mesh, 1, seq_axes="data")
    assert cand == ("data",)


def test_gather_outer_local_split():
    assert gather_outer_local("pod,data") == (("pod",), ("data",))
    assert gather_outer_local("data") == ((), ("data",))
    assert gather_outer_local("") == ((), ())


def test_param_specs_three_pod_geometry():
    """q = 3 pods: per-leaf geometry when q ∤ a leaf dim — dims divisible by
    the full 3·p_data span shard composite, dims divisible only by p_data
    fall back to intra-pod 'data' (pods replicate that leaf)."""
    import jax
    sds = jax.ShapeDtypeStruct
    f32 = np.float32
    tree = {"blocks": {"slot0": {"attn": {
        "wq": sds((2, 96, 64), f32),      # 96 % 12 == 0 -> composite
        "wo": sds((2, 64, 96), f32),
    }}},
        "head": sds((64, 512), f32),      # 64 % 12 != 0, 64 % 4 == 0 -> data
    }
    mesh = _fake_mesh((3, 4), ("pod", "data"))
    specs = param_specs(tree, mesh, fsdp=True)
    wq = specs["blocks"]["slot0"]["attn"]["wq"]
    assert wq == P(None, ("pod", "data"), None)
    assert fsdp_leaf_axes(wq) == "pod,data"
    assert fsdp_leaf_axes(specs["head"]) == "data"


def test_three_pod_mesh_builders():
    from repro.launch.mesh import make_production_mesh  # noqa: F401 (sig)
    import inspect
    assert "pods" in inspect.signature(make_production_mesh).parameters


# ---------------------------------------------------------------------------
# serve cache layout + combine geometry
# ---------------------------------------------------------------------------
def test_seq_axes_resolution():
    from repro.serve.engine import _cache_layout, _seq_axes_for
    mesh = _fake_mesh((2, 4, 2), ("pod", "data", "model"))
    batch_sharded, cand = _cache_layout(mesh, 1)
    assert not batch_sharded and cand == ("pod", "data")
    assert _seq_axes_for(mesh, 32, cand) == ("pod", "data")   # 32 % 8 == 0
    assert _seq_axes_for(mesh, 12, cand) == ("data",)         # intra-pod only
    assert _seq_axes_for(mesh, 10, cand) is None
    # forcing the legacy layout narrows the candidates
    _, cand_d = _cache_layout(mesh, 1, seq_axes=("data",))
    assert cand_d == ("data",)
    assert _seq_axes_for(mesh, 32, cand_d) == ("data",)
    # single-pod mesh: unchanged behaviour
    mesh1 = _fake_mesh((8,), ("data",))
    _, cand1 = _cache_layout(mesh1, 1)
    assert cand1 == ("data",)


def test_resolve_cache_combine_multipod_geometry():
    import dataclasses
    from repro import configs
    from repro.serve.engine import resolve_cache_combine
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
    mesh = _fake_mesh((2, 4), ("pod", "data"))
    ch = resolve_cache_combine(cfg, mesh, 1, 32, override="locality")
    assert (ch.p, ch.p_local) == (8, 4)
    ch_d = resolve_cache_combine(cfg, mesh, 1, 32, override="locality",
                                 seq_axes=("data",))
    assert (ch_d.p, ch_d.p_local) == (4, 4)
    # indivisible by the composite span but divisible intra-pod
    ch_n = resolve_cache_combine(cfg, mesh, 1, 12, override="locality")
    assert (ch_n.p, ch_n.p_local) == (4, 4)
    assert resolve_cache_combine(cfg, mesh, 1, 10).algorithm == "none"


def test_resolve_cache_combine_three_pods():
    """q = 3: the combine geometry resolves the (p, p_local) pair the
    hierarchical (fold/unfold max + Bruck-transpose sum) structure runs
    over; L ∤ 3·p_data falls back per layer to 'data'."""
    import dataclasses
    from repro import configs
    from repro.serve.engine import resolve_cache_combine
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
    mesh = _fake_mesh((3, 4), ("pod", "data"))
    ch = resolve_cache_combine(cfg, mesh, 1, 48, override="locality")
    assert (ch.p, ch.p_local) == (12, 4)
    ch_n = resolve_cache_combine(cfg, mesh, 1, 32, override="locality")
    assert (ch_n.p, ch_n.p_local) == (4, 4)     # 32 % 12 != 0, 32 % 4 == 0


# ---------------------------------------------------------------------------
# overlap policy: measured dispatch overhead beats modeled hidden comm
# ---------------------------------------------------------------------------
def test_select_overlap_dispatch_guard():
    from repro.tuning.policy import Policy
    pol = Policy(None)
    nbytes, flops = 1 << 20, 1e12
    base = pol.select_overlap(16, 4, nbytes, flops)
    assert base.algorithm == "prefetch"          # big window hides the wire
    guarded = pol.select_overlap(16, 4, nbytes, flops,
                                 dispatch_overhead_s=10.0)
    assert guarded.algorithm == "eager" and guarded.source == "dispatch"
    # negligible measured overhead: the model's choice stands
    tiny = pol.select_overlap(16, 4, nbytes, flops,
                              dispatch_overhead_s=1e-12)
    assert tiny.algorithm == "prefetch"


def test_dispatch_overhead_is_measured_and_cached():
    from repro.tuning import measure
    t1 = measure.dispatch_overhead_s(refresh=True)
    assert t1 > 0.0
    assert measure.dispatch_overhead_s() == t1   # cached


# ---------------------------------------------------------------------------
# bench_trend: median-of-K baseline
# ---------------------------------------------------------------------------
def _run_trend(prev, cur, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_trend.py"),
         "--prev", str(prev), "--cur", str(cur), *extra],
        capture_output=True, text=True)


def test_bench_trend_median_of_k(tmp_path):
    meta = {"jax_version": "1", "backend": "cpu", "device_count": 8,
            "device_kind": "cpu"}
    prev = tmp_path / "prev-bench"
    cur = tmp_path / "cur"
    cur.mkdir()

    def write(d, val, m=meta):
        d.mkdir(parents=True, exist_ok=True)
        (d / "BENCH_x.json").write_text(
            json.dumps({"cell": {"modeled_step_s": val}, "meta": m}))

    # three baseline runs: median 1.0 even though one run spiked to 5.0
    for i, v in enumerate((1.0, 5.0, 1.0)):
        write(prev / f"run{i}", v)
    write(cur, 1.05)
    r = _run_trend(prev, cur)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "3 baseline run(s)" in r.stdout
    # vs the single spiked run alone the same value would "improve"; vs the
    # median a real 30% regression is caught
    write(cur, 1.3)
    r = _run_trend(prev, cur)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "median-of-3" in r.stderr + r.stdout
    # baseline runs with a foreign meta stamp are excluded from the median
    write(prev / "run3", 0.1, m={**meta, "jax_version": "2"})
    write(cur, 1.05)
    r = _run_trend(prev, cur)
    assert r.returncode == 0, r.stdout + r.stderr
    # single-run layout (artifacts directly in --prev) still works
    flat = tmp_path / "flat"
    write(flat, 1.0)
    write(cur, 1.3)
    r = _run_trend(flat, cur)
    assert r.returncode == 1, r.stdout + r.stderr


def test_bench_trend_plot_history(tmp_path, monkeypatch):
    """--plot renders the per-metric history: one SVG panel per tracked
    metric, a markdown table, and a $GITHUB_STEP_SUMMARY append."""
    meta = {"jax_version": "1", "backend": "cpu", "device_count": 8,
            "device_kind": "cpu"}
    prev = tmp_path / "prev-bench"
    cur = tmp_path / "cur"
    cur.mkdir()

    def write(d, val, m=meta):
        d.mkdir(parents=True, exist_ok=True)
        (d / "BENCH_x.json").write_text(json.dumps(
            {"cell": {"modeled_step_s": val, "tokens_per_s": val * 100},
             "meta": m}))

    for i, v in enumerate((1.0, 1.1, 0.9)):
        write(prev / f"run{i}", v)
    write(cur, 1.0)
    plot = tmp_path / "hist"
    summary = tmp_path / "summary.md"
    env = dict(os.environ, GITHUB_STEP_SUMMARY=str(summary))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_trend.py"),
         "--prev", str(prev), "--cur", str(cur), "--plot", str(plot)],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    svg = (plot / "BENCH_x.svg").read_text()
    assert svg.count("<polyline") == 2          # one line per tracked metric
    assert "baseline 1/3: 1" in svg and "current" in svg
    md = (plot / "history.md").read_text()
    assert "cell.modeled_step_s" in md and "1 → 1.1 → 0.9" in md
    assert "cell.modeled_step_s" in summary.read_text()
    # --plot with NO baselines still renders the current point
    plot2 = tmp_path / "hist2"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_trend.py"),
         "--prev", str(tmp_path / "nope"), "--cur", str(cur),
         "--plot", str(plot2)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert (plot2 / "BENCH_x.svg").exists()


# ---------------------------------------------------------------------------
# integration: layouts agree (8-device subprocess)
# ---------------------------------------------------------------------------
EQUIV_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.data import SyntheticLM
from repro.serve.engine import Engine
from repro.serve.spec import ServeSpec
from repro.train.step import custom_batch_specs, init_state, make_train_step

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                   seed=0)
bspec = custom_batch_specs(cfg, 8, 32)
losses = {}
for name, axes in (("pod_data", "auto"), ("data_only", ("data",))):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          fsdp_axes=axes, shape=bspec, donate=False)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    _, metrics = art.step_fn(state, batch)
    losses[name] = float(metrics["loss"])
    if name == "pod_data":
        assert art.fsdp_axes == ("pod", "data"), art.fsdp_axes
# the gather is pure data movement: identical forward on both layouts
assert losses["pod_data"] == losses["data_only"], losses

from repro.models import transformer
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.array([[3, 5, 7, 2]], dtype=np.int32)
toks = {}
for name, kw in (("pod_loc", dict(combine="locality")),
                 ("pod_xla", dict(combine="xla")),
                 ("data_loc", dict(combine="locality", seq_axes=("data",)))):
    eng = Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=32, **kw))
    if name == "pod_loc":
        assert eng.combine.p == 8 and eng.combine.p_local == 4, eng.combine
        assert eng.art.decode_fn_locality is not None, eng.art
    toks[name] = eng.generate(prompts, 4)
assert np.array_equal(toks["pod_loc"], toks["pod_xla"]), toks
assert np.array_equal(toks["pod_loc"], toks["data_loc"]), toks
print("MULTIPOD_EQUIV_OK")
"""


@pytest.mark.slow
def test_multipod_layouts_agree(subproc):
    assert "MULTIPOD_EQUIV_OK" in subproc(EQUIV_CODE, devices=8,
                                          timeout=1800)


EQUIV3_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.data import SyntheticLM
from repro.serve.engine import Engine
from repro.serve.spec import ServeSpec
from repro.train.step import custom_batch_specs, init_state, make_train_step

mesh = make_mesh((3, 2), ("pod", "data"))
jax.set_mesh(mesh)
# dims divisible by the 3x2 composite span so the FSDP transpose really runs
# Algorithm 2's allgatherv rounds (the wrapped final round is PARTIAL here)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          d_model=96, d_ff=192, vocab_size=384)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=6,
                   seed=0)
bspec = custom_batch_specs(cfg, 6, 32)

# pod-aware vs data-only layout: forward is pure data movement -> bitwise
losses = {}
for name, axes in (("pod_data", "auto"), ("data_only", ("data",))):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          fsdp_axes=axes, shape=bspec, donate=False)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    _, metrics = art.step_fn(state, batch)
    losses[name] = float(metrics["loss"])
    if name == "pod_data":
        assert art.fsdp_axes == ("pod", "data"), art.fsdp_axes
assert losses["pod_data"] == losses["data_only"], losses

# prefetch-depth sweep on the 3-pod mesh: the double-buffered pipeline must
# stay bitwise-exact (loss AND params) when the deferred finish completes a
# PARTIAL final round — q=3, p_local=2 wraps at group 2
outs = {}
for depth in (0, 1, 2):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          shape=bspec, donate=False, prefetch_depth=depth)
    assert art.prefetch_depth == depth, (depth, art)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    st2, metrics = art.step_fn(state, batch)
    outs[depth] = (float(metrics["loss"]), st2)
for d in (1, 2):
    assert outs[0][0] == outs[d][0], (d, outs[0][0], outs[d][0])
    pa = jax.tree.leaves(outs[0][1].params)
    pb = jax.tree.leaves(outs[d][1].params)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(pa, pb)), d

# decode: q=3 combine (fold/unfold max, Bruck-transpose sum) == XLA == legacy
from repro.models import transformer
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.array([[3, 5, 7, 2]], dtype=np.int32)
toks = {}
for name, kw in (("pod_loc", dict(combine="locality")),
                 ("pod_xla", dict(combine="xla")),
                 ("data_loc", dict(combine="locality", seq_axes=("data",)))):
    eng = Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=48, **kw))
    if name == "pod_loc":
        assert eng.combine.algorithm == "locality", eng.combine
        assert eng.combine.p == 6 and eng.combine.p_local == 2, eng.combine
        assert eng.art.decode_fn_locality is not None, eng.art
    toks[name] = eng.generate(prompts, 4)
assert np.array_equal(toks["pod_loc"], toks["pod_xla"]), toks
assert np.array_equal(toks["pod_loc"], toks["data_loc"]), toks
print("MULTIPOD3_EQUIV_OK")
"""


@pytest.mark.slow
def test_three_pod_layouts_agree(subproc):
    """q = 3 pods (non-power region count): train loss bitwise across
    layouts, prefetch-depth sweep bitwise (loss + params), greedy decode
    tokens exactly equal across locality/XLA/legacy layouts."""
    assert "MULTIPOD3_EQUIV_OK" in subproc(EQUIV3_CODE, devices=6,
                                           timeout=1800)
