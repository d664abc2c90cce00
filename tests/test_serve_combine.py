"""Locality-aware decode cache-combine (the executed §Perf serve hook).

Four layers of guarantees:
  * exact-match: the manual shard_map decode path ("locality") emits tokens
    identical to the GSPMD path ("xla") and the single-device reference,
    across sequence-sharded, batch-sharded, unsharded, TP-mixed, ring-cache
    (windowed) and encoder-decoder cache layouts;
  * compiled artifact: the locality decode HLO carries the explicit combine
    schedule (collective-permutes + reduce-scatters) and NO all-reduce of
    the attention-stat payload (no max-combiner all-reduce — the signature
    of GSPMD's implicit sharded-softmax combine);
  * resolution: resolve_cache_combine classifies every cache layout and
    prices the combine as the two-phase logsumexp collective;
  * primitives: allreduce(op=max/min) and locality_logsumexp_combine match
    lax ground truth on a two-region mesh.
"""
import json

import jax
import pytest

B_SEQ = 1          # sequence-parallel layouts decode a single long row

EXACT_MATCH_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models import encdec, transformer
from repro.serve.engine import Engine
from repro.serve.spec import ServeSpec

CL, NEW = 64, 10

def tokens_for(cfg, mesh, params, prompts, combine, extra=None):
    jax.set_mesh(mesh)
    eng = Engine(cfg, mesh, params, ServeSpec(batch=prompts.shape[0],
                                              cache_len=CL,
                                              combine=combine))
    toks = eng.generate(prompts, NEW, extra=extra)
    return eng, toks

def check_arch(arch, mesh8, mesh1, n_layers=2):
    cfg = dataclasses.replace(configs.get_smoke(arch), n_layers=n_layers,
                              dtype=jnp.float32)
    mod = encdec if cfg.family == "audio" else transformer
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
    extra = None
    if cfg.family == "audio":
        extra = {"frames": jnp.asarray(
            rng.standard_normal((1, cfg.enc_seq, cfg.d_model), np.float32))}

    eng_loc, t_loc = tokens_for(cfg, mesh8, params, prompts, "locality", extra)
    assert eng_loc.combine.algorithm == "locality", (arch, eng_loc.combine)
    assert eng_loc.art.decode_fn_locality is not None
    _, t_xla = tokens_for(cfg, mesh8, params, prompts, "xla", extra)
    _, t_ref = tokens_for(cfg, mesh1, params, prompts, "auto", extra)
    assert np.array_equal(t_loc, t_xla), (arch, t_loc, t_xla)
    assert np.array_equal(t_loc, t_ref), (arch, t_loc, t_ref)
    st = eng_loc.stats()
    assert st["decode_steps"] == NEW and st["combine_steps"] == NEW
    assert eng_loc.art.decode_fn_locality is not None
    # combine traffic is sourced from the compiled decode HLO (CommReport),
    # not the analytic nbytes x layer-count estimate
    comm = st["comm"]
    per_step = comm["per_step"]["dp_bytes"]
    assert per_step > 0, comm
    assert st["combine_bytes"] == NEW * per_step, st
    rec = comm["reconcile"]
    assert rec["invocations"] == NEW and rec["match"], rec
    return t_ref

mesh8 = make_mesh((8,), ("data",))
mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))

check_arch("llama3.2-3b", mesh8, mesh1)     # dense, full attention
check_arch("gemma2-9b", mesh8, mesh1)       # [window, full] plan: ring cache
check_arch("whisper-tiny", mesh8, mesh1)    # encoder-decoder self-attn cache

# mixed sequence x tensor parallelism: KV heads sharded over 'model'
mesh42 = make_mesh((4, 2), ("data", "model"))
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          dtype=jnp.float32)
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
eng, t_loc = tokens_for(cfg, mesh42, params, prompts, "locality")
assert eng.combine.p == 4, eng.combine
# per-RANK payload: KV heads sharded over the model axis halve the stats
assert eng.combine.nbytes == 1 * (cfg.n_heads // 2) * (cfg.head_dim_ + 1) * 4
_, t_xla = tokens_for(cfg, mesh42, params, prompts, "xla")
_, t_ref = tokens_for(cfg, mesh1, params, prompts, "auto")
assert np.array_equal(t_loc, t_xla), (t_loc, t_xla)
assert np.array_equal(t_loc, t_ref), (t_loc, t_ref)
print("EXACT_MATCH_OK")
"""


@pytest.mark.slow
def test_locality_decode_exact_match(subproc):
    assert "EXACT_MATCH_OK" in subproc(EXACT_MATCH_CODE, devices=8,
                                       timeout=1800)


HLO_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, json, math
import jax, jax.numpy as jnp
from repro import configs
from repro.models import transformer
from repro.serve.engine import make_serve_fns
from repro.serve.spec import ServeSpec
from repro.core.hlo_analysis import (allreduce_combiners, collective_stats,
                                     op_payloads)

mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
B, CL, n = 1, 64, 8
art = make_serve_fns(cfg, mesh, ServeSpec(batch=B, cache_len=CL,
                                          combine="locality"))
cache_sds = transformer.cache_specs(cfg, B, CL)
tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)

out = {}
for name, fn in (("locality", art.decode_fn_locality),
                 ("xla", art.decode_fn_xla)):
    hlo = fn.lower(art.abstract_params, cache_sds, tok_sds).compile().as_text()
    st = collective_stats(hlo)
    out[name] = {"counts": dict(st.counts),
                 "combiners": allreduce_combiners(hlo),
                 "ar_payloads": op_payloads(hlo, "all-reduce")}

loc, xla = out["locality"], out["xla"]
# the scanned layers share one while body in the HLO text: count the
# layers it spells out (one period of the plan plus the remainder)
period, _, rem = transformer.find_period(cfg.layer_plan())
layers, lg = period + rem, int(math.log2(n))
# 1. the explicit schedule: one packed-sum reduce-scatter per attention
#    layer, plus max-phase recursive doubling and the Bruck allgather
assert loc["counts"].get("reduce-scatter", 0) >= layers, loc
assert loc["counts"].get("collective-permute", 0) >= 2 * layers * lg, loc
# 2. no all-reduce of the stat payload: GSPMD's implicit combine of a
#    softmax over the sharded axis needs a MAX-combiner all-reduce; the
#    manual path must have none (add-combiner all-reduces from sharded
#    projection matmuls are unrelated and allowed)
bad = [c for c in loc["combiners"] if c in ("maximum", "minimum")]
assert not bad, bad
# 2b. positive control for the detector itself: a plain GSPMD softmax over
#     a sharded axis MUST surface a maximum-combiner all-reduce (combiner
#     computations carry opaque names — the detector resolves root ops)
from jax.sharding import NamedSharding, PartitionSpec as P
sh = NamedSharding(mesh, P("data"))
ctrl = jax.jit(lambda x: jax.nn.softmax(x, axis=0), in_shardings=sh,
               out_shardings=sh)
ctrl_hlo = ctrl.lower(
    jax.ShapeDtypeStruct((64, 16), jnp.float32)).compile().as_text()
assert "maximum" in allreduce_combiners(ctrl_hlo), \
    allreduce_combiners(ctrl_hlo)
# 3. nor an all-reduce carrying the packed o+l stat payload itself
o_elems = B * cfg.n_heads * cfg.head_dim_
packed = (o_elems + B * cfg.n_heads) * 4
padded = -(-(o_elems + B * cfg.n_heads) // n) * n * 4
assert not [b for b in loc["ar_payloads"] if b in (packed, padded)], loc
# 4. the xla path is all-implicit: no explicit schedule leaked into it
assert not xla["counts"].get("reduce-scatter", 0), xla
assert not xla["counts"].get("collective-permute", 0), xla
print("HLO_OK" + json.dumps(out))
"""


@pytest.mark.slow
def test_locality_decode_hlo_has_no_stat_allreduce(subproc):
    assert "HLO_OK" in subproc(HLO_CODE, devices=8, timeout=1200)


COMBINE_PRIMITIVES_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C

mesh = make_mesh((2, 4), ("pod", "local"))
x = jnp.arange(8 * 5, dtype=jnp.float32).reshape(8, 5) * 0.7 - 11.0

def run(fn, arr, out_specs=None):
    f = jax.shard_map(fn, mesh=mesh, in_specs=P(("pod", "local")),
                      out_specs=out_specs or P(("pod", "local")),
                      check_vma=False)
    return jax.jit(f)(arr)

# generic reduction-op hook: locality max/min == lax ground truth
for op, lax_fn in (("max", jax.lax.pmax), ("min", jax.lax.pmin)):
    truth = run(lambda s, f=lax_fn: f(s, ("pod", "local")), x)
    for alg in ("locality", "xla"):
        out = run(lambda s, a=alg, o=op: C.allreduce(
            s, "pod", "local", algorithm=a, op=o), x)
        assert np.allclose(out, truth), (op, alg)

# logsumexp combine == softmax ground truth over the full axis
k, d = 6, 3
S = jax.random.normal(jax.random.PRNGKey(0), (8 * k,)) * 4.0
V = jax.random.normal(jax.random.PRNGKey(1), (8 * k, d))

def partial_stats(s, v):
    m = jnp.max(s)[None]                    # (1,)
    p = jnp.exp(s - m)
    return p[None, :] @ v, m, jnp.sum(p)[None]   # (1,d), (1,), (1,)

def combined(s, v, alg):
    o, m, l = partial_stats(s, v)
    o, l = C.locality_logsumexp_combine(o, m, l, "pod", "local",
                                        algorithm=alg)
    return (o / l[:, None])[0]

truth = jax.nn.softmax(S) @ V
for alg in ("locality", "xla"):
    f = jax.shard_map(lambda s, v, a=alg: combined(s, v, a), mesh=mesh,
                      in_specs=(P(("pod", "local")), P(("pod", "local"))),
                      out_specs=P(), check_vma=False)
    out = jax.jit(f)(S, V)
    assert np.allclose(np.asarray(out), np.asarray(truth), atol=1e-5), alg
print("PRIMITIVES_OK")
"""


@pytest.mark.slow
def test_logsumexp_combine_primitives(subproc):
    assert "PRIMITIVES_OK" in subproc(COMBINE_PRIMITIVES_CODE, devices=8)


RESOLVE_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, json
import jax, numpy as np
from repro import configs
from repro.serve.engine import resolve_cache_combine

cfg = configs.get_smoke("llama3.2-3b")
mesh_d = make_mesh((8,), ("data",))
mesh_m = make_mesh((8,), ("model",))
out = {
    "batch_sharded": resolve_cache_combine(cfg, mesh_d, batch=8, cache_len=64),
    "seq_sharded": resolve_cache_combine(cfg, mesh_d, batch=1, cache_len=64),
    "no_data_axis": resolve_cache_combine(cfg, mesh_m, batch=1, cache_len=64),
    "indivisible": resolve_cache_combine(cfg, mesh_d, batch=1, cache_len=60),
    "forced_xla": resolve_cache_combine(cfg, mesh_d, batch=1, cache_len=64,
                                        override="xla"),
}
print("JSON" + json.dumps({k: dataclasses.asdict(v) for k, v in out.items()}))
"""


@pytest.fixture(scope="module")
def resolved_layouts(subproc):
    stdout = subproc(RESOLVE_CODE, devices=8)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][0]
    return json.loads(line[4:])


@pytest.mark.slow
@pytest.mark.parametrize("layout,expect", [
    ("batch_sharded", dict(algorithm="none", source="n/a", nbytes=0, p=1,
                           p_local=1)),
    ("seq_sharded", dict(nbytes=528, p=8, p_local=8)),
    ("no_data_axis", dict(algorithm="none", source="n/a", nbytes=0, p=1,
                          p_local=1)),
    ("indivisible", dict(algorithm="none", source="n/a", nbytes=0, p=1,
                         p_local=1)),
    ("forced_xla", dict(algorithm="xla", source="explicit", nbytes=528, p=8,
                        p_local=8)),
])
def test_resolve_cache_combine_layouts(resolved_layouts, layout, expect):
    got = resolved_layouts[layout]
    for k, v in expect.items():
        assert got[k] == v, (layout, k, got)
    if layout == "seq_sharded":
        assert got["algorithm"] in ("locality", "xla")
        assert got["source"] in ("model", "table")


# ---------------------------------------------------------------------------
# fast (single-device / deviceless) coverage — runs in --smoke mode
# ---------------------------------------------------------------------------
def test_policy_prices_logsumexp_combine():
    from repro.tuning.measure import simulate_logsumexp_combine
    from repro.tuning.policy import Policy
    pol = Policy(None, machine="lassen")
    sel = pol.select("logsumexp_combine", 16, 4, 528)
    assert sel.algorithm in ("locality", "xla") and sel.source == "model"
    assert sel.cost is not None and sel.cost > 0
    for alg in ("locality", "xla"):
        c = simulate_logsumexp_combine(alg, 16, 4, 65536, "lassen")
        assert c > 0
    # multi-region, bandwidth regime: the locality structure moves ~1/p_l of
    # the non-local bytes and must win under the postal model
    big = 4 << 20
    assert (simulate_logsumexp_combine("locality", 16, 4, big, "lassen")
            < simulate_logsumexp_combine("xla", 16, 4, big, "lassen"))


def test_reduce_op_hook_validates():
    from repro.core import collectives as C
    with pytest.raises(ValueError):
        C._binop("prod")
    assert set(C.REDUCE_BINOPS) == {"sum", "max", "min"}


def test_engine_stats_and_next_token_single_device():
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import Engine
    from repro.serve.spec import ServeSpec

    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
    mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):   # scoped: a global mesh leaks to later tests
        from repro.models import transformer
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        eng = Engine(cfg, mesh, params, ServeSpec(batch=2, cache_len=32))
        assert eng.combine.algorithm == "none"
        prompts = np.zeros((2, 4), np.int32)
        toks = eng.generate(prompts, 3)
        assert toks.shape == (2, 3)
        st = eng.stats()
        assert st["decode_steps"] == 3
        assert st["combine_steps"] == 0 and st["combine_bytes"] == 0
        assert "comm" not in st          # combine "none": telemetry stays off
        assert eng.comm_report is None
        # the sampling rule is the one helper: clamps padded-vocab ids
        big = jnp.zeros((2, 1, cfg.padded_vocab))
        big = big.at[:, :, cfg.padded_vocab - 1].set(9.0)
        tok = eng._next_token(big)
        assert int(tok.max()) <= cfg.vocab_size - 1
