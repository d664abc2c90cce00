"""multipod: prove the ('pod','data') sharding domain pays off across the DCN.

Paper Fig. 7/8 analogue on the compiled artifact, two halves:

1. **HLO ground truth** — lower + compile the train step and the decode
   step on multi-pod meshes, scan the compiled HLO with
   ``core.hlo_analysis.collective_stats`` + ``core.topology.device_pod_map``
   (exact per-edge classification for collective-permutes, ring-decomposed
   accounting for XLA's group collectives), and assert the locality paths
   move STRICTLY fewer non-local (inter-pod) bytes AND messages than the
   flat XLA paths:

   * train FSDP on the 2×16 ('pod','data') mesh: the locality-aware Bruck
     gather + its reduce-scatter transpose (grad_sync="locality",
     fsdp_axes=('pod','data')) vs GSPMD's flat all-gather/reduce-scatter
     (grad_sync="xla") over the same composite layout;
   * serve decode on the production 2×16×16 mesh: the hierarchical
     logsumexp cache-combine (combine="locality", sequence-parallel cache
     over ('pod','data')) vs GSPMD's implicit flat combine
     (combine="xla").

   * serve cache migration on a 2×4 ('pod','data') mesh: the scheduler's
     cross-pod KV-slab replication through the explicit ``cache_migrate``
     collective (locality-Bruck schedule inside a manual shard_map region)
     vs GSPMD's implicit flat resharding of the same donor-layout input —
     plus a runtime half that forces four real migrations through the
     continuous scheduler and requires every comm-ledger label (prefill,
     migrate, decode) to reconcile predicted == actual exactly.

   * MoE expert dispatch ("moe-multipod") on 2×8 and 3×8 ('pod','data')
     meshes: the qwen2-moe train step with ``moe_dispatch="locality"``
     (two-tier ``locality_all_to_all`` + token transport — the batch block
     crosses the DCN once per destination pod and only int32 slot tables
     ride the exchange) vs ``moe_dispatch="xla"`` (flat slot all-to-all).
     Gated exactly like the other cells — strictly fewer inter-pod bytes
     AND messages — plus a structural check that the locality step lowers
     without a single grouped all-to-all op (DESIGN.md §12).

   * BOTH halves again on THREE-pod meshes (3×8 ('pod','data')) — the
     non-power region count that exercises Algorithm 2's allgatherv
     adaptation (partial final-round payloads; Bruck-transpose grad
     reduce-scatter; fold/unfold max phase — DESIGN.md §7). Before this
     adaptation the locality paths silently fell back to flat psum on
     q = 3, so this cell is the CI gate that the locality claim holds on
     the mesh shapes real fleets actually have.

2. **Numerics** — on a 2×4 ('pod','data') mesh (8 host devices), the
   pod-aware layouts must agree with the legacy 'data'-only layouts on the
   same device count: train loss bitwise-identical and params equal to
   fp32 resolution (the grad reduction ASSOCIATES differently across
   layouts — two-tier RS vs intra-pod RS + pod allreduce — so the last-ulp
   pattern differs while every forward value is bitwise-identical; the
   recorded ``params_bitwise`` flag shows what this host produced), and
   greedy decode tokens exactly equal across pod-aware locality, pod-aware
   XLA, and data-only layouts. The same equivalences re-run on a 3×2 mesh
   (6 host devices) where the wrapped final Bruck round carries a genuine
   partial payload.

Writes ``BENCH_multipod.json``; any violated inequality fails the run.
"""
from __future__ import annotations

import json
import os

from .common import REPO, emit, run_multidevice, write_bench_json

OUT = os.path.join(REPO, "BENCH_multipod.json")

TRAIN_HLO_CODE = r"""
from repro.launch.mesh import make_mesh
import json, dataclasses
import jax
from repro import configs
from repro.core.hlo_analysis import collective_stats
from repro.core.topology import device_pod_map
from repro.train.step import custom_batch_specs, make_train_step

mesh = make_mesh((2, 16), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
bspec = custom_batch_specs(cfg, 32, 64)
pod_map = device_pod_map(mesh, ("pod",))
out = {"mesh": "2x16 (pod,data)", "n_devices": 32}
for name, kw in (("locality", dict(grad_sync="locality")),
                 ("flat_xla", dict(grad_sync="xla"))):
    art = make_train_step(cfg, mesh, fsdp=True, shape=bspec, donate=False,
                          **kw)
    assert art.fsdp_axes == ("pod", "data"), art.fsdp_axes
    hlo = art.step_fn.lower(art.abstract_state, bspec).compile().as_text()
    st = collective_stats(hlo, pod_map)
    out[name] = {
        "counts": dict(st.counts),
        "permute_edges_nonlocal": st.permute_edges_nonlocal,
        "permute_bytes_nonlocal": st.permute_bytes_nonlocal,
        "group_msgs_nonlocal": st.group_msgs_nonlocal,
        "group_bytes_nonlocal": st.group_bytes_nonlocal,
        "nonlocal_msgs": st.nonlocal_msgs,
        "nonlocal_bytes": st.nonlocal_bytes,
    }
print("JSON" + json.dumps(out))
"""

SERVE_HLO_CODE = r"""
import json, dataclasses
import jax, numpy as np
from repro import configs
from repro.core.hlo_analysis import collective_stats
from repro.core.topology import device_pod_map
from repro.launch.mesh import make_production_mesh
from repro.serve import ServeSpec
from repro.serve.engine import cache_specs, make_serve_fns

mesh = make_production_mesh(multi_pod=True)          # 2x16x16
jax.set_mesh(mesh)
# 16-KV-head variant so the KV heads (not head_dim) carry the model axis —
# the locality region's eligibility condition on a 16-wide TP axis
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          n_heads=32, n_kv_heads=16)
B, L = 1, 64                                          # seq-sharded over 32
art = make_serve_fns(cfg, mesh, ServeSpec(batch=B, cache_len=L,
                                          combine="locality"))
assert art.combine.algorithm == "locality", art.combine
assert art.combine.p == 32 and art.combine.p_local == 16, art.combine
assert art.seq_axes == ("pod", "data"), art.seq_axes
assert art.decode_fn_locality is not None, art
c_specs = cache_specs(cfg, B, L)
tok = jax.ShapeDtypeStruct((B, 1), np.int32)
pod_map = device_pod_map(mesh, ("pod",))
out = {"mesh": "2x16x16 (pod,data,model)", "n_devices": 512,
       "combine": art.combine.algorithm}
for name, fn in (("locality", art.decode_fn_locality),
                 ("flat_xla", art.decode_fn_xla)):
    hlo = fn.lower(art.abstract_params, c_specs, tok).compile().as_text()
    st = collective_stats(hlo, pod_map)
    out[name] = {
        "counts": dict(st.counts),
        "permute_edges_nonlocal": st.permute_edges_nonlocal,
        "permute_bytes_nonlocal": st.permute_bytes_nonlocal,
        "group_msgs_nonlocal": st.group_msgs_nonlocal,
        "group_bytes_nonlocal": st.group_bytes_nonlocal,
        "nonlocal_msgs": st.nonlocal_msgs,
        "nonlocal_bytes": st.nonlocal_bytes,
    }
print("JSON" + json.dumps(out))
"""

THREEPOD_HLO_CODE = r"""
from repro.launch.mesh import make_mesh
import json, dataclasses
import jax, numpy as np
from repro import configs
from repro.core.hlo_analysis import collective_stats, op_payloads
from repro.core.topology import device_pod_map
from repro.serve import ServeSpec
from repro.serve.engine import cache_specs, make_serve_fns
from repro.train.step import custom_batch_specs, make_train_step

mesh = make_mesh((3, 8), ("pod", "data"))
jax.set_mesh(mesh)
# dims divisible by the 3x8 composite span (24) so every FSDP leaf genuinely
# shards across all three pods — the allgatherv adaptation's domain
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          d_model=96, d_ff=192, vocab_size=384)
pod_map = device_pod_map(mesh, ("pod",))
out = {"mesh": "3x8 (pod,data)", "n_devices": 24}

# --- train FSDP: locality Algorithm-2 gather vs flat GSPMD ----------------
bspec = custom_batch_specs(cfg, 24, 64)
train = {}
for name, kw in (("locality", dict(grad_sync="locality")),
                 ("flat_xla", dict(grad_sync="xla"))):
    art = make_train_step(cfg, mesh, fsdp=True, shape=bspec, donate=False,
                          **kw)
    assert art.fsdp_axes == ("pod", "data"), art.fsdp_axes
    hlo = art.step_fn.lower(art.abstract_state, bspec).compile().as_text()
    st = collective_stats(hlo, pod_map)
    train[name] = {
        "counts": dict(st.counts),
        "permute_edges_nonlocal": st.permute_edges_nonlocal,
        "permute_bytes_nonlocal": st.permute_bytes_nonlocal,
        "group_msgs_nonlocal": st.group_msgs_nonlocal,
        "group_bytes_nonlocal": st.group_bytes_nonlocal,
        "nonlocal_msgs": st.nonlocal_msgs,
        "nonlocal_bytes": st.nonlocal_bytes,
    }
out["train_fsdp_3pod"] = train

# --- serve decode: hierarchical combine over q=3 pods vs flat GSPMD -------
B, L = 1, 48                                  # seq-sharded over 24
art = make_serve_fns(cfg, mesh, ServeSpec(batch=B, cache_len=L,
                                          combine="locality"))
assert art.combine.algorithm == "locality", art.combine
assert art.combine.p == 24 and art.combine.p_local == 8, art.combine
assert art.seq_axes == ("pod", "data"), art.seq_axes
assert art.decode_fn_locality is not None, art
c_specs = cache_specs(cfg, B, L)
tok = jax.ShapeDtypeStruct((B, 1), np.int32)
serve = {"combine": art.combine.algorithm}
for name, fn in (("locality", art.decode_fn_locality),
                 ("flat_xla", art.decode_fn_xla)):
    hlo = fn.lower(art.abstract_params, c_specs, tok).compile().as_text()
    st = collective_stats(hlo, pod_map)
    serve[name] = {
        "counts": dict(st.counts),
        "permute_edges_nonlocal": st.permute_edges_nonlocal,
        "permute_bytes_nonlocal": st.permute_bytes_nonlocal,
        "group_msgs_nonlocal": st.group_msgs_nonlocal,
        "group_bytes_nonlocal": st.group_bytes_nonlocal,
        "nonlocal_msgs": st.nonlocal_msgs,
        "nonlocal_bytes": st.nonlocal_bytes,
    }
    if name == "locality":
        # the non-power outer tiers must run Algorithm 2, not a psum
        # fallback: no add- or max-combiner all-reduce may survive in the
        # locality decode HLO (the flat path keeps GSPMD's implicit ones)
        assert not op_payloads(hlo, "all-reduce"), "psum fallback resurfaced"
out["serve_combine_3pod"] = serve
print("JSON" + json.dumps(out))
"""

NUMERICS3_CODE = r"""
from repro.launch.mesh import make_mesh
import json, dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.data import SyntheticLM
from repro.optim import AdamW
from repro.serve import ServeSpec
from repro.serve.engine import Engine
from repro.train.step import custom_batch_specs, init_state, make_train_step

mesh = make_mesh((3, 2), ("pod", "data"))
jax.set_mesh(mesh)
out = {"mesh": "3x2 (pod,data)", "n_devices": 6}
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          d_model=96, d_ff=192, vocab_size=384)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=6,
                   seed=0)
bspec = custom_batch_specs(cfg, 6, 32)
# With q=3 the two layouts' grad reductions associate a THREE-term sum
# differently (two-tier Bruck-transpose RS vs intra-pod RS + pod
# allreduce), so grads agree only to fp32 ulp — and Adam's g/sqrt(g^2)
# normalization amplifies an ulp-level sign flip of a near-zero gradient
# into an lr-scale param difference (q=2 dodges this: a+b has one
# association). eps=1e-2 keeps the optimizer in its linear regime so the
# strict rtol below measures the gradient-sync equivalence itself.
opt = AdamW(eps=1e-2)
runs = {}
for name, axes in (("pod_data", "auto"), ("data_only", ("data",))):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          fsdp_axes=axes, shape=bspec, donate=False,
                          optimizer=opt)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    state2, metrics = art.step_fn(state, batch)
    runs[name] = (art, float(metrics["loss"]), state2)
assert runs["pod_data"][0].fsdp_axes == ("pod", "data")
loss_pod, loss_dat = runs["pod_data"][1], runs["data_only"][1]
assert loss_pod == loss_dat, (loss_pod, loss_dat)
max_rel, bitwise = 0.0, True
for x, y in zip(jax.tree.leaves(runs["pod_data"][2].params),
                jax.tree.leaves(runs["data_only"][2].params)):
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    # grads differ at fp32 ulp (three-term association), so params land a
    # few ulp apart after clip/sqrt — measured max ~3e-7 abs on this cell
    np.testing.assert_allclose(x, y, rtol=5e-4, atol=1e-6)
    if not np.array_equal(x, y):
        bitwise = False
        denom = np.maximum(np.abs(y), 1e-30)
        max_rel = max(max_rel, float(np.max(np.abs(x - y) / denom)))
out["train"] = {"loss_bitwise_equal": True, "loss": loss_pod,
                "params_bitwise": bitwise, "params_max_rel_diff": max_rel}

from repro.models import transformer
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.array([[3, 5, 7, 2, 9, 4]], dtype=np.int32)
NEW = 6
toks = {}
for name, kw in (("pod_loc", dict(combine="locality")),
                 ("pod_xla", dict(combine="xla")),
                 ("data_loc", dict(combine="locality", seq_axes=("data",)))):
    eng = Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=48, **kw))
    if name == "pod_loc":
        assert eng.combine.algorithm == "locality", eng.combine
        assert eng.combine.p == 6 and eng.combine.p_local == 2, eng.combine
        assert eng.art.decode_fn_locality is not None, eng.art
    toks[name] = eng.generate(prompts, NEW)
for a in ("pod_xla", "data_loc"):
    assert np.array_equal(toks["pod_loc"], toks[a]), (a, toks)
out["decode"] = {"tokens_exact_equal": True, "steps": NEW,
                 "tokens": toks["pod_loc"].tolist()}
print("JSON" + json.dumps(out))
"""

NUMERICS_CODE = r"""
from repro.launch.mesh import make_mesh
import json, dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.data import SyntheticLM
from repro.serve import ServeSpec
from repro.serve.engine import Engine
from repro.train.step import custom_batch_specs, init_state, make_train_step

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
out = {"mesh": "2x4 (pod,data)", "n_devices": 8}

# --- train: pod-aware vs 'data'-only FSDP layout on the same mesh --------
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                   seed=0)
bspec = custom_batch_specs(cfg, 8, 64)
runs = {}
for name, axes in (("pod_data", "auto"), ("data_only", ("data",))):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          fsdp_axes=axes, shape=bspec, donate=False)
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    state2, metrics = art.step_fn(state, batch)
    runs[name] = (art, float(metrics["loss"]), state2)
a_pod, a_dat = runs["pod_data"][0], runs["data_only"][0]
assert a_pod.fsdp_axes == ("pod", "data"), a_pod.fsdp_axes
assert a_dat.fsdp_axes == ("data",), a_dat.fsdp_axes
loss_pod, loss_dat = runs["pod_data"][1], runs["data_only"][1]
assert loss_pod == loss_dat, (loss_pod, loss_dat)   # forward is pure data
                                                    # movement: bitwise
pa = jax.tree.leaves(runs["pod_data"][2].params)
pb = jax.tree.leaves(runs["data_only"][2].params)
max_rel = 0.0
bitwise = True
for x, y in zip(pa, pb):
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    np.testing.assert_allclose(x, y, rtol=2e-6, atol=1e-7)
    if not np.array_equal(x, y):
        bitwise = False
        denom = np.maximum(np.abs(y), 1e-30)
        max_rel = max(max_rel, float(np.max(np.abs(x - y) / denom)))
out["train"] = {"loss_bitwise_equal": True, "loss": loss_pod,
                "params_bitwise": bitwise, "params_max_rel_diff": max_rel}

# --- decode: pod-aware locality vs pod-aware xla vs 'data'-only ----------
from repro.models import transformer
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = np.array([[3, 5, 7, 2, 9, 4]], dtype=np.int32)
NEW = 6
toks, logits_meta = {}, {}
for name, kw in (("pod_loc", dict(combine="locality")),
                 ("pod_xla", dict(combine="xla")),
                 ("data_loc", dict(combine="locality", seq_axes=("data",)))):
    eng = Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=32, **kw))
    if name == "pod_loc":
        assert eng.combine.algorithm == "locality", eng.combine
        assert eng.combine.p == 8 and eng.combine.p_local == 4, eng.combine
        assert eng.art.seq_axes == ("pod", "data"), eng.art.seq_axes
        assert eng.art.decode_fn_locality is not None, eng.art
    toks[name] = eng.generate(prompts, NEW)
for a in ("pod_xla", "data_loc"):
    assert np.array_equal(toks["pod_loc"], toks[a]), (a, toks)
out["decode"] = {"tokens_exact_equal": True, "steps": NEW,
                 "tokens": toks["pod_loc"].tolist()}
print("JSON" + json.dumps(out))
"""


MIGRATE_HLO_CODE = r"""
from repro.launch.mesh import make_mesh
import json, dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.core.hlo_analysis import collective_stats
from repro.core.topology import device_pod_map
from repro.models import transformer
from repro.serve import Engine, Request, ServeSpec, StepClock
from repro.serve.scheduler import make_migrate_insert_fn

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          dtype=jnp.float32)
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
pod_map = device_pod_map(mesh, ("pod",))
out = {"mesh": "2x4 (pod,data)", "n_devices": 8}

spec = ServeSpec(batch=8, cache_len=32, page_len=8, migrate="locality_bruck")
eng = Engine(cfg, mesh, params, spec, clock=StepClock())
sched = eng.scheduler
assert sched._migrate_fn is not None, "no migrate path on a 2-pod mesh?"

# --- HLO ground truth: explicit cache_migrate vs flat GSPMD reshard ------
# Both variants consume the SAME donor-layout input (a B=1 prefill cache,
# KV slabs sequence-sharded over ('pod','data')) and produce the same
# batch-sharded serving cache; the only difference is who moves the slab —
# the locality-Bruck allgather or GSPMD's implicit flat resharding.
a_cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                       sched.abstract_cache)
a_req = transformer.cache_specs(cfg, 1, spec.cache_len)
a_row = jax.ShapeDtypeStruct((), jnp.int32)
for name, alg in (("locality", "locality_bruck"), ("flat_xla", "gspmd")):
    fn = make_migrate_insert_fn(mesh, spec.batch, sched.cache_sh,
                                sched.donor_specs, sched.donor_sh, alg)
    hlo = fn.lower(a_cache, a_req, a_row).compile().as_text()
    st = collective_stats(hlo, pod_map)
    out[name] = {
        "counts": dict(st.counts),
        "permute_edges_nonlocal": st.permute_edges_nonlocal,
        "permute_bytes_nonlocal": st.permute_bytes_nonlocal,
        "group_msgs_nonlocal": st.group_msgs_nonlocal,
        "group_bytes_nonlocal": st.group_bytes_nonlocal,
        "nonlocal_msgs": st.nonlocal_msgs,
        "nonlocal_bytes": st.nonlocal_bytes,
    }

# --- runtime ledger: forced cross-pod migrations reconcile exactly -------
# 8 requests, every one homed in pod 0: four land in pod-0 rows (local
# insert), four spill into pod-1 rows — each spill is one cache migration
# the comm ledger must account exactly (predicted == actual, not approx)
rng = np.random.default_rng(0)
for i in range(8):
    eng.submit(Request(tokens=rng.integers(0, cfg.vocab_size, (6,),
                                           ).astype(np.int32),
                       max_new=4, home_pod=0, arrival_s=0.0))
res = eng.drain()
assert len(res) == 8, len(res)
assert all(r.finish_reason == "length" for r in res.values()), res
assert sched._migrations == 4, sched._migrations
assert sum(r.migrated for r in res.values()) == 4, res
comm = eng.scheduler.stats()["comm"]
mig = comm["serve/migrate:locality_bruck"]
assert mig["match"] is True, comm
assert mig["predicted_nonlocal_bytes"] > 0, comm      # crossed the DCN
assert all(rec["match"] for rec in comm.values()), comm
out["ledger"] = {k: {"match": bool(v["match"]),
                     "invocations": v["invocations"],
                     "nonlocal_bytes": v["predicted_nonlocal_bytes"]}
                 for k, v in comm.items()}
out["migrations"] = sched._migrations
print("JSON" + json.dumps(out))
"""


MOE_HLO_CODE = r"""
import json, dataclasses
import jax, numpy as np
from repro import configs
from repro.core.hlo_analysis import collective_stats, op_payloads
from repro.core.topology import device_pod_map
from repro.launch.mesh import make_mesh
from repro.train.step import custom_batch_specs, make_train_step

out = {}
base = configs.get_smoke("qwen2-moe-a2.7b")
# E = p so the expert dimension shards exactly across the composite DP span;
# q=3 exercises the non-power partial-round geometry of the inter-pod phase
for key, (q, pl) in (("moe_2pod", (2, 8)), ("moe_3pod", (3, 8))):
    p = q * pl
    mesh = make_mesh((q, pl), ("pod", "data"), devices=jax.devices()[:p])
    jax.set_mesh(mesh)
    cfg = dataclasses.replace(base, n_layers=2, n_experts=p)
    bspec = custom_batch_specs(cfg, p, 32)
    pod_map = device_pod_map(mesh, ("pod",))
    cell = {"mesh": f"{q}x{pl} (pod,data)", "n_devices": p}
    for name, md in (("locality", "locality"), ("flat_xla", "xla")):
        art = make_train_step(cfg, mesh, grad_sync="locality", shape=bspec,
                              donate=False, moe_dispatch=md)
        assert art.moe_dispatch == md, art
        hlo = art.step_fn.lower(art.abstract_state, bspec).compile().as_text()
        st = collective_stats(hlo, pod_map)
        cell[name] = {
            "counts": dict(st.counts),
            "transport": art.moe_transport,
            "permute_edges_nonlocal": st.permute_edges_nonlocal,
            "permute_bytes_nonlocal": st.permute_bytes_nonlocal,
            "group_msgs_nonlocal": st.group_msgs_nonlocal,
            "group_bytes_nonlocal": st.group_bytes_nonlocal,
            "nonlocal_msgs": st.nonlocal_msgs,
            "nonlocal_bytes": st.nonlocal_bytes,
        }
        if name == "locality":
            # token transport must engage (q < top_k * capacity_factor) and
            # the whole step must lower without a single grouped all-to-all:
            # every exchange beyond the minimized inter-pod phase is a
            # collective-permute
            assert art.moe_transport == "tokens", art
            assert not op_payloads(hlo, "all-to-all"), \
                "grouped all-to-all survived in the locality dispatch"
        else:
            assert op_payloads(hlo, "all-to-all"), \
                "flat baseline lost its all-to-all"
    out[key] = cell
print("JSON" + json.dumps(out))
"""


def _reduction(cell: dict) -> dict:
    loc, flat = cell["locality"], cell["flat_xla"]
    return {
        "nonlocal_bytes_ratio": (loc["nonlocal_bytes"] / flat["nonlocal_bytes"]
                                 if flat["nonlocal_bytes"] else None),
        "nonlocal_msgs_ratio": (loc["nonlocal_msgs"] / flat["nonlocal_msgs"]
                                if flat["nonlocal_msgs"] else None),
    }


def main() -> list[tuple]:
    results = {}
    for key, code, devices in (("train_fsdp", TRAIN_HLO_CODE, 32),
                               ("serve_combine", SERVE_HLO_CODE, 512),
                               ("threepod", THREEPOD_HLO_CODE, 24),
                               ("cache_migrate", MIGRATE_HLO_CODE, 8),
                               ("moe", MOE_HLO_CODE, 24),
                               ("numerics", NUMERICS_CODE, 8),
                               ("numerics_3pod", NUMERICS3_CODE, 6)):
        stdout = run_multidevice(code, devices=devices, timeout=3000)
        line = [l for l in stdout.splitlines() if l.startswith("JSON")][0]
        results[key] = json.loads(line[4:])

    # the 3-pod subprocess emits both halves in one JSON — promote each to a
    # top-level cell so the gate below (and the trend plots) see four cells
    three = results.pop("threepod")
    for key in ("train_fsdp_3pod", "serve_combine_3pod"):
        results[key] = {"mesh": three["mesh"], "n_devices": three["n_devices"],
                        **three[key]}
    # same for the two moe-multipod cells (one subprocess, q=2 and q=3)
    moe = results.pop("moe")
    results.update(moe)

    rows = []
    for key in ("train_fsdp", "serve_combine",
                "train_fsdp_3pod", "serve_combine_3pod", "cache_migrate",
                "moe_2pod", "moe_3pod"):
        cell = results[key]
        loc, flat = cell["locality"], cell["flat_xla"]
        red = _reduction(cell)
        cell["reduction"] = red
        # the acceptance gate FIRST (before any ratio formatting — a flat
        # path with zero classified traffic must fail with the real
        # numbers, not a NoneType format error): the locality path must
        # move strictly fewer non-local bytes AND messages than the flat
        # XLA path, and its outer rounds must genuinely cross the DCN
        assert loc["nonlocal_bytes"] > 0 and loc["nonlocal_msgs"] > 0, cell
        assert loc["nonlocal_bytes"] < flat["nonlocal_bytes"], cell
        assert loc["nonlocal_msgs"] < flat["nonlocal_msgs"], cell
        assert loc["permute_edges_nonlocal"] > 0, cell
        # mirror the per-cell DCN ground truth into the metrics registry so
        # results/metrics.json carries it alongside the step telemetry
        from repro import telemetry
        reg = telemetry.get_registry()
        for path_, v in ((f"multipod/{key}/locality_nonlocal_bytes",
                          loc["nonlocal_bytes"]),
                         (f"multipod/{key}/flat_nonlocal_bytes",
                          flat["nonlocal_bytes"]),
                         (f"multipod/{key}/bytes_ratio",
                          red["nonlocal_bytes_ratio"]),
                         (f"multipod/{key}/msgs_ratio",
                          red["nonlocal_msgs_ratio"])):
            if v is not None:
                reg.gauge(path_).set(v)
        rows.append((
            f"multipod/{key}/nonlocal_bytes", None,
            f"locality={loc['nonlocal_bytes']:.0f} "
            f"flat={flat['nonlocal_bytes']:.0f} "
            f"ratio={red['nonlocal_bytes_ratio']:.4f}"))
        rows.append((
            f"multipod/{key}/nonlocal_msgs", None,
            f"locality={loc['nonlocal_msgs']:.0f} "
            f"flat={flat['nonlocal_msgs']:.0f} "
            f"ratio={red['nonlocal_msgs_ratio']:.4f}"))
    mig = results["cache_migrate"]
    assert all(rec["match"] for rec in mig["ledger"].values()), mig["ledger"]
    rows.append(("multipod/cache_migrate/ledger", None,
                 f"migrations={mig['migrations']} labels="
                 f"{len(mig['ledger'])} all_reconciled=True"))
    for nkey in ("numerics", "numerics_3pod"):
        num = results[nkey]
        assert num["train"]["loss_bitwise_equal"], num
        assert num["decode"]["tokens_exact_equal"], num
        rows.append((f"multipod/{nkey}/train", None,
                     f"loss_bitwise=True params_bitwise="
                     f"{num['train']['params_bitwise']} "
                     f"params_max_rel_diff="
                     f"{num['train']['params_max_rel_diff']:.2e}"))
        rows.append((f"multipod/{nkey}/decode", None,
                     f"tokens_exact=True steps={num['decode']['steps']}"))

    write_bench_json(OUT, results)
    return emit(rows)


if __name__ == "__main__":
    main()
