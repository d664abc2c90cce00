"""overlap: eager vs double-buffered prefetch for the FSDP train pipeline.

Per model size, spawns an 8-device (2 pods × 4) subprocess that builds the
paper-mode FSDP train step twice — ``prefetch_depth=0`` (eager: the whole
stacked param gather serialized in front of the forward) and
``prefetch_depth=1`` (layer i+1's gather issued inside the scan before
layer i's compute) — asserts EXACT loss/metric equality between the two,
and reports wall-clock step time + tokens/s.

Host-CPU wall clock cannot show the overlap win (there is no real network
to hide), so the exposed-communication split additionally comes from the
simulated backend: the cost-model overlap term
(``cost_model.overlap_model``) prices each layer's gather bytes against its
matmul window on the tpu_v5e parameter set — the same term
``prefetch_depth="auto"`` resolves through. The prefetched exposed-comm
numbers must come out strictly below the eager ones; the acceptance gate of
the overlap subsystem.

Wall clock is gated (prefetched strictly faster) ONLY on accelerator
backends; on the CPU harness it is reported, and the gate is instead that
``prefetch_depth="auto"`` resolves to eager via the measured-dispatch
guard (``Policy.select_overlap(dispatch_overhead_s=...)``) — a host with
no wire must never be told to prefetch. Writes ``BENCH_overlap.json``.
"""
from __future__ import annotations

import json
import os

from .common import REPO, emit, run_multidevice, write_bench_json

OUT = os.path.join(REPO, "BENCH_overlap.json")

#: (name, smoke config, n_layers) — three sizes, one windowed-ring plan
SIZES = (("llama3b_2L", "llama3.2-3b", 2),
         ("llama3b_6L", "llama3.2-3b", 6),
         ("gemma9b_4L", "gemma2-9b", 4))

STEPS = 3
BATCH, SEQ = 8, 64

CODE_TMPL = r"""
from repro.launch.mesh import make_mesh
import json, time
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro import configs
from repro.core import cost_model
from repro.train.sharding import fsdp_param_dims
from repro.train.step import make_train_step, init_state, custom_batch_specs
from repro.data import SyntheticLM

ARCH, NL, BATCH, SEQ, STEPS = %r, %d, %d, %d, %d

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke(ARCH), n_layers=NL)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SEQ,
                   global_batch=BATCH, seed=0)
bspec = custom_batch_specs(cfg, BATCH, SEQ)
out = {}
metrics_by_depth = {}
for depth in (0, 1):
    art = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                          shape=bspec, donate=False, prefetch_depth=depth)
    assert art.prefetch_depth == depth, art
    state = init_state(cfg, mesh, art)
    batch = {k: jax.device_put(v, art.batch_shardings[k])
             for k, v in data.batch(0).items()}
    state2, metrics = art.step_fn(state, batch)        # compile + warm
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state2, metrics = art.step_fn(state, batch)
    jax.block_until_ready(metrics["loss"])
    us = (time.perf_counter() - t0) / STEPS * 1e6
    metrics_by_depth[depth] = float(metrics["loss"])
    out["prefetched" if depth else "eager"] = {
        "us_per_step": us,
        "tokens_per_s": BATCH * SEQ / (us / 1e6),
        "loss": float(metrics["loss"]),
    }
assert metrics_by_depth[0] == metrics_by_depth[1], metrics_by_depth

# prefetch_depth="auto" through the tuning policy + the measured-dispatch
# guard: on a host-CPU harness (no wire to hide, real per-dispatch cost)
# it must resolve to the eager schedule
art_auto = make_train_step(cfg, mesh, grad_sync="locality", fsdp=True,
                           shape=bspec, donate=False, prefetch_depth="auto")
out["auto"] = {"depth": art_auto.prefetch_depth,
               "source": art_auto.prefetch_source,
               "backend": jax.default_backend()}

# --- simulated backend: the cost-model overlap term on this topology -------
from repro.models import transformer
a_params = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))
from repro.train.sharding import param_specs
pspecs = param_specs(a_params, mesh, fsdp=True)
dims = fsdp_param_dims(pspecs)["blocks"]
blk = jax.tree.leaves(a_params["blocks"])
dlv = jax.tree.leaves(dims)
reps = blk[0].shape[0]
itemsize = jnp.dtype(cfg.dtype).itemsize
sharded = sum(int(np.prod(l.shape[1:])) for l, k in zip(blk, dlv) if k >= 0)
total = sum(int(np.prod(l.shape[1:])) for l in blk)
d_size = 4
gather_bytes = sharded * itemsize / d_size            # per-rank shard/layer
tokens_per_dev = BATCH * SEQ // 8
layer_flops = 2.0 * total * tokens_per_dev
oc = cost_model.overlap_model(d_size, d_size, gather_bytes, layer_flops,
                              cost_model.MACHINES["tpu_v5e"])
n_layers_scanned = reps
sim = {}
for name, exposed in (("eager", oc.exposed_eager),
                      ("prefetched", oc.exposed_prefetch)):
    comm = exposed * n_layers_scanned
    comp = oc.t_compute * n_layers_scanned
    sim[name] = {
        "exposed_comm_s": comm,
        "exposed_comm_fraction": comm / (comm + comp),
        "modeled_step_s": comm + comp,
    }
out["simulated"] = {
    "machine": "tpu_v5e", "per_layer_gather_bytes": gather_bytes,
    "per_layer_flops": layer_flops, "layers": n_layers_scanned,
    "hidden_s_per_layer": oc.hidden,
    **{k: v for k, v in sim.items()},
}

# same layer geometry at a production token batch (4k tokens/device): the
# smoke shapes are latency-toys, so also report the window the pipeline is
# built for — where the matmuls are big enough to hide most of the gather
prod_flops = 2.0 * total * 4096
ocp = cost_model.overlap_model(d_size, d_size, gather_bytes, prod_flops,
                               cost_model.MACHINES["tpu_v5e"])
out["simulated_production_batch"] = {
    "tokens_per_device": 4096,
    "eager": {"exposed_comm_s": ocp.exposed_eager * n_layers_scanned},
    "prefetched": {"exposed_comm_s": ocp.exposed_prefetch * n_layers_scanned},
    "hidden_fraction": (ocp.hidden / ocp.exposed_eager
                        if ocp.exposed_eager else 0.0),
}
print("JSON" + json.dumps(out))
"""


def main() -> list[tuple]:
    results = {}
    for name, arch, n_layers in SIZES:
        code = CODE_TMPL % (arch, n_layers, BATCH, SEQ, STEPS)
        stdout = run_multidevice(code, devices=8, timeout=1800)
        line = [l for l in stdout.splitlines() if l.startswith("JSON")][0]
        results[name] = json.loads(line[4:])
    write_bench_json(OUT, results)

    rows = []
    for name, r in results.items():
        sim = r["simulated"]
        for mode in ("eager", "prefetched"):
            rows.append((
                f"overlap/{name}/{mode}", r[mode]["us_per_step"],
                f"tokens_per_s={r[mode]['tokens_per_s']:.0f} "
                f"exposed_comm_fraction={sim[mode]['exposed_comm_fraction']:.4f}"))
        e, p = (sim["eager"]["exposed_comm_s"],
                sim["prefetched"]["exposed_comm_s"])
        rows.append((f"overlap/{name}/exposed_reduction", None,
                     f"eager_s={e:.3e} prefetched_s={p:.3e} "
                     f"hidden_fraction={(e - p) / e if e else 0.0:.4f}"))
        prod = r["simulated_production_batch"]
        rows.append((f"overlap/{name}/exposed_reduction_prod_batch", None,
                     f"eager_s={prod['eager']['exposed_comm_s']:.3e} "
                     f"prefetched_s={prod['prefetched']['exposed_comm_s']:.3e} "
                     f"hidden_fraction={prod['hidden_fraction']:.4f}"))
        assert (prod["prefetched"]["exposed_comm_s"]
                < prod["eager"]["exposed_comm_s"]), name
        # the acceptance gate: the prefetched pipeline must expose strictly
        # less modeled communication time than the eager baseline
        assert p < e, (name, e, p)
        assert r["eager"]["loss"] == r["prefetched"]["loss"], name
        # wall clock: REPORTED everywhere, GATED only on accelerator
        # backends — a host-CPU harness has no network to hide, so the
        # pipeline's dispatch overhead legitimately makes prefetched
        # slower there (the recorded gemma9b_4L 463.7ms vs 377.5ms); on
        # CPU the policy fix is the gate instead: "auto" must resolve to
        # eager (depth 0, source "dispatch" when the guard fired)
        wall_e, wall_p = r["eager"]["us_per_step"], r["prefetched"]["us_per_step"]
        auto = r["auto"]
        rows.append((f"overlap/{name}/wall_clock_gate", None,
                     f"prefetched_faster={wall_p < wall_e} "
                     f"auto_depth={auto['depth']} "
                     f"auto_source={auto['source']} "
                     f"backend={auto['backend']}"))
        if auto["backend"] == "cpu":
            assert auto["depth"] == 0, (name, auto)
        else:
            assert wall_p < wall_e, (name, wall_e, wall_p)
    return emit(rows)


if __name__ == "__main__":
    main()
