"""serve-combine: xla vs locality decode cache-combine, per decode step.

Spawns an 8-device subprocess, builds the serve engine twice over a
sequence-sharded KV cache — once with GSPMD's implicit combine ("xla"),
once with the manual shard_map + ``locality_logsumexp_combine`` path — and
reports wall-clock per decode step plus the compiled collective inventory
of each decode_fn. Writes ``BENCH_serve_combine.json`` so the perf
trajectory of the §Perf serve hook is a tracked artifact, not hand-curated
numbers.
"""
from __future__ import annotations

import json
import os

from .common import REPO, emit, run_multidevice, write_bench_json

OUT = os.path.join(REPO, "BENCH_serve_combine.json")

CODE = r"""
from repro.launch.mesh import make_mesh
import json, time
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro import configs
from repro.models import transformer
from repro.serve.engine import make_serve_fns, resolve_cache_combine
from repro.serve.spec import ServeSpec
from repro.core.hlo_analysis import (allreduce_combiners, collective_stats,
                                     op_payloads)

mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2)
B, CL, STEPS = 1, 128, 32
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
prompts = jnp.asarray(
    np.random.default_rng(0).integers(0, cfg.vocab_size, (B, 8), np.int32))
choice = resolve_cache_combine(cfg, mesh, B, CL)
cache_sds = transformer.cache_specs(cfg, B, CL)
tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)

out = {"payload_bytes": choice.nbytes, "p": choice.p,
       "o_bytes": B * cfg.n_heads * cfg.head_dim_ * 4,
       "auto_resolution": {"algorithm": choice.algorithm,
                           "source": choice.source}}
for alg in ("xla", "locality"):
    art = make_serve_fns(cfg, mesh, ServeSpec(batch=B, cache_len=CL,
                                          combine=alg))
    fn = art.decode_fn
    hlo = fn.lower(art.abstract_params, cache_sds, tok_sds).compile().as_text()
    st = collective_stats(hlo)
    p16 = jax.tree.map(
        lambda p: p.astype(cfg.dtype) if p.dtype == jnp.float32 else p, params)
    p16 = jax.device_put(p16, art.param_shardings)
    logits, cache = art.prefill_fn(p16, {"tokens": prompts})
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    logits, cache = fn(p16, cache, tok)         # compile + warm
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        logits, cache = fn(p16, cache, tok)
    jax.block_until_ready(logits)
    out[alg] = {
        "us_per_step": (time.perf_counter() - t0) / STEPS * 1e6,
        "collectives": {"counts": dict(st.counts), "bytes": dict(st.bytes_)},
        "allreduce_payloads": op_payloads(hlo, "all-reduce"),
        "allreduce_combiners": allreduce_combiners(hlo),
    }
print("JSON" + json.dumps(out))
"""


def main() -> list[tuple]:
    stdout = run_multidevice(CODE, devices=8, timeout=1800)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][0]
    out = json.loads(line[4:])
    write_bench_json(OUT, out)

    rows = []
    for alg in ("xla", "locality"):
        st = out[alg]["collectives"]["counts"]
        rows.append((f"serve_combine/{alg}", out[alg]["us_per_step"],
                     f"collectives={st}"))
    ratio = out["xla"]["us_per_step"] / max(out["locality"]["us_per_step"], 1e-9)
    rows.append(("serve_combine/xla_over_locality", None,
                 f"ratio={ratio:.3f} payload={out['payload_bytes']}B "
                 f"auto={out['auto_resolution']['algorithm']}"))
    # the manual path must not run the stat combine through all-reduce: no
    # max-combiner all-reduce (implicit sharded-softmax signature) and the
    # explicit permute/reduce-scatter schedule must be present instead
    combiners = out["locality"]["allreduce_combiners"]
    bad = [c for c in combiners if c in ("maximum", "minimum")]
    assert not bad, f"locality decode still all-reduces softmax stats: {bad}"
    assert out["locality"]["collectives"]["counts"].get("reduce-scatter", 0), \
        "locality decode lost its explicit combine (no reduce-scatter)"
    return emit(rows)


if __name__ == "__main__":
    main()
