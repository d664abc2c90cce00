"""checkpoint: distributed save/restore wall-clock (DESIGN.md §10).

Spawns an 8-device ('pod','data') subprocess with an FSDP-style sharded
pytree (each device holds 1/8 of every matrix leaf), and times the v2
store's three paths:

* ``save_wall_s``      — sharded save (per-chunk npy + sha256 + replicas +
  atomic commit); per-process traffic is the *shard* bytes, never the
  assembled leaves (``max_chunk_bytes`` asserts it);
* ``restore_wall_s``   — same-layout restore (chunk-exact reload);
* ``reshard_wall_s``   — restore onto the flat 8-device layout (every
  device's slice assembled from intersecting chunks).

Byte accounting (``save_bytes``, ``replica_bytes``, ``max_chunk_bytes``)
and the postal-model replication estimate ride along so the trend gate
sees layout drift, not just runner noise. Writes ``BENCH_checkpoint.json``.
"""
from __future__ import annotations

import json
import os

from .common import REPO, emit, run_multidevice, write_bench_json

OUT = os.path.join(REPO, "BENCH_checkpoint.json")

DEVICES = 8

CODE = r"""
from repro.launch.mesh import make_mesh
import json, shutil, tempfile, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import telemetry
from repro.checkpoint import restore_checkpoint, save_checkpoint

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
sh = NamedSharding(mesh, P(("pod", "data")))
rep = NamedSharding(mesh, P())

keys = jax.random.split(jax.random.PRNGKey(0), 8)
tree = {f"w{i}": jax.device_put(
            jax.random.normal(keys[i], (1024, 256), jnp.float32), sh)
        for i in range(6)}
tree["scale"] = jax.device_put(jnp.ones((256,), jnp.float32), rep)
tree["step"] = jnp.asarray(0, jnp.int32)

ckdir = tempfile.mkdtemp()
ITERS = 5
t0 = time.perf_counter()
for it in range(ITERS):
    save_checkpoint(ckdir, it, tree, keep_last=2)
save_s = (time.perf_counter() - t0) / ITERS

like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
shardings = jax.tree.map(lambda x: x.sharding, tree)
t0 = time.perf_counter()
for _ in range(ITERS):
    step, out = restore_checkpoint(ckdir, like, shardings=shardings)
restore_s = (time.perf_counter() - t0) / ITERS
assert step == ITERS - 1, step

flat = make_mesh((1, 8), ("pod", "data"))
fsh = jax.tree.map(
    lambda x: NamedSharding(flat, P(("pod", "data")) if x.ndim == 2
                            else P()), tree)
t0 = time.perf_counter()
for _ in range(ITERS):
    step, out2 = restore_checkpoint(ckdir, like, shardings=fsh)
reshard_s = (time.perf_counter() - t0) / ITERS
for k in tree:
    assert np.array_equal(np.asarray(out[k]), np.asarray(out2[k])), k

g = telemetry.get_registry().snapshot()["gauges"]
full_leaf = 1024 * 256 * 4
assert g["checkpoint/max_chunk_bytes"] == full_leaf // 8, g
shutil.rmtree(ckdir)
print("RESULT " + json.dumps({
    "save_wall_s": save_s, "restore_wall_s": restore_s,
    "reshard_wall_s": reshard_s,
    "save_bytes": g["checkpoint/save_bytes"],
    "replica_bytes": g["checkpoint/replica_bytes"],
    "max_chunk_bytes": g["checkpoint/max_chunk_bytes"],
    "replication": g["checkpoint/replication"],
    "replication_model_s": g.get("checkpoint/replication_model_s", 0.0),
}))
"""


def main() -> None:
    out = run_multidevice(CODE, DEVICES)
    line = [l for l in out.splitlines() if l.startswith("RESULT ")][-1]
    r = json.loads(line[len("RESULT "):])
    emit([("ckpt_save", r["save_wall_s"] * 1e6, "sharded save, 8 dev"),
          ("ckpt_restore", r["restore_wall_s"] * 1e6, "same-layout restore"),
          ("ckpt_reshard", r["reshard_wall_s"] * 1e6,
           "(2,4)->flat(8) reshard restore")])
    write_bench_json(OUT, {"checkpoint": r})


if __name__ == "__main__":
    import sys
    if __package__ in (None, ""):
        _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, _REPO)
        sys.path.insert(1, os.path.join(_REPO, "src"))
        __package__ = "benchmarks"
    main()
