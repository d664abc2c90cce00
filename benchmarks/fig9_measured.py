"""Paper Figs. 9-10 analogue: measured allgather comparison.

The paper measures wall-time on Quartz/Lassen; the TPU-adapted equivalent
here has two parts:

  1. MEASURED: wall-clock of the five allgather algorithms (shard_map +
     ppermute) on a 16-device host mesh (4 regions × 4) — the CPU backend's
     inter-process costs are uniform, so this checks overhead/correctness
     rather than locality gains.
  2. STRUCTURAL (the TPU-relevant reproduction): compiled-HLO non-local
     edge/byte counts on the production mesh — see collective_hlo_audit.
"""
from __future__ import annotations

from .common import emit, run_multidevice

CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp, time
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C

mesh = make_mesh((4, 4), ("r", "l"))
x = jnp.ones((16, 1024), jnp.float32)   # 4 KiB per rank
def make(alg):
    def body(s):
        return C.allgather(s, "r", "l", algorithm=alg, tiled=True)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(("r","l")),
                                  out_specs=P(("r","l"))))
for alg in ["xla", "bruck", "ring", "hierarchical", "multilane",
            "locality_bruck"]:
    f = make(alg)
    out = f(x); out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(50):
        out = f(x)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / 50 * 1e6
    print(f"RESULT {alg} {us:.1f}")
"""


def main() -> list[tuple]:
    out = run_multidevice(CODE, devices=16)
    rows = []
    for line in out.splitlines():
        if line.startswith("RESULT"):
            _, alg, us = line.split()
            rows.append((f"fig9/measured_allgather_{alg}_16dev_4KiB",
                         float(us), "host-CPU wall time"))
    assert len(rows) == 6
    return emit(rows)


if __name__ == "__main__":
    main()
