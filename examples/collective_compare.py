"""The paper, hands-on: run all five allgather algorithms on a (regions ×
local) host mesh, check bit-exactness against XLA, measure wall time, count
the schedule's non-local traffic, and show the postal-model projection for a
real TPU pod boundary.

    PYTHONPATH=src python examples/collective_compare.py --regions 2 --local 4
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--regions", type=int, default=2)
    ap.add_argument("--local", type=int, default=4)
    ap.add_argument("--kib", type=float, default=4.0, help="payload per rank")
    args = ap.parse_args()

    p = args.regions * args.local
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={p}")
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C
    from repro.core import schedules as S
    from repro.core.autotune import model_costs
    from repro.core.topology import RegionMap
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((args.regions, args.local), ("r", "l"))
    jax.set_mesh(mesh)
    n = int(args.kib * 1024 / 4)
    x = jnp.arange(p * n, dtype=jnp.float32).reshape(p, n)

    def run(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(("r", "l")),
                                     out_specs=P(("r", "l"))))

    truth_fn = run(lambda s: jax.lax.all_gather(s, ("r", "l"), tiled=True))
    truth = truth_fn(x)
    region = RegionMap(p, args.local)

    print(f"allgather of {args.kib:.0f} KiB/rank over {p} ranks "
          f"({args.regions} regions x {args.local}):\n")
    print(f"{'algorithm':16s} {'wall us':>9s} {'nl msgs':>8s} {'nl blocks':>10s}")
    for alg in ["xla", "bruck", "ring", "hierarchical", "multilane",
                "locality_bruck"]:
        f = run(lambda s, a=alg: C.allgather(s, "r", "l", algorithm=a,
                                             tiled=True))
        out = f(x)
        assert np.allclose(np.asarray(out), np.asarray(truth)), alg
        t0 = time.perf_counter()
        for _ in range(20):
            out = f(x)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / 20 * 1e6
        if alg == "xla":
            nl = blocks = "-"
        else:
            sched = (S.ALGORITHMS[alg](p, args.local)
                     if alg in ("hierarchical", "multilane", "locality_bruck")
                     else S.ALGORITHMS[alg](p, args.local))
            nl = sched.max_nonlocal_msgs(region)
            blocks = sched.max_nonlocal_blocks(region)
        print(f"{alg:16s} {us:9.1f} {nl!s:>8s} {blocks!s:>10s}")

    print("\npostal-model projection, 1024 regions x 16 (pod boundary = DCN):")
    for name, cost in sorted(
            model_costs(1024 * 16, 16, args.kib * 1024, "tpu_v5e").items(),
            key=lambda kv: kv[1]):
        print(f"  {name:16s} {cost*1e6:9.1f} us")


if __name__ == "__main__":
    main()
