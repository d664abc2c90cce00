"""Micro-benchmark harness for collective algorithms.

Two executors behind one ``measure()`` entry point:

* **real** — wall-clock timing of the actual shard_map/ppermute collective
  on the live mesh: jit, warmup, ``block_until_ready``, median of k. This is
  the number that matters on TPU/GPU fleets.
* **simulated** — deterministic stand-in for containers with one CPU device
  (CI, laptops): the *schedule generators* of ``core/schedules.py`` execute
  the algorithm over an abstract network and each synchronous round is
  priced with ``core/cost_model.schedule_cost(mode="round")`` under a named
  machine parameter set. "Measured" is therefore the per-round simulation on
  real schedules while "modeled" stays the paper's closed forms (Eqs. 3-4)
  — the two disagree exactly where Fig. 9 shows the closed forms mispredict
  (final-round over-count, non-power region counts), so the policy layer has
  a genuine crossover signal to learn even on CPU.

The machine fingerprint keys cache entries so a table measured on one
platform is never consulted on another.
"""
from __future__ import annotations

import dataclasses
import statistics
import time

from repro.core import cost_model, schedules
from repro.core.topology import RegionMap, ceil_log

ALLGATHER_ALGORITHMS = tuple(schedules.ALGORITHMS)   # the five paper algs
ALLREDUCE_ALGORITHMS = ("locality", "xla")
LOGSUMEXP_ALGORITHMS = ("locality", "xla")
OVERLAP_ALGORITHMS = ("eager", "prefetch")
MIGRATE_ALGORITHMS = ("locality_bruck", "multilane", "xla")
ALL_TO_ALL_ALGORITHMS = ("locality", "xla")   # == collectives.ALL_TO_ALL_ALGORITHMS

# Serving head dims are 64-128; the running-max phase of the logsumexp
# combine moves payload/(D+1) bytes. Priced at D=64 (the conservative end:
# the largest relative max-phase cost).
LOGSUMEXP_HEAD_DIM = 64

# The overlap term is a function of (topology, bytes, FLOPs) but the table
# schema is 2-D (topology × byte bucket), so arithmetic intensity
# (flops-per-gathered-byte) is folded into the collective NAME at octave
# resolution: "overlap:i<k>" covers intensities in (2^{k-1}, 2^k]. For an
# FSDP transformer layer the intensity is ≈ tokens-per-device-per-step
# (flops ≈ 2·params·tokens, bytes ≈ 2·params), so the sweep default spans
# small-batch (2^7) to large-batch (2^13) regimes.
OVERLAP_INTENSITY_OCTAVES = (7, 10, 13)


def overlap_collective(flops_per_byte: float) -> str:
    """Collective name keying the overlap term's intensity octave."""
    import math
    k = max(0, math.ceil(math.log2(max(flops_per_byte, 1.0))))
    return f"overlap:i{k}"


def overlap_intensity(collective: str) -> float:
    """Representative flops-per-byte of an "overlap:i<k>" collective name."""
    return float(2 ** int(collective.split(":i", 1)[1]))


@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """Identity of the machine a measurement is valid for."""

    platform: str          # jax backend: cpu / tpu / gpu
    device_kind: str
    n_devices: int
    simulated_machine: str = ""   # set when the simulated executor was used

    def key(self) -> str:
        kind = self.device_kind.replace(" ", "_").replace("|", "_")
        base = f"{self.platform}:{kind}:{self.n_devices}"
        return f"sim:{self.simulated_machine}" if self.simulated_machine else base

    @classmethod
    def detect(cls, simulated_machine: str = "") -> "Fingerprint":
        import jax
        devs = jax.devices()
        return cls(platform=jax.default_backend(),
                   device_kind=devs[0].device_kind,
                   n_devices=len(devs),
                   simulated_machine=simulated_machine)


# ---------------------------------------------------------------------------
# simulated executor
# ---------------------------------------------------------------------------
def simulate_allgather(algorithm: str, p: int, p_local: int,
                       nbytes_per_rank: float,
                       machine: cost_model.MachineParams | str) -> float:
    """Round-synchronous schedule simulation (seconds, deterministic)."""
    if isinstance(machine, str):
        machine = cost_model.MACHINES[machine]
    if p <= 1:
        return 0.0
    sched = schedules.ALGORITHMS[algorithm](p, p_local)
    region = RegionMap(p=p, p_local=p_local)
    return cost_model.schedule_cost(sched, machine, nbytes_per_rank,
                                    region=region, mode="round")


def simulate_allreduce(algorithm: str, p: int, p_local: int,
                       nbytes: float,
                       machine: cost_model.MachineParams | str) -> float:
    """Deterministic model of the two allreduce structures we can emit.

    "xla": flat ring reduce-scatter + ring allgather — 2(p-1) neighbor
    messages of nbytes/p, of which 2·r cross a region boundary.
    "locality": core/collectives.allreduce(algorithm="locality") — local ring RS, per
    lane across regions a recursive-halving RS + Bruck AG (power-of-two
    region counts) or the Bruck-transpose RS + Bruck AG of the allgatherv
    adaptation (any other count) — both 2·ceil(log2 r) non-local messages
    moving 2·(r-1)/r of the per-lane shard, so one formula prices both.
    """
    if isinstance(machine, str):
        machine = cost_model.MACHINES[machine]
    if p <= 1:
        return 0.0
    region = RegionMap(p=p, p_local=p_local)
    r, pl = region.n_regions, p_local
    if algorithm == "xla":
        n = 2 * (p - 1)
        per = nbytes / p
        n_nl = 2 * r if r > 1 else 0
        n_l = n - n_nl
        return machine.cost(n_local=n_l, s_local=per * n_l,
                            n_nonlocal=n_nl, s_nonlocal=per * n_nl)
    if algorithm == "locality":
        t = 0.0
        if pl > 1:   # local ring reduce-scatter
            t += machine.cost(n_local=pl - 1,
                              s_local=nbytes * (pl - 1) / pl,
                              n_nonlocal=0, s_nonlocal=0.0)
        shard = nbytes / pl
        if r > 1:    # recursive-halving RS + Bruck AG over regions, per lane
            lg = ceil_log(2, r)
            t += machine.cost(n_local=0, s_local=0.0, n_nonlocal=2 * lg,
                              s_nonlocal=2.0 * shard * (r - 1) / r)
        if pl > 1:   # local Bruck allgather of the reduced shards
            t += machine.cost(n_local=ceil_log(2, pl),
                              s_local=nbytes * (pl - 1) / pl,
                              n_nonlocal=0, s_nonlocal=0.0)
        return t
    raise ValueError(f"unknown allreduce algorithm {algorithm!r}")


def simulate_logsumexp_combine(algorithm: str, p: int, p_local: int,
                               nbytes: float,
                               machine: cost_model.MachineParams | str,
                               head_dim: int = LOGSUMEXP_HEAD_DIM) -> float:
    """Two-phase decode cache-combine: max-allreduce of the running maxima
    (payload nbytes/(head_dim+1)) then the packed o+l sum-allreduce
    (payload nbytes). "xla" prices GSPMD's implicit combine (flat recursive
    doubling for the max, flat ring for the sum); "locality" the explicit
    ``collectives.logsumexp_combine`` structure. The two-phase
    accounting replaces the single-sum-allreduce pricing the serve layer
    used before it could execute the combine.
    """
    if isinstance(machine, str):
        machine = cost_model.MACHINES[machine]
    if p <= 1:
        return 0.0
    max_bytes = nbytes / (head_dim + 1)
    if algorithm == "xla":
        return (cost_model.max_allreduce_model(p, p_local, max_bytes, machine,
                                               structure="flat")
                + simulate_allreduce("xla", p, p_local, nbytes, machine))
    if algorithm == "locality":
        return (cost_model.max_allreduce_model(p, p_local, max_bytes, machine,
                                               structure="locality")
                + simulate_allreduce("locality", p, p_local, nbytes, machine))
    raise ValueError(f"unknown logsumexp_combine algorithm {algorithm!r}")


def simulate_cache_migrate(algorithm: str, p: int, p_local: int,
                           nbytes: float,
                           machine: cost_model.MachineParams | str) -> float:
    """KV-slab migration (``core/collectives.cache_migrate``): replicate a
    sequence-sharded cache slab over the full mesh so the destination insert
    can mask it into the owning batch row.

    Executes the same schedule generators as the activation allgather, but
    keyed as its own tuning cell: slab payloads (a whole request's KV) sit
    orders of magnitude above decode activations, so the α-dominated
    locality schedule and the β-dominated multilane schedule cross over in
    a different byte regime. "xla" prices GSPMD's flat all-gather at its
    ring decomposition (every hop a potential boundary crossing).
    """
    if algorithm not in MIGRATE_ALGORITHMS:
        raise ValueError(f"unknown cache_migrate algorithm {algorithm!r}")
    sched_alg = "ring" if algorithm == "xla" else algorithm
    return simulate_allgather(sched_alg, p, p_local, nbytes, machine)


def simulate_all_to_all(algorithm: str, p: int, p_local: int,
                        nbytes: float,
                        machine: cost_model.MachineParams | str) -> float:
    """Personalized exchange (``core/collectives.all_to_all`` — the MoE
    dispatch transport). ``nbytes`` is the per-rank buffer; the schedules
    count blocks in (source, destination)-pair units of ``nbytes / p``.
    Round-synchronous pricing over the ``ALL_TO_ALL_SCHEDULES`` oracles:
    "locality" is the two-tier exchange (q-1 aggregated DCN messages per
    region), "xla" the flat p-1-round pairwise rotation GSPMD emits.
    """
    if isinstance(machine, str):
        machine = cost_model.MACHINES[machine]
    if algorithm not in ALL_TO_ALL_ALGORITHMS:
        raise ValueError(f"unknown all_to_all algorithm {algorithm!r}")
    if p <= 1:
        return 0.0
    sched = schedules.ALL_TO_ALL_SCHEDULES[algorithm](p, p_local)
    region = RegionMap(p=p, p_local=p_local)
    return cost_model.schedule_cost(sched, machine, nbytes / p,
                                    region=region, mode="round")


def simulate_overlap(algorithm: str, p: int, p_local: int, nbytes: float,
                     machine: cost_model.MachineParams | str, *,
                     flops: float | None = None,
                     flops_per_byte: float | None = None) -> float:
    """Per-layer step-time under the eager vs prefetched gather schedule.

    ``nbytes`` is the per-rank shard of one layer's parameters. The compute
    window is ``flops`` (exact, when the caller knows the layer) or
    ``flops_per_byte · nbytes`` (the octave representative the sweep grids
    over). Deterministic — there is no wall-clock overlap executor; real
    overlap is measured end-to-end by ``benchmarks/overlap.py``.
    """
    if isinstance(machine, str):
        machine = cost_model.MACHINES[machine]
    if algorithm not in OVERLAP_ALGORITHMS:
        raise ValueError(f"unknown overlap algorithm {algorithm!r}")
    if flops is None:
        flops = (flops_per_byte or 1.0) * nbytes
    oc = cost_model.overlap_model(p, p_local, nbytes, flops, machine)
    return oc.step_time(prefetch=(algorithm == "prefetch"))


def simulate(collective: str, algorithm: str, p: int, p_local: int,
             nbytes: float, machine: cost_model.MachineParams | str) -> float:
    if collective == "allgather":
        return simulate_allgather(algorithm, p, p_local, nbytes, machine)
    if collective == "allreduce":
        return simulate_allreduce(algorithm, p, p_local, nbytes, machine)
    if collective == "logsumexp_combine":
        return simulate_logsumexp_combine(algorithm, p, p_local, nbytes,
                                          machine)
    if collective == "cache_migrate":
        return simulate_cache_migrate(algorithm, p, p_local, nbytes, machine)
    if collective == "all_to_all":
        return simulate_all_to_all(algorithm, p, p_local, nbytes, machine)
    if collective.startswith("overlap:i"):
        return simulate_overlap(algorithm, p, p_local, nbytes, machine,
                                flops_per_byte=overlap_intensity(collective))
    raise ValueError(f"unknown collective {collective!r}")


# ---------------------------------------------------------------------------
# measured dispatch overhead (the overlap policy's reality check)
# ---------------------------------------------------------------------------
_DISPATCH_OVERHEAD: float | None = None


def dispatch_overhead_s(*, iters: int = 20, refresh: bool = False) -> float:
    """Measured (not modeled) per-dispatch overhead of the live backend.

    Times a cached trivial jitted computation end to end (dispatch + sync)
    and returns the median — the floor cost every extra issued collective /
    unrolled pipeline stage pays on this host. ``Policy.select_overlap``
    compares this MEASURED quantity against the MODELED hidden
    communication of the prefetch schedule: on a host-CPU harness there is
    no real wire, the modeled hidden time is fiction, and the dispatch
    overhead is what the double-buffered pipeline actually adds per layer
    (the BENCH_overlap wall-clock regression: prefetched slower than eager
    on CPU). Cached per process.
    """
    global _DISPATCH_OVERHEAD
    if _DISPATCH_OVERHEAD is not None and not refresh:
        return _DISPATCH_OVERHEAD
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    jax.block_until_ready(f(x))                 # compile outside the timing
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        samples.append(time.perf_counter() - t0)
    _DISPATCH_OVERHEAD = statistics.median(samples)
    return _DISPATCH_OVERHEAD


# ---------------------------------------------------------------------------
# real executor
# ---------------------------------------------------------------------------
def _measure_real(collective: str, algorithm: str, p: int, p_local: int,
                  nbytes: float, dtype: str, *, iters: int, warmup: int) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < p:
        raise RuntimeError(f"need {p} devices, have {len(devs)}")
    mesh = make_mesh((p // p_local, p_local), ("outer", "local"),
                     devices=devs[:p])
    itemsize = jnp.dtype(dtype).itemsize
    n_elems = max(1, int(nbytes) // itemsize)
    x = jnp.zeros((p * n_elems,), dtype=dtype)

    if collective == "allgather":
        def body(s):
            return C.allgather(s, "outer", "local", algorithm=algorithm,
                               tiled=True)
    elif collective == "cache_migrate":
        def body(s):
            return C.cache_migrate(s, "outer", "local", algorithm=algorithm,
                                   tiled=True)
    elif collective == "allreduce":
        def body(s):
            return C.allreduce(s, "outer", "local", algorithm=algorithm)
    elif collective == "all_to_all":
        # per-rank buffer must split p ways: round the element count up
        n_elems = -(-n_elems // p) * p
        x = jnp.zeros((p * n_elems,), dtype=dtype)

        def body(s):
            return C.all_to_all(s, "outer", "local", algorithm=algorithm)
    elif collective == "logsumexp_combine":
        # payload layout mirrors the decode stats: (n, D) o-accumulator +
        # (n,) running max + (n,) sumexp, n rows per rank
        D = LOGSUMEXP_HEAD_DIM
        n_rows = max(1, int(nbytes) // ((D + 1) * itemsize))
        x = (jnp.zeros((p * n_rows, D), dtype), jnp.zeros((p * n_rows,), dtype),
             jnp.ones((p * n_rows,), dtype))

        def body(o, m, l):
            ot, lt = C.logsumexp_combine(
                o, m, l, "outer", "local", algorithm=algorithm)
            return ot, lt
    else:
        raise ValueError(f"unknown collective {collective!r}")

    if collective == "logsumexp_combine":
        spec = P(("outer", "local"))
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=(spec, spec, spec),
                                  out_specs=(spec, spec), check_vma=False))
        args = x
    else:
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=P(("outer", "local")),
                                  out_specs=P(("outer", "local"))))
        args = (x,)

    def run():
        out = f(*args)
        jax.block_until_ready(out)

    for _ in range(warmup):
        run()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def measure(collective: str, algorithm: str, p: int, p_local: int,
            nbytes: float, dtype: str = "float32", *, mode: str = "auto",
            machine: str = "lassen", iters: int = 5, warmup: int = 2) -> float:
    """Median time (seconds) for one collective configuration.

    mode: "real" (wall clock on the live mesh), "simulated" (deterministic
    schedule pricing under ``machine``), or "auto" — real on accelerator
    backends with enough devices, simulated otherwise (the CPU fallback
    that makes sweeps runnable in single-device containers).

    The overlap term ("overlap:i<k>") is always simulated: its "real"
    number needs a fused compute+gather pipeline, which is exactly what
    ``benchmarks/overlap.py`` measures end-to-end.
    """
    if collective.startswith("overlap:"):
        mode = "simulated"
    if mode == "auto":
        import jax
        real = jax.default_backend() != "cpu" and len(jax.devices()) >= p
        mode = "real" if real else "simulated"
    if mode == "simulated":
        return simulate(collective, algorithm, p, p_local, nbytes, machine)
    if mode == "real":
        return _measure_real(collective, algorithm, p, p_local, nbytes, dtype,
                             iters=iters, warmup=warmup)
    raise ValueError(f"unknown mode {mode!r}")
