"""TPU-native allgather: the paper's schedule as Pallas remote DMAs.

Each device runs one kernel instance (inside ``shard_map``); round r issues a
single ``pltpu.make_async_remote_copy`` moving the scheduled contiguous slice
of its HBM-resident output buffer directly into the destination device's
buffer (RDMA put), synchronized with DMA semaphores. Because the whole
exchange is one kernel, a fused consumer can overlap the non-local rounds
with compute — the capability XLA's monolithic all-gather op lacks.

Locality-awareness is inherited from the compiled schedule
(kernels/dma_allgather/schedule_compile.py): with ``locality_bruck`` the
kernel performs exactly Algorithm 2's rounds — local Bruck, one remote
exchange per lane, local redistribution.

Validated with the Pallas TPU *interpret* backend (cross-device DMAs
emulated on CPU) against ``lax.all_gather``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import schedules as S
from .schedule_compile import DmaSchedule, compile_schedule


def _ag_kernel(sched_ref, x_ref, o_ref, send_sem, recv_sem, *,
               n: int, sizes: tuple[int, ...], axes: tuple[str, ...],
               axis_sizes: tuple[int, ...]):
    o_ref[pl.ds(0, n)] = x_ref[...]

    def unflatten(rank):
        """flat gather-rank -> per-axis mesh coordinates (row-major)."""
        coords = []
        rem = rank
        for sz in reversed(axis_sizes):
            coords.append(rem % sz)
            rem = rem // sz
        return tuple(reversed(coords))

    for r, size in enumerate(sizes):
        tgt = sched_ref[r, 0]
        soff = sched_ref[r, 1] * n
        roff = sched_ref[r, 2] * n
        sflag = sched_ref[r, 3]
        rflag = sched_ref[r, 4]
        device_id = dict(zip(axes, unflatten(tgt)))
        # per-round semaphores: a shared counting semaphore would let an
        # early round-(r+1) arrival satisfy the round-r wait, and a device
        # could forward a slice whose round-r data has not landed yet (a
        # real race caught by the TPU interpret backend).
        copy = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[pl.ds(soff, size * n)],
            dst_ref=o_ref.at[pl.ds(roff, size * n)],
            send_sem=send_sem.at[r], recv_sem=recv_sem.at[r],
            device_id=device_id,
            device_id_type=pltpu.DeviceIdType.MESH)

        @pl.when(sflag == 1)
        def _start():
            copy.start()

        @pl.when(sflag == 1)
        def _wait_send():
            copy.wait_send()

        @pl.when(rflag == 1)
        def _wait_recv():
            copy.wait_recv()


def dma_allgather(x: jax.Array, axes, dma_sched: DmaSchedule, perm: jax.Array,
                  *, axis_sizes: tuple[int, ...], interpret=None) -> jax.Array:
    """Per-device body (call inside shard_map over ``axes``).

    x: this device's shard, any shape — flattened to (n,).
    perm: (p, p) canonicalization table (global, replicated).
    Returns (p, *x.shape): all shards in canonical order.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    p = dma_sched.p
    cap = dma_sched.capacity
    n = x.size
    xf = x.reshape(-1)

    # my row of the schedule table / perm
    idx = lax.axis_index(axes)
    table = jnp.asarray(dma_sched.table)             # (p, R, 5)
    my_sched = lax.dynamic_index_in_dim(table, idx, 0, keepdims=False)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(
        _ag_kernel, n=n, sizes=dma_sched.sizes, axes=axes,
        axis_sizes=axis_sizes)
    out = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (cap * n,), x.dtype,
            vma=frozenset(axes) | jax.typeof(xf).vma),
        scratch_shapes=[pltpu.SemaphoreType.DMA((max(len(dma_sched.sizes), 1),)),
                        pltpu.SemaphoreType.DMA((max(len(dma_sched.sizes), 1),))],
        compiler_params=pltpu.CompilerParams(
            collective_id=7,  # same logical collective across devices
        ),
        interpret=(pltpu.InterpretParams() if interpret else False),
    )(my_sched, xf)

    buf = out.reshape(cap, *x.shape)
    my_perm = lax.dynamic_index_in_dim(perm, idx, 0, keepdims=False)
    return jnp.take(buf, my_perm, axis=0)


@functools.lru_cache(maxsize=64)
def build_schedule(algorithm: str, p: int, p_local: int | None) -> DmaSchedule:
    if algorithm == "locality_bruck":
        from .schedule_compile import locality_bruck_raw
        return compile_schedule(locality_bruck_raw(p, p_local))
    if algorithm == "hierarchical":
        raise NotImplementedError(
            "hierarchical's master broadcast is not raw-contiguous; use the "
            "XLA/ppermute path (core/collectives.py) for it")
    gen = S.ALGORITHMS[algorithm]
    sched = gen(p, p_local) if p_local else gen(p)
    return compile_schedule(sched)
