"""Fused decode partial-stat accumulation (Pallas TPU kernel).

The serve engine's locality decode region splits one-token attention into
(1) masked scores + running max — cheap, feeds the max-allreduce that is
issued immediately — and (2) this kernel: exp(s − m), the row sums l, and
the P·V contraction o, blocked over the local cache length with scratch
accumulators (the ``kernels/flash_attention`` schedule minus the online
max, which the combine already owns). Fusing (2) keeps it one VMEM-resident
op — the "real compute" the in-flight max-allreduce hides behind
(DESIGN.md §5).

Grid: (B·KV, num_kv_blocks), KV axis innermost and sequential
("arbitrary"): acc/lsum scratch persist across the KV steps of one row
group and the outputs are written on the last step.

Masking needs no position logic for the cache itself: the scores arrive
already NEG_INF-masked (models/attention.decode_stats_scores), so
``s ≤ NEG_INF/2`` identifies masked slots — exact for every pattern
including the fully-masked shard (m = NEG_INF would make exp(s − m) = 1
there). Only a cache length that is not a multiple of the block needs one
positional mask: the grid takes ceil(L / block_k) steps and the last block
reads past L, where both the scores and the values are undefined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _stats_kernel(s_ref, m_ref, v_ref, o_ref, l_ref, acc_scr, lsum_scr, *,
                  block_k: int, length: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        lsum_scr[...] = jnp.zeros_like(lsum_scr)

    s = s_ref[0]                                   # (G, block_k) fp32
    m = m_ref[0]                                   # (G, 1) fp32
    v = v_ref[0].astype(jnp.float32)               # (block_k, D)
    live = s > NEG_INF * 0.5
    if length % block_k:                           # ragged last block
        base = ki * block_k
        col = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = base + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        live = live & (col < length)
        v = jnp.where(row < length, v, 0.0)        # 0·NaN would poison o
    p = jnp.where(live, jnp.exp(s - m), 0.0)
    lsum_scr[...] += jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] += jax.lax.dot(p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = acc_scr[...]
        l_ref[0] = lsum_scr[...]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_stats_accumulate_pallas(s: jax.Array, m: jax.Array,
                                   v_cache: jax.Array, *, block_k: int = 512,
                                   interpret: bool = False
                                   ) -> tuple[jax.Array, jax.Array]:
    """s (B,KV,G,L) masked fp32 scores, m (B,KV,G) running max,
    v_cache (B,L,KV,D). Returns fp32 (o (B,1,H,D), l (B,1,H)), H = KV·G.
    fp32 accumulation throughout (the jnp oracle contracts P·V in the cache
    dtype — identical for fp32 caches, tighter for bf16)."""
    B, KV, G, L = s.shape
    D = v_cache.shape[-1]
    # a cache no longer than one block is one full-extent block; a longer
    # one takes block_k-sized blocks (a multiple of 128 on the chip: the
    # score block's lane dim) and masks the ragged tail in-kernel, so VMEM
    # holds one block whatever L is
    bk = L if L <= block_k else block_k
    sf = s.reshape(B * KV, G, L)
    mf = m.reshape(B * KV, G, 1)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, L, D)

    grid = (B * KV, pl.cdiv(L, bk))
    o, l = pl.pallas_call(
        functools.partial(_stats_kernel, block_k=bk, length=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, bk), lambda b, j: (b, 0, j)),
            pl.BlockSpec((1, G, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, G, 1), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * KV, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B * KV, G, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(sf, mf, vf)
    return o.reshape(B, 1, KV * G, D), l.reshape(B, 1, KV * G)
