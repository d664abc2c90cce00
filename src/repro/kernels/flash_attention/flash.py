"""Flash attention (tiled online softmax) Pallas TPU kernel.

Grid: (batch·heads, num_q_blocks, num_kv_blocks) with the KV axis innermost
and sequential ("arbitrary" dimension semantics): scratch accumulators
(m, l, acc) persist across the KV steps of one Q block and the output is
written on the last KV step — the standard TPU flash schedule.

Tiling: q block (block_q, D), k/v blocks (block_k, D) in VMEM. Defaults
512/512 keep every matmul dim a multiple of the 128×128 MXU tile. GQA is
expressed through the KV index map (q head h reads kv head h // G) — the
grouped KV blocks are never materialized per-head in HBM.

Variants: causal, sliding window, chunked-local (llama4), logit softcap
(gemma2) — same mask set as ``models/attention.py`` (the oracle, ref.py).
Fully-masked KV blocks short-circuit via ``pl.when`` (no MXU work), matching
the exact-FLOPs accounting of the q-chunked jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool, window: int,
                  chunk: int, cap: float, scale: float, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability: fully-masked KV blocks do no MXU work
    reachable = jnp.asarray(True)
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 >= q_start - (window - 1))
    if chunk:
        reachable = jnp.logical_and(
            reachable, (q_start // chunk) * chunk <= k_start + block_k - 1)
        reachable = jnp.logical_and(reachable, k_start <= q_start + block_q - 1)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (block_q, D)
        k = k_ref[0].astype(jnp.float32)             # (block_k, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        if chunk:
            mask = jnp.logical_and(mask, q_pos // chunk == k_pos // chunk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "chunk", "cap", "block_q",
                              "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, chunk: int = 0,
                    cap: float = 0.0, block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, T, KV, D), H % KV == 0. Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = D ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, T)
    if S % bq:
        bq = S
    if T % bk:
        bk = T

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)

    grid = (B * H, S // bq, T // bk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=bq, block_k=bk, causal=causal,
                          window=window, chunk=chunk, cap=cap, scale=scale,
                          kv_len=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # GQA: q head (b % H) reads kv head (b % H) // G of batch b // H
            pl.BlockSpec((1, bk, D),
                         lambda b, i, j, G=G, H=H, KV=KV:
                         ((b // H) * KV + (b % H) // G, j, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda b, i, j, G=G, H=H, KV=KV:
                         ((b // H) * KV + (b % H) // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
