"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid: (batch·heads, num_chunks), chunk axis sequential ("arbitrary"): the
running inter-chunk state h ∈ R^{N×P} lives in VMEM scratch and is carried
across the chunk steps of one (batch, head) program — the HBM-resident
(nc, N, P) state tensor of the jnp path (``models/ssm.ssd_chunked``, the
oracle) never exists.

Per chunk (all fp32, in VMEM):
    da   = dt·A;  cum = cumsum(da);  seg = cum[Q-1]
    y    = ((C Bᵀ) ⊙ tril(exp(cum_i − cum_j))) (dt ⊙ x)      intra-chunk
         + exp(cum) ⊙ (C h)                                    inter-chunk
    h   ←  exp(seg) h + Bᵀ (exp(seg − cum) dt ⊙ x)            state update
Tiling: x (Q,P), B/C (Q,N), score (Q,Q) — Q=256, N≤128, P=64 keeps every
matmul MXU-aligned and the working set ≈ (Q² + 2QN + 2QP + NP)·4B ≈ 0.5 MB.
Multi-group (G>1) maps head → group through the B/C index maps (GQA-style).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
                Q: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)                  # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)                # (Q, 1)
    A = a_ref[0].astype(jnp.float32)                  # (1,) per-head scalar
    Bm = b_ref[0].astype(jnp.float32)                 # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)                 # (Q, N)

    da = dt * A                                       # (Q, 1)
    cum = jnp.cumsum(da, axis=0)                      # (Q, 1)
    seg = cum[Q - 1]                                  # (1,)

    # intra-chunk dual form
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    decay = jnp.exp(cum - cum.T)                      # exp(cum_i - cum_j)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, CB * decay, 0.0)
    dtx = dt * x                                      # (Q, P)
    y = jax.lax.dot(L, dtx, preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    h = h_scr[...]                                    # (N, P)
    y = y + jnp.exp(cum) * jax.lax.dot(
        Cm, h, preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update
    w = jnp.exp(seg - cum) * dt                       # (Q, 1)
    h_new = jnp.exp(seg)[0] * h + jax.lax.dot_general(
        Bm, w * x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (N, P)
    h_scr[...] = h_new

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0] = h_new


@functools.partial(jax.jit, static_argnames=("Q", "interpret"))
def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, *, Q: int = 256, interpret: bool = False):
    """x: (Bt,S,H,P); dt: (Bt,S,H); A: (H,); B/C: (Bt,S,G,N).

    Returns (y (Bt,S,H,P) fp32, h_final (Bt,H,N,P) fp32) — same contract as
    ``models.ssm.ssd_chunked`` (the oracle).
    """
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    q = min(Q, S)
    if S % q:
        q = S
    nc = S // q

    xf = x.transpose(0, 2, 1, 3).reshape(Bt * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(Bt * H, S, 1)
    af = jnp.broadcast_to(A[None, :], (Bt, H)).reshape(Bt * H, 1)
    bf = B.transpose(0, 2, 1, 3).reshape(Bt * G, S, N)
    cf = C.transpose(0, 2, 1, 3).reshape(Bt * G, S, N)
    Hg = H // G

    def bc_map(b, c, G=G, H=H, Hg=Hg):
        return ((b // H) * G + (b % H) // Hg, c, 0)

    y, h_fin = pl.pallas_call(
        functools.partial(_ssd_kernel, Q=q),
        grid=(Bt * H, nc),
        in_specs=[
            pl.BlockSpec((1, q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, q, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
            pl.BlockSpec((1, q, N), bc_map),
            pl.BlockSpec((1, q, N), bc_map),
        ],
        out_specs=[
            pl.BlockSpec((1, q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt * H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((Bt * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, dtf, af, bf, cf)
    y = y.reshape(Bt, H, S, P).transpose(0, 2, 1, 3)
    h_fin = h_fin.reshape(Bt, H, N, P)
    return y, h_fin
