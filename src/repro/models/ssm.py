"""Mamba2 (SSD — state-space duality) mixer: chunked train/prefill scan and
O(1)-state decode. [Dao & Gu 2024, arXiv:2405.21060]

Recurrence (per head h, state size N, head dim P):
    h_t = exp(dt_t·A) · h_{t-1} + dt_t · B_t ⊗ x_t        h ∈ R^{N×P}
    y_t = C_t · h_t + D · x_t
Chunked SSD: within chunks of Q tokens the quadratic (dual) form is used;
across chunks a sequential scan carries the state. The Pallas kernel in
``kernels/ssd`` implements the same tiling for TPU VMEM; this file is the
pure-jnp reference used by the model and as the kernel oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import dense_init, rmsnorm


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_headdim
    H = d_inner // P
    N = cfg.ssm_state
    G = cfg.ssm_ngroups
    return d_inner, H, P, N, G


def mamba_init(rng, cfg) -> dict:
    d_inner, H, P, N, G = _dims(cfg)
    W = cfg.ssm_conv
    conv_ch = d_inner + 2 * G * N
    d_in_proj = 2 * d_inner + 2 * G * N + H
    ks = jax.random.split(rng, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32) *
                 (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "in_proj": dense_init(ks[0], cfg.d_model, d_in_proj, cfg.param_dtype),
        "conv_w": (jax.random.normal(ks[1], (W, conv_ch), jnp.float32) /
                   math.sqrt(W)).astype(cfg.param_dtype),
        "conv_b": jnp.zeros((conv_ch,), cfg.param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.param_dtype),
        "A_log": jnp.log(1.0 + jax.random.uniform(ks[3], (H,)) * 15.0
                         ).astype(cfg.param_dtype),
        "D": jnp.ones((H,), cfg.param_dtype),
        "norm": {"scale": jnp.zeros((d_inner,), cfg.param_dtype)},
        "out_proj": dense_init(ks[4], d_inner, cfg.d_model, cfg.param_dtype),
    }


def mamba_param_count(cfg) -> int:
    d_inner, H, P, N, G = _dims(cfg)
    conv_ch = d_inner + 2 * G * N
    d_in_proj = 2 * d_inner + 2 * G * N + H
    return (cfg.d_model * d_in_proj + cfg.ssm_conv * conv_ch + conv_ch +
            3 * H + d_inner + d_inner * cfg.d_model)


# ---------------------------------------------------------------------------
# chunked SSD core
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, Q: int, h0=None, *, precise: bool = False):
    """x:(Bt,S,H,P) dt:(Bt,S,H) A:(H,) B,C:(Bt,S,G,N). Returns (y, h_final).

    Mixed precision (§Perf): the *scalar path* — softplus'd dt, the cumsum
    of log-decays and their exponentials, shapes ≤ (Bt,S,H) or (H,Q,Q) —
    stays fp32 (exponential stability); every (…,P)/(…,N)-scale tensor and
    both dual-form matmuls run in bf16 with fp32 accumulation. This halves
    the HBM traffic of the jnp lowering that the dry-run measures — the
    Pallas SSD kernel fuses the same math into VMEM tiles on real TPUs.
    The sequential part is a lax.scan over S/Q chunks.
    """
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Hg = H // G
    nc = S // Q
    assert nc * Q == S, f"seq {S} not divisible by chunk {Q}"
    f32 = jnp.float32
    bf16 = f32 if precise else jnp.bfloat16
    xc = x.reshape(Bt, nc, Q, H, P).astype(bf16)
    dtc = dt.reshape(Bt, nc, Q, H).astype(f32)
    Bc = B.reshape(Bt, nc, Q, G, N).astype(bf16)
    Cc = C.reshape(Bt, nc, Q, G, N).astype(bf16)

    da = dtc * A.astype(f32)                         # (Bt,nc,Q,H), negative
    cum = jnp.cumsum(da, axis=2)                     # within-chunk cumulative
    seg_end = cum[:, :, -1]                          # (Bt,nc,H) full-chunk decay

    def to_heads(t):
        """(Bt,nc,Q,G,N) -> (Bt,nc,Q,H,N) by repeating each group Hg times."""
        if G == 1:
            return jnp.broadcast_to(t, (Bt, nc, Q, H, N))
        return jnp.repeat(t, Hg, axis=3)

    # --- intra-chunk (dual quadratic form) --------------------------------
    # bf16-out einsums: TPU MXU accumulates bf16 dots in fp32 internally, so
    # this is the native semantic; crucially it keeps the *cotangents* bf16
    # too — a preferred_element_type=f32 here poisons the entire backward
    # chain (conv, split, in_proj grads) into fp32 (§Perf iteration 5).
    CB = jnp.einsum("bcigν,bcjgν->bcgij", Cc, Bc)    # (Bt,nc,G,Q,Q)
    CBh = (jnp.broadcast_to(CB, (Bt, nc, H, Q, Q)) if G == 1
           else jnp.repeat(CB, Hg, axis=2))
    cum_h = cum.transpose(0, 1, 3, 2)                # (Bt,nc,H,Q)
    # mask before the exponential: above the diagonal the exponent is
    # positive and overflows f32 at real chunk lengths (Q=128), and exp's
    # cotangent there would be inf·0 = NaN in every gradient
    seg = cum_h[..., :, None] - cum_h[..., None, :]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg,
                              -jnp.inf))
    dtx = dtc.astype(bf16)[..., None] * xc           # (Bt,nc,Q,H,P)
    L = CBh * decay.astype(bf16)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", L, dtx)

    # --- chunk states -------------------------------------------------------
    dec_to_end = jnp.exp(seg_end[:, :, None] - cum)  # (Bt,nc,Q,H)
    Bh = to_heads(Bc)
    S_c = jnp.einsum("bcjh,bcjhν,bcjhp->bchνp",
                     (dec_to_end * dtc).astype(bf16), Bh, xc)

    # --- inter-chunk recurrence (fp32 carry: exact state) --------------------
    if h0 is None:
        h0 = jnp.zeros((Bt, H, N, P), f32)

    def step(h, inp):
        dec, s = inp                                  # dec (Bt,H), s (Bt,H,N,P)
        h_out = h                                     # state BEFORE this chunk
        h = jnp.exp(dec)[..., None, None] * h + s.astype(f32)
        return h, h_out

    h_fin, h_prev = jax.lax.scan(
        step, h0, (seg_end.transpose(1, 0, 2), S_c.transpose(1, 0, 2, 3, 4)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)          # (Bt,nc,H,N,P)

    Ch = to_heads(Cc)
    y_inter = jnp.einsum("bcihν,bchνp->bcihp",
                         (jnp.exp(cum).astype(bf16))[..., None] * Ch,
                         h_prev.astype(bf16))

    y = (y_intra.astype(f32) + y_inter.astype(f32)).reshape(Bt, S, H, P)
    return y, h_fin


# ---------------------------------------------------------------------------
# layer apply
# ---------------------------------------------------------------------------

def _causal_conv(u, w, b):
    """u: (B,S,Ch), depthwise causal conv width W.

    (§Perf iteration 4 tried W shifted multiply-adds instead — REFUTED:
    the pads/FMAs materialize ~2.75× the tensor traffic of the single
    grouped-conv op; reverted.)
    """
    W = w.shape[0]
    Ch = u.shape[-1]
    out = jax.lax.conv_general_dilated(
        u, w[:, None, :], window_strides=(1,), padding=[(W - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=Ch)
    return out + b


def _split_proj(cfg, proj):
    d_inner, H, P, N, G = _dims(cfg)
    z, xBC, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * G * N], axis=-1)
    return z, xBC, dt


def mamba_apply(params, x_in, cfg, *, cache=None, shard=None):
    """Mamba2 mixer. Train/prefill: full sequence (returns final state for
    prefill cache). Decode: cache = {"conv": (B,W-1,Ch), "h": (B,H,N,P)}."""
    shard = shard or (lambda t, _k: t)
    d_inner, H, P, N, G = _dims(cfg)
    W = cfg.ssm_conv
    dt_ = x_in.dtype
    Bt, S, _ = x_in.shape

    proj = x_in @ params["in_proj"].astype(dt_)
    z, xBC_raw, dt_raw = _split_proj(cfg, proj)

    if cache is not None and S == 1:
        xBC = xBC_raw
        conv_cache = cache["conv"]
        window = jnp.concatenate([conv_cache, xBC.astype(conv_cache.dtype)], 1)
        w = params["conv_w"].astype(jnp.float32)
        u = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w)
        xBC_c = jax.nn.silu(u + params["conv_b"].astype(jnp.float32))[:, None]
        new_conv = window[:, 1:]
        x, Bs, Cs = jnp.split(
            xBC_c, [d_inner, d_inner + G * N], axis=-1)
        x = x.reshape(Bt, H, P)
        Bs = Bs.reshape(Bt, G, N)
        Cs = Cs.reshape(Bt, G, N)
        dtv = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) +
                              params["dt_bias"].astype(jnp.float32))  # (B,H)
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        h = cache["h"]
        Hg = H // G
        Bh = jnp.repeat(Bs, Hg, axis=1)[:, :H]
        Ch = jnp.repeat(Cs, Hg, axis=1)[:, :H]
        h = (jnp.exp(dtv * A)[..., None, None] * h +
             jnp.einsum("bh,bhν,bhp->bhνp", dtv, Bh, x.astype(jnp.float32)))
        y = jnp.einsum("bhν,bhνp->bhp", Ch, h)
        y = y + params["D"].astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
        y = y.reshape(Bt, 1, d_inner).astype(dt_)
        y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
        out = y @ params["out_proj"].astype(dt_)
        return out, {"conv": new_conv, "h": h}

    xBC = _causal_conv(xBC_raw.astype(dt_), params["conv_w"].astype(dt_),
                       params["conv_b"].astype(dt_))
    xBC = jax.nn.silu(xBC)
    x, Bs, Cs = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    x = x.reshape(Bt, S, H, P)
    x = shard(x, "act_heads")
    Bs = Bs.reshape(Bt, S, G, N)
    Cs = Cs.reshape(Bt, S, G, N)
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                          params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    Q = min(cfg.ssm_chunk, S)
    if S % Q:
        Q = S
    y, h_fin = ssd_chunked(x, dtv, A, Bs, Cs, Q)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(Bt, S, d_inner).astype(dt_)
    y = shard(y, "act_ff")
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ params["out_proj"].astype(dt_)

    if cache is not None:  # prefill: conv window = last W-1 raw inputs
        pad = jnp.zeros((Bt, max(0, W - 1 - S), xBC_raw.shape[-1]), cfg.dtype)
        tail = xBC_raw[:, max(0, S - (W - 1)):].astype(cfg.dtype)
        new_cache = {"conv": jnp.concatenate([pad, tail], 1), "h": h_fin}
        return out, new_cache
    return out, None


def mamba_cache_specs(cfg, batch: int):
    d_inner, H, P, N, G = _dims(cfg)
    conv_ch = d_inner + 2 * G * N
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv - 1, conv_ch), cfg.dtype),
        "h": jax.ShapeDtypeStruct((batch, H, N, P), jnp.float32),
    }
