"""Grouped-query attention with full / sliding-window / chunked-local variants.

Design notes
------------
* Long sequences never materialize an S×S score tensor: the query axis is
  processed in static chunks (python loop → static HLO slices), and each
  query chunk attends only to the *statically known* valid KV range:
    - causal full:   kv[0 : (i+1)·c]
    - sliding window kv[(i+1)·c - c - w : (i+1)·c]
    - chunked local  kv[floor(i·c / chunk)·chunk : (i+1)·c]
  This keeps compiled FLOPs at the exact triangular count (no masked waste),
  which matters because cost_analysis() of the dry-run is our roofline input.
* Decode attends a single query against the KV cache with position masks.
* GQA: query heads are grouped over KV heads; softmax in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rmsnorm, softcap

NEG_INF = -2.0 ** 30  # large-negative for bf16-safe masking (cast later)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(rng, cfg) -> dict:
    D = cfg.head_dim_
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * D, cfg.param_dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * D, cfg.param_dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * D, cfg.param_dtype),
        "wo": dense_init(ks[3], cfg.n_heads * D, cfg.d_model, cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((D,), cfg.param_dtype)}
        p["k_norm"] = {"scale": jnp.zeros((D,), cfg.param_dtype)}
    return p


def attn_param_count(cfg) -> int:
    D = cfg.head_dim_
    n = 2 * cfg.d_model * cfg.n_heads * D + 2 * cfg.d_model * cfg.n_kv_heads * D
    if cfg.qk_norm:
        n += 2 * D
    return n


# ---------------------------------------------------------------------------
# core scores for one query chunk against a KV slice
# ---------------------------------------------------------------------------

def _chunk_attend(q, k, v, q_pos, k_pos, *, causal, window, chunk, cap):
    """q: (B,Cq,H,D) k/v: (B,L,KV,D); positions are (Cq,)/(L,) int arrays.

    Returns (B,Cq,H,D). Masks: causal (q>=k), window (q-k < w), chunked-local
    (same chunk). fp32 softmax.
    """
    B, Cq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Cq, KV, G, D)
    s = jnp.einsum("bikgd,bjkd->bkgij", qg, k).astype(jnp.float32)
    s = s * (D ** -0.5)
    if cap:
        s = cap * jnp.tanh(s / cap)
    mask = jnp.ones((Cq, k.shape[1]), bool)
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    if causal:
        mask &= dq >= dk
    if window:
        mask &= (dq - dk) < window
    if chunk:
        mask &= (dq // chunk) == (dk // chunk)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgij,bjkd->bikgd", p.astype(v.dtype), v)
    return o.reshape(B, Cq, H, D)


def multihead_attention(q, k, v, *, causal=True, window=0, chunk=0, cap=0.0,
                        q_chunk=512, q_offset=0):
    """Full-sequence attention, q-chunked with static valid-KV slices.

    q: (B,S,H,D), k/v: (B,T,KV,D). ``q_offset`` is the absolute position of
    q[0] relative to k[0] (0 for self-attention over the same sequence).
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    c = min(q_chunk, S)
    if S % c != 0:
        c = S  # fall back to a single chunk for odd lengths (smoke tests)
    outs = []
    for i in range(S // c):
        q_i = q[:, i * c:(i + 1) * c]
        q_pos = q_offset + i * c + jnp.arange(c)
        hi = min(T, q_offset + (i + 1) * c) if causal else T
        lo = 0
        if window:
            lo = max(0, q_offset + i * c - (window - 1))
        elif chunk:
            lo = ((q_offset + i * c) // chunk) * chunk
        # align to nice boundaries for static-shape reuse
        k_i = k[:, lo:hi]
        v_i = v[:, lo:hi]
        k_pos = lo + jnp.arange(hi - lo)
        outs.append(_chunk_attend(q_i, k_i, v_i, q_pos, k_pos, causal=causal,
                                  window=window, chunk=chunk, cap=cap))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def _mask_bcast(mask):
    """Broadcast a slot mask over (B,KV,G,Lloc) scores.

    Lockstep decode carries a scalar ``pos`` and an (Lloc,) mask; continuous
    batching carries a per-row (B,) ``pos`` and a (B,Lloc) mask.
    """
    return mask[None, None, None] if mask.ndim == 1 else mask[:, None, None, :]


def decode_stats_scores(q, k_cache, pos, *, slot_offset=0, total_len=None,
                        window=0, chunk=0, cap=0.0, ring=False):
    """The cheap prefix of one-token decode attention over a cache slice:
    masked fp32 scores.

    q (B,1,H,D) vs k (B,Lloc,KV,D) holding global slots
    [slot_offset, slot_offset + Lloc) of a ``total_len``-slot cache.
    Returns ``(s, mask)`` with s (B,KV,G,Lloc) already NEG_INF-masked and
    mask (Lloc,) boolean — (B,Lloc) when ``pos`` is a per-row (B,) vector.
    Split out so the serve engine can issue the
    max-allreduce of the running maxima right here — everything after
    (exp / sum / the P·V matmul, :func:`decode_stats_accumulate` or the
    Pallas kernel in ``kernels/decode_stats``) is independent compute the
    collective hides behind (DESIGN.md §5).
    """
    B, _, H, D = q.shape
    L_loc = k_cache.shape[1]
    L_tot = total_len or L_loc
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bjkd->bkgj", qg, k_cache).astype(jnp.float32)
    s = s * (D ** -0.5)
    if cap:
        s = cap * jnp.tanh(s / cap)
    p_ = pos[:, None] if jnp.ndim(pos) == 1 else pos  # (B,1) rows broadcast
    j = slot_offset + jnp.arange(L_loc)
    t_j = (p_ - ((p_ - j) % L_tot)) if ring else j    # token held by slot j
    mask = t_j >= 0 if ring else (j <= p_)
    if window:
        mask &= (p_ - t_j) < window
    if chunk:
        mask &= (t_j // chunk) == (p_ // chunk)
    s = jnp.where(_mask_bcast(mask), s, NEG_INF)
    return s, mask


def decode_stats_accumulate(s, mask, m, v_cache):
    """The heavy suffix: exp(s − m), row sums, and the P·V contraction.

    s/mask from :func:`decode_stats_scores`, m (B,KV,G) the slice's running
    max. Returns fp32 ``(o, l)`` reshaped to (B,1,H,D) / (B,1,H).
    """
    B, KV, G, _ = s.shape
    H = KV * G
    D = v_cache.shape[-1]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(_mask_bcast(mask), p, 0.0)          # m=NEG_INF ⇒ exp(0)=1
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgj,bjkd->bkgd", p.astype(v_cache.dtype),
                   v_cache).astype(jnp.float32)
    return o.reshape(B, 1, H, D), l.reshape(B, 1, H)


def decode_partial_stats(q, k_cache, v_cache, pos, *, slot_offset=0,
                         total_len=None, window=0, chunk=0, cap=0.0,
                         ring=False):
    """Flash-style partial stats of one-token decode attention over a cache
    *slice*: q (B,1,H,D) vs k/v (B,Lloc,KV,D) holding global slots
    [slot_offset, slot_offset + Lloc) of a ``total_len``-slot cache.

    Returns fp32 ``(o, m, l)`` with o (B,1,H,D) the UNNORMALIZED accumulator
    Σ_j exp(s_j − m)·v_j, m (B,1,H) the running max over this slice, and
    l (B,1,H) = Σ_j exp(s_j − m). A fully-masked slice yields (0, NEG_INF, 0)
    — the combine's global rescale exp(m − M) zeroes its contribution. This
    is the per-shard body the serve engine wraps in ``shard_map`` for the
    sequence-parallel locality cache-combine; the single-device decode path
    below finalizes the same stats, so the two paths cannot drift.

    Composed of :func:`decode_stats_scores` + :func:`decode_stats_accumulate`
    — the exact op sequence the engine's overlapped region traces, so the
    split path is bitwise-identical to this one.
    """
    B, _, H, _ = q.shape
    s, mask = decode_stats_scores(q, k_cache, pos, slot_offset=slot_offset,
                                  total_len=total_len, window=window,
                                  chunk=chunk, cap=cap, ring=ring)
    m = jnp.max(s, axis=-1)                           # (B,KV,G)
    o, l = decode_stats_accumulate(s, mask, m, v_cache)
    return o, m.reshape(B, 1, H), l


def decode_attention(q, k_cache, v_cache, pos, *, window=0, chunk=0, cap=0.0,
                     ring=False):
    """One-token decode: q (B,1,H,D) vs cache (B,L,KV,D); ``pos`` = absolute
    index of the query token (its own KV already written).

    ring=True: the cache is a ring buffer of L slots (L = window or chunk
    size), slot j holding token t_j = pos − ((pos − j) mod L). Windowed and
    chunked-local layers never need more history than that — a long_500k
    windowed cache shrinks from 524288 to 4096 slots (§Perf iteration 7).
    """
    o, _, l = decode_partial_stats(q, k_cache, v_cache, pos, window=window,
                                   chunk=chunk, cap=cap, ring=ring)
    # slot ``pos`` is always attendable, so l > 0 on the full cache
    return (o / l[..., None]).astype(v_cache.dtype)


def write_token(cache, new, pos, layer=None, *, ring=False):
    """Write one decode token's keys or values ``new`` (B,1,KV,D) into
    ``cache`` at slot ``pos`` (``pos % L`` for a ring) and return it.

    ``cache`` is one layer's (B,L,KV,D), or with ``layer`` given the layer
    scan's stacked (reps,B,L,KV,D) leaf, written at entry ``layer`` only.
    ``pos`` is a scalar (lockstep) or per-row (B,) (continuous batching).
    """
    L = cache.shape[-3]
    slot = pos % L if ring else pos
    new = new.astype(cache.dtype)
    lead = () if layer is None else (layer,)
    if jnp.ndim(pos) == 0:
        if layer is not None:
            new = new[None]
        return jax.lax.dynamic_update_slice(cache, new, lead + (0, slot, 0, 0))
    # row b's token at slot[b]: the batch axis is a batching dim of the
    # scatter, so a batch-sharded cache is written where it lives and the
    # stacked leaf keeps its own layout
    b = len(lead)
    idx = jnp.stack([jnp.broadcast_to(i, slot.shape) for i in lead]
                    + [jnp.clip(slot, 0, L - 1)], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(*range(b), b + 1),
        scatter_dims_to_operand_dims=(*range(b), b + 1),
        operand_batching_dims=(b,), scatter_indices_batching_dims=(0,))
    return jax.lax.scatter(cache, idx, new[:, 0], dnums,
                           indices_are_sorted=True, unique_indices=True,
                           mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


# ---------------------------------------------------------------------------
# public layer apply
# ---------------------------------------------------------------------------

def attention(params, x, cfg, spec, *, positions=None, cache=None,
              cross_kv=None, causal=True, shard=None, decode_combine=None):
    """Self- (or cross-) attention layer.

    Modes:
      cache None, cross_kv None : full-sequence self-attention; returns
                                  (out, (k, v)) so prefill can build a cache.
      cache (k,v,pos[,layer])   : single-token decode; returns (out, new_cache).
      cross_kv (k,v)            : cross-attention (whisper decoder); no mask.

    decode_combine: optional serve-layer hook replacing the decode cache
    write + attention with a distributed implementation (the locality-aware
    sequence-parallel combine). Called as
    ``decode_combine(q, k_new, v_new, k_cache, v_cache, pos, meta)`` with
    meta = {window, chunk, cap, ring}; returns ``(o, k_cache', v_cache')``
    or None to fall back to the plain (GSPMD) path for this layer.
    """
    shard = shard or (lambda t, _k: t)
    dt = cfg.dtype
    B, S, _ = x.shape
    D = cfg.head_dim_
    H, KV = cfg.n_heads, cfg.n_kv_heads
    window = cfg.window if spec.attn == "window" else 0
    chunk = cfg.chunk if spec.attn == "chunked" else 0

    q = (x @ params["wq"].astype(dt)).reshape(B, S, H, D)
    q = shard(q, "act_heads")
    if cross_kv is None:
        k = (x @ params["wk"].astype(dt)).reshape(B, S, KV, D)
        v = (x @ params["wv"].astype(dt)).reshape(B, S, KV, D)
    else:
        k, v = cross_kv

    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        if cross_kv is None:
            k = rmsnorm(params["k_norm"], k, cfg.norm_eps)

    if positions is None:
        positions = jnp.arange(S)[None]
    if spec.rope and cross_kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is not None:
        # decode: write this token's KV (ring slot pos % L for ring caches)
        # then attend to the cache. With cache["layer"] set, k/v are the
        # layer scan's stacked (reps,B,L,KV,D) leaves and this layer reads
        # and writes entry ``layer`` of them in place.
        k_cache, v_cache, pos = cache["k"], cache["v"], cache["pos"]
        layer = cache.get("layer")
        ring = bool(cache.get("ring", False))
        read = ((lambda c: c) if layer is None else
                (lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, False)))
        res = None
        if decode_combine is not None:
            res = decode_combine(q, k, v, read(k_cache), read(v_cache), pos,
                                 {"window": window, "chunk": chunk,
                                  "cap": cfg.attn_softcap, "ring": ring})
        if res is None:
            k_cache = write_token(k_cache, k, pos, layer, ring=ring)
            v_cache = write_token(v_cache, v, pos, layer, ring=ring)
            o = decode_attention(q, read(k_cache), read(v_cache), pos,
                                 window=window, chunk=chunk,
                                 cap=cfg.attn_softcap, ring=ring)
        else:
            # the hook returns the whole updated layer slice
            o, k_layer, v_layer = res
            if layer is None:
                k_cache, v_cache = k_layer, v_layer
            else:
                k_cache = jax.lax.dynamic_update_index_in_dim(
                    k_cache, k_layer, layer, 0)
                v_cache = jax.lax.dynamic_update_index_in_dim(
                    v_cache, v_layer, layer, 0)
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos}
        out = o.reshape(B, S, H * D) @ params["wo"].astype(dt)
        return out, new_cache

    if cross_kv is not None:
        o = multihead_attention(q, k, v, causal=False, cap=cfg.attn_softcap)
        out = o.reshape(B, S, H * D) @ params["wo"].astype(dt)
        return out, None

    o = multihead_attention(q, k, v, causal=causal, window=window, chunk=chunk,
                            cap=cfg.attn_softcap)
    o = shard(o, "act_heads")
    out = o.reshape(B, S, H * D) @ params["wo"].astype(dt)
    return out, (k, v)
