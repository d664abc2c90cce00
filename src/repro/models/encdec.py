"""Whisper-style encoder-decoder backbone.

The audio conv frontend is a STUB per the assignment: ``input_specs()``
supplies precomputed frame embeddings (B, enc_seq, d_model) — the two
conv1d+GELU layers of real Whisper are out of scope. Sinusoidal absolute
positions are used on both sides (real Whisper: sinusoidal encoder, learned
decoder — recorded in DESIGN.md; sinusoidal generalizes to the assigned
32k decode shapes that exceed Whisper's native 448-token table).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .attention import attention, attn_init, attn_param_count
from .layers import (embed_init, mlp_apply, mlp_init, mlp_param_count,
                     norm_apply, norm_init)
from repro.configs.base import LayerSpec

_noop = lambda t, _k: t


def _sinusoid(positions, d):
    """positions: (...,) -> (..., d) transformer sinusoidal embedding."""
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


_SELF = LayerSpec(mixer="attn", attn="full", mlp="dense", rope=False)


def _enc_layer_init(rng, cfg):
    ks = jax.random.split(rng, 2)
    return {"ln1": norm_init(cfg, cfg.d_model), "attn": attn_init(ks[0], cfg),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act,
                            cfg.param_dtype)}


def _dec_layer_init(rng, cfg):
    ks = jax.random.split(rng, 3)
    return {"ln1": norm_init(cfg, cfg.d_model), "self_attn": attn_init(ks[0], cfg),
            "lnx": norm_init(cfg, cfg.d_model), "cross_attn": attn_init(ks[1], cfg),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_act,
                            cfg.param_dtype)}


def init_params(rng, cfg) -> dict:
    ks = jax.random.split(rng, 4)
    enc_keys = jax.random.split(ks[0], cfg.n_enc_layers)
    dec_keys = jax.random.split(ks[1], cfg.n_layers)
    return {
        "embed": embed_init(ks[2], cfg.padded_vocab, cfg.d_model, cfg.param_dtype),
        "enc_layers": jax.vmap(lambda k: _enc_layer_init(k, cfg))(enc_keys),
        "enc_norm": norm_init(cfg, cfg.d_model),
        "dec_layers": jax.vmap(lambda k: _dec_layer_init(k, cfg))(dec_keys),
        "final_norm": norm_init(cfg, cfg.d_model),
    }


def encode(params, cfg, frames, *, shard=None):
    """frames: (B, T, d) stub embeddings -> (B, T, d) encoder output."""
    shard = shard or _noop
    dt = cfg.dtype
    T = frames.shape[1]
    x = frames.astype(dt) + _sinusoid(jnp.arange(T), cfg.d_model).astype(dt)
    x = shard(x, "act")

    def body(x_c, lp):
        h = norm_apply(cfg, lp["ln1"], x_c)
        a, _ = attention(lp["attn"], h, cfg, _SELF, causal=False, shard=shard)
        x_c = x_c + a
        h = norm_apply(cfg, lp["ln2"], x_c)
        x_c = shard(x_c + mlp_apply(lp["mlp"], h, cfg.mlp_act), "act")
        return x_c, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return norm_apply(cfg, params["enc_norm"], x)


def _cross_kv(lp, cfg, enc_out):
    dt = cfg.dtype
    B, T, _ = enc_out.shape
    D = cfg.head_dim_
    k = (enc_out @ lp["cross_attn"]["wk"].astype(dt)).reshape(B, T, cfg.n_kv_heads, D)
    v = (enc_out @ lp["cross_attn"]["wv"].astype(dt)).reshape(B, T, cfg.n_kv_heads, D)
    return k, v


def forward(params, cfg, tokens, *, frames=None, mode="train", cache=None,
            cache_len=0, shard=None, remat=True, decode_combine=None,
            prefetch=None):
    """Returns (logits, aux, new_cache). See transformer.forward for modes.

    decode-mode cache: {"self": stacked {k,v}, "cross": stacked (k,v),
                        "pos": int32} — cross K/V computed once at prefill.
    decode_combine applies to the decoder *self*-attention caches only; the
    cross-attention K/V are read-only prefill products and stay on the
    GSPMD path.
    prefetch: the double-buffered FSDP pipeline hook is a decoder-only-stack
    feature; the encoder-decoder path keeps eager gathers (train/step.py
    never enables it for the audio family) and rejects a hook loudly rather
    than consuming sharded params as if they were gathered.
    """
    if prefetch is not None:
        raise NotImplementedError(
            "prefetch pipeline is transformer-only (see DESIGN.md §5)")
    shard = shard or _noop
    dt = cfg.dtype
    B, S = tokens.shape
    decode = cache is not None
    build = (mode == "prefill")

    if decode:
        pos = cache["pos"]
        positions = jnp.broadcast_to(pos, (B, 1))
        enc_out = None
        cross_stack = cache["cross"]
    else:
        pos = None
        positions = jnp.arange(S)[None]
        enc_out = encode(params, cfg, frames, shard=shard)
        cross_stack = None

    x = params["embed"].astype(dt)[tokens]
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    x = x + _sinusoid(positions, cfg.d_model).astype(dt)
    x = shard(x, "act")

    def body(x_c, xs):
        lp, c, cross = xs
        h = norm_apply(cfg, lp["ln1"], x_c)
        if decode:
            self_cache = {"k": c["k"], "v": c["v"], "pos": pos}
            a, nc_full = attention(lp["self_attn"], h, cfg, _SELF,
                                   positions=positions, cache=self_cache,
                                   shard=shard,
                                   decode_combine=decode_combine)
            nc = {"k": nc_full["k"], "v": nc_full["v"]}
            ck, cv = cross
        else:
            a, kv = attention(lp["self_attn"], h, cfg, _SELF,
                              positions=positions, shard=shard)
            nc = None
            if build:
                k, v = kv
                L = cache_len or S
                padw = [(0, 0), (0, L - S), (0, 0), (0, 0)]
                nc = {"k": jnp.pad(k.astype(dt), padw),
                      "v": jnp.pad(v.astype(dt), padw)}
            ck, cv = _cross_kv(lp, cfg, enc_out)
        x_c = x_c + a
        h = norm_apply(cfg, lp["lnx"], x_c)
        ca, _ = attention(lp["cross_attn"], h, cfg, _SELF, cross_kv=(ck, cv),
                          shard=shard)
        x_c = x_c + ca
        h = norm_apply(cfg, lp["ln2"], x_c)
        x_c = shard(x_c + mlp_apply(lp["mlp"], h, cfg.mlp_act), "act")
        new_cross = (ck, cv) if build else None
        return x_c, (nc, new_cross)

    body_fn = jax.checkpoint(body) if (remat and mode == "train") else body
    self_stack = cache["self"] if decode else None
    x, (self_ncs, cross_ncs) = jax.lax.scan(
        body_fn, x, (params["dec_layers"], self_stack, cross_stack),
        length=cfg.n_layers)

    x = norm_apply(cfg, params["final_norm"], x)
    if mode == "prefill":
        x = x[:, -1:]
    logits = x @ params["embed"].T.astype(dt)
    logits = shard(logits, "logits")

    new_cache = None
    if build:
        new_cache = {"self": self_ncs, "cross": cross_ncs,
                     "pos": jnp.asarray(S, jnp.int32)}
    elif decode:
        new_cache = {"self": self_ncs, "cross": cache["cross"],
                     "pos": cache["pos"] + 1}
    return logits, {"moe_aux": jnp.zeros((), jnp.float32)}, new_cache


def cache_specs(cfg, batch: int, cache_len: int) -> dict:
    D = cfg.head_dim_
    L = cfg.n_layers
    kv = (L, batch, cache_len, cfg.n_kv_heads, D)
    ckv = (L, batch, cfg.enc_seq, cfg.n_kv_heads, D)
    sd = jax.ShapeDtypeStruct
    return {
        "self": {"k": sd(kv, cfg.dtype), "v": sd(kv, cfg.dtype)},
        "cross": (sd(ckv, cfg.dtype), sd(ckv, cfg.dtype)),
        "pos": sd((), jnp.int32),
    }


def param_count(cfg, active_only: bool = False) -> int:
    d = cfg.d_model
    norm_n = 2 * d if cfg.norm_type == "ln" else d
    enc = cfg.n_enc_layers * (2 * norm_n + attn_param_count(cfg) +
                              mlp_param_count(d, cfg.d_ff, cfg.mlp_act))
    dec = cfg.n_layers * (3 * norm_n + 2 * attn_param_count(cfg) +
                          mlp_param_count(d, cfg.d_ff, cfg.mlp_act))
    # embed + encoder + decoder + enc_norm + final_norm
    return int(cfg.padded_vocab * d + enc + dec + 2 * norm_n)
