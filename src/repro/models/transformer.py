"""Decoder-only stack builder: dense / MoE / SSM / hybrid / VLM backbones.

The layer plan (configs.base.ModelConfig.layer_plan) is compiled into a
*periodic super-block scan*: the smallest repeating pattern of layers (e.g.
gemma2's [window, full], llama4's [chunked ×3, global-NoPE], zamba2's
[mamba ×6, shared-attn]) becomes one ``lax.scan`` body with per-slot stacked
parameters; any non-periodic remainder is applied unrolled. This bounds HLO
size at 512 devices while supporting weight sharing (zamba2's shared
attention block closes over a single parameter set inside the scan body).

Modes: 'train' (full-seq logits), 'prefill' (build KV/SSM caches, last-token
logits), 'decode' (one token against caches).
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .attention import attention, attn_init, attn_param_count
from .layers import (embed_init, mlp_apply, mlp_init, mlp_param_count,
                     norm_apply, norm_init, softcap)
from .moe import moe_apply, moe_init, moe_param_count
from .ssm import mamba_apply, mamba_cache_specs, mamba_init, mamba_param_count

Shard = Callable[[jax.Array, str], jax.Array]
_noop: Shard = lambda t, _k: t


# ---------------------------------------------------------------------------
# layer plan → (period, reps, remainder)
# ---------------------------------------------------------------------------

def find_period(plan) -> tuple[int, int, int]:
    keys = [s.key() for s in plan]
    n = len(keys)
    for pi in range(1, n + 1):
        reps = n // pi
        if reps < 1:
            break
        if all(keys[i] == keys[i % pi] for i in range(reps * pi)):
            return pi, reps, n - reps * pi
    return n, 1, 0


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(rng, cfg, spec) -> dict:
    d = cfg.d_model
    if spec.mixer == "mamba2":
        k1, k2 = jax.random.split(rng)
        return {"ln": norm_init(cfg, d), "mamba": mamba_init(k2, cfg)}
    if spec.mixer == "shared_attn":
        return {}                      # params live once at the top level
    ks = jax.random.split(rng, 2)
    p = {"ln1": norm_init(cfg, d), "attn": attn_init(ks[0], cfg),
         "ln2": norm_init(cfg, d)}
    if spec.mlp == "moe":
        p["moe"] = moe_init(ks[1], cfg)
    elif spec.mlp == "dense":
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_act, cfg.param_dtype)
    if cfg.sandwich_norm:
        p["post_ln1"] = norm_init(cfg, d)
        p["post_ln2"] = norm_init(cfg, d)
    return p


def ring_cache_len(cfg, spec) -> int | None:
    """Ring-buffer cache size for windowed/chunked-local attention layers —
    they never attend past the last window/chunk tokens, so the decode cache
    is a W-slot ring instead of the full context (§Perf iteration 7)."""
    if spec.mixer not in ("attn", "shared_attn"):
        return None
    if spec.attn == "window" and cfg.window:
        return cfg.window
    if spec.attn == "chunked" and cfg.chunk:
        return cfg.chunk
    return None


def _shared_attn_init(rng, cfg) -> dict:
    """zamba2's weight-shared attention+MLP block."""
    ks = jax.random.split(rng, 2)
    return {"ln1": norm_init(cfg, cfg.d_model), "attn": attn_init(ks[0], cfg),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act,
                            cfg.param_dtype)}


def _apply_layer(lp, x, cfg, spec, *, positions, cache, build_cache,
                 cache_len, pos, shard: Shard, decode_combine=None,
                 moe_dispatch=None, layer=None):
    """Returns (x, aux, new_cache).

    ``layer`` set (decode in the layer scan): ``cache`` holds the slot's
    stacked (reps, ...) leaves and the returned cache is them with entry
    ``layer`` updated in place.
    """
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer == "mamba2":
        h = norm_apply(cfg, lp["ln"], x)
        if cache is not None and layer is not None:
            c = {k: jax.lax.dynamic_index_in_dim(t, layer, 0, False)
                 for k, t in cache.items()}
            y, nc = mamba_apply(lp["mamba"], h, cfg, cache=c, shard=shard)
            nc = {k: jax.lax.dynamic_update_index_in_dim(cache[k], nc[k],
                                                         layer, 0)
                  for k in cache}
        elif cache is not None:
            y, nc = mamba_apply(lp["mamba"], h, cfg, cache=cache, shard=shard)
        elif build_cache:
            y, nc = mamba_apply(lp["mamba"], h, cfg, cache={}, shard=shard)
        else:
            y, nc = mamba_apply(lp["mamba"], h, cfg, shard=shard)
        return shard(x + y, "act"), aux, nc

    ring_len = ring_cache_len(cfg, spec)
    h = norm_apply(cfg, lp["ln1"], x)
    if cache is not None:
        attn_cache = {"k": cache["k"], "v": cache["v"], "pos": pos,
                      "ring": ring_len is not None, "layer": layer}
        a, nc_full = attention(lp["attn"], h, cfg, spec, positions=positions,
                               cache=attn_cache, shard=shard,
                               decode_combine=decode_combine)
        nc = {"k": nc_full["k"], "v": nc_full["v"]}
    else:
        a, kv = attention(lp["attn"], h, cfg, spec, positions=positions,
                          shard=shard)
        nc = None
        if build_cache:
            k, v = kv
            B, S = k.shape[0], k.shape[1]
            L = cache_len or S
            if ring_len is not None:
                L = min(L, ring_len)
            if S <= L:
                pad = [(0, 0), (0, L - S), (0, 0), (0, 0)]
                nc = {"k": jnp.pad(k.astype(cfg.dtype), pad),
                      "v": jnp.pad(v.astype(cfg.dtype), pad)}
            else:
                # ring: keep the last L keys, token t at slot t % L
                sh = (S - L) % L
                nc = {"k": jnp.roll(k[:, S - L:].astype(cfg.dtype), sh, axis=1),
                      "v": jnp.roll(v[:, S - L:].astype(cfg.dtype), sh, axis=1)}
    if cfg.sandwich_norm:
        a = norm_apply(cfg, lp["post_ln1"], a)
    x = shard(x + a, "act")

    h = norm_apply(cfg, lp["ln2"], x)
    if spec.mlp == "moe":
        m, aux = moe_apply(lp["moe"], h, cfg, shard=shard,
                           dispatch=moe_dispatch)
    elif spec.mlp == "dense":
        m = mlp_apply(lp["mlp"], h, cfg.mlp_act)
    else:
        m = jnp.zeros_like(h)
    if cfg.sandwich_norm:
        m = norm_apply(cfg, lp["post_ln2"], m)
    return shard(x + m, "act"), aux, nc


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def init_params(rng, cfg) -> dict:
    plan = cfg.layer_plan()
    pi, reps, rem = find_period(plan)
    ks = jax.random.split(rng, 4 + pi)
    params: dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                            cfg.param_dtype),
        "final_norm": norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(ks[1], cfg.padded_vocab, cfg.d_model,
                                    cfg.param_dtype).T
    if any(s.mixer == "shared_attn" for s in plan):
        params["shared_attn"] = _shared_attn_init(ks[2], cfg)

    scan_params = {}
    for j in range(pi):
        spec = plan[j]
        keys = jax.random.split(jax.random.fold_in(ks[3], j), reps)
        scan_params[f"slot{j}"] = jax.vmap(
            lambda k, s=spec: _layer_init(k, cfg, s))(keys)
    params["blocks"] = scan_params
    params["rest"] = [
        _layer_init(jax.random.fold_in(ks[3], 1000 + i), cfg, plan[reps * pi + i])
        for i in range(rem)]
    return params


def forward(params, cfg, tokens, *, img_embeds=None, mode="train", cache=None,
            cache_len=0, shard: Shard | None = None, remat=True,
            decode_combine=None, prefetch=None, moe_dispatch=None):
    """Returns (logits, aux, new_cache).

    train:   logits (B,S,Vpad); new_cache None.
    prefill: logits (B,1,Vpad) for the last position; new_cache filled, with
             cache["pos"] = S (next write position).
    decode:  tokens (B,1); cache required; logits (B,1,Vpad).
    decode_combine: serve-layer hook for the decode cache write + attention
             over a sequence-sharded cache (see models/attention.attention).
    moe_dispatch: expert-parallel dispatch hook (models/moe.MoeDispatch) —
             train-mode paper path where MoE expert weights arrive as E/p
             per-rank shards and slot routing runs over the manual DP axes.
    prefetch: train-layer hook for the double-buffered FSDP pipeline
             (DESIGN.md §5). When set (train mode only), ``params["blocks"]``
             holds per-device SHARDS and the scan becomes a pipelined
             double buffer: ``prefetch.start`` issues the gather for layer
             i + depth BEFORE layer i's compute, ``prefetch.finish``
             completes it at the consumer. The hook carries ``.depth``
             (lookahead slots); every other param subtree arrives gathered
             as usual.
    """
    shard = shard or _noop
    plan = cfg.layer_plan()
    pi, reps, rem = find_period(plan)
    block_specs = plan[:pi]
    dt = cfg.dtype
    B, S = tokens.shape
    decode = cache is not None
    build_cache = (mode == "prefill")

    x = params["embed"].astype(dt)[tokens]
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    if img_embeds is not None:
        n_img = img_embeds.shape[1]
        x = jnp.concatenate([img_embeds.astype(dt), x[:, n_img:]], axis=1)
    x = shard(x, "act")

    if decode:
        pos = cache["pos"]
        # scalar pos = lockstep batch; (B,) pos = continuous batching with
        # per-row positions ((B,) does not broadcast to (B,1) — reshape)
        positions = (pos[:, None] if jnp.ndim(pos) == 1
                     else jnp.broadcast_to(pos, (B, 1)))
    else:
        pos = None
        positions = jnp.arange(S)[None]

    aux_total = jnp.zeros((), jnp.float32)

    def body(x_carry, lp_all):
        aux_acc = jnp.zeros((), jnp.float32)
        ncs = {}
        for j, spec in enumerate(block_specs):
            lp = (params["shared_attn"] if spec.mixer == "shared_attn"
                  else lp_all[f"slot{j}"])
            x_carry, aux, nc = _apply_layer(
                lp, x_carry, cfg, spec, positions=positions, cache=None,
                build_cache=build_cache, cache_len=cache_len, pos=pos,
                shard=shard, moe_dispatch=moe_dispatch if mode == "train"
                else None)
            aux_acc += aux
            ncs[f"slot{j}"] = nc
        return x_carry, (aux_acc, ncs)

    def decode_body(carry, lp_all):
        # the stacked cache rides in the carry: layer i reads its entries
        # and writes them back in place (as xs/ys, XLA copies whole stacks
        # to alias the donated input)
        x_carry, blocks, i = carry
        blocks = dict(blocks)
        aux_acc = jnp.zeros((), jnp.float32)
        for j, spec in enumerate(block_specs):
            lp = (params["shared_attn"] if spec.mixer == "shared_attn"
                  else lp_all[f"slot{j}"])
            x_carry, aux, blocks[f"slot{j}"] = _apply_layer(
                lp, x_carry, cfg, spec, positions=positions,
                cache=blocks[f"slot{j}"], build_cache=False, cache_len=0,
                pos=pos, shard=shard, decode_combine=decode_combine, layer=i)
            aux_acc += aux
        return (x_carry, blocks, i + 1), aux_acc

    if prefetch is not None and mode != "train":
        # the step.py contract puts per-device SHARDS in params["blocks"]
        # whenever the hook is set — falling through to the eager scan
        # would consume shard-shaped leaves as full weights
        raise NotImplementedError(
            "prefetch pipeline is train-mode only (see DESIGN.md §5)")
    body_fn = jax.checkpoint(body) if (remat and mode == "train") else body
    if prefetch is not None:
        # Double-buffered pipeline: the scan carries a FIFO of `depth`
        # in-flight gathers. Each iteration issues the gather for layer
        # i + depth FIRST (data-independent of this layer's output — the
        # scheduler can put its rounds on the wire under the matmuls),
        # then completes layer i's pending gather and computes. The last
        # `depth` layers drain the FIFO unrolled. Gathers stay OUTSIDE the
        # remat boundary so the backward transposes them into their
        # reduce-scatters exactly once (no re-gather on recompute).
        def apply_block(x_carry, lp_all):
            aux_acc = jnp.zeros((), jnp.float32)
            for j, spec in enumerate(block_specs):
                lp = (params["shared_attn"] if spec.mixer == "shared_attn"
                      else lp_all[f"slot{j}"])
                x_carry, aux, _ = _apply_layer(
                    lp, x_carry, cfg, spec, positions=positions, cache=None,
                    build_cache=False, cache_len=cache_len, pos=pos,
                    shard=shard, decode_combine=None,
                    moe_dispatch=moe_dispatch)
                aux_acc += aux
            return x_carry, aux_acc

        block_fn = jax.checkpoint(apply_block) if remat else apply_block
        blocks = params["blocks"]
        take = lambda i: jax.tree.map(lambda t: t[i], blocks)
        depth = max(1, int(getattr(prefetch, "depth", 1)))
        scan_ncs = None

        def drain(x_c, pendings):
            # the last layers run as a scan over their stacked weights,
            # exactly as the eager path runs every layer: straight-line
            # layers would let XLA fuse across the layer boundary and skip
            # bf16 roundings the looped layers keep (excess precision)
            full = [prefetch.finish(p) for p in pendings]
            stacked = jax.tree.map(lambda *t: jnp.stack(t), *full)
            x_c, aux_d = jax.lax.scan(block_fn, x_c, stacked)
            return x_c, jnp.sum(aux_d)

        if reps <= depth:
            # lookahead covers the whole stack: issue everything up front
            x, aux = drain(x, [prefetch.start(take(i)) for i in range(reps)])
            aux_total += aux
        else:
            fifo = tuple(prefetch.start(take(i)) for i in range(depth))
            xs_ahead = jax.tree.map(lambda t: t[depth:], blocks)

            def pf_body(carry, lp_ahead):
                x_c, pend = carry
                nxt = prefetch.start(lp_ahead)          # layer i + depth
                x_c, aux = block_fn(x_c, prefetch.finish(pend[0]))
                return (x_c, pend[1:] + (nxt,)), aux

            (x, fifo), aux_s = jax.lax.scan(pf_body, (x, fifo), xs_ahead,
                                            length=reps - depth)
            x, aux = drain(x, fifo)
            aux_total += jnp.sum(aux_s) + aux
    elif decode:
        (x, scan_ncs, _), aux_s = jax.lax.scan(
            decode_body, (x, cache["blocks"], jnp.zeros((), jnp.int32)),
            params["blocks"], length=reps)
        aux_total += jnp.sum(aux_s)
    else:
        x, (aux_s, scan_ncs) = jax.lax.scan(body_fn, x, params["blocks"],
                                            length=reps)
        aux_total += jnp.sum(aux_s)

    rest_ncs = []
    for i in range(rem):
        spec = plan[reps * pi + i]
        c = cache["rest"][i] if decode else None
        lp = (params["shared_attn"] if spec.mixer == "shared_attn"
              else params["rest"][i])
        x, aux, nc = _apply_layer(
            lp, x, cfg, spec, positions=positions, cache=c,
            build_cache=build_cache, cache_len=cache_len, pos=pos, shard=shard,
            decode_combine=decode_combine,
            moe_dispatch=moe_dispatch if mode == "train" else None)
        aux_total += aux
        rest_ncs.append(nc)

    x = norm_apply(cfg, params["final_norm"], x)
    if mode == "prefill":
        x = x[:, -1:]
    head = (params["embed"].T if cfg.tie_embeddings else params["head"])
    logits = x @ head.astype(dt)
    logits = shard(logits, "logits")
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)

    new_cache = None
    if build_cache:
        new_cache = {"blocks": scan_ncs, "rest": rest_ncs,
                     "pos": jnp.asarray(S, jnp.int32)}
    elif decode:
        new_cache = {"blocks": scan_ncs, "rest": rest_ncs,
                     "pos": cache["pos"] + 1}
    return logits, {"moe_aux": aux_total}, new_cache


# ---------------------------------------------------------------------------
# caches for decode dry-run (ShapeDtypeStructs, no allocation)
# ---------------------------------------------------------------------------

def cache_specs(cfg, batch: int, cache_len: int, *,
                vector_pos: bool = False) -> dict:
    plan = cfg.layer_plan()
    pi, reps, rem = find_period(plan)
    D = cfg.head_dim_

    def slot_spec(spec, stacked: bool):
        lead = (reps,) if stacked else ()
        if spec.mixer == "mamba2":
            base = mamba_cache_specs(cfg, batch)
            return {k: jax.ShapeDtypeStruct(lead + v.shape, v.dtype)
                    for k, v in base.items()}
        L = cache_len
        rl = ring_cache_len(cfg, spec)
        if rl is not None:
            L = min(L, rl)
        shp = lead + (batch, L, cfg.n_kv_heads, D)
        return {"k": jax.ShapeDtypeStruct(shp, cfg.dtype),
                "v": jax.ShapeDtypeStruct(shp, cfg.dtype)}

    return {
        "blocks": {f"slot{j}": slot_spec(plan[j], True) for j in range(pi)},
        "rest": [slot_spec(plan[reps * pi + i], False) for i in range(rem)],
        "pos": jax.ShapeDtypeStruct((batch,) if vector_pos else (),
                                    jnp.int32),
    }


# ---------------------------------------------------------------------------
# exact parameter counts (roofline MODEL_FLOPS input)
# ---------------------------------------------------------------------------

def param_count(cfg, active_only: bool = False) -> int:
    plan = cfg.layer_plan()
    d = cfg.d_model
    norm_n = 2 * d if cfg.norm_type == "ln" else d
    total = cfg.padded_vocab * d          # embed (tied head reuses it)
    if not cfg.tie_embeddings:
        total += cfg.padded_vocab * d
    total += norm_n                        # final norm
    shared_counted = False
    for spec in plan:
        if spec.mixer == "mamba2":
            total += norm_n + mamba_param_count(cfg)
            continue
        if spec.mixer == "shared_attn":
            if shared_counted:
                continue
            shared_counted = True
            total += 2 * norm_n + attn_param_count(cfg) + mlp_param_count(
                d, cfg.d_ff, cfg.mlp_act)
            continue
        total += 2 * norm_n + attn_param_count(cfg)
        if cfg.sandwich_norm:
            total += 2 * norm_n
        if spec.mlp == "moe":
            total += moe_param_count(cfg, active_only=active_only)
        elif spec.mlp == "dense":
            total += mlp_param_count(d, cfg.d_ff, cfg.mlp_act)
    return int(total)
