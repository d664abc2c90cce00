"""Serving: jitted prefill/decode steps, cache sharding, batched engine.

Cache placement policy (per leaf):
  * KV caches (…, B, L, KV, D): batch over the DP axes when divisible
    (decode_32k: 128 rows over 16/32 chips); otherwise the *sequence* dim
    is sharded over the DP axes — ('pod','data') on a multi-pod mesh when
    the layer's cache length divides the full span (long_500k: B=1, 512k
    context split across BOTH pods; the decode combine then crosses the
    DCN), falling back to 'data' alone (pods replicate) otherwise —
    sequence-parallel decode. KV heads shard over 'model' when divisible.
  * SSM caches: batch over DP, heads over 'model'.

Decode is compiled twice when the tuning policy picks a non-XLA combine for
the sequence-parallel cache reduction:
  * "xla"      — single jit; XLA turns the position-masked attention over
    the sequence-sharded cache into partial reductions + its own implicit
    combine (an all-reduce of the full per-step stat payload).
  * "locality" — the same forward, but every decode-attention layer runs
    inside a FULLY-manual ``shard_map`` region (all mesh axes manual, so
    the cache update and the partial-stat attention stay device-local):
    per-shard flash-style partial stats
    (o-accumulator, running max, sumexp) from
    ``models.attention.decode_partial_stats``, combined with the explicit
    ``core.collectives.logsumexp_combine``
    (max-allreduce → rescale → packed sum-allreduce). The cache write lands
    on the owning shard via a masked device-local dynamic_update_slice —
    no gather of the sharded cache, and no all-reduce of the stat payload
    in the compiled HLO.
``Engine`` dispatches on the resolved ``CombineChoice`` and surfaces
per-step combine traffic in ``Engine.stats()``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as C
from repro.kernels.decode_stats import ops as stats_ops
from repro.models import encdec, transformer
from repro.models.attention import decode_stats_scores
from repro.train.sharding import (dp_axes, make_shard_fn, normalize_axes,
                                  param_specs)
from .spec import Request, RequestResult, ServeSpec


def _axsize(mesh, name) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.devices.shape[list(mesh.axis_names).index(name)]


def cache_specs(cfg, batch: int, cache_len: int, *, vector_pos: bool = False):
    mod = encdec if cfg.family == "audio" else transformer
    if vector_pos:                 # continuous batching: per-row positions
        return mod.cache_specs(cfg, batch, cache_len, vector_pos=True)
    return mod.cache_specs(cfg, batch, cache_len)


def _coerce_spec(spec, batch, cache_len, prefill_len, combine, fused_stats,
                 seq_axes, caller: str) -> ServeSpec:
    """Normalize the serve surface to a ServeSpec.

    New API: ``caller(cfg, mesh, ServeSpec(...))``. The legacy keyword
    surface (``batch=``, ``cache_len=``, ...) keeps working one release
    behind a DeprecationWarning; mixing both is an error."""
    legacy = {k: v for k, v in dict(batch=batch, cache_len=cache_len,
                                    prefill_len=prefill_len, combine=combine,
                                    fused_stats=fused_stats,
                                    seq_axes=seq_axes).items()
              if v is not None}
    if spec is not None:
        if legacy:
            raise TypeError(
                f"{caller}: pass either a ServeSpec or the legacy keywords, "
                f"not both (got {sorted(legacy)})")
        return spec
    if batch is None or cache_len is None:
        raise TypeError(f"{caller} requires a ServeSpec (or, deprecated, "
                        "the batch=/cache_len= keywords)")
    warnings.warn(
        f"{caller}(..., batch=, cache_len=, ...) is deprecated; pass "
        f"{caller}(cfg, mesh, ServeSpec(batch=..., cache_len=..., ...)) "
        "(removal one release out, see DESIGN.md §9)",
        DeprecationWarning, stacklevel=3)
    return ServeSpec(
        batch=batch, cache_len=cache_len, prefill_len=prefill_len,
        combine=combine if combine is not None else "auto",
        fused_stats=fused_stats if fused_stats is not None else "auto",
        seq_axes=seq_axes if seq_axes is not None else "auto")


def _cache_layout(mesh, batch: int,
                  seq_axes: str | tuple[str, ...] = "auto"
                  ) -> tuple[bool, tuple[str, ...] | None]:
    """(batch_sharded, seq_axes_candidates): the one placement decision both
    the cache shardings and the combine resolution key off — kept in one
    place so they cannot drift.

    The candidates are the DP axes a sequence-parallel cache may shard
    over, outer-major: ``('pod','data')`` on a multi-pod mesh (the decode
    combine then genuinely crosses the DCN boundary) and ``('data',)``
    otherwise. Per-layer divisibility narrows them via
    :func:`_seq_axes_for`. ``seq_axes=("data",)`` forces the legacy
    intra-pod layout (pods replicate the cache — the flat baseline the
    multipod benchmark compares against)."""
    dp = dp_axes(mesh)
    dp_size = max(1, int(np.prod([_axsize(mesh, a) for a in dp])))
    batch_sharded = bool(dp) and batch % dp_size == 0 and batch >= dp_size
    if "data" not in mesh.axis_names:
        cand = None
    elif seq_axes == "auto":
        cand = dp
    else:
        cand = tuple(a for a in normalize_axes(seq_axes)
                     if a in mesh.axis_names) or None
    return batch_sharded, cand


def _seq_axes_for(mesh, L: int, cand: tuple[str, ...] | None
                  ) -> tuple[str, ...] | None:
    """The widest span a cache of ``L`` slots actually shards over: the full
    composite when divisible, the intra-pod ('data',) slice otherwise, None
    when neither divides (that layer keeps a replicated cache)."""
    if not cand:
        return None
    full = int(np.prod([_axsize(mesh, a) for a in cand]))
    if full > 1 and L % full == 0:
        return cand
    if "data" in cand:
        d = _axsize(mesh, "data")
        if d > 1 and L % d == 0:
            return ("data",)
    return None


def cache_shardings(cfg, mesh, batch: int, cache_len: int,
                    seq_axes: str | tuple[str, ...] = "auto"):
    """PartitionSpec pytree matching cache_specs."""
    dp = dp_axes(mesh)
    m = _axsize(mesh, "model")

    def on_model(dim: int) -> bool:    # shardable over a real 'model' axis?
        return m > 1 and dim % m == 0

    batch_sharded, seq_cand = _cache_layout(mesh, batch, seq_axes)

    def visit(path, leaf):
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", ""))) for p in path)
        shape = leaf.shape
        name = keys[-1] if keys else ""
        # find batch dim: stacked leaves carry leading (reps/L,) dims
        if name in ("k", "v") or (len(keys) >= 2 and keys[-2] == "cross"):
            nd = len(shape)
            b_dim = nd - 4
            L_dim, kv_dim, d_dim = b_dim + 1, b_dim + 2, b_dim + 3
            spec = [None] * nd
            if batch_sharded:
                spec[b_dim] = dp
                # model axis: prefer KV heads; else head_dim (a dynamic
                # update on a sharded *sequence* dim makes GSPMD gather the
                # whole cache); else the sequence dim as last resort.
                if on_model(shape[kv_dim]):
                    spec[kv_dim] = "model"
                elif on_model(shape[d_dim]):
                    spec[d_dim] = "model"
                elif on_model(shape[L_dim]):
                    spec[L_dim] = "model"
            else:
                # B=1 long-context: sequence-parallel cache over the DP
                # axes (('pod','data') on multi-pod when divisible — the
                # locality combine's domain), plus KV-heads/head_dim over
                # 'model' when divisible.
                ax = _seq_axes_for(mesh, shape[L_dim], seq_cand)
                if ax:
                    spec[L_dim] = ax if len(ax) > 1 else ax[0]
                if on_model(shape[kv_dim]):
                    spec[kv_dim] = "model"
                elif on_model(shape[d_dim]):
                    spec[d_dim] = "model"
            return P(*spec)
        if name == "conv":
            nd = len(shape)
            spec = [None] * nd
            if batch_sharded:
                spec[nd - 3] = dp
            if on_model(shape[nd - 1]):
                spec[nd - 1] = "model"
            return P(*spec)
        if name == "h":
            nd = len(shape)
            spec = [None] * nd
            if batch_sharded:
                spec[nd - 4] = dp
            if on_model(shape[nd - 3]):
                spec[nd - 3] = "model"
            return P(*spec)
        return P()                                 # pos scalar

    return jax.tree_util.tree_map_with_path(visit, cache_specs(cfg, batch, cache_len))


@dataclasses.dataclass(frozen=True)
class ServeArtifacts:
    prefill_fn: Callable      # (params, batch) -> (logits, cache)
    decode_fn: Callable       # (params, cache, tokens) -> (logits, cache)
    param_shardings: Any
    cache_shardings_: Any
    abstract_params: Any
    combine: Any = None       # CombineChoice for the decode cache-combine
    decode_fn_xla: Callable | None = None       # always-compiled GSPMD path
    decode_fn_locality: Callable | None = None  # manual combine path (or None)
    fused_stats: str = "jnp"  # resolved partial-stat impl ("jnp"/"pallas"/...)
    seq_axes: Any = None      # sequence-shard candidates (('pod','data')/...)
    tok_sharding: Any = None  # decode-token sharding (AOT calls don't reshard)
    abstract_cache: Any = None  # ShapeDtypeStruct pytree for decode lowering


@dataclasses.dataclass(frozen=True)
class CombineChoice:
    """Resolved collective for the sequence-parallel decode combine.

    When the KV cache is sequence-sharded over 'data' (B=1 long-context),
    every decode step reduces per-shard partial attention stats — o (B,1,H,D)
    plus the logsumexp accumulator (B,1,H) in fp32 — across the sequence
    shards. ``algorithm`` is what the tuning policy picks for an allreduce
    of that payload on this topology; "xla" keeps GSPMD's own combine,
    "locality" routes it through the paper-structured allreduce.
    """

    algorithm: str            # "xla" | "locality" | "none" (no seq sharding)
    source: str               # "table" | "model" | "n/a"
    nbytes: int               # per-step combine payload in bytes
    p: int                    # ranks participating in the combine
    p_local: int


def resolve_cache_combine(cfg, mesh, batch: int, cache_len: int,
                          override: str | None = None,
                          seq_axes: str | tuple[str, ...] = "auto"
                          ) -> CombineChoice:
    """Resolve the decode cache-combine collective through repro.tuning.

    The combine is priced as the two-phase ``logsumexp_combine`` collective
    (max-allreduce of the running max, then the packed o+l sum-allreduce) —
    not as a single sum allreduce, which is what it replaces.
    ``override`` ("xla"/"locality") forces the algorithm, keeping the
    resolved geometry (source becomes "explicit"); the layout still decides
    whether there is anything to combine at all.

    On a multi-pod mesh with the cache sequence-sharded over
    ``('pod','data')`` the combine spans both tiers: ``p`` is the full
    shard count and ``p_local`` the intra-pod 'data' slice, so the policy
    prices the hierarchical (intra-pod, then inter-pod) structure against
    GSPMD's flat combine. The pod count q = p/p_local may be ANY integer —
    non-power counts price (and execute) the fold/unfold max phase and the
    Bruck-transpose sum phase of DESIGN.md §7 rather than falling back to
    a flat psum. ``seq_axes=("data",)`` forces the legacy intra-pod domain.
    """
    if override is not None and override not in ("xla", "locality"):
        raise ValueError(f"unknown combine override {override!r}")
    batch_sharded, seq_cand = _cache_layout(mesh, batch, seq_axes)
    ax = None if batch_sharded else _seq_axes_for(mesh, cache_len, seq_cand)
    if ax is None:
        return CombineChoice("none", "n/a", 0, 1, 1)
    H = getattr(cfg, "n_heads", 1)
    D = getattr(cfg, "head_dim_", getattr(cfg, "d_model", 0) // max(H, 1))
    # per-RANK stat payload: when cache_shardings puts KV heads on 'model'
    # the combine moves H/m heads per rank, not H — pricing with the full
    # head count would overstate the payload by the TP factor
    m = _axsize(mesh, "model")
    if m > 1 and getattr(cfg, "n_kv_heads", H) % m == 0:
        H //= m
    nbytes = batch * H * (D + 1) * 4          # fp32 o + logsumexp per step
    p = int(np.prod([_axsize(mesh, a) for a in ax]))
    p_local = _axsize(mesh, "data") if "pod" in ax else p
    if override is not None:
        return CombineChoice(override, "explicit", nbytes, p, p_local)
    from repro.tuning.policy import default_policy
    sel = default_policy().select("logsumexp_combine", p, p_local, nbytes)
    return CombineChoice(sel.algorithm, sel.source, nbytes, p, p_local)


def _combine_eligible(cfg, mesh, cache_len: int,
                      seq_cand: tuple[str, ...] | None) -> bool:
    """Whether ANY decode-attention layer will take the locality hook —
    mirrors the per-layer fallbacks of ``_make_locality_decode_combine``
    (ring/chunk cache lengths indivisible by the shard count, head_dim
    model-sharded caches), so a layout where every layer would fall back
    never compiles a manual path that executes nothing. Per-step combine
    traffic is read off the compiled HLO's CommReport, never an analytic
    layer count."""
    if not seq_cand:
        return False
    m = _axsize(mesh, "model")
    kv = getattr(cfg, "n_kv_heads", 1)
    kv_sharded = m > 1 and kv % m == 0
    if m > 1 and not kv_sharded and cfg.head_dim_ % m == 0:
        return False                   # head_dim-sharded caches: xla path
    if cfg.family == "audio":
        return bool(_seq_axes_for(mesh, cache_len, seq_cand))
    for spec in cfg.layer_plan():
        if spec.mixer not in ("attn", "shared_attn"):
            continue
        rl = transformer.ring_cache_len(cfg, spec)
        L = cache_len if rl is None else min(cache_len, rl)
        if _seq_axes_for(mesh, L, seq_cand):
            return True
    return False


def _make_locality_decode_combine(cfg, mesh, seq_cand: tuple[str, ...],
                                  stats_impl: str = "jnp"):
    """Build the per-layer ``decode_combine`` hook for sequence-sharded caches.

    Returns a callable matching ``models.attention.attention``'s
    ``decode_combine`` protocol. Per layer it traces ONE fully-manual
    ``shard_map`` region (manual over every mesh axis — it keeps the whole
    cache update + partial-stat attention device-local) that:

      1. writes the new token's K/V into the owning sequence shard
         (masked device-local dynamic_update_slice — slot ``pos`` lives on
         shard ``pos // L_loc`` of the region-major (pod-major) flat rank;
         ring caches use slot ``pos % L``);
      2. computes the masked scores + running max over the local cache
         slice and IMMEDIATELY issues the combine's max-allreduce
         (``logsumexp_combine_start`` — split halves of
         core/collectives). On a ``('pod','data')``-sharded cache the max
         runs HIERARCHICALLY: intra-pod recursive doubling first, then the
         inter-pod exchange — rd_rounds(q) tiny DCN messages for ANY pod
         count q (non-power counts fold/unfold, DESIGN.md §7) instead of
         GSPMD's flat tree over all shards;
      3. accumulates the flash-style o/l partials (``stats_impl`` picks the
         jnp ops or the fused Pallas kernel of ``kernels/decode_stats``) —
         the real compute the in-flight max-allreduce hides behind;
      4. finishes the combine (rescale + packed sum-allreduce: intra-pod
         psum-scatter, per-lane inter-pod exchange of 1/p_ℓ of the bytes —
         each of the p_ℓ lanes reduce-scatters + allgathers its slice
         across all q pods, Bruck-transpose schedule on non-power q —
         local allgather) and normalizes.

    Falls back (returns None → the layer keeps the GSPMD path) when the
    layer's cache length is not divisible by any candidate shard span, or
    when ``cache_shardings`` would put 'model' on the head_dim (the q·k
    contraction would then need a model-axis reduction inside the region).
    A layer divisible intra-pod but not by the full composite span shards
    over ('data',) alone — its combine stays all-ICI, pods replicate.
    """
    m = _axsize(mesh, "model")
    axis_names = set(mesh.axis_names)        # fully manual region

    def combine(q, k_new, v_new, k_cache, v_cache, pos, meta):
        B, L, KV, D = k_cache.shape
        ax = _seq_axes_for(mesh, L, seq_cand)
        if ax is None:
            return None
        sizes = [_axsize(mesh, a) for a in ax]
        n = int(np.prod(sizes))
        if n == 1:
            return None
        kv_m = "model" if (m > 1 and KV % m == 0) else None
        if m > 1 and kv_m is None and D % m == 0:
            return None       # head_dim model-sharded cache: xla path
        outer = tuple(a for a in ax if a == "pod")
        local = tuple(a for a in ax if a != "pod")
        L_loc = L // n
        ring = meta["ring"]
        cache_spec = P(None, ax if len(ax) > 1 else ax[0], kv_m, None)
        new_spec = P(None, None, kv_m, None)
        q_spec = P(None, None, kv_m, None)   # H sharded iff KV heads are

        def region(q_, k_n, v_n, k_c, v_c, pos_):
            # flat shard index, region-major over (outer, local) — matches
            # GSPMD's row-major composite-axis enumeration of cache_spec
            i = lax.axis_index(ax[0])
            for a, sz in zip(ax[1:], sizes[1:]):
                i = i * sz + lax.axis_index(a)
            offset = i * L_loc
            slot_g = pos_ % L if ring else pos_
            slot_l = slot_g - offset
            owns = (slot_l >= 0) & (slot_l < L_loc)
            idx = jnp.clip(slot_l, 0, L_loc - 1)
            k_u = lax.dynamic_update_slice(k_c, k_n.astype(k_c.dtype),
                                           (0, idx, 0, 0))
            v_u = lax.dynamic_update_slice(v_c, v_n.astype(v_c.dtype),
                                           (0, idx, 0, 0))
            k_c = jnp.where(owns, k_u, k_c)
            v_c = jnp.where(owns, v_u, v_c)
            s, smask = decode_stats_scores(
                q_, k_c, pos_, slot_offset=offset, total_len=L,
                window=meta["window"], chunk=meta["chunk"], cap=meta["cap"],
                ring=ring)
            mx = jnp.max(s, axis=-1)                 # (B, KV/m, G)
            B_, KV_, G_ = mx.shape
            pend = C.logsumexp_combine_start(
                mx.reshape(B_, 1, KV_ * G_), outer, local)
            o, l = stats_ops.accumulate(s, smask, mx, v_c, impl=stats_impl)
            o, l = C.logsumexp_combine_finish(o, l, pend)
            out = (o / l[..., None]).astype(v_c.dtype)
            return out, k_c, v_c

        fn = jax.shard_map(region, mesh=mesh,
                           in_specs=(q_spec, new_spec, new_spec,
                                     cache_spec, cache_spec, P()),
                           out_specs=(q_spec, cache_spec, cache_spec),
                           axis_names=axis_names, check_vma=False)
        return fn(q, k_new, v_new, k_cache, v_cache, pos)

    return combine


def make_serve_fns(cfg, mesh, spec: ServeSpec | None = None, *,
                   batch: int | None = None, cache_len: int | None = None,
                   prefill_len: int | None = None,
                   combine: str | None = None,
                   fused_stats: str | None = None,
                   seq_axes: str | tuple[str, ...] | None = None
                   ) -> ServeArtifacts:
    """Compile the serving steps for a :class:`ServeSpec`.

    ``make_serve_fns(cfg, mesh, ServeSpec(batch=..., cache_len=...))`` is
    the API; the spread keywords are the deprecated legacy surface (see
    ``_coerce_spec``). Spec fields: ``combine`` "auto" resolves through
    repro.tuning, "xla"/"locality" force the decode cache-combine algorithm
    (explicit benchmark/test dispatch); ``fused_stats`` picks the
    partial-stat accumulation inside the locality combine region — "auto"
    (Pallas kernel on TPU, jnp elsewhere), "jnp", "pallas", or
    "pallas_interpret" (kernel-path testing on CPU); ``seq_axes`` sets the
    sequence-parallel cache domain — "auto" spans every DP axis
    (('pod','data') on multi-pod meshes: the combine crosses the DCN),
    ("data",) forces the legacy intra-pod layout (pods replicate)."""
    spec = _coerce_spec(spec, batch, cache_len, prefill_len, combine,
                        fused_stats, seq_axes, "make_serve_fns")
    batch, cache_len = spec.batch, spec.cache_len
    combine, fused_stats = spec.combine, spec.fused_stats
    seq_axes = spec.seq_axes
    mod = encdec if cfg.family == "audio" else transformer
    a_params = jax.eval_shape(
        lambda k: mod.init_params(k, cfg), jax.random.PRNGKey(0))
    # serving weights live in bf16 (no optimizer → no fp32 master copy):
    # halves the resident params (llama4-scout: 25 GiB → 12.6 GiB per chip)
    a_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, cfg.dtype if s.dtype == jnp.float32 else s.dtype),
        a_params)
    pspecs = param_specs(a_params, mesh, fsdp=False)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    c_specs = cache_shardings(cfg, mesh, batch, cache_len, seq_axes)
    c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs)
    dp = dp_axes(mesh)
    shard = make_shard_fn(mesh)

    def prefill(params, batch_in):
        kw = {}
        if cfg.family == "audio":
            kw["frames"] = batch_in["frames"]
        if cfg.family == "vlm" and "img_embeds" in batch_in:
            kw["img_embeds"] = batch_in["img_embeds"]
        logits, _, cache = mod.forward(params, cfg, batch_in["tokens"],
                                       mode="prefill", cache_len=cache_len,
                                       shard=shard, **kw)
        return logits, cache

    def decode(params, cache, tokens):
        logits, _, cache = mod.forward(params, cfg, tokens, cache=cache,
                                       shard=shard)
        return logits, cache

    choice = resolve_cache_combine(
        cfg, mesh, batch, cache_len,
        override=None if combine == "auto" else combine, seq_axes=seq_axes)
    _, seq_cand = _cache_layout(mesh, batch, seq_axes)
    if choice.algorithm == "locality" and not _combine_eligible(
            cfg, mesh, cache_len, seq_cand):
        # every layer would take the per-layer fallback — don't compile
        # a manual path that executes nothing
        choice = dataclasses.replace(choice, algorithm="xla")

    stats_impl = stats_ops.resolve_impl(fused_stats)

    def decode_locality(params, cache, tokens):
        hook = _make_locality_decode_combine(cfg, mesh, seq_cand,
                                             stats_impl=stats_impl)
        logits, _, cache = mod.forward(params, cfg, tokens, cache=cache,
                                       shard=shard, decode_combine=hook)
        return logits, cache

    dp_size = max(1, int(np.prod([_axsize(mesh, a) for a in dp])))
    row_spec = P(dp, None) if (dp and batch % dp_size == 0) else P()
    tok_sh = NamedSharding(mesh, row_spec)

    def in_sh(ndim):
        if dp and batch % dp_size == 0:
            return NamedSharding(mesh, P(dp, *([None] * (ndim - 1))))
        return NamedSharding(mesh, P())

    batch_in_sh = {"tokens": tok_sh}
    if cfg.family == "audio":
        batch_in_sh["frames"] = in_sh(3)
    if cfg.family == "vlm":
        batch_in_sh["img_embeds"] = in_sh(3)
    prefill_fn = jax.jit(prefill, in_shardings=(p_sh, batch_in_sh),
                         out_shardings=(None, c_sh))
    decode_jit_kw: dict[str, Any] = dict(in_shardings=(p_sh, c_sh, tok_sh),
                                         donate_argnums=(1,),
                                         out_shardings=(None, c_sh))
    decode_fn_xla = jax.jit(decode, **decode_jit_kw)
    decode_fn_locality = None
    if choice.algorithm == "locality":
        decode_fn_locality = jax.jit(decode_locality, **decode_jit_kw)
    # dispatch: the CombineChoice picks which compiled decode serves traffic
    decode_fn = decode_fn_locality or decode_fn_xla
    return ServeArtifacts(prefill_fn=prefill_fn, decode_fn=decode_fn,
                          param_shardings=p_sh, cache_shardings_=c_sh,
                          abstract_params=a_params, combine=choice,
                          decode_fn_xla=decode_fn_xla,
                          decode_fn_locality=decode_fn_locality,
                          fused_stats=stats_impl, seq_axes=seq_cand,
                          tok_sharding=tok_sh,
                          abstract_cache=cache_specs(cfg, batch, cache_len))


class Engine:
    """Minimal batched greedy-decoding engine over the jitted steps.

    Telemetry (DESIGN.md §8): when the decode path has a cache combine at
    all (``comm_telemetry="auto"``), the active decode fn is AOT-compiled at
    construction — the compiled executable serves the decode loop (same
    compile the first decode call would have paid) and its HLO yields the
    :class:`~repro.telemetry.CommReport` stamped under ``"serve/decode"``:
    per-step combine traffic in ``stats()`` is read off the compiled
    artifact's DP-crossing bytes, not a hand-maintained layer count, and
    each executed step is accounted against the prediction
    (``registry.reconcile(engine.comm_label)``). The label is qualified by
    the combine algorithm (``serve/decode:locality`` / ``serve/decode:xla``)
    so side-by-side A/B engines in one process keep separate ledgers."""

    def __init__(self, cfg, mesh, params, spec: ServeSpec | None = None, *,
                 batch: int | None = None, cache_len: int | None = None,
                 combine: str | None = None, fused_stats: str | None = None,
                 seq_axes: str | tuple[str, ...] | None = None,
                 log: Callable[[str], None] | None = None,
                 comm_telemetry: bool | str = "auto",
                 tracer=None, registry=None, clock=None):
        from repro import telemetry
        spec = _coerce_spec(spec, batch, cache_len, None, combine,
                            fused_stats, seq_axes, "Engine")
        self.cfg = cfg
        self.mesh = mesh
        self.spec = spec
        self.resolved = spec.resolve(cfg, mesh)
        self.tracer = tracer or telemetry.get_tracer()
        self.registry = registry or telemetry.get_registry()
        with self.tracer.span("serve/build"):
            self.art = make_serve_fns(cfg, mesh, spec)
        params = jax.tree.map(
            lambda p: p.astype(cfg.dtype) if p.dtype == jnp.float32 else p,
            params)
        self.params = jax.device_put(params, self.art.param_shardings)
        self.batch = spec.batch
        self.cache_len = spec.cache_len
        self.combine = self.art.combine
        self._comm_requested = comm_telemetry
        self._clock = clock
        self._scheduler = None
        self._stats = {"decode_steps": 0, "combine_steps": 0,
                       "combine_bytes": 0.0, "nonlocal_bytes": 0.0,
                       "nonlocal_msgs": 0.0}
        self._decode_callable = self.art.decode_fn
        self.comm_report = None
        self.comm_label = f"serve/decode:{self.combine.algorithm}"
        if comm_telemetry == "auto":
            comm_telemetry = self.combine.algorithm != "none"
        if comm_telemetry:
            self._stamp_comm()
        if log and self.combine.algorithm != "none":
            log(f"[engine] cache-combine: {self.combine.algorithm} "
                f"({self.combine.source}, {self.combine.nbytes} B/step, "
                f"p={self.combine.p} p_local={self.combine.p_local})")

    def _stamp_comm(self) -> None:
        """AOT-compile the active decode fn; stamp its CommReport."""
        from repro import telemetry
        import time as _time
        with self.tracer.span("serve/compile"):
            t0 = _time.perf_counter()
            a_tok = jax.ShapeDtypeStruct((self.batch, 1), jnp.int32)
            lowered = self.art.decode_fn.lower(
                self.art.abstract_params, self.art.abstract_cache, a_tok)
            compiled = lowered.compile()
            compile_s = _time.perf_counter() - t0
        report = telemetry.comm_report(compiled.as_text(), self.mesh,
                                       label=self.comm_label)
        self._decode_callable = compiled
        self.comm_report = report
        self.registry.gauge("serve/compile_time_s").set(compile_s)
        self.registry.attach_comm_report(self.comm_label, report)

    def _next_token(self, logits) -> jax.Array:
        """Greedy sampling rule, shared by prefill and decode so it cannot
        drift: argmax over the last position, clamped below the padded-vocab
        ids (vocab is padded to a multiple; padding logits must never be
        emitted as tokens)."""
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        return jnp.minimum(tok, self.cfg.vocab_size - 1)

    def stats(self) -> dict:
        """Cumulative serving counters: decode steps and the per-step
        combine traffic they generated. ``combine_bytes`` is sourced from
        the compiled artifact's CommReport (DP-domain-crossing bytes of the
        decode HLO × steps) when comm telemetry is on — the ground truth,
        not an analytic layer count — and stays 0 without it. ``nonlocal_*``
        are the
        inter-pod (DCN) accumulations; a ``comm`` entry carries the
        per-step report and its runtime reconciliation when stamped."""
        out = dict(self._stats)
        if self.comm_report is not None:
            out["comm"] = {
                "per_step": self.comm_report.asdict(),
                "reconcile": self.registry.reconcile(self.comm_label),
            }
        return out

    # -- request-level API (DESIGN.md §9) -------------------------------
    @property
    def scheduler(self):
        """The continuous-batching scheduler over this engine's compiled
        steps — built lazily on the first ``submit``."""
        if self._scheduler is None:
            from .scheduler import Scheduler
            self._scheduler = Scheduler(
                self, clock=self._clock,
                comm_telemetry=self._comm_requested is not False)
        return self._scheduler

    def submit(self, request: Request) -> int:
        """Enqueue one request; returns its handle (the request id)."""
        return self.scheduler.submit(request)

    def step(self) -> list[RequestResult]:
        """Admit what fits, decode one step; the requests that finished."""
        return self.scheduler.step()

    def drain(self, *, checkpoint_dir: str | None = None
              ) -> dict[int, RequestResult]:
        """Run until every submitted request finished; results by handle.

        ``checkpoint_dir`` turns the drain into a *graceful preemption
        drain*: instead of decoding the backlog to completion, every
        in-flight request (KV state and all) is checkpointed via
        :meth:`suspend` and only the already-finished results return — a
        restarted engine's :meth:`resume` replays the rest."""
        if checkpoint_dir is not None:
            self.suspend(checkpoint_dir)
            return dict(self.scheduler.results)
        return self.scheduler.drain()

    def suspend(self, checkpoint_dir: str) -> str:
        """Checkpoint all in-flight/queued request state (DESIGN.md §10)."""
        return self.scheduler.suspend(checkpoint_dir)

    def resume(self, checkpoint_dir: str) -> int:
        """Reload a suspend checkpoint into this (fresh) engine; returns
        the number of requests replayed back in."""
        return self.scheduler.resume(checkpoint_dir)

    def cancel(self, rid: int) -> bool:
        return self.scheduler.cancel(rid)

    def result(self, rid: int) -> RequestResult | None:
        return self.scheduler.result(rid)

    def generate(self, prompts: np.ndarray, max_new: int,
                 extra: dict | None = None) -> np.ndarray:
        """prompts: (B, S) int32. Returns (B, max_new) greedy tokens.

        Legacy lockstep loop: the whole batch prefills together and decodes
        to the same budget. Kept one release behind a DeprecationWarning —
        ``submit()``/``step()``/``drain()`` is the serving API."""
        import time as _time
        warnings.warn(
            "Engine.generate is the legacy lockstep loop; use "
            "Engine.submit/step/drain (DESIGN.md §9)",
            DeprecationWarning, stacklevel=2)
        batch_in = {"tokens": jnp.asarray(prompts)}
        batch_in.update(extra or {})
        with self.tracer.span("serve/prefill", prompt_len=int(prompts.shape[-1])):
            logits, cache = self.art.prefill_fn(self.params, batch_in)
        out = []
        tok = self._next_token(logits)
        combining = self.combine.algorithm == "locality"
        rep = self.comm_report
        reg = self.registry
        for _ in range(max_new):
            out.append(np.asarray(tok))
            if rep is not None:
                # the AOT-compiled executable does not reshard inputs
                tok = jax.device_put(tok, self.art.tok_sharding)
            with self.tracer.span("serve/decode_step"):
                t0 = _time.perf_counter()
                logits, cache = self._decode_callable(self.params, cache, tok)
                tok = self._next_token(logits)
            reg.observe("serve/decode_step_s", _time.perf_counter() - t0)
            self._stats["decode_steps"] += 1
            if rep is not None:
                self._stats["nonlocal_bytes"] += rep.nonlocal_bytes
                self._stats["nonlocal_msgs"] += rep.nonlocal_msgs
                reg.record_comm(self.comm_label)
            if combining:
                self._stats["combine_steps"] += 1
                if rep is not None:
                    # ground truth only: the compiled HLO's DP-crossing
                    # bytes — without telemetry the counter stays 0 rather
                    # than reporting an analytic guess as traffic
                    self._stats["combine_bytes"] += rep.dp_bytes
        reg.count("serve/tokens", max_new * prompts.shape[0])
        return np.concatenate(out, axis=1)
