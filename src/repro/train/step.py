"""Train-step factory: loss, backward, gradient sync, optimizer — one jit.

Two gradient-sync paths (selected by ``grad_sync``):

* ``"xla"``      — pure GSPMD: batch sharded over DP axes, params replicated
                   (or FSDP-sharded) — XLA inserts its own all-reduce /
                   reduce-scatter. The stock baseline.
* ``"locality"`` / ``"locality_rd"`` / ``"flat_psum"`` — paper mode: the
  forward/backward runs inside a ``shard_map`` that is *manual* over the DP
  axes (``pod`` crossing the expensive boundary, ``data`` local) and *auto*
  over ``model`` (GSPMD still handles TP). Per-DP-shard gradients are then
  synchronized with the locality-aware collectives of ``core/collectives.py``
  — the paper's algorithm is the literal gradient-sync path, and its
  schedule is visible in the compiled HLO as collective-permutes.

Distributed-optimization extras: gradient bucketing (fuse small leaves into
~bucket_mb collectives) and optional bf16 compression of the DP sync.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as C
from repro.models import encdec, transformer
from repro.optim import AdamW, TrainState
from .sharding import (DP_AXES, batch_spec, block_slice_dims, dp_axes,
                       fsdp_param_axes, fsdp_param_dims, gather_outer_local,
                       make_shard_fn, moe_ep_mask, normalize_axes,
                       param_specs)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def xent_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy that stays sharded over the vocab dim.

    take_along_axis on a 'model'-sharded vocab would make GSPMD replicate
    the logits (an all-gather of the largest tensor in the step — measured
    ~2.5 GiB/device on the 151k-vocab cells). A sharded iota==label mask
    keeps every op elementwise over the sharded dim; only (B, S) partials
    cross shards.
    """
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    if os.environ.get("REPRO_XENT_GATHER"):      # §Perf A/B baseline path
        ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    else:
        vocab_pos = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
        mask = vocab_pos == labels[..., None]
        ll = jnp.sum(jnp.where(mask, lg, 0.0), axis=-1)
    return jnp.mean(lse - ll)


def make_loss_fn(cfg, *, remat: bool = True):
    model = encdec if cfg.family == "audio" else transformer

    def loss_fn(params, batch, shard, prefetch=None, moe_dispatch=None):
        kw: dict[str, Any] = {}
        if cfg.family == "audio":
            kw["frames"] = batch["frames"]
        if cfg.family == "vlm" and "img_embeds" in batch:
            kw["img_embeds"] = batch["img_embeds"]
        if prefetch is not None:
            kw["prefetch"] = prefetch
        if moe_dispatch is not None:
            kw["moe_dispatch"] = moe_dispatch
        logits, aux, _ = model.forward(params, cfg, batch["tokens"],
                                       mode="train", shard=shard, remat=remat,
                                       **kw)
        loss = xent_loss(logits, batch["labels"])
        total = loss + aux["moe_aux"]
        return total, {"loss": loss, "moe_aux": aux["moe_aux"]}

    return loss_fn


# ---------------------------------------------------------------------------
# double-buffered FSDP param prefetch (the train half of DESIGN.md §5)
# ---------------------------------------------------------------------------

class BlockPrefetch:
    """Per-layer ZeRO-3 gather hook for the scanned transformer pipeline.

    ``start`` issues the allgather of ONE super-block slice's shards over
    its DP axes (split halves of core/collectives — the wire rounds,
    including every non-local DCN round of a ('pod','data')-sharded leaf,
    complete in start); ``finish`` completes the local ICI tail at the
    consumer. The PendingCollective rides the scan carry with the two-tier
    (outer, local) layout in its meta, so the double buffer hides exactly
    the DCN rounds. The model scan calls start for layer i + depth before
    layer i's compute; autodiff transposes each start/finish pair into the
    matching reduce-scatter, placed with the same lookahead in the
    backward.

    Bitwise-identical to the eager ``_gather`` path: same cast, same
    moveaxis, same locality-Bruck schedule per leaf (on a single region it
    degenerates to the local Bruck with a deferred reorder).
    """

    def __init__(self, slice_dims, slice_axes, dtype, depth: int):
        self.dims = slice_dims        # fsdp dim per slice leaf (-1 = repl.)
        self.axes = slice_axes        # comma-joined DP axes per leaf ("")
        self.dtype = dtype
        self.depth = depth

    def _cast(self, leaf):
        return leaf.astype(self.dtype) if leaf.dtype == jnp.float32 else leaf

    def start(self, slice_shards):
        def go(leaf, k, ax):
            if k < 0:
                return self._cast(leaf)
            x = jnp.moveaxis(self._cast(leaf), k, 0)
            outer, local = gather_outer_local(ax)
            return C.allgather_start(x, outer, local,
                                     algorithm="locality_bruck", tiled=True,
                                     assume_varying=True)
        with jax.named_scope("repro:prefetch_start"):
            return jax.tree.map(go, slice_shards, self.dims, self.axes)

    def finish(self, pending):
        def done(p, k):
            if k < 0:
                return p
            return jnp.moveaxis(C.allgather_finish(p), 0, k)
        with jax.named_scope("repro:prefetch_finish"):
            return jax.tree.map(done, pending, self.dims,
                                is_leaf=lambda v: isinstance(v, C.PendingCollective))


# ---------------------------------------------------------------------------
# gradient bucketing for the DP sync
# ---------------------------------------------------------------------------

def bucketed_sync(grads, sync_flat: Callable[[jax.Array], jax.Array],
                  bucket_mb: float = 64.0, compress: bool = False):
    """Flatten grads into ≤bucket_mb fp32 buckets, sync each, unflatten.

    Fuses the many small-leaf collectives (norm scales, biases) into a few
    large ones — the standard DDP bucketing trick, which also puts the
    collectives squarely in the paper's bandwidth regime.
    """
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    limit = int(bucket_mb * 1024 * 1024 / 4)
    buckets: list[list[int]] = [[]]
    acc = 0
    for i, s in enumerate(sizes):
        if acc + s > limit and buckets[-1]:
            buckets.append([])
            acc = 0
        buckets[-1].append(i)
        acc += s
    out: list[jax.Array | None] = [None] * len(leaves)
    for idxs in buckets:
        flat = jnp.concatenate(
            [leaves[i].astype(jnp.float32).reshape(-1) for i in idxs])
        if compress:
            flat = flat.astype(jnp.bfloat16)
        flat = sync_flat(flat)
        flat = flat.astype(jnp.float32)
        off = 0
        for i in idxs:
            out[i] = flat[off:off + sizes[i]].reshape(leaves[i].shape)
            off += sizes[i]
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# step factory
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepArtifacts:
    step_fn: Callable                 # jitted (state, batch) -> (state, metrics)
    state_shardings: Any
    batch_shardings: Any
    abstract_state: Any               # ShapeDtypeStruct pytree
    pspecs: Any
    grad_sync: str = ""               # resolved mode (never "auto")
    grad_algorithm: str = ""          # collective algorithm behind it
    grad_sync_source: str = ""        # "table" | "model" | "explicit"
    prefetch_depth: int = 0           # resolved FSDP gather lookahead (0=eager)
    prefetch_source: str = ""         # "table"|"model"|"dispatch"|"explicit"|"n/a"
    fsdp_axes: tuple = ()             # resolved FSDP sharding domain
    moe_dispatch: str = "none"        # resolved EP algorithm ("none" = off)
    moe_transport: str = ""           # "tokens" | "slots" when dispatch is on
    moe_dispatch_source: str = ""     # "table" | "model" | "explicit" | "n/a"
    events: tuple = ()                # TelemetryEvents raised while building


def abstract_batch(cfg, shape) -> dict:
    """shape: a ShapeSpec/name (uses cfg.input_specs) or an explicit dict of
    ShapeDtypeStructs (smoke tests / custom drivers)."""
    if isinstance(shape, dict):
        return dict(shape)
    return dict(cfg.input_specs(shape))


def custom_batch_specs(cfg, global_batch: int, seq_len: int) -> dict:
    """Token/label specs for an arbitrary (B, S) — examples and tests."""
    sd = jax.ShapeDtypeStruct
    out = {"tokens": sd((global_batch, seq_len), jnp.int32),
           "labels": sd((global_batch, seq_len), jnp.int32)}
    if cfg.family == "audio":
        out["frames"] = sd((global_batch, cfg.enc_seq, cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        out["img_embeds"] = sd((global_batch, cfg.n_img_tokens, cfg.d_model),
                               cfg.dtype)
    return out


def make_train_step(cfg, mesh, *, optimizer: AdamW | None = None,
                    grad_sync: str = "xla", fsdp: bool = False,
                    fsdp_axes: str | tuple[str, ...] = "auto",
                    seq_shard: bool = False, remat: bool = True,
                    bucket_mb: float = 64.0, compress: bool = False,
                    donate: bool = True, shape="train_4k",
                    grad_accum: int = 1,
                    prefetch_depth: int | str = 0,
                    moe_dispatch: str = "none") -> StepArtifacts:
    """grad_accum > 1 splits the per-device batch into microbatches inside a
    lax.scan: activation residency drops ~grad_accum×, the DP sync still
    happens once per step on the accumulated grads (the paper's collective
    amortizes over the whole global batch).

    grad_sync="auto" resolves the algorithm from the postal model
    (core/autotune.py) using the model's gradient size and the mesh
    topology — the paper's Eq. 2-4 promoted into a runtime policy.

    fsdp_axes: the DP axes FSDP shards params over — "auto" spans every DP
    axis on the mesh (('pod','data') on multi-pod: the ZeRO-3 gather runs
    the locality-aware Bruck with outer=('pod',), local=('data',) and its
    transpose reduce-scatters the grads over the SAME two-tier schedule,
    so only the ceil(log_{p_ℓ}(r)) non-local rounds cross the DCN — for
    ANY pod count r, power of two or not: non-power counts take
    Algorithm 2's allgatherv adaptation with partial final-round payloads
    and the grad sync's outer tier runs the Bruck-transpose
    reduce-scatter instead of silently degrading to psum, DESIGN.md §7);
    ("data",) forces the legacy intra-pod layout (pods replicate params
    and the grad sync adds a pod allreduce per bucket).

    prefetch_depth: lookahead of the double-buffered FSDP gather pipeline
    (DESIGN.md §5): 0 = eager (whole stacked gather in front of the
    forward), d >= 1 = layer i + d's gather issued before layer i's
    compute inside the scan. "auto" asks the tuning policy's overlap term
    (per-layer gather bytes × layer flops on this topology), guarded by
    the measured per-dispatch overhead of the live backend — a host-CPU
    harness with no real wire resolves to 0. Applies to paper-mode FSDP
    on the transformer family; degrades to eager where the in-scan gather
    cannot run (encdec).

    moe_dispatch: locality expert parallelism (DESIGN.md §12) — "none" keeps
    replicated experts; "locality" / "xla" shard routed-expert weights over
    the full DP composite and route token slots through
    ``core/collectives.all_to_all`` with that algorithm; "auto" resolves via
    the tuning policy's all_to_all cell. Engages only in paper mode
    (grad_sync != "xla") on transformer-family MoE configs whose expert and
    batch counts divide the DP size; otherwise records "n/a" and keeps the
    replicated path."""
    optimizer = optimizer or AdamW()
    model = encdec if cfg.family == "audio" else transformer
    loss_fn = make_loss_fn(cfg, remat=remat)

    build_events: list = []

    def _warn(msg: str, **attrs) -> None:
        # degradations must be LOUD: a structured event on the artifact
        # (Trainer surfaces it) plus a stdlib warning for direct callers
        import warnings
        from repro.telemetry import TelemetryEvent
        build_events.append(TelemetryEvent(msg, kind="warning", attrs=attrs))
        warnings.warn(msg, stacklevel=3)

    grad_algorithm = grad_sync
    grad_sync_source = "explicit"
    if grad_sync == "auto":
        # resolve through the tuning policy with the model's gradient size
        # and the mesh topology: measured crossover table when persisted,
        # postal-model prior otherwise (paper Eqs. 2-4 as a runtime policy).
        from repro.tuning.policy import default_policy
        import numpy as _np
        a_p = jax.eval_shape(lambda k: model.init_params(k, cfg),
                             jax.random.PRNGKey(0))
        grad_bytes = sum(int(_np.prod(l.shape)) for l in jax.tree.leaves(a_p)) * 2
        names = list(mesh.axis_names)
        p_l = (mesh.devices.shape[names.index("data")]
               if "data" in names else 1)
        r = (mesh.devices.shape[names.index("pod")] if "pod" in names else 1)
        # allreduce convention: nbytes is the FULL reduced vector (the
        # executors send nbytes/p per message themselves)
        sel = default_policy().select("allreduce", r * p_l, p_l, grad_bytes)
        grad_algorithm, grad_sync_source = sel.algorithm, sel.source
        grad_sync = "locality" if sel.algorithm == "locality" else "flat_psum"

    # --- abstract state + shardings ------------------------------------------
    a_params = jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))

    dp = dp_axes(mesh)
    outer = ("pod",) if "pod" in mesh.axis_names else ()
    local = tuple(a for a in dp if a != "pod")

    b_abstract = abstract_batch(cfg, shape)
    b_specs = {k: P(dp, *([None] * (len(v.shape) - 1)))
               for k, v in b_abstract.items()}
    batch_sh = {k: NamedSharding(mesh, s) for k, s in b_specs.items()}

    dp_size = 1
    for ax in dp:
        dp_size *= mesh.devices.shape[list(mesh.axis_names).index(ax)]

    # --- locality expert-parallel dispatch resolution (DESIGN.md §12) -------
    from repro.models.moe import MoeDispatch
    _names = list(mesh.axis_names)
    n_pods = mesh.devices.shape[_names.index("pod")] if "pod" in _names else 1
    moe_algorithm = moe_dispatch
    moe_transport = ""
    moe_dispatch_source = "explicit"
    ep_ok = (moe_dispatch != "none" and grad_sync != "xla"
             and cfg.family != "audio" and getattr(cfg, "n_experts", 0) > 0
             and dp_size > 1 and cfg.n_experts % dp_size == 0
             and int(b_abstract["tokens"].shape[0]) % dp_size == 0)
    if not ep_ok:
        moe_algorithm = "none"
        moe_dispatch_source = "n/a"
    elif moe_dispatch == "auto":
        # price the slot-table exchange (the larger of the two transports)
        # through the tuning policy's all_to_all cell
        from repro.models.moe import capacity as _moe_capacity
        from repro.tuning.policy import default_policy as _dpol
        S = int(b_abstract["tokens"].shape[1])
        slot_bytes = ((int(b_abstract["tokens"].shape[0]) // dp_size)
                      * cfg.n_experts * _moe_capacity(cfg, S)
                      * cfg.d_model * jnp.dtype(cfg.dtype).itemsize)
        sel = _dpol().select("all_to_all", dp_size, dp_size // n_pods,
                             slot_bytes)
        moe_algorithm, moe_dispatch_source = sel.algorithm, sel.source
    ep_on = moe_algorithm != "none"
    moe_hook = None
    if ep_on:
        # tokens transport wins bytes when one pod-aggregated copy of the
        # token block undercuts K·cf slot copies (strict for qwen2's
        # K·cf = 5 at q in {2,3}); algorithm="xla" stays on slots — it IS
        # the flat baseline the multipod gate compares against.
        kcf = cfg.top_k * cfg.capacity_factor
        span = n_pods if n_pods > 1 else dp_size
        moe_transport = ("tokens"
                        if moe_algorithm == "locality" and span < kcf
                        else "slots")
        moe_hook = MoeDispatch(outer=outer, local=local,
                               algorithm=moe_algorithm,
                               transport=moe_transport, p=dp_size)

    pspecs = param_specs(a_params, mesh, fsdp=fsdp, fsdp_axes=fsdp_axes,
                         moe_ep=ep_on)
    ep_tree = (moe_ep_mask(a_params) if ep_on
               else jax.tree.map(lambda _: False, a_params))
    resolved_fsdp_axes = (() if not fsdp else
                          dp_axes(mesh) if fsdp_axes == "auto" else
                          tuple(a for a in normalize_axes(fsdp_axes)
                                if a in mesh.axis_names))
    a_state = jax.eval_shape(TrainState.create, a_params)
    state_specs = TrainState(params=pspecs, mu=pspecs, nu=pspecs, step=P())
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs)

    # --- prefetch pipeline resolution (paper-mode FSDP, transformer only) ---
    names = list(mesh.axis_names)
    d_size = mesh.devices.shape[names.index("data")] if "data" in names else 1
    can_prefetch = (fsdp and grad_sync != "xla" and cfg.family != "audio"
                    and d_size > 1 and "blocks" in a_params)
    prefetch_source = "explicit"
    if prefetch_depth == "auto":
        prefetch_source = "n/a"
        resolved_depth = 0
        if can_prefetch:
            # per-layer overlap term: per-rank gather bytes of one scanned
            # super-block slice vs that slice's forward matmul window. The
            # gather span is per-leaf: ('pod','data')-sharded leaves split
            # over the full DP size, data-only leaves over the pod-local
            # slice — and the topology handed to the policy is the widest
            # span so the DCN rounds are priced when any leaf crosses pods.
            blk_dims = fsdp_param_dims(pspecs)["blocks"]
            blk_axes = fsdp_param_axes(pspecs)["blocks"]
            blk_leaves = jax.tree.leaves(a_params["blocks"])
            dim_leaves = jax.tree.leaves(blk_dims)
            axes_leaves = jax.tree.leaves(blk_axes)
            itemsize = jnp.dtype(cfg.dtype).itemsize
            slice_elems = sum(int(np.prod(l.shape[1:])) for l in blk_leaves)
            gather_bytes = sum(
                int(np.prod(l.shape[1:])) * itemsize
                / (dp_size if "pod" in a else d_size)
                for l, k, a in zip(blk_leaves, dim_leaves, axes_leaves)
                if k >= 0)
            crosses_pods = any("pod" in a for k, a in
                               zip(dim_leaves, axes_leaves) if k >= 0)
            p_gather = dp_size if crosses_pods else d_size
            tokens_per_dev = int(np.prod(b_abstract["tokens"].shape)) \
                // max(dp_size, 1)
            layer_flops = 2.0 * slice_elems * tokens_per_dev
            from repro.tuning.measure import dispatch_overhead_s
            from repro.tuning.policy import default_policy
            sel = default_policy().select_overlap(
                p_gather, d_size, gather_bytes, layer_flops,
                dispatch_overhead_s=dispatch_overhead_s())
            resolved_depth = (C.PREFETCH_DEPTH_DEFAULT
                              if sel.algorithm == "prefetch" else 0)
            prefetch_source = sel.source
    else:
        resolved_depth = int(prefetch_depth)
        if resolved_depth and not can_prefetch:
            # nothing to pipeline on this config — encdec has no scanned
            # transformer blocks, non-FSDP/flat-sync no in-scan gather
            _warn(f"prefetch_depth={resolved_depth} requested but this "
                  f"config cannot pipeline the FSDP gather (family="
                  f"{cfg.family}, fsdp={fsdp}, grad_sync={grad_sync}): "
                  f"degrading to eager",
                  requested_depth=resolved_depth, family=cfg.family,
                  fsdp=fsdp, grad_sync=grad_sync)
            resolved_depth = 0
            prefetch_source = "n/a"

    # --- microbatch accumulation helper -------------------------------------
    def _accumulated(one_fn, batch):
        """Run one_fn over grad_accum microbatches via lax.scan, summing the
        ((loss, metrics), grads) pytree; caller divides by grad_accum."""
        if grad_accum <= 1:
            return one_fn(batch)
        mbs = jax.tree.map(
            lambda t: t.reshape((grad_accum, t.shape[0] // grad_accum)
                                + t.shape[1:]), batch)
        first = jax.tree.map(lambda t: t[0], mbs)
        out_sh = jax.eval_shape(one_fn, first)
        init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), out_sh)

        def sbody(acc, mb):
            return jax.tree.map(lambda a, b: a + b, acc, one_fn(mb)), None

        acc, _ = jax.lax.scan(sbody, init, mbs)
        return jax.tree.map(lambda t: t / grad_accum, acc)

    # --- gradient computation ---------------------------------------------
    if grad_sync == "xla":
        def grads_of(params, batch):
            shard = make_shard_fn(mesh, seq_shard=seq_shard)

            def one(mb):
                with jax.named_scope("repro:compute"):
                    return jax.value_and_grad(loss_fn, has_aux=True)(
                        params, mb, shard)

            (_, metrics), grads = _accumulated(one, batch)
            return grads, metrics
    else:
        alg = {"locality": ("locality", "rhd"),
               "locality_rd": ("locality", "rd"),
               "flat_psum": ("xla", "rhd")}[grad_sync]

        # fsdp dim + DP axes per leaf (-1/"" = replicated). In paper mode
        # the DP axes are *manual*: ZeRO-3-style shards enter the
        # shard_map, are gathered with the (locality-aware) Bruck
        # allgather before use, and autodiff transposes the gather into
        # the matching reduce-scatter of the gradients — paper Algorithm 2
        # as the literal FSDP communication path. A ('pod','data')-sharded
        # leaf runs the two-tier schedule with outer=('pod',): its
        # non-local rounds are the ONLY DCN traffic of that leaf's whole
        # gather+sync cycle (the transpose reduce-scatters over both tiers
        # at once, no separate pod allreduce). Leaves sharded over 'data'
        # alone keep the per-shard pod allreduce (1/p_ℓ of the bytes).
        fsdp_dims = fsdp_param_dims(pspecs)
        fsdp_axs = fsdp_param_axes(pspecs)
        # EP expert leaves stay sharded through the forward (the dispatch
        # all-to-all IS their exchange); gather-skip them here and let the
        # return-leg transpose deliver their complete grads to the owner.
        gdims = jax.tree.map(lambda k, e: -1 if e else k, fsdp_dims, ep_tree)
        param_in_specs = jax.tree.map(
            lambda sp, k: P(*[(sp[i] if i == k else None)
                              for i in range(len(sp))]),
            pspecs, fsdp_dims, is_leaf=lambda x: isinstance(x, P))

        def _gather(shard_leaf, k, ax=""):
            if k < 0:
                return shard_leaf.astype(cfg.dtype) \
                    if shard_leaf.dtype == jnp.float32 else shard_leaf
            with jax.named_scope("repro:fsdp_gather"):
                x = shard_leaf.astype(cfg.dtype)   # gather the bf16 copy
                x = jnp.moveaxis(x, k, 0)
                g_outer, g_local = gather_outer_local(ax)
                if g_outer:
                    full = C.allgather(x, g_outer, g_local,
                                       algorithm="locality_bruck", tiled=True,
                                       assume_varying=True)
                else:
                    full = C.allgather(x, (), g_local or ("data",),
                                       algorithm="bruck", tiled=True,
                                       assume_varying=True)
                return jnp.moveaxis(full, 0, k)

        def sync_pod(t):
            if not outer:
                return t / dp_size
            with jax.named_scope("repro:grad_sync"):
                return C.allreduce(t, (), outer, algorithm="locality",
                                   outer_algorithm=alg[1]) / dp_size

        def sync_full(t):
            with jax.named_scope("repro:grad_sync"):
                return C.allreduce(t, outer, local, algorithm=alg[0],
                                   outer_algorithm=alg[1]) / dp_size

        # the double-buffered pipeline hook: block shards stay sharded into
        # the forward, gathered per scanned layer with depth-ahead issue
        hook = None
        if resolved_depth > 0 and can_prefetch:
            hook = BlockPrefetch(block_slice_dims(gdims["blocks"]),
                                 fsdp_axs["blocks"], cfg.dtype,
                                 resolved_depth)

        def body(params, batch):
            shard = make_shard_fn(mesh, manual_dp=True, seq_shard=seq_shard)

            def one(mb):
                def sharded_loss(shards):
                    if hook is not None:
                        rest = {k: v for k, v in shards.items()
                                if k != "blocks"}
                        rdims = {k: v for k, v in gdims.items()
                                 if k != "blocks"}
                        raxes = {k: v for k, v in fsdp_axs.items()
                                 if k != "blocks"}
                        full = jax.tree.map(_gather, rest, rdims, raxes)
                        full["blocks"] = shards["blocks"]
                        with jax.named_scope("repro:compute"):
                            return loss_fn(full, mb, shard, prefetch=hook,
                                           moe_dispatch=moe_hook)
                    full = jax.tree.map(_gather, shards, gdims, fsdp_axs)
                    with jax.named_scope("repro:compute"):
                        return loss_fn(full, mb, shard,
                                       moe_dispatch=moe_hook)
                return jax.value_and_grad(sharded_loss, has_aux=True)(params)

            # microbatches accumulate per-device; the (locality-aware) DP
            # sync below runs ONCE on the accumulated grads.
            (_, metrics), grads = _accumulated(one, batch)

            # sync, by per-leaf FSDP geometry:
            #   ('pod','data')-sharded: the gather transpose already
            #     reduce-scattered over BOTH tiers — scale to the mean,
            #     zero extra collectives;
            #   EP expert shards: the return-leg all-to-all transpose
            #     already summed every rank's cotangent at the owner —
            #     scale only, same bucket;
            #   'data'-sharded: reduce-scattered intra-pod — finish with
            #     the pod allreduce;
            #   replicated: full locality allreduce over (pod, data).
            leaves, treedef = jax.tree.flatten(grads)
            dims = jax.tree.leaves(fsdp_dims)
            axs = jax.tree.leaves(fsdp_axs)
            eps = jax.tree.leaves(ep_tree)
            idx_done = [i for i, (k, a, e) in enumerate(zip(dims, axs, eps))
                        if e or (k >= 0 and "pod" in a)]
            idx_rs = [i for i, (k, a, e) in enumerate(zip(dims, axs, eps))
                      if not e and k >= 0 and "pod" not in a]
            idx_full = [i for i, (k, e) in enumerate(zip(dims, eps))
                        if not e and k < 0]

            for i in idx_done:
                leaves[i] = leaves[i] / dp_size
            if idx_rs and fsdp:
                sub = bucketed_sync([leaves[i] for i in idx_rs], sync_pod,
                                    bucket_mb=bucket_mb, compress=compress)
                for j, i in enumerate(idx_rs):
                    leaves[i] = sub[j]
            if idx_full:
                sub = bucketed_sync([leaves[i] for i in idx_full], sync_full,
                                    bucket_mb=bucket_mb, compress=compress)
                for j, i in enumerate(idx_full):
                    leaves[i] = sub[j]
            grads = jax.tree.unflatten(treedef, leaves)
            metrics = jax.tree.map(
                lambda t: jax.lax.psum(t, dp) / dp_size, metrics)
            return grads, metrics

        in_specs = (param_in_specs if (fsdp or ep_on) else P(),
                    {k: b_specs[k] for k in b_abstract})
        out_specs = ((param_in_specs if (fsdp or ep_on) else P()), P())
        grads_of = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=set(dp), check_vma=False)

    # --- the full step -------------------------------------------------------
    def step(state: TrainState, batch):
        grads, metrics = grads_of(state.params, batch)
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, pspecs)
        with jax.named_scope("repro:optimizer"):
            new_state, opt_metrics = optimizer.apply(state, grads)
        return new_state, {**metrics, **opt_metrics}

    jit_kw: dict[str, Any] = dict(
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
    )
    if donate:
        jit_kw["donate_argnums"] = (0,)
    step_fn = jax.jit(step, **jit_kw)
    return StepArtifacts(step_fn=step_fn, state_shardings=state_sh,
                         batch_shardings=batch_sh, abstract_state=a_state,
                         pspecs=pspecs, grad_sync=grad_sync,
                         grad_algorithm=grad_algorithm,
                         grad_sync_source=grad_sync_source,
                         prefetch_depth=resolved_depth,
                         prefetch_source=prefetch_source,
                         fsdp_axes=resolved_fsdp_axes,
                         moe_dispatch=moe_algorithm,
                         moe_transport=moe_transport,
                         moe_dispatch_source=moe_dispatch_source,
                         events=tuple(build_events))


def init_state(cfg, mesh, artifacts: StepArtifacts, seed: int = 0) -> TrainState:
    model = encdec if cfg.family == "audio" else transformer
    init = jax.jit(lambda k: TrainState.create(model.init_params(k, cfg)),
                   out_shardings=artifacts.state_shardings)
    return init(jax.random.PRNGKey(seed))
