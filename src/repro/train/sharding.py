"""Sharding rules: parameter PartitionSpecs + activation constraint hooks.

Parameters are sharded 2-D (Megatron-style TP over ``model`` + optional
FSDP/ZeRO over the DP axes); a dim is sharded only if divisible by the axis
size (otherwise GSPMD padding would silently waste memory — we prefer
explicit replication and record it). On a multi-pod mesh the FSDP dim
shards over the COMPOSITE ``('pod', 'data')`` axes (pod-major, matching the
region-major rank of ``core/topology.RegionMap``) whenever the dim is
divisible by the full DP size — so the ZeRO-3 gather genuinely crosses the
DCN boundary and the locality-aware Bruck schedule has non-local rounds to
optimize. This holds for ANY pod count q (3, 5, 6 — Algorithm 2's
allgatherv adaptation, DESIGN.md §7): the divisibility test is against
q·p_data, so when q ∤ dim (but p_data | dim) the leaf falls back to
intra-pod 'data' sharding (pods hold replicas, the grad sync adds a pod
allreduce) — per-leaf geometry, never an all-or-nothing layout switch.
Activation hooks are the ``shard`` callbacks threaded through the model
zoo; in paper-mode (inside the ``shard_map`` over DP axes) the DP axes are
manual and must be dropped from every constraint —
``make_shard_fn(..., manual_dp=True)`` does exactly that.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

DP_AXES = ("pod", "data")       # batch axes (outer = pod boundary)
MODEL_AXIS = "model"


def dp_axes(mesh) -> tuple[str, ...]:
    """The DP axes actually present on this mesh ('pod' only if multi-pod)."""
    return tuple(a for a in DP_AXES if a in mesh.axis_names)


def normalize_axes(axes: str | tuple[str, ...]) -> tuple[str, ...]:
    """A bare axis-name string means ONE axis, not its characters —
    ``"data"`` → ``("data",)`` (iterating the raw string would silently
    match no axis and disable the feature it configures)."""
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _axsize(mesh, name) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.devices.shape[list(mesh.axis_names).index(name)]


def _div(dim: int, n: int) -> bool:
    return n > 1 and dim % n == 0


def moe_expert_leaf(path: tuple[str, ...], shape: tuple[int, ...]) -> bool:
    """True for routed-expert weight leaves — the (E, d_in, d_out) stacks
    locality expert parallelism shards over the DP axes (shared-expert and
    dense-MLP projections are 2-D and never match)."""
    return path[-1] in ("gate", "up", "down") and len(shape) == 3 \
        and "shared" not in path


def _leaf_spec(path: tuple[str, ...], shape: tuple[int, ...], mesh,
               fs_axes: tuple[str, ...],
               ep_axes: tuple[str, ...] = ()) -> P:
    """Heuristic spec from the leaf's key name; leading stacked dims are
    handled by the caller. ``fs_axes`` are the DP axes the FSDP dim may
    shard over (empty = no FSDP); ``ep_axes`` the DP axes routed-expert
    E dims shard over (empty = replicated/TP experts)."""
    name = path[-1]
    m = _axsize(mesh, MODEL_AXIS)
    d = _axsize(mesh, "data")
    full = math.prod(_axsize(mesh, a) for a in fs_axes) if fs_axes else 1
    ep = math.prod(_axsize(mesh, a) for a in ep_axes) if ep_axes else 1

    def fdim(dim):
        # FSDP: prefer the full composite ('pod','data') span; dims only
        # divisible by the 'data' size shard intra-pod (pods replicate).
        if not fs_axes:
            return None
        if len(fs_axes) > 1 and _div(dim, full):
            return tuple(fs_axes)
        return "data" if ("data" in fs_axes and _div(dim, d)) else None

    def mdim(dim):
        return MODEL_AXIS if _div(dim, m) else None

    if len(shape) == 0:
        return P()
    if name in ("scale", "bias", "A_log", "D", "dt_bias", "conv_b"):
        return P(*([None] * len(shape)))
    if name == "router":                       # (d, E) small, replicated
        return P(None, None)
    if name in ("embed", "head"):
        v_dim, d_dim = (0, 1) if name == "embed" else (1, 0)
        spec = [None, None]
        spec[v_dim] = mdim(shape[v_dim])
        spec[d_dim] = fdim(shape[d_dim])
        return P(*spec)
    if name in ("wq", "wk", "wv", "in_proj"):  # col-parallel: (d, out)
        return P(fdim(shape[0]), mdim(shape[1]))
    if name in ("wo", "out_proj"):             # row-parallel: (in, d)
        return P(mdim(shape[0]), fdim(shape[1]))
    if name in ("gate", "up"):
        if len(shape) == 3:                    # MoE experts (E, d, f): EP
            if moe_expert_leaf(path, shape) and _div(shape[0], ep):
                return P(tuple(ep_axes), None, None)
            return P(mdim(shape[0]), fdim(shape[1]), None)
        return P(fdim(shape[0]), mdim(shape[1]))
    if name == "down":
        if len(shape) == 3:                    # (E, f, d)
            if moe_expert_leaf(path, shape) and _div(shape[0], ep):
                return P(tuple(ep_axes), None, None)
            return P(mdim(shape[0]), None, fdim(shape[2]))
        return P(mdim(shape[0]), fdim(shape[1]))
    if name == "conv_w":                       # (W, Ch) depthwise
        return P(None, mdim(shape[1]))
    # fallback: shard the largest divisible dim over model
    best = max(range(len(shape)), key=lambda i: shape[i])
    spec = [None] * len(shape)
    if _div(shape[best], m):
        spec[best] = MODEL_AXIS
    return P(*spec)


def param_specs(abstract_params, mesh, *, fsdp: bool = False,
                fsdp_axes: str | tuple[str, ...] = "auto",
                moe_ep: bool = False):
    """PartitionSpec pytree for a params tree (use jax.eval_shape output).

    fsdp_axes: DP axes the FSDP dim shards over — "auto" uses every DP axis
    on the mesh (('pod','data') on multi-pod, the locality-aware layout);
    pass ("data",) to force the legacy intra-pod layout (pods replicate
    params; benchmarks use this as the flat baseline).

    moe_ep: shard routed-expert weight E dims over the full DP composite
    (the locality-dispatch layout — each rank owns E/p experts and tokens
    travel, DESIGN.md §12). Only leaves whose E is divisible by the DP size
    take the EP spec; others keep the TP/FSDP layout.
    """
    if not fsdp:
        fs_axes: tuple[str, ...] = ()
    elif fsdp_axes == "auto":
        fs_axes = dp_axes(mesh)
    else:
        fs_axes = tuple(a for a in normalize_axes(fsdp_axes)
                        if a in mesh.axis_names)
    ep_axes = dp_axes(mesh) if moe_ep else ()

    def visit(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        keys = tuple(str(k) for k in keys)
        # stacked scan segments: ("blocks", "slotj", ...) carry a leading
        # reps dim; encdec stacks under enc_layers/dec_layers.
        stacked = any(k in ("blocks",) or k.endswith("_layers") for k in keys)
        spec = _leaf_spec(keys, leaf.shape[1:] if stacked else leaf.shape,
                          mesh, fs_axes, ep_axes)
        return P(None, *spec) if stacked else spec

    return jax.tree_util.tree_map_with_path(visit, abstract_params)


def moe_ep_mask(abstract_params):
    """Per-leaf bool pytree: True for routed-expert weight leaves (the
    leaves ``param_specs(..., moe_ep=True)`` shards over DP and the paper
    mode must NOT gather — their grads arrive complete at the owner)."""
    def visit(path, leaf):
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", None)))
                     for p in path)
        stacked = any(k in ("blocks",) or k.endswith("_layers") for k in keys)
        shape = leaf.shape[1:] if stacked else leaf.shape
        return moe_expert_leaf(keys, shape)
    return jax.tree_util.tree_map_with_path(visit, abstract_params)


def batch_spec() -> dict:
    return {"tokens": P(DP_AXES, None), "labels": P(DP_AXES, None)}


# ---------------------------------------------------------------------------
# FSDP gather geometry (shared by the eager gather and the prefetch pipeline)
# ---------------------------------------------------------------------------
def fsdp_dim(spec: P) -> int:
    """Index of the DP-sharded dim of a leaf spec (-1 = replicated) —
    the dim the ZeRO-3 gather (and its reduce-scatter transpose) runs over.
    Matches both the intra-pod ('data') and the composite ('pod','data')
    layouts (every FSDP entry contains 'data')."""
    for i, s in enumerate(spec):
        names = (s,) if isinstance(s, str) else tuple(s or ())
        if "data" in names:
            return i
    return -1


def fsdp_leaf_axes(spec: P) -> str:
    """Comma-joined DP axes of the leaf's FSDP dim, outer-major
    ("pod,data" / "data" / "" = replicated). A flat string — not a tuple —
    so a whole-tree ``jax.tree.map`` keeps one leaf per parameter."""
    k = fsdp_dim(spec)
    if k < 0:
        return ""
    s = spec[k]
    names = (s,) if isinstance(s, str) else tuple(s or ())
    return ",".join(a for a in DP_AXES if a in names)


def fsdp_param_dims(pspecs):
    """Per-leaf fsdp dim for a whole param-spec pytree."""
    return jax.tree.map(fsdp_dim, pspecs, is_leaf=lambda x: isinstance(x, P))


def fsdp_param_axes(pspecs):
    """Per-leaf comma-joined FSDP axes ("" = replicated) for a spec pytree."""
    return jax.tree.map(fsdp_leaf_axes, pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def gather_outer_local(axes: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(outer, local) split of a comma-joined FSDP axes string: 'pod' is the
    non-local (DCN) tier, everything else stays local (ICI) — the split the
    locality-aware Bruck gather and its reduce-scatter transpose run over."""
    names = tuple(a for a in axes.split(",") if a)
    return (tuple(a for a in names if a == "pod"),
            tuple(a for a in names if a != "pod"))


def block_slice_dims(block_dims):
    """Shift stacked-block fsdp dims to ONE scan slice's coordinates.

    ``param_specs`` prefixes stacked leaves with P(None, ...) for the reps
    dim, so a leaf sharded on dim k of the slice reports k+1 on the stack;
    inside the scan the slice has no leading dim and the gather runs on
    k — this undoes the offset (replicated leaves stay -1).
    """
    return jax.tree.map(lambda k: k - 1 if k >= 1 else -1, block_dims)


# ---------------------------------------------------------------------------
# activation constraint hooks
# ---------------------------------------------------------------------------
_ACT_RULES: dict[str, tuple] = {
    # kind -> dims description; DP marks the batch dim, M the model-sharded dim
    "act":        ("dp", None, None),            # (B, S, d)
    "act_heads":  ("dp", None, "model", None),   # (B, S, H, D)
    "act_ff":     ("dp", None, "model"),         # (B, S, F)
    "moe_act":    ("dp", "model", None, None),   # (B, E, C, d)
    "logits":     ("dp", None, "model"),         # (B, S, V)
}


def make_shard_fn(mesh=None, *, manual_dp: bool = False, seq_shard: bool = False,
                  enable: bool = True):
    """Returns shard(x, kind) applying with_sharding_constraint per rules.

    manual_dp: inside a shard_map manual over DP — drop DP axes (only auto
    'model' axis constraints are legal there).
    seq_shard: sequence-parallel residuals — shard the seq dim of (B,S,d)
    activations over 'model' between blocks (perf knob).
    """
    if not enable:
        return lambda x, kind: x
    dp = dp_axes(mesh) if mesh is not None else DP_AXES
    m = _axsize(mesh, MODEL_AXIS) if mesh is not None else 1
    dp_size = (math.prod(_axsize(mesh, a) for a in dp)
               if mesh is not None else 1)

    def on_model(dim: int) -> bool:
        return m > 1 and dim % m == 0

    def shard(x, kind):
        rule = _ACT_RULES.get(kind)
        if rule is None or x.ndim != len(rule):
            return x
        spec = []
        for i, r in enumerate(rule):
            if r == "dp":
                # constrain only divisible dims — hinting a batch-1 decode
                # activation onto 8 DP devices makes GSPMD shard the
                # upstream projection matmuls over idle ranks and pay a
                # (pod,data) partial-sum all-reduce to re-replicate at the
                # manual-region boundary (pure noise traffic)
                on_dp = (dp and not manual_dp and mesh is not None
                         and x.shape[i] % max(dp_size, 1) == 0)
                if mesh is None:
                    on_dp = not manual_dp and bool(dp)
                spec.append(dp if on_dp else None)
            elif r == "model":
                spec.append(MODEL_AXIS if on_model(x.shape[i]) else None)
            else:
                spec.append(None)
        if seq_shard and kind == "act":
            spec[1] = MODEL_AXIS if on_model(x.shape[1]) else None
        if all(s is None for s in spec):
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))

    return shard
