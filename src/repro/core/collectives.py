"""The paper's collective algorithms as JAX collectives, behind one API.

Every algorithm here is a *pure function of per-device shards*, usable inside
``jax.shard_map`` over any subset of mesh axes. Point-to-point MPI sends map
onto ``jax.lax.ppermute`` (XLA ``collective-permute`` with explicit
``source_target_pairs``) — one ppermute per communication round. Locality is
expressed through the (outer_axes, local_axes) split: ``outer`` axes cross the
expensive boundary (inter-pod DCN), ``local`` axes stay inside it (intra-pod
ICI). The flat rank over ``outer + local`` is region-major, matching
``topology.RegionMap``.

Because each algorithm is a composition of linear ops (ppermute / concat /
roll / slice), JAX autodiff transposes an allgather into the matching
reduce-scatter with the *reversed schedule* for free — used by the FSDP
parameter gathering in ``train/`` and the expert-parallel return leg in
``models/moe.py``.

Public surface (DESIGN.md §12) — one family function per collective *kind*,
each taking ``(operands..., outer, local, algorithm=..., **kw)``:

  allgather          kinds of gather: ``bruck`` (Algorithm 1 [Bruck '97]),
                     ``ring`` [Chan '07], ``hierarchical`` [Träff '06],
                     ``multilane`` [Träff & Hunold '20], and
                     ``locality_bruck`` — Algorithm 2, THE paper's
                     contribution. Same five as ``core/schedules.py``,
                     which is the oracle the runtime is reconciled against.
  reduce_scatter     linear transpose of any allgather (reversed schedule)
  allreduce          ``locality``: local RS → per-lane outer allreduce →
                     local AG (generic over sum / max / min), or ``psum``
  all_to_all         ``locality``: two-tier expert dispatch — intra-pod
                     exchange + one minimized inter-pod phase shipping
                     per-destination-pod aggregates (reuses Algorithm 2's
                     partial-round geometry); ``xla``: flat lax.all_to_all
  logsumexp_combine  numerically-safe combine of flash-style partial
                     softmax stats: max-allreduce → rescale → packed
                     sum-allreduce (the serve decode cache-combine)
  cache_migrate      serve-time KV-cache resharding (serve/scheduler.py)

Each family has ``_start``/``_finish`` split halves for the overlap pipeline
(DESIGN.md §5): the non-local ``outer`` rounds issue in ``start``; the local
redistribution completes in ``finish`` at the consumer, so calling start for
layer i+1 before layer i's compute takes the wire time off the critical path.

``collective(kind, *operands, outer=..., local=..., algorithm=...)`` is the
uniform string-keyed entry point over the same table (``KINDS`` /
``ALGORITHMS_BY_KIND`` / ``DEFAULT_ALGORITHM``); ``algorithm="auto"``
defers to the tuning policy (``tuning/policy.py``). The pre-redesign names
(``bruck_allgather``, ``locality_bruck_allgather``, ``locality_allreduce``,
``locality_logsumexp_combine``, ...) remain as deprecated aliases that warn
once per process and forward to the family functions.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

Axes = str | Sequence[str]


def _tup(axes: Axes) -> tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _vma_tracked(axes: tuple[str, ...]) -> bool:
    """Whether the enclosing ``shard_map`` tracks varying manual axes.

    Under ``check_vma=False`` every value reports an empty vma — even
    ``axis_index``, which is varying by definition under tracking."""
    return bool(axes) and bool(jax.typeof(lax.axis_index(axes[0])).vma)


def _varying(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Mark ``x`` device-varying over ``axes`` (no-op if already varying).

    shard_map's vma tracking treats unvarying inputs as replicated values;
    collectives on them transpose into psums. All algorithms here assume a
    genuinely per-device shard, so we normalize at entry. Without tracking
    (``check_vma=False``) nothing is cast: there the pcast's transpose is a
    psum that the untracked region rejects, and no cast is needed.
    """
    if not _vma_tracked(axes):
        return x
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def _size(axes: tuple[str, ...]) -> int:
    return math.prod(lax.axis_size(a) for a in axes)


def _stack_to_tiled(buf: jax.Array, x_shape: tuple[int, ...]) -> jax.Array:
    """[p, *x_shape] -> concatenation along axis 0 (lax.all_gather tiled=True)."""
    p = buf.shape[0]
    if not x_shape:
        return buf
    return buf.reshape((p * x_shape[0],) + x_shape[1:])


def _out(buf: jax.Array, tiled: bool, x_shape: tuple[int, ...]) -> jax.Array:
    return _stack_to_tiled(buf, x_shape) if tiled else buf


# =============================================================================
# Algorithm 1 — standard Bruck allgather: log2(p) rounds, doubling buffers.
# =============================================================================
def _bruck_allgather(x: jax.Array, axes: Axes, *, tiled: bool = False,
                    assume_varying: bool = False) -> jax.Array:
    """Bruck allgather over ``axes``. Returns [p, *x.shape] (or tiled concat).

    Round i (distance d=2^i): every rank sends its entire current buffer
    (first min(d, p-d) blocks) to rank id-d and receives from id+d; a final
    rotation by ``axis_index`` restores canonical block order.

    assume_varying: skip the vma normalization — required when the gather is
    *differentiated* inside a ``check_vma=False`` region (the inserted pcast
    would transpose into an invalid psum); the caller asserts the input is
    genuinely per-device.
    """
    axes = _tup(axes)
    p = _size(axes)
    if not assume_varying:
        x = _varying(x, axes)
    if p == 1:
        return _out(x[None], tiled, x.shape)
    idx = lax.axis_index(axes)
    with jax.named_scope(f"bruck_ag_p{p}"):
        buf = x[None]                       # buf[k] = block (idx + k) mod p
        d = 1
        while d < p:
            cnt = min(d, p - d)
            perm = [(s, (s - d) % p) for s in range(p)]
            recv = lax.ppermute(buf[:cnt], axes, perm)
            buf = jnp.concatenate([buf, recv], axis=0)
            d *= 2
        buf = jnp.roll(buf, idx, axis=0)    # out[j] = block j
    return _out(buf, tiled, x.shape)


# =============================================================================
# Ring allgather: p-1 neighbor rounds (bandwidth-optimal, locality-friendly).
# =============================================================================
def _ring_allgather(x: jax.Array, axes: Axes, *, tiled: bool = False) -> jax.Array:
    axes = _tup(axes)
    p = _size(axes)
    x = _varying(x, axes)
    if p == 1:
        return _out(x[None], tiled, x.shape)
    idx = lax.axis_index(axes)
    perm = [(s, (s - 1) % p) for s in range(p)]
    with jax.named_scope(f"ring_ag_p{p}"):
        def body(cur, _):
            nxt = lax.ppermute(cur, axes, perm)
            return nxt, nxt

        _, rest = lax.scan(body, x, None, length=p - 1)
        buf = jnp.concatenate([x[None], rest], axis=0)  # buf[k] = block idx+k
        buf = jnp.roll(buf, idx, axis=0)
    return _out(buf, tiled, x.shape)


# =============================================================================
# Hierarchical allgather [Träff '06]: binomial gather to a master per region,
# Bruck among masters, binomial broadcast. Non-masters idle during phase 2.
# =============================================================================
def _hierarchical_allgather(x: jax.Array, outer: Axes, local: Axes, *,
                           tiled: bool = False) -> jax.Array:
    outer, local = _tup(outer), _tup(local)
    r, pl = _size(outer), _size(local)
    x = _varying(x, outer + local)
    if pl == 1:
        return _bruck_allgather(x, outer + local, tiled=tiled)
    R = lax.axis_index(outer)
    l = lax.axis_index(local)
    flat = lambda Rg, lg: Rg * pl + lg
    zeros = lambda shape: jnp.zeros(shape, x.dtype) + x.reshape(-1)[0] * 0

    with jax.named_scope(f"hier_ag_r{r}_pl{pl}"):
        # --- Phase 1: binomial gather to lane-0 master --------------------------
        # B[k] = block of lane k of own region (zeros where unknown). Slots
        # are padded to the next power of two so a sender's subtree slice
        # [l, l+d) and a receiver's write at l+d are always in bounds — the
        # min() clamps never bind (for a non-power p_ℓ the old pl-sized
        # buffer made the clamp grab the wrong subtree and the final
        # partial sender overwrite slots it didn't own).
        pl2 = 1 << (pl - 1).bit_length()
        B = lax.dynamic_update_slice(
            zeros((pl2,) + x.shape), x[None], (l,) + (0,) * x.ndim)
        d = 1
        while d < pl:
            # lanes with l % 2d == d send their subtree slots [l, l+d) to lane l-d
            pairs = [(flat(Rg, lg), flat(Rg, lg - d))
                     for Rg in range(r) for lg in range(d, pl, 2 * d)]
            payload = lax.dynamic_slice(
                B, (jnp.minimum(l, pl2 - d),) + (0,) * x.ndim, (d,) + x.shape)
            recv = lax.ppermute(payload, outer + local, pairs)
            is_recv = (l % (2 * d) == 0) & (l + d < pl)
            upd = lax.dynamic_update_slice(
                B, recv, (jnp.minimum(l + d, pl2 - d),) + (0,) * x.ndim)
            B = jnp.where(is_recv, upd, B)
            d *= 2
        B = B[:pl]                      # drop the power-of-two padding

        # --- Phase 2: Bruck allgather among masters (lane 0) over regions -------
        buf = B[None]                       # [chunks, pl, ...]; chunk k = region R+k
        d = 1
        while d < r:
            cnt = min(d, r - d)
            pairs = [(flat(Rg, 0), flat((Rg - d) % r, 0)) for Rg in range(r)]
            recv = lax.ppermute(buf[:cnt], outer + local, pairs)
            buf = jnp.concatenate([buf, recv], axis=0)
            d *= 2
        buf = jnp.roll(buf, R, axis=0)      # canonical region order (masters)

        # --- Phase 3: binomial broadcast of the full buffer within each region --
        have = 1
        while have < pl:
            pairs = [(flat(Rg, lg), flat(Rg, lg + have))
                     for Rg in range(r) for lg in range(min(have, pl - have))]
            recv = lax.ppermute(buf, outer + local, pairs)
            is_recv = (l >= have) & (l < 2 * have)
            buf = jnp.where(is_recv, recv, buf)
            have *= 2

        buf = buf.reshape((r * pl,) + x.shape)
    return _out(buf, tiled, x.shape)


# =============================================================================
# Multi-lane allgather [Träff & Hunold '20]: every lane runs a Bruck over the
# regions concurrently (its own block only), then one local allgather combines
# the lanes. Non-local bytes drop by p_local; message count unchanged.
# =============================================================================
def _multilane_allgather(x: jax.Array, outer: Axes, local: Axes, *,
                        tiled: bool = False) -> jax.Array:
    outer, local = _tup(outer), _tup(local)
    r, pl = _size(outer), _size(local)
    x = _varying(x, outer + local)
    with jax.named_scope(f"multilane_ag_r{r}_pl{pl}"):
        lane = _bruck_allgather(x, outer)      # [r, ...] canonical region order
        allb = _bruck_allgather(lane, local)   # [pl, r, ...] lane-major
        buf = jnp.moveaxis(allb, 1, 0)        # [r, pl, ...] region-major
        buf = buf.reshape((r * pl,) + x.shape)
    return _out(buf, tiled, x.shape)


# =============================================================================
# Algorithm 2 — locality-aware Bruck allgather (the paper's contribution).
# =============================================================================
def _nonlocal_round_geometry(r: int, pl: int, group: int
                             ) -> tuple[int, int, int]:
    """Static geometry of one Algorithm-2 non-local round.

    With ``group`` region chunks held per rank, returns ``(active, span,
    rem)``: the lanes that exchange this round (offsets 0..active-1 name
    distinct peer regions), the chunks held after the round (``span =
    min(active·group, r)``), and the chunk count the LAST active lane's peer
    is actually missing (``rem ∈ (0, group]``; ``rem < group`` only on the
    wrapped final round of a non-power region count — the allgatherv case).
    """
    n_groups = -(-r // group)                 # distinct groups remaining
    active = min(pl, n_groups)
    span = min(active * group, r)
    rem = span - (active - 1) * group
    return active, span, rem


def _nonlocal_exchange(buf: jax.Array, axes: tuple[str, ...], r: int, pl: int,
                       group: int, active: int, rem: int, l: jax.Array,
                       step: int) -> jax.Array:
    """One Algorithm-2 non-local round, allgatherv-adapted (paper §3).

    Lane ℓ ∈ [1, active) sends to region R - ℓ·group (same lane) and
    receives from R + ℓ·group. Lanes 1..active-2 need their peer's full
    ``group``-chunk buffer; the last active lane's peer is missing only
    ``rem`` chunks, so on a wrapped final round (``rem < group``) that lane
    sends exactly the ``rem``-chunk prefix — the partial final-round payload
    that replaces the paper's MPI_Allgatherv for non-power region counts
    (previously the full buffer went over the DCN and the duplicate chunks
    were discarded after the fact). The partial receive is zero-padded back
    to ``group`` chunks so the local redistribution stays one uniform Bruck
    allgather; the caller's ``span`` trim drops the padding statically.
    Message count is unchanged: the two ppermutes carry disjoint edge sets,
    one send per active lane per round.
    """
    flat = lambda Rg, lg: Rg * pl + lg
    last = active - 1
    full_pairs = [(flat(Rg, lg), flat((Rg - lg * group) % r, lg))
                  for Rg in range(r) for lg in range(1, last)]
    last_pairs = [(flat(Rg, last), flat((Rg - last * group) % r, last))
                  for Rg in range(r)]
    with jax.named_scope(f"nonlocal_step{step}"):
        if rem == group:                      # uniform round: one ppermute
            return lax.ppermute(buf, axes, full_pairs + last_pairs)
        part = lax.ppermute(buf[: rem * pl], axes, last_pairs)
        pad = [(0, (group - rem) * pl)] + [(0, 0)] * (buf.ndim - 1)
        part = jnp.pad(part, pad)
        if not full_pairs:                    # active == 2: only the partial
            return part
        recv = lax.ppermute(buf, axes, full_pairs)
        return jnp.where(l == last, part, recv)


def _locality_bruck_allgather(x: jax.Array, outer: Axes, local: Axes, *,
                             tiled: bool = False,
                             assume_varying: bool = False) -> jax.Array:
    """Paper Algorithm 2 over mesh axes — ANY outer region count.

    1. Local Bruck allgather inside each region (``local`` axes).
    2. ceil(log_{p_ℓ}(r)) non-local rounds: with ``group`` regions' data held,
       lane ℓ ∈ [1, active) sends its buffer to region R - ℓ·group (same
       lane) and receives from R + ℓ·group — one non-local message per rank
       per round, each pair of regions exchanging disjoint data. Lane 0
       stays idle (paper §3) and re-contributes its own buffer.
    3. A local allgather of the received buffers redistributes them in-region.

    Allgatherv adaptation (DESIGN.md §7): where the paper uses
    MPI_Allgatherv for non-power region counts, the wrapped final round
    sends only the partial payload its peer is missing
    (:func:`_nonlocal_exchange`), the uniform local allgather runs on
    zero-padded units, and the ``pl - active`` empty units plus the padding
    are discarded statically — strictly fewer non-local bytes than the
    full-buffer exchange, identical message count, slightly padded local
    traffic.

    assume_varying: as for :func:`_bruck_allgather` — required when this
    gather is differentiated inside a ``check_vma=False`` region (the
    two-tier FSDP param gather of train/step.py).
    """
    outer, local = _tup(outer), _tup(local)
    r, pl = _size(outer), _size(local)
    if not assume_varying:
        x = _varying(x, outer + local)
    if pl == 1:
        return _bruck_allgather(x, outer + local, tiled=tiled,
                               assume_varying=True)
    R = lax.axis_index(outer)
    l = lax.axis_index(local)

    with jax.named_scope(f"loc_bruck_ag_r{r}_pl{pl}"):
        # Step 0 (Alg. 2 line 1): local allgather of initial values.
        buf = _bruck_allgather(x, local, assume_varying=True)
        # Invariant: buf = region chunks [R, R+group) (mod r), chunk = pl blocks.
        group = 1
        step = 0
        while group < r:
            active, span, rem = _nonlocal_round_geometry(r, pl, group)
            recv = _nonlocal_exchange(buf, outer + local, r, pl, group,
                                      active, rem, l, step)
            # Lane 0 re-contributes its current buffer; lanes >= active carry
            # no new data (their unit is discarded below).
            unit = jnp.where(l == 0, buf, recv)
            with jax.named_scope(f"redistribute_step{step}"):
                stacked = _bruck_allgather(unit, local,  # [pl, group*pl, ...]
                                          assume_varying=True)
            stacked = stacked[:active]
            buf = stacked.reshape((active * group * pl,) + x.shape)
            buf = buf[: span * pl]             # drop final-round padding
            group = span
            step += 1

        chunks = buf.reshape((r, pl) + x.shape)
        chunks = jnp.roll(chunks, R, axis=0)   # canonical region order
        buf = chunks.reshape((r * pl,) + x.shape)
    return _out(buf, tiled, x.shape)


# =============================================================================
# Dispatcher
# =============================================================================
ALLGATHERS = {
    "bruck": lambda x, outer, local, tiled: _bruck_allgather(
        x, _tup(outer) + _tup(local), tiled=tiled),
    "ring": lambda x, outer, local, tiled: _ring_allgather(
        x, _tup(outer) + _tup(local), tiled=tiled),
    "hierarchical": lambda x, outer, local, tiled: _hierarchical_allgather(
        x, outer, local, tiled=tiled),
    "multilane": lambda x, outer, local, tiled: _multilane_allgather(
        x, outer, local, tiled=tiled),
    "locality_bruck": lambda x, outer, local, tiled: _locality_bruck_allgather(
        x, outer, local, tiled=tiled),
    "xla": lambda x, outer, local, tiled: lax.all_gather(
        x, _tup(outer) + _tup(local), tiled=tiled),
}


def _resolve_auto(collective: str, x: jax.Array, outer: tuple[str, ...],
                  local: tuple[str, ...]) -> str:
    """Trace-time resolution of ``algorithm="auto"`` through repro.tuning.

    Axis sizes and the shard's byte count are Python ints during tracing, so
    the choice is static: the jitted program contains exactly the selected
    schedule (resolve again to re-tune, e.g. after a sweep).
    """
    from repro.tuning.policy import resolve
    p_local = _size(local) if local else 1
    p = _size(outer + local)
    nbytes = x.size * x.dtype.itemsize
    return resolve(collective, p, p_local, nbytes, str(x.dtype))


def allgather(x: jax.Array, outer: Axes, local: Axes = (), *,
              algorithm: str = "locality_bruck", tiled: bool = False,
              assume_varying: bool = False) -> jax.Array:
    """Gather ``x`` shards over ``outer + local`` mesh axes (region-major).

    ``algorithm="auto"`` selects via the tuning policy: the persisted
    measured crossover table when one exists, the postal model otherwise.

    assume_varying: skip the vma normalization (see
    :func:`_bruck_allgather`) — only the Bruck schedules support being
    differentiated inside a ``check_vma=False`` region.
    """
    if algorithm == "auto":
        algorithm = _resolve_auto("allgather", x, _tup(outer), _tup(local))
    if not _tup(local):
        algorithm = "bruck" if algorithm in ("locality_bruck", "hierarchical",
                                             "multilane") else algorithm
    if assume_varying:
        if algorithm == "bruck":
            return _bruck_allgather(x, _tup(outer) + _tup(local), tiled=tiled,
                                    assume_varying=True)
        if algorithm == "locality_bruck":
            return _locality_bruck_allgather(x, outer, local, tiled=tiled,
                                             assume_varying=True)
        raise ValueError(f"assume_varying is only supported for the Bruck "
                         f"schedules, not algorithm={algorithm!r}")
    return ALLGATHERS[algorithm](x, outer, local, tiled)


# Algorithms eligible for KV-cache migration (see ``cache_migrate``): the
# locality schedule minimizes inter-pod messages, multilane minimizes
# per-rank inter-pod bytes, and flat XLA is the ring-decomposed baseline.
MIGRATE_ALGORITHMS = ("locality_bruck", "multilane", "xla")


def cache_migrate(x: jax.Array, outer: Axes, local: Axes = (), *,
                  algorithm: str = "auto", tiled: bool = True) -> jax.Array:
    """Replicate a sequence-sharded KV-cache slab over ``outer + local``.

    The serve scheduler calls this when a request's cache must move across
    the pod (DCN) boundary: the donor layout shards the slab's sequence dim
    over every rank, and the destination insert needs the full slab on the
    owning ranks — a gatherv-shaped replication where the Algorithm-2
    machinery applies directly (uneven tails ride the allgatherv adaptation
    inside :func:`_locality_bruck_allgather`). Priced as its own tuning cell
    (``"cache_migrate"``) because the slab-sized payloads sit in a different
    α/β regime than activation allgathers.
    """
    if algorithm == "auto":
        algorithm = _resolve_auto("cache_migrate", x, _tup(outer), _tup(local))
    if algorithm not in MIGRATE_ALGORITHMS:
        raise ValueError(f"cache_migrate algorithm {algorithm!r} not in "
                         f"{MIGRATE_ALGORITHMS}")
    if not _tup(local):
        algorithm = "bruck" if algorithm != "xla" else "xla"
    with jax.named_scope(f"cache_migrate_{algorithm}"):
        return ALLGATHERS[algorithm](x, outer, local, tiled)


# =============================================================================
# Split (start/finish) collectives — the overlap pipeline's communication half
# =============================================================================
# ``allgather_finish(allgather_start(x, ...)) == allgather(x, ...)`` — the
# same op sequence, divided so the expensive non-local rounds run in start
# and only the cheap local redistribution remains at the consumer. A caller
# that issues start(layer i+1) before layer i's compute makes the non-local
# ppermutes data-independent of that compute, which is exactly what XLA's
# latency-hiding scheduler needs to overlap them (it splits collectives into
# -start/-done pairs and slides independent work between).

#: Default lookahead of the double-buffered pipelines (layers of params
#: gathered ahead of the consumer). 1 = classic double buffering; 0 = eager.
PREFETCH_DEPTH_DEFAULT = 1


@dataclasses.dataclass(frozen=True)
class _SplitMeta:
    """Static half of a PendingCollective (hashable: safe under jit/scan)."""

    op: str                        # "allgather" | "allreduce" | "logsumexp"
    kind: str                      # phase tag, see the start functions
    outer: tuple[str, ...] = ()
    local: tuple[str, ...] = ()
    tiled: bool = False
    x_shape: tuple[int, ...] = ()
    group: int = 1                 # locality_bruck: chunks held pre-finish
    active: int = 1                # locality_bruck: lanes live in last round
    rem: int = 0                   # chunks the last active lane really
                                   # carried in the final round — always
                                   # set on "pending" metas (rem < group on
                                   # the allgatherv wrapped round); unused
                                   # by the other kinds


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PendingCollective:
    """An in-flight split collective.

    Registered as a pytree so it can ride a ``lax.scan`` carry (the
    double-buffered pipelines keep one pending gather per lookahead slot).
    """

    arrays: tuple
    meta: _SplitMeta

    def tree_flatten(self):
        return tuple(self.arrays), self.meta

    @classmethod
    def tree_unflatten(cls, meta, arrays):
        return cls(tuple(arrays), meta)


def _locality_bruck_allgather_start(x: jax.Array, outer: Axes, local: Axes, *,
                                   tiled: bool = False,
                                   assume_varying: bool = False
                                   ) -> PendingCollective:
    """Algorithm 2, split: everything through the LAST non-local ppermute.

    Intermediate rounds keep their local redistribution (the next non-local
    round consumes it), so only the final local allgather + canonical
    reordering — pure ICI traffic — is deferred to ``finish``. All DCN bytes
    are on the wire when start returns. On a wrapped final round (non-power
    region counts) the partial payload is already zero-padded back to
    ``group`` chunks here, so the PendingCollective's arrays stay the
    uniform ``(buf, recv)`` pair and the meta's ``(group, active, rem)``
    record the uneven geometry — the prefetch pipeline and the FSDP
    transpose carry it without caring about the region count.
    """
    outer, local = _tup(outer), _tup(local)
    r, pl = _size(outer), _size(local)
    if not assume_varying:
        x = _varying(x, outer + local)
    if pl == 1:
        full = _bruck_allgather(x, outer + local, tiled=tiled,
                               assume_varying=True)
        return PendingCollective((full,), _SplitMeta("allgather", "done"))
    l = lax.axis_index(local)

    with jax.named_scope(f"loc_bruck_ag_start_r{r}_pl{pl}"):
        buf = _bruck_allgather(x, local, assume_varying=True)
        if r == 1:
            return PendingCollective(
                (buf,), _SplitMeta("allgather", "local_done", outer, local,
                                   tiled, x.shape, group=1, active=1))
        group = 1
        step = 0
        while True:
            active, span, rem = _nonlocal_round_geometry(r, pl, group)
            recv = _nonlocal_exchange(buf, outer + local, r, pl, group,
                                      active, rem, l, step)
            if span >= r:                  # last round: defer redistribution
                return PendingCollective(
                    (buf, recv), _SplitMeta("allgather", "pending", outer,
                                            local, tiled, x.shape,
                                            group=group, active=active,
                                            rem=rem))
            unit = jnp.where(l == 0, buf, recv)
            with jax.named_scope(f"redistribute_step{step}"):
                stacked = _bruck_allgather(unit, local, assume_varying=True)
            stacked = stacked[:active]
            buf = stacked.reshape((active * group * pl,) + x.shape)
            group = span
            step += 1


def _locality_bruck_allgather_finish(pending: PendingCollective) -> jax.Array:
    """Complete a split Algorithm 2: final local redistribution + reorder."""
    meta = pending.meta
    if meta.kind == "done":
        return pending.arrays[0]
    outer, local = meta.outer, meta.local
    r, pl = _size(outer) if outer else 1, _size(local)
    x_shape = meta.x_shape
    with jax.named_scope(f"loc_bruck_ag_finish_r{r}_pl{pl}"):
        if meta.kind == "local_done":
            (buf,) = pending.arrays
        else:
            buf, recv = pending.arrays
            l = lax.axis_index(local)
            unit = jnp.where(l == 0, buf, recv)
            with jax.named_scope("redistribute_final"):
                stacked = _bruck_allgather(unit, local, assume_varying=True)
            stacked = stacked[:meta.active]
            buf = stacked.reshape((meta.active * meta.group * pl,) + x_shape)
            # the uneven geometry recorded at start: the last lane carried
            # only `rem` real chunks — drop its zero padding (and with it
            # any wrap past region r)
            valid = (meta.active - 1) * meta.group + meta.rem
            assert valid == r, (meta, r)
            buf = buf[: valid * pl]
        chunks = buf.reshape((r, pl) + x_shape)
        if outer:                          # canonical region order
            chunks = jnp.roll(chunks, lax.axis_index(outer), axis=0)
        buf = chunks.reshape((r * pl,) + x_shape)
    return _out(buf, meta.tiled, x_shape)


def allgather_start(x: jax.Array, outer: Axes, local: Axes = (), *,
                    algorithm: str = "locality_bruck", tiled: bool = False,
                    assume_varying: bool = False) -> PendingCollective:
    """Issue an allgather; complete it with :func:`allgather_finish`.

    For ``locality_bruck`` the non-local rounds genuinely complete in start
    (_locality_bruck_allgather_start); every other algorithm has no local
    tail to defer, so start runs the whole gather and the split is a
    program-order hook — still the mechanism that lets a double-buffered
    caller issue it before independent compute.
    """
    if algorithm == "auto":
        algorithm = _resolve_auto("allgather", x, _tup(outer), _tup(local))
    if not _tup(local):
        algorithm = "bruck" if algorithm in ("locality_bruck", "hierarchical",
                                             "multilane") else algorithm
    if algorithm == "locality_bruck":
        return _locality_bruck_allgather_start(
            x, outer, local, tiled=tiled, assume_varying=assume_varying)
    if algorithm == "bruck":
        full = _bruck_allgather(x, _tup(outer) + _tup(local), tiled=tiled,
                               assume_varying=assume_varying)
    else:
        full = ALLGATHERS[algorithm](x, outer, local, tiled)
    return PendingCollective((full,), _SplitMeta("allgather", "done"))


def allgather_finish(pending: PendingCollective) -> jax.Array:
    """Complete an :func:`allgather_start`; bit-identical to the eager path."""
    assert pending.meta.op == "allgather", pending.meta
    return _locality_bruck_allgather_finish(pending)


# =============================================================================
# Locality-aware all-to-all — the MoE expert-dispatch collective family.
# =============================================================================
# The paper's two-tier decomposition applied to personalized exchange: block
# (i → j) must cross the DCN at most once, and every inter-pod message is the
# AGGREGATE of a whole pod-pair's blocks instead of a rank-pair's.  Three
# phases, all ppermute (the compiled HLO carries explicit source_target_pairs,
# so collective_stats classifies every edge exactly):
#
#   1. intra-pod collect   — offsets o ∈ [1, q) to the q-1 other pods are
#      assigned round-robin to the p_ℓ lanes (offset o → lane (o-1) mod p_ℓ,
#      round (o-1) div p_ℓ — the same modular lane assignment as Algorithm
#      2's non-local rounds); a local all-to-all hands lane ℓ every local
#      rank's blocks destined to ℓ's pods.
#   2. inter-pod rounds    — ceil((q-1)/p_ℓ) rounds; in round t, active lane
#      ℓ ships ONE aggregated (p_ℓ × p_ℓ)-block slab to pod R + (t·p_ℓ+ℓ+1)
#      and receives the mirror slab.  The last round runs with only
#      (q-1) - (nrounds-1)·p_ℓ active lanes — the non-power-q partial-round
#      geometry of `_nonlocal_round_geometry`, here with group = 1 (no
#      doubling: every block already knows its destination).  q-1 aggregated
#      DCN messages per pod total vs p_ℓ²·(q-1) for the flat pairwise
#      exchange.
#   3. intra-pod deliver   — a second local all-to-all fans the received
#      slabs' columns out to their destination lanes (own-pod blocks ride
#      the same ppermutes), and a static reassembly restores canonical
#      source-rank order.
#
# Linear throughout (roll / reshape / pad / ppermute), so jax.vjp transposes
# the whole exchange into the reversed all-to-all for free — the MoE return
# leg and the router-gradient path reuse the same machinery.

#: Canonical algorithm names for the all_to_all family.
ALL_TO_ALL_ALGORITHMS = ("locality", "xla")


def _a2a_rounds(q: int, pl: int) -> int:
    """Inter-pod round count of the two-tier all-to-all: offsets 1..q-1
    spread over p_ℓ lanes."""
    return -(-(q - 1) // pl) if q > 1 else 0


def _a2a_active(q: int, pl: int, t: int) -> int:
    """Active lanes in inter-pod round ``t`` (partial on the last round of a
    non-power q, mirroring `_nonlocal_round_geometry`'s ``active``)."""
    return max(0, min(pl, (q - 1) - t * pl))


def _local_exchange(struct: jax.Array, axes: tuple[str, ...], q: int, pl: int,
                    l: jax.Array, tag: str) -> jax.Array:
    """Local all-to-all of ``struct`` (leading dim p_ℓ: entry λ is the
    payload for local rank λ).  Returns the mirrored structure: entry m is
    the payload local rank m addressed to us.  p_ℓ - 1 intra-pod ppermutes
    (offset k pairs lane m with lane m+k), plus the rank's own entry.
    """
    flat = lambda Rg, lg: Rg * pl + lg
    sends = jnp.roll(struct, -l, axis=0)          # sends[k] -> lane (l+k)%pl
    arr = [sends[0]]                              # k = 0: own payload
    with jax.named_scope(tag):
        for k in range(1, pl):
            pairs = [(flat(Rg, m), flat(Rg, (m + k) % pl))
                     for Rg in range(q) for m in range(pl)]
            arr.append(lax.ppermute(sends[k], axes, pairs))
    # arr[k] came from lane (l-k)%pl; reindex to source-lane order.
    return jnp.roll(jnp.stack(arr)[::-1], l + 1, axis=0)


def locality_all_to_all_start(x: jax.Array, outer: Axes, local: Axes = (), *,
                              tiled: bool = False,
                              assume_varying: bool = False
                              ) -> PendingCollective:
    """Two-tier all-to-all, split: the intra-pod collect and ALL inter-pod
    rounds run here — every DCN byte is on the wire when start returns; only
    the intra-pod delivery + static reassembly remain in finish."""
    outer, local = _tup(outer), _tup(local)
    q, pl = _size(outer), _size(local)
    p = q * pl
    if not assume_varying:
        x = _varying(x, outer + local)
    assert x.shape[0] % p == 0, \
        f"all_to_all leading dim {x.shape[0]} not divisible by p={p}"
    blk = (x.shape[0] // p,) + x.shape[1:]
    xb = x.reshape((q, pl) + blk)                 # [dest_pod][dest_lane]
    if p == 1:
        return PendingCollective((x,), _SplitMeta("all_to_all", "done"))
    axes = outer + local
    l = lax.axis_index(local) if pl > 1 else jnp.int32(0)
    nrounds = _a2a_rounds(q, pl)

    with jax.named_scope(f"loc_a2a_start_q{q}_pl{pl}"):
        if q > 1:
            R = lax.axis_index(outer)
            # xs[s] = block-slab destined to pod (R+1+s)%q; xs[q-1] = own pod.
            xs = jnp.roll(xb, -(R + 1), axis=0)
            own = xs[q - 1]                       # (pl_dst, *blk)
            rs = xs[: q - 1]
            pad = [(0, nrounds * pl - (q - 1))] + [(0, 0)] * (rs.ndim - 1)
            rs = jnp.pad(rs, pad)                 # zero slots: inactive lanes
            # offset slot s = t·pl + λ  →  send-structure [λ][t][dest_lane]
            sendst = jnp.moveaxis(
                rs.reshape((nrounds, pl, pl) + blk), 1, 0)
            # Phase 1: lane λ collects every local rank's slabs for λ's pods.
            coll = _local_exchange(sendst, axes, q, pl, l, "a2a_collect")
            # coll: (pl_src, nrounds, pl_dst, *blk) → per-round slabs
            A = jnp.moveaxis(coll, 1, 0)          # (nrounds, pl_src, pl_dst, ...)
            # Phase 2: one aggregated DCN message per active lane per round.
            recvs = []
            for t in range(nrounds):
                active = _a2a_active(q, pl, t)
                pairs = [(Rg * pl + lg,
                          ((Rg + t * pl + lg + 1) % q) * pl + lg)
                         for lg in range(active) for Rg in range(q)]
                with jax.named_scope(f"a2a_nonlocal_round{t}"):
                    recvs.append(lax.ppermute(A[t], axes, pairs))
            slabs = jnp.stack(recvs)              # (nrounds, pl_src, pl_dst, ...)
            return PendingCollective(
                (slabs, own), _SplitMeta("all_to_all", "pending", outer,
                                         local, tiled, blk, group=nrounds,
                                         active=_a2a_active(q, pl,
                                                            nrounds - 1)))
        # q == 1: nothing crosses the pod boundary; delivery happens in finish.
        own = xb[0]                               # (pl_dst, *blk)
        return PendingCollective(
            (own,), _SplitMeta("all_to_all", "local_only", outer, local,
                               tiled, blk))


def locality_all_to_all_finish(pending: PendingCollective) -> jax.Array:
    """Complete a split two-tier all-to-all: intra-pod delivery of the
    received slab columns (+ own-pod blocks) and canonical reordering."""
    meta = pending.meta
    assert meta.op == "all_to_all", meta
    if meta.kind == "done":
        return pending.arrays[0]
    outer, local, blk = meta.outer, meta.local, meta.x_shape
    q = _size(outer) if outer else 1
    pl = _size(local) if local else 1
    p = q * pl
    axes = outer + local
    l = lax.axis_index(local) if pl > 1 else jnp.int32(0)
    nrounds = meta.group if meta.kind == "pending" else 0

    with jax.named_scope(f"loc_a2a_finish_q{q}_pl{pl}"):
        if meta.kind == "pending":
            slabs, own = pending.arrays
            # Phase 3 payload for dest lane m: the m-columns of every
            # received slab, then the own-pod block — one structure so the
            # own-pod blocks ride the same p_ℓ-1 local ppermutes.
            cols = jnp.moveaxis(slabs, 2, 0)      # (pl_dst, nrounds, pl_src, ...)
            cols = cols.reshape((pl, nrounds * pl) + blk)
            struct = jnp.concatenate([cols, own[:, None]], axis=1)
        else:
            (own,) = pending.arrays
            struct = own[:, None]                 # (pl_dst, 1, *blk)
        got = _local_exchange(struct, axes, q, pl, l, "a2a_deliver")
        # got[λ][s] for s < nrounds·pl: block from pod (R - (t·pl+λ+1))%q,
        # src lane s%pl; got[λ][-1]: own-pod block from lane λ.
        own_blocks = got[:, -1]                   # (pl_src, *blk)
        if q > 1:
            rem = jnp.moveaxis(
                got[:, :-1].reshape((pl, nrounds, pl) + blk), 1, 0)
            rem = rem.reshape((nrounds * pl, pl) + blk)[: q - 1]
            stacked = jnp.concatenate([own_blocks[None], rem], axis=0)
            # stacked[o] = blocks from pod (R-o)%q → canonical pod order.
            R = lax.axis_index(outer)
            canon = jnp.roll(stacked[::-1], R + 1, axis=0)
        else:
            canon = own_blocks[None]
        buf = canon.reshape((p,) + blk)
    # unlike allgather, the exchange preserves shape: block i of the output
    # (same leading-dim split as the input) came from rank i
    return buf.reshape((p * blk[0],) + blk[1:])


def locality_all_to_all(x: jax.Array, outer: Axes, local: Axes = (), *,
                        tiled: bool = False,
                        assume_varying: bool = False) -> jax.Array:
    """Two-tier personalized exchange over ``outer + local`` (region-major).

    ``x``'s leading dim is split into p equal blocks; block j goes to rank j
    and the output's block i came from rank i — ``lax.all_to_all`` with
    ``split_axis=concat_axis=0, tiled=True`` semantics.  Composed of the
    split halves so the eager and overlapped paths cannot drift.
    """
    return locality_all_to_all_finish(locality_all_to_all_start(
        x, outer, local, tiled=tiled, assume_varying=assume_varying))


def all_to_all(x: jax.Array, outer: Axes, local: Axes = (), *,
               algorithm: str = "locality", tiled: bool = False,
               assume_varying: bool = False) -> jax.Array:
    """All-to-all dispatcher: 'locality' (two-tier, minimized inter-pod
    phase), 'xla' (lax.all_to_all — direct pairwise under the analyzer's
    pricing), or 'auto' (tuning policy)."""
    if algorithm == "auto":
        algorithm = _resolve_auto("all_to_all", x, _tup(outer), _tup(local))
    if algorithm == "locality":
        return locality_all_to_all(x, outer, local, tiled=tiled,
                                   assume_varying=assume_varying)
    if algorithm == "xla":
        axes = _tup(outer) + _tup(local)
        if not assume_varying:
            x = _varying(x, axes)
        if _size(axes) == 1:
            return x
        return lax.all_to_all(x, axes, split_axis=0, concat_axis=0,
                              tiled=True)
    raise ValueError(f"unknown all_to_all algorithm {algorithm!r}; "
                     f"known: {ALL_TO_ALL_ALGORITHMS + ('auto',)}")


def all_to_all_start(x: jax.Array, outer: Axes, local: Axes = (), *,
                     algorithm: str = "locality", tiled: bool = False,
                     assume_varying: bool = False) -> PendingCollective:
    """Issue an all-to-all; complete with :func:`all_to_all_finish`.  For
    'locality' the DCN rounds genuinely complete in start; 'xla' has no
    local tail, so the split is a program-order hook."""
    if algorithm == "auto":
        algorithm = _resolve_auto("all_to_all", x, _tup(outer), _tup(local))
    if algorithm == "locality":
        return locality_all_to_all_start(x, outer, local, tiled=tiled,
                                         assume_varying=assume_varying)
    full = all_to_all(x, outer, local, algorithm=algorithm, tiled=tiled,
                      assume_varying=assume_varying)
    return PendingCollective((full,), _SplitMeta("all_to_all", "done"))


def all_to_all_finish(pending: PendingCollective) -> jax.Array:
    """Complete an :func:`all_to_all_start`; bit-identical to eager."""
    assert pending.meta.op == "all_to_all", pending.meta
    return locality_all_to_all_finish(pending)


# =============================================================================
# Reductions
# =============================================================================
def reduce_scatter(y: jax.Array, outer: Axes, local: Axes = (), *,
                   algorithm: str = "locality_bruck") -> jax.Array:
    """Sum-reduce-scatter: linear transpose of the chosen allgather.

    ``y`` has leading dim divisible by p; rank i ends with the i-th tile of
    the sum over ranks. The transposed schedule communicates exactly the same
    edges as the forward allgather, reversed — so the locality structure (and
    the non-local message/byte counts of paper Eq. 4) carry over.
    """
    outer, local = _tup(outer), _tup(local)
    p = _size(outer + local)
    assert y.shape[0] % p == 0, f"leading dim {y.shape[0]} not divisible by {p}"
    x_shape = (y.shape[0] // p,) + y.shape[1:]
    y = _varying(y, outer + local)

    def ag(x):
        return allgather(x, outer, local, algorithm=algorithm, tiled=True)

    # vjp at a *device-varying* zero primal: an unvarying primal would make
    # the vma-aware transpose psum the cotangent (replicated-input semantics).
    primal = jnp.zeros(x_shape, y.dtype) + y.reshape(-1)[0] * 0
    _, vjp = jax.vjp(ag, primal)
    (out,) = vjp(y)
    return out


# Generic reduction-op hook: every hand-rolled reduction below is written
# against a binary combiner, so allreduce is not sum-only (the serve decode
# cache-combine needs a max phase for its running softmax maximum).
REDUCE_BINOPS = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}
_XLA_REDUCERS = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}


def _binop(op):
    if op not in REDUCE_BINOPS:
        raise ValueError(f"unknown reduction op {op!r}; "
                         f"known: {sorted(REDUCE_BINOPS)}")
    return REDUCE_BINOPS[op]


def _rhd_reduce_scatter(x: jax.Array, axes: tuple[str, ...],
                        op: str = "sum") -> jax.Array:
    """Recursive-halving reduce-scatter over ``axes`` (XOR partners).

    Leading dim must be divisible by p. Rank i ends with tile i of the
    reduction. log2(p) rounds; round k exchanges 1/2^{k+1} of the buffer.
    """
    combine = _binop(op)
    p = _size(axes)
    idx = lax.axis_index(axes)
    assert x.shape[0] % p == 0
    assert p & (p - 1) == 0, "recursive halving needs power-of-two size"
    buf = x
    d = p // 2
    while d >= 1:
        pairs = [(s, s ^ d) for s in range(p)]
        half = buf.shape[0] // 2
        bit = (idx & d) != 0
        # keep the half matching our bit (MSB-first -> final tile index = idx)
        send_start = jnp.where(bit, 0, half)
        keep_start = jnp.where(bit, half, 0)
        starts = lambda s: (s,) + (0,) * (buf.ndim - 1)
        send = lax.dynamic_slice(buf, starts(send_start), (half,) + buf.shape[1:])
        keep = lax.dynamic_slice(buf, starts(keep_start), (half,) + buf.shape[1:])
        recv = lax.ppermute(send, axes, pairs)
        buf = combine(keep, recv)
        d //= 2
    return buf


def _rd_allreduce(x: jax.Array, axes: tuple[str, ...],
                  op: str = "sum") -> jax.Array:
    """Recursive-doubling allreduce over ``axes`` — ANY axis size.

    Powers of two run the classic log2(p) XOR-partner full-buffer exchange
    (latency-optimal). Other sizes take the standard fold/unfold adaptation
    (Rabenseifner; the allreduce generalization of the padded-Bruck /
    allgatherv machinery in Jocksch et al.): the p - m surplus ranks
    (m = largest power of two <= p) first fold their value into a core
    partner, the power-of-two core runs recursive doubling, and one unfold
    round sends the result back — log2(m) + 2 full-buffer messages, still
    logarithmic. ppermute delivers zeros to ranks outside a round's pair
    set, so every fold/core combine is masked to the ranks that really
    received (an unmasked ``max`` with an implicit zero would corrupt
    negative operands).
    """
    combine = _binop(op)
    p = _size(axes)
    if p == 1:
        return x
    buf = x
    m = 1 << (p.bit_length() - 1)      # largest power of two <= p
    surplus = p - m
    idx = lax.axis_index(axes) if surplus else None
    if surplus:
        pairs = [(s, s - m) for s in range(m, p)]
        recv = lax.ppermute(buf, axes, pairs)
        buf = jnp.where(idx < surplus, combine(buf, recv), buf)
    d = 1
    while d < m:
        pairs = [(s, s ^ d) for s in range(m)]
        recv = lax.ppermute(buf, axes, pairs)
        nxt = combine(buf, recv)
        buf = nxt if not surplus else jnp.where(idx < m, nxt, buf)
        d *= 2
    if surplus:
        pairs = [(s, s + m) for s in range(surplus)]
        recv = lax.ppermute(buf, axes, pairs)
        buf = jnp.where(idx >= m, recv, buf)
    return buf




def _locality_allreduce(x: jax.Array, outer: Axes, local: Axes, *,
                       outer_algorithm: str = "rhd",
                       op: str = "sum") -> jax.Array:
    """Locality-aware allreduce (paper's structure applied to reductions).

    local reduce-scatter → per-lane allreduce across regions → local
    allgather (Bruck). Non-local traffic per rank: 2·ceil(log2 r) messages
    of b/p_ℓ bytes ("rhd"), or ~log2(r) messages ("rd", latency-optimal),
    or XLA's choice ("psum", explicit opt-in only) — vs ~2·b bytes for a
    flat ring allreduce.

    Every structure runs on ARBITRARY region counts (no silent psum
    fallback): "rhd" on a non-power r swaps the recursive-halving
    reduce-scatter for the Bruck-transpose reduce-scatter
    (:func:`reduce_scatter` with ``algorithm="bruck"`` — the allgatherv
    adaptation's reversed schedule, same ceil(log2 r) rounds and partial
    payloads), and "rd" uses the fold/unfold generalization of
    :func:`_rd_allreduce` (log2(m) + 2 rounds).

    ``op`` selects the reduction ("sum"/"max"/"min"). Non-sum reductions
    skip the scatter structure (there is no pmax_scatter, and their use
    case — running softmax maxima — is latency-bound): local
    recursive-doubling then per-lane outer recursive-doubling, any axis
    size via the same fold/unfold rounds.

    Works on arbitrary-shaped ``x`` (flattens + pads internally).
    """
    outer, local = _tup(outer), _tup(local)
    r, pl = _size(outer), _size(local)
    x = _varying(x, outer + local)
    if op != "sum":
        _binop(op)                           # validate
        with jax.named_scope(f"loc_allreduce_{op}_r{r}_pl{pl}"):
            if pl > 1:
                x = _rd_allreduce(x, local, op=op)
            if r > 1:
                x = _rd_allreduce(x, outer, op=op)
        return x
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % pl
    if pad:
        flat = jnp.pad(flat, (0, pad))

    with jax.named_scope(f"loc_allreduce_r{r}_pl{pl}"):
        if pl > 1:
            part = lax.psum_scatter(flat, local, scatter_dimension=0, tiled=True)
        else:
            part = flat
        if r > 1:
            if outer_algorithm == "rhd":
                npart = part.shape[0]
                pad2 = (-npart) % r
                if pad2:
                    part = jnp.pad(part, (0, pad2))
                if r & (r - 1):
                    # non-power region count: the Bruck-TRANSPOSE RS (the
                    # allgatherv adaptation's reversed schedule — same
                    # ceil(log2 r) rounds and partial payloads as the
                    # forward gather)
                    rs = reduce_scatter(part, outer, algorithm="bruck")
                else:
                    rs = _rhd_reduce_scatter(part, outer)
                part = _bruck_allgather(rs, outer, tiled=True)
                if pad2:
                    part = part[:npart]
            elif outer_algorithm == "rd":
                part = _rd_allreduce(part, outer)
            elif outer_algorithm == "psum":
                part = lax.psum(part, outer)
            else:
                raise ValueError(f"unknown outer_algorithm {outer_algorithm!r}")
        if pl > 1:
            full = _bruck_allgather(part, local, tiled=True)
        else:
            full = part
    if pad:
        full = full[:n]
    return full.reshape(shape)


def allreduce(x: jax.Array, outer: Axes, local: Axes = (), *,
              algorithm: str = "locality", outer_algorithm: str = "rhd",
              op: str = "sum") -> jax.Array:
    """Allreduce dispatcher: 'locality' (paper-structured), 'xla' (lax.psum /
    pmax / pmin per ``op``), or 'auto' (tuning policy picks between the two)."""
    outer, local = _tup(outer), _tup(local)
    _binop(op)                               # validate early
    if algorithm == "auto":
        algorithm = _resolve_auto("allreduce", x, outer, local)
    if algorithm == "xla" or (not local) or _size(local) == 1:
        return _XLA_REDUCERS[op](x, outer + local)
    if algorithm == "locality":
        return _locality_allreduce(x, outer, local,
                                  outer_algorithm=outer_algorithm, op=op)
    raise ValueError(f"unknown allreduce algorithm {algorithm!r}")


def allreduce_start(x: jax.Array, outer: Axes, local: Axes = (), *,
                    algorithm: str = "locality", outer_algorithm: str = "rhd",
                    op: str = "sum") -> PendingCollective:
    """Issue an allreduce; complete it with :func:`allreduce_finish`.

    Reduction rounds form one dependency chain (each combines the previous
    round's result), so there is no local tail to defer: start runs the
    whole reduction. The split is a *program-order* hook — call start as
    soon as the operand exists and finish at the consumer, and every op
    between the two is independent compute XLA can overlap the wire with.
    """
    red = allreduce(x, outer, local, algorithm=algorithm,
                    outer_algorithm=outer_algorithm, op=op)
    return PendingCollective((red,), _SplitMeta("allreduce", "done"))


def allreduce_finish(pending: PendingCollective) -> jax.Array:
    assert pending.meta.op == "allreduce", pending.meta
    return pending.arrays[0]


# =============================================================================
# Logsumexp combine — the serve decode cache-combine (§Perf, serve/engine.py)
# =============================================================================
def logsumexp_combine(o: jax.Array, m: jax.Array, l: jax.Array,
                               outer: Axes, local: Axes = (), *,
                               algorithm: str = "locality",
                               outer_algorithm: str = "rhd"
                               ) -> tuple[jax.Array, jax.Array]:
    """Numerically-safe combine of flash-style partial softmax stats.

    Each rank holds, for its slice of the attention (reduction) axis:
      o: (..., D)  unnormalized accumulator  Σ_j exp(s_j − m)·v_j
      m: (...)     running maximum of its local scores
      l: (...)     Σ_j exp(s_j − m)

    Three steps over the ``(outer, local)`` axes:
      1. max-allreduce of ``m`` → global maximum M (latency-bound:
         recursive doubling per locality level, payload is bytes/(D+1));
      2. device-local rescale of o and l by exp(m − M) — a rank whose slice
         is fully masked carries m = −big and contributes exp(−big) ≈ 0;
      3. ONE packed sum-allreduce of [o, l] (paper-structured RS→AG for
         "locality", psum for "xla") instead of two separate collectives.

    Returns (o_total, l_total) in fp32; the caller normalizes o/l.

    Composed of the split halves below, so the eager path and the
    overlapped serve path (max-allreduce issued right after the scores,
    finished after the o/l accumulation) cannot drift.
    """
    with jax.named_scope("logsumexp_combine"):
        pending = logsumexp_combine_start(m, outer, local,
                                                   algorithm=algorithm)
        return logsumexp_combine_finish(
            o, l, pending, algorithm=algorithm,
            outer_algorithm=outer_algorithm)


def logsumexp_combine_start(m: jax.Array, outer: Axes,
                                     local: Axes = (), *,
                                     algorithm: str = "locality"
                                     ) -> PendingCollective:
    """Phase 1 of the decode cache-combine: max-allreduce of the running
    maxima. Depends ONLY on ``m`` — issue it the moment the masked scores
    exist, before the (heavy) exp/accumulate that produces o and l, and the
    latency-bound max phase rides behind that compute."""
    outer, local = _tup(outer), _tup(local)
    m = m.astype(jnp.float32)
    with jax.named_scope("logsumexp_combine_start"):
        M = allreduce(m, outer, local, algorithm=algorithm,
                      outer_algorithm="rd", op="max")
    return PendingCollective((m, M), _SplitMeta("logsumexp", "max_done",
                                                outer, local))


def logsumexp_combine_finish(o: jax.Array, l: jax.Array,
                                      pending: PendingCollective, *,
                                      algorithm: str = "locality",
                                      outer_algorithm: str = "rhd"
                                      ) -> tuple[jax.Array, jax.Array]:
    """Phases 2+3: rescale by exp(m − M), one packed [o, l] sum-allreduce."""
    assert pending.meta.op == "logsumexp", pending.meta
    m, M = pending.arrays
    outer, local = pending.meta.outer, pending.meta.local
    with jax.named_scope("logsumexp_combine_finish"):
        scale = jnp.exp(m - M)
        o32 = o.astype(jnp.float32) * scale[..., None]
        l32 = l.astype(jnp.float32) * scale
        payload = jnp.concatenate([o32.reshape(-1), l32.reshape(-1)])
        tot = allreduce(payload, outer, local, algorithm=algorithm,
                        outer_algorithm=outer_algorithm, op="sum")
    n_o = o32.size
    return tot[:n_o].reshape(o32.shape), tot[n_o:].reshape(l32.shape)


# =============================================================================
# Unified collective surface (DESIGN.md §12) — ONE entry point, one vocabulary
# =============================================================================
#: Canonical collective kinds. "combine" is the decode logsumexp cache-combine
#: (tuning cell name: "logsumexp_combine" — accepted as a kind alias).
KINDS = ("allgather", "allreduce", "reduce_scatter", "all_to_all",
         "cache_migrate", "combine")

#: THE algorithm vocabulary, per kind.  These exact strings are what the
#: tuning cache keys (tuning/cache.make_key), the policy crossover tables,
#: and the comm-ledger labels (telemetry: "train/moe_dispatch:locality") use
#: — one enum, no per-subsystem drift.  "auto" resolves through
#: repro.tuning.policy at trace time.
ALGORITHMS_BY_KIND = {
    "allgather": ("bruck", "ring", "hierarchical", "multilane",
                  "locality_bruck", "xla", "auto"),
    "allreduce": ("locality", "xla", "auto"),
    "reduce_scatter": ("bruck", "ring", "hierarchical", "multilane",
                       "locality_bruck", "xla"),
    "all_to_all": ("locality", "xla", "auto"),
    "cache_migrate": ("locality_bruck", "multilane", "xla", "auto"),
    "combine": ("locality", "xla", "auto"),
}

#: Per-kind default when ``algorithm`` is omitted — the locality schedule
#: everywhere one exists, matching each family function's own default.
DEFAULT_ALGORITHM = {
    "allgather": "locality_bruck", "allreduce": "locality",
    "reduce_scatter": "locality_bruck", "all_to_all": "locality",
    "cache_migrate": "auto", "combine": "locality",
}

_KIND_ALIASES = {"logsumexp_combine": "combine"}


def _norm_kind(kind: str) -> str:
    kind = _KIND_ALIASES.get(kind, kind)
    if kind not in KINDS:
        raise ValueError(f"unknown collective kind {kind!r}; known: {KINDS}")
    return kind


def collective(kind: str, *operands: jax.Array, outer: Axes,
               local: Axes = (), algorithm: str | None = None,
               start: bool = False, **kwargs):
    """The single collective entry point (thin dispatch, zero new math).

    ``collective(kind, x, outer=..., local=..., algorithm=...)`` runs the
    named family eagerly; ``start=True`` returns a :class:`PendingCollective`
    to complete with :func:`finish`.  Operands per kind: one array for
    allgather / allreduce / reduce_scatter / all_to_all / cache_migrate;
    ``(o, m, l)`` for the eager "combine" and just ``(m,)`` for its start
    half (o and l are supplied to :func:`finish`).  Remaining ``kwargs``
    (``tiled``, ``op``, ``outer_algorithm``, ``assume_varying``) pass
    through to the family function.
    """
    kind = _norm_kind(kind)
    if algorithm is None:
        algorithm = DEFAULT_ALGORITHM[kind]
    if algorithm not in ALGORITHMS_BY_KIND[kind]:
        raise ValueError(
            f"unknown algorithm {algorithm!r} for kind {kind!r}; known: "
            f"{ALGORITHMS_BY_KIND[kind]}")
    if kind == "combine":
        if start:
            (m,) = operands
            return logsumexp_combine_start(m, outer, local,
                                           algorithm=algorithm, **kwargs)
        o, m, l = operands
        return logsumexp_combine(o, m, l, outer, local, algorithm=algorithm,
                                 **kwargs)
    (x,) = operands
    if kind == "reduce_scatter":
        if start:
            raise NotImplementedError(
                "reduce_scatter has no start/finish split (its rounds form "
                "one dependency chain ending at the caller)")
        return reduce_scatter(x, outer, local, algorithm=algorithm, **kwargs)
    eager, starter = {
        "allgather": (allgather, allgather_start),
        "allreduce": (allreduce, allreduce_start),
        "all_to_all": (all_to_all, all_to_all_start),
        "cache_migrate": (cache_migrate, None),
    }[kind]
    if start:
        if starter is None:
            raise NotImplementedError(f"{kind} has no start/finish split")
        return starter(x, outer, local, algorithm=algorithm, **kwargs)
    return eager(x, outer, local, algorithm=algorithm, **kwargs)


def finish(pending: PendingCollective, *operands: jax.Array, **kwargs):
    """Complete any ``collective(..., start=True)``; dispatches on the
    pending op.  The "combine" kind takes its deferred ``(o, l)`` operands
    here; every other kind takes none."""
    op = pending.meta.op
    if op == "logsumexp":
        o, l = operands
        return logsumexp_combine_finish(o, l, pending, **kwargs)
    assert not operands, (op, len(operands))
    return {"allgather": allgather_finish, "allreduce": allreduce_finish,
            "all_to_all": all_to_all_finish}[op](pending, **kwargs)


@dataclasses.dataclass(frozen=True)
class Collective:
    """A configured collective: kind + algorithm + axes bound once, applied
    many times — ``Collective("allgather", outer=("pod",), local=("data",))``
    then ``c(x)`` / ``c.start(x)`` + ``c.finish(pending)``.  Pure sugar over
    :func:`collective`; exists so call sites carry ONE object instead of
    re-threading (kind, algorithm, outer, local) through every layer."""

    kind: str
    outer: tuple[str, ...] = ()
    local: tuple[str, ...] = ()
    algorithm: str | None = None

    def __post_init__(self):
        object.__setattr__(self, "outer", _tup(self.outer))
        object.__setattr__(self, "local", _tup(self.local))
        _norm_kind(self.kind)

    def __call__(self, *operands, **kwargs):
        return collective(self.kind, *operands, outer=self.outer,
                          local=self.local, algorithm=self.algorithm,
                          **kwargs)

    def start(self, *operands, **kwargs) -> PendingCollective:
        return self(*operands, start=True, **kwargs)

    @staticmethod
    def finish(pending: PendingCollective, *operands, **kwargs):
        return finish(pending, *operands, **kwargs)


# =============================================================================
# Deprecated aliases (DESIGN.md §12 deprecation policy)
# =============================================================================
# The algorithm-specific entry points predate the unified surface; they warn
# ONCE per process and forward unchanged.  Removal one release out.  The
# family functions (allgather/allreduce/reduce_scatter/all_to_all/
# cache_migrate/logsumexp_combine, their _start/_finish halves, and
# collective()/Collective/finish) are the supported API.
_WARNED: set[str] = set()


def _deprecated(name: str, replacement: str, fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if name not in _WARNED:
            _WARNED.add(name)
            warnings.warn(
                f"repro.core.collectives.{name} is deprecated; use "
                f"{replacement} (removal one release out, see DESIGN.md §12)",
                DeprecationWarning, stacklevel=2)
        return fn(*args, **kwargs)
    wrapper.__name__ = name
    wrapper.__qualname__ = name
    return wrapper


bruck_allgather = _deprecated(
    "bruck_allgather", 'collective("allgather", ..., algorithm="bruck")',
    _bruck_allgather)
ring_allgather = _deprecated(
    "ring_allgather", 'collective("allgather", ..., algorithm="ring")',
    _ring_allgather)
hierarchical_allgather = _deprecated(
    "hierarchical_allgather",
    'collective("allgather", ..., algorithm="hierarchical")',
    _hierarchical_allgather)
multilane_allgather = _deprecated(
    "multilane_allgather",
    'collective("allgather", ..., algorithm="multilane")',
    _multilane_allgather)
locality_bruck_allgather = _deprecated(
    "locality_bruck_allgather",
    'collective("allgather", ..., algorithm="locality_bruck")',
    _locality_bruck_allgather)
locality_bruck_allgather_start = _deprecated(
    "locality_bruck_allgather_start",
    'collective("allgather", ..., algorithm="locality_bruck", start=True)',
    _locality_bruck_allgather_start)
locality_bruck_allgather_finish = _deprecated(
    "locality_bruck_allgather_finish", "finish(pending)",
    _locality_bruck_allgather_finish)
locality_allreduce = _deprecated(
    "locality_allreduce", 'collective("allreduce", ..., '
    'algorithm="locality")', _locality_allreduce)
locality_logsumexp_combine = _deprecated(
    "locality_logsumexp_combine", 'collective("combine", o, m, l, ...)',
    logsumexp_combine)
locality_logsumexp_combine_start = _deprecated(
    "locality_logsumexp_combine_start",
    'collective("combine", m, ..., start=True)', logsumexp_combine_start)
locality_logsumexp_combine_finish = _deprecated(
    "locality_logsumexp_combine_finish", "finish(pending, o, l)",
    logsumexp_combine_finish)
