"""Continuous-batching request scheduler over the compiled serve steps.

The lockstep ``Engine.generate`` loop admits a whole batch, decodes every
row to the same budget, and returns — production traffic never looks like
that. The scheduler runs the decode loop *continuously*: requests are
admitted into free cache rows between steps (FCFS against the paged
accounting of :mod:`repro.serve.paged`), each admitted request is prefilled
at B=1 **inside its home pod** (a submesh jit over that pod's devices — the
prefill's collectives cannot cross the DCN by construction), its cache row
is inserted into the live batch cache, and rows free the moment their
request finishes. Decode carries a per-row ``(B,)`` position vector (the
scalar lockstep path is untouched — see ``models/attention.py``).

Cross-pod cache migration: when the only free row lives in another pod,
the prefilled KV slab moves through ``core.collectives.cache_migrate`` —
a gatherv-shaped replication over ('pod','data') executed with the
locality-Bruck family, priced by the ``cache_migrate`` tuning cell, and
classified by ``telemetry.comm.comm_report`` so the comm ledger reconciles
migration traffic exactly like decode traffic (labels ``serve/migrate:*``,
``serve/prefill:*``, ``serve/decode:cont``).

Sequence-sharded layouts (B=1 long-context, the locality decode-combine's
domain) schedule too: admission degenerates to one request at a time with
the engine's own scalar-pos decode fn, so batch-sharded and
sequence-sharded requests run under one scheduler API.

Clocks are injectable: :class:`WallClock` for real latency numbers,
:class:`StepClock` for deterministic replay (same trace → identical
admission order, tokens, and stamps — the property the determinism test
pins).
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as C
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.train.sharding import make_shard_fn, param_specs
from .paged import PagedKVCache
from .spec import Request, RequestResult, ResolvedServeSpec


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------
class WallClock:
    """Real time; ``idle_until`` naps toward the next arrival."""

    def now(self) -> float:
        return time.perf_counter()

    def advance(self, kind: str) -> None:   # wall time advances itself
        pass

    def idle_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(min(dt, 0.05))


class StepClock:
    """Deterministic virtual clock: each decode step / prefill advances
    time by a fixed cost. Latencies become exact functions of the trace and
    the schedule — replayable, noise-free (what the determinism test and
    the trace benchmark's continuous-vs-waves comparison key on)."""

    def __init__(self, decode_cost: float = 1.0, prefill_cost: float = 1.0):
        self.t = 0.0
        self.decode_cost = decode_cost
        self.prefill_cost = prefill_cost

    def now(self) -> float:
        return self.t

    def advance(self, kind: str) -> None:
        self.t += self.prefill_cost if kind == "prefill" else self.decode_cost

    def idle_until(self, t: float) -> None:
        self.t = max(self.t, t)


# ---------------------------------------------------------------------------
# cache-leaf geometry (mirrors cache_shardings' name-keyed placement)
# ---------------------------------------------------------------------------
def _leaf_name(path) -> str:
    keys = tuple(str(getattr(p, "key", getattr(p, "idx", ""))) for p in path)
    return keys[-1] if keys else ""


def _leaf_batch_dim(path, leaf) -> int | None:
    """Batch dim of a cache leaf (stacked leaves carry leading dims);
    None for the pos leaf."""
    name = _leaf_name(path)
    nd = leaf.ndim
    if name in ("k", "v"):
        return nd - 4
    if name == "conv":
        return nd - 3
    if name == "h":
        return nd - 4
    if name == "pos":
        return None
    raise ValueError(f"unknown cache leaf {name!r}")


def _seq_axes_of_spec(spec) -> tuple[int, tuple[str, ...]] | None:
    """(dim, axes) of the sequence-sharded dim in a donor PartitionSpec —
    the dim carrying 'pod'/'data' — or None for unsharded-seq leaves."""
    for d, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if "pod" in axes or "data" in axes:
            return d, tuple(axes)
    return None


# ---------------------------------------------------------------------------
# compiled helpers: insert / migrate-insert
# ---------------------------------------------------------------------------
def _row_mask_insert(cache, req, row, batch):
    """Masked row insert: elementwise ``where`` on the batch dim only, so
    GSPMD keeps every update device-local on a batch-sharded cache (a
    dynamic_update_slice at a *dynamic row index* on the sharded dim would
    make it gather the whole cache)."""
    onehot = jnp.arange(batch) == row

    def visit(path, leaf, req_leaf):
        b = _leaf_batch_dim(path, leaf)
        if b is None:                      # pos: scalar -> the row's entry
            return jnp.where(onehot, req_leaf.astype(leaf.dtype), leaf)
        m = onehot.reshape([batch if i == b else 1 for i in range(leaf.ndim)])
        return jnp.where(m, req_leaf.astype(leaf.dtype), leaf)

    return jax.tree_util.tree_map_with_path(visit, cache, req)


def make_insert_fn(mesh, batch: int, cache_sh, req_sh):
    """jit((cache, req_cache, row) -> cache): donated masked row insert."""
    fn = jax.jit(lambda cache, req, row: _row_mask_insert(cache, req, row,
                                                          batch),
                 in_shardings=(cache_sh, req_sh, None),
                 donate_argnums=(0,), out_shardings=cache_sh)
    return fn


def make_migrate_insert_fn(mesh, batch: int, cache_sh, donor_specs,
                           donor_sh, algorithm: str):
    """jit((cache, req_cache, row) -> cache) where the request cache
    arrives in the DONOR layout (KV slabs sequence-sharded over
    ('pod','data') per cache_shardings at B=1) and is replicated by the
    explicit ``cache_migrate`` collective — one fully-manual shard_map per
    sharded leaf — before the masked row insert. ``algorithm=None``/"gspmd"
    skips the explicit collective: GSPMD reshards the same donor-layout
    input with its flat all-gather (the baseline the multipod benchmark
    compares against)."""
    axis_names = set(mesh.axis_names)

    def gather_leaf(path, leaf, spec):
        sharded = _seq_axes_of_spec(spec)
        if sharded is None or algorithm in (None, "gspmd"):
            return leaf
        dim, axes = sharded
        if "pod" in axes:
            outer = ("pod",)
            local = tuple(a for a in axes if a != "pod")
        else:
            outer = axes
            local = ()
        out_entries = [None if d == dim else e for d, e in enumerate(spec)]

        def region(x):
            y = jnp.moveaxis(x, dim, 0)
            shp = y.shape
            g = C.cache_migrate(y.reshape(-1), outer, local,
                                algorithm=algorithm, tiled=True)
            g = g.reshape((-1,) + shp[1:])
            return jnp.moveaxis(g, 0, dim)

        return jax.shard_map(region, mesh=mesh, in_specs=spec,
                             out_specs=P(*out_entries),
                             axis_names=axis_names, check_vma=False)(leaf)

    def migrate_insert(cache, req, row):
        req_full = jax.tree_util.tree_map_with_path(gather_leaf, req,
                                                    donor_specs)
        return _row_mask_insert(cache, req_full, row, batch)

    return jax.jit(migrate_insert,
                   in_shardings=(cache_sh, donor_sh, None),
                   donate_argnums=(0,), out_shardings=cache_sh)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Active:
    req: Request
    row: int
    started_s: float
    migrated: bool
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)


class Scheduler:
    """Continuous-batching loop over an Engine's compiled steps.

    Use through ``Engine.submit / Engine.step / Engine.drain`` — the
    engine constructs one lazily and forwards. ``step()`` performs: admit
    (FCFS while the paged cache has rows and the queue head has arrived) →
    one decode step over the live batch → harvest finished rows.
    """

    def __init__(self, engine, *, clock=None, comm_telemetry: bool = True):
        cfg = engine.cfg
        if cfg.family == "audio":
            raise NotImplementedError(
                "the continuous scheduler serves decoder-only families; "
                "enc-dec audio keeps Engine.generate")
        self.engine = engine
        self.cfg = cfg
        self.mesh = engine.mesh
        self.resolved: ResolvedServeSpec = engine.resolved
        self.spec = self.resolved.spec
        self.clock = clock or WallClock()
        self.comm_telemetry = comm_telemetry
        self.tracer = engine.tracer
        self.registry = engine.registry
        self.sequential = self.resolved.combine.algorithm != "none"
        if self.sequential and self.spec.batch != 1:
            raise ValueError(
                "sequence-sharded layouts schedule one request at a time: "
                f"batch must be 1, got {self.spec.batch}")
        self.paged = PagedKVCache(self.spec.batch, self.spec.cache_len,
                                  self.spec.page_len,
                                  n_pods=self.resolved.n_pods
                                  if self.resolved.batch_sharded else 1)
        self.queue: list[Request] = []       # sorted by (arrival_s, rid)
        self.active: dict[int, _Active] = {}
        self.results: dict[int, RequestResult] = {}
        self._next_rid = 0
        self._tok = np.zeros((self.spec.batch, 1), np.int32)
        self._prefills: dict[tuple, tuple] = {}   # (pod, S) -> compiled
        self._pod_params: dict[int, Any] = {}
        self._migrations = 0
        self._steps = 0
        # Diagnostic hook: (B, 1, Vpad) logits of the latest decode step,
        # row-indexed like the cache (RequestResult.slot). A row holds its
        # request's final logits only if that request finished on the last
        # step; chip_smoke.py checks them against a cache-free forward.
        self.last_logits = None
        self._insert_fn = None
        self._migrate_fn = None
        self._migrate_compiled = None
        self._migrate_label = None
        self._extract_fn = None
        self._build_decode()
        self._build_insert()

    # -- compiled-step construction ------------------------------------
    def _build_decode(self) -> None:
        """The continuous decode step: the engine's forward with a per-row
        (B,) position vector (batch mode), or the engine's own scalar-pos
        decode fn (sequential mode)."""
        art = self.engine.art
        if self.sequential:
            self._decode = self.engine._decode_callable
            self._decode_label = self.engine.comm_label
            self._cache = None            # sequential: cache per request
            self.cache_sh = art.cache_shardings_
            return
        cfg, mesh = self.cfg, self.mesh
        shard = make_shard_fn(mesh)
        B, L = self.spec.batch, self.spec.cache_len
        self.cache_sh = art.cache_shardings_
        self.abstract_cache = transformer.cache_specs(cfg, B, L,
                                                      vector_pos=True)

        def decode(params, cache, tokens):
            logits, _, cache = transformer.forward(params, cfg, tokens,
                                                   cache=cache, shard=shard)
            return logits, cache

        fn = jax.jit(decode,
                     in_shardings=(art.param_shardings, self.cache_sh,
                                   art.tok_sharding),
                     donate_argnums=(1,), out_shardings=(None, self.cache_sh))
        self._decode = fn
        self._decode_label = "serve/decode:cont"
        if self.comm_telemetry:
            a_tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            with self.tracer.span("serve/compile", label=self._decode_label):
                compiled = fn.lower(art.abstract_params, self.abstract_cache,
                                    a_tok).compile()
            from repro import telemetry
            rep = telemetry.comm_report(compiled.as_text(), mesh,
                                        label=self._decode_label)
            self.registry.attach_comm_report(self._decode_label, rep)
            self._decode = compiled
        init = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.abstract_cache),
            out_shardings=self.cache_sh)
        self._cache = init()

    def _build_insert(self) -> None:
        if self.sequential:
            return
        from .engine import cache_shardings
        cfg, mesh = self.cfg, self.mesh
        B, L = self.spec.batch, self.spec.cache_len
        # donor layout: a B=1 prefill cache as cache_shardings places it —
        # KV slabs sequence-sharded over ('pod','data') where divisible
        self.donor_specs = cache_shardings(cfg, mesh, 1, L,
                                           self.spec.seq_axes)
        self.donor_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                     self.donor_specs)
        rep_specs = jax.tree.map(lambda _: P(), self.donor_specs)
        self.rep_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   rep_specs)
        self._insert_fn = make_insert_fn(mesh, B, self.cache_sh, self.rep_sh)
        self._migrate_fn = None
        self._migrate_label = None
        if self.resolved.n_pods > 1 and self.resolved.batch_sharded:
            alg = self.spec.migrate
            if alg == "auto":
                slab = self._slab_bytes()
                from repro.tuning.policy import default_policy
                p = self.resolved.n_pods * self.resolved.p_local
                alg = default_policy().select(
                    "cache_migrate", p, self.resolved.p_local,
                    slab).algorithm
            self._migrate_alg = alg
            self._migrate_fn = make_migrate_insert_fn(
                mesh, B, self.cache_sh, self.donor_specs, self.donor_sh, alg)
            self._migrate_label = f"serve/migrate:{alg}"
            if self.comm_telemetry:
                self._stamp_migrate()

    def _slab_bytes(self) -> int:
        """Per-rank bytes of one request's KV slab (the migrate payload)."""
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                transformer.cache_specs(self.cfg, 1, self.spec.cache_len))[0]:
            if _leaf_name(path) in ("k", "v"):
                total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        p = self.resolved.n_pods * self.resolved.p_local
        return max(1, total // max(p, 1))

    def _stamp_migrate(self) -> None:
        a_cache = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            self.abstract_cache, self.cache_sh)
        a_req = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            transformer.cache_specs(self.cfg, 1, self.spec.cache_len),
            self.donor_sh)
        a_row = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = self._migrate_fn.lower(a_cache, a_req, a_row).compile()
        from repro import telemetry
        rep = telemetry.comm_report(compiled.as_text(), self.mesh,
                                    label=self._migrate_label)
        self.registry.attach_comm_report(self._migrate_label, rep)
        self._migrate_compiled = compiled

    # -- pod-local prefill ---------------------------------------------
    def _pod_mesh(self, pod: int | None):
        """The home pod's submesh (axes minus 'pod') — prefill jitted over
        it provably cannot emit a DCN-crossing collective. None = the full
        mesh (single-pod topologies, sequential mode)."""
        if pod is None:
            return self.mesh
        names = list(self.mesh.axis_names)
        sub = np.take(np.asarray(self.mesh.devices), pod,
                      axis=names.index("pod"))
        return make_mesh(sub.shape, tuple(n for n in names if n != "pod"),
                         devices=sub)

    def _prefill_for(self, pod: int | None, S: int):
        """(compiled_prefill, params, tok_sharding, label) for one home pod
        and prompt length — built lazily, cached per (pod, S)."""
        key = (pod, S)
        hit = self._prefills.get(key)
        if hit is not None:
            return hit
        cfg = self.cfg
        mesh = self._pod_mesh(pod)
        from .engine import cache_shardings
        shard = make_shard_fn(mesh)

        def prefill(params, tokens):
            logits, _, cache = transformer.forward(
                params, cfg, tokens, mode="prefill",
                cache_len=self.spec.cache_len, shard=shard)
            return logits, cache

        a_params = self.engine.art.abstract_params
        pspecs = param_specs(a_params, mesh, fsdp=False)
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
        if pod is None:
            params = self.engine.params
            if mesh is not self.mesh:   # pragma: no cover
                params = jax.device_put(params, p_sh)
        else:
            params = self._pod_params.get(pod)
            if params is None:
                # serve params are replicated over the DP axes (fsdp=False),
                # so the pod's devices already hold every value — this pins
                # a pod-local copy the submesh jit can consume
                params = jax.device_put(self.engine.params, p_sh)
                self._pod_params[pod] = params
        c_specs = cache_shardings(cfg, mesh, 1, self.spec.cache_len,
                                  self.spec.seq_axes)
        c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs)
        tok_sh = NamedSharding(mesh, P())
        fn = jax.jit(prefill, in_shardings=(p_sh, tok_sh),
                     out_shardings=(None, c_sh))
        label = f"serve/prefill:pod{pod if pod is not None else 'all'}:s{S}"
        if self.comm_telemetry:
            a_tok = jax.ShapeDtypeStruct((1, S), jnp.int32)
            # trace under the submesh: the forward's bare-P sharding
            # constraints (model axis) must resolve on the pod's devices,
            # not the ambient full mesh
            with jax.set_mesh(mesh), self.tracer.span("serve/compile",
                                                       label=label):
                compiled = fn.lower(a_params, a_tok).compile()
            from repro import telemetry
            rep = telemetry.comm_report(compiled.as_text(), mesh, label=label)
            self.registry.attach_comm_report(label, rep)
            fn = compiled
        entry = (fn, params, tok_sh, label, mesh)
        self._prefills[key] = entry
        return entry

    # -- public API -----------------------------------------------------
    def submit(self, req: Request) -> int:
        """Enqueue; returns the request id (the handle)."""
        if not self.paged.fits(req.tokens.size, req.max_new):
            raise ValueError(
                f"request of {req.tokens.size}+{req.max_new} tokens can "
                f"never fit a {self.spec.cache_len}-slot row")
        rid = self._next_rid
        self._next_rid += 1
        arrival = req.arrival_s if req.arrival_s is not None \
            else self.clock.now()
        req = dataclasses.replace(req, rid=rid, arrival_s=arrival)
        bisect.insort(self.queue, req,
                      key=lambda r: (r.arrival_s, r.rid))
        return rid

    def cancel(self, rid: int) -> bool:
        """Evict a queued or running request (finish_reason "evicted")."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                self._finish_meta(rid, req, None, "evicted")
                return True
        st = self.active.pop(rid, None)
        if st is not None:
            self.paged.release(rid)
            self._finish_meta(rid, st.req, st, "evicted")
            return True
        return False

    def step(self) -> list[RequestResult]:
        """Admit what fits, run one decode step, harvest finished rows."""
        self._admit()
        if not self.active:
            if self.queue:
                self.clock.idle_until(self.queue[0].arrival_s)
                self._admit()
            if not self.active:
                return []
        if self.sequential:
            return self._step_sequential()
        toks = jnp.asarray(self._tok)
        if self.comm_telemetry:
            toks = jax.device_put(toks, self.engine.art.tok_sharding)
        with self.tracer.span("serve/decode_step"):
            logits, self._cache = self._decode(self.engine.params,
                                               self._cache, toks)
            nxt = np.asarray(self._next_token(logits))
        self.last_logits = logits
        self.clock.advance("decode")
        self._steps += 1
        if self.comm_telemetry:
            self.registry.record_comm(self._decode_label)
        return self._harvest(nxt)

    def drain(self) -> dict[int, RequestResult]:
        """Run until queue and batch are empty; all results by rid."""
        while self.queue or self.active:
            self.step()
        return dict(self.results)

    # -- suspend / resume (graceful drain for restarts, DESIGN.md §10) --
    def _make_extract_fn(self):
        """jit((cache, row) -> B=1 slab in the donor/replicated layout) —
        the transpose of ``make_insert_fn``: the suspended request's row
        leaves the live batch cache the same shape the insert/migration
        machinery puts it back with on resume."""
        def extract(cache, row):
            def visit(path, leaf):
                b = _leaf_batch_dim(path, leaf)
                if b is None:              # (B,) pos vector -> donor scalar
                    return leaf[row]
                return jax.lax.dynamic_slice_in_dim(leaf, row, 1, b)
            return jax.tree_util.tree_map_with_path(visit, cache)

        return jax.jit(extract, in_shardings=(self.cache_sh, None),
                       out_shardings=self.rep_sh)

    def _req_meta(self, st: _Active) -> dict:
        return {"rid": st.req.rid,
                "prompt": np.asarray(st.req.tokens).tolist(),
                "max_new": int(st.req.max_new),
                "arrival_s": st.req.arrival_s,
                "home_pod": st.req.home_pod,
                "generated": [int(t) for t in st.tokens],
                "times": [float(t) for t in st.times],
                "started_s": float(st.started_s),
                "migrated": bool(st.migrated)}

    def suspend(self, ckpt_dir: str) -> str:
        """Checkpoint every in-flight request — per-row KV slab (extracted
        through the insert machinery's transpose) plus token/queue state —
        through the v2 store (atomic commit, replication, sharded chunks in
        sequential mode). A restarted engine's :meth:`resume` replays them;
        nothing is dropped. The scheduler itself is left untouched."""
        from repro.checkpoint import save_checkpoint
        tree: dict[str, Any] = {}
        meta_active = []
        for rid, st in sorted(self.active.items()):
            if self.sequential:
                tree[f"r{rid}"] = self._cache     # B=1: cache IS the slab
            else:
                if self._extract_fn is None:
                    self._extract_fn = self._make_extract_fn()
                tree[f"r{rid}"] = self._extract_fn(
                    self._cache, jnp.asarray(st.row, jnp.int32))
            meta_active.append(self._req_meta(st))
        queued = [{"rid": r.rid, "prompt": np.asarray(r.tokens).tolist(),
                   "max_new": int(r.max_new), "arrival_s": r.arrival_s,
                   "home_pod": r.home_pod} for r in self.queue]
        extra = {"kind": "serve_suspend", "active": meta_active,
                 "queued": queued, "next_rid": self._next_rid,
                 "now": float(self.clock.now()), "steps": self._steps,
                 "batch": self.spec.batch}
        with self.tracer.span("serve/suspend", active=len(meta_active),
                              queued=len(queued)):
            path = save_checkpoint(ckpt_dir, self._steps, tree, extra=extra)
        self.registry.count("serve/suspends")
        return path

    def resume(self, ckpt_dir: str) -> int:
        """Reload a :meth:`suspend` checkpoint into this (fresh) scheduler:
        re-reserve rows, re-insert each KV slab via the same insert path a
        migrated prefill takes, rebuild the queue — restart replays rather
        than drops. Returns the number of requests brought back."""
        from repro.checkpoint import (CheckpointError, read_manifest,
                                      restore_checkpoint)
        if self.active or self.queue:
            raise RuntimeError("resume() requires a fresh scheduler")
        rm = read_manifest(ckpt_dir)
        if rm is None:
            raise CheckpointError(f"no serve checkpoint under {ckpt_dir}")
        step, manifest = rm
        extra = manifest.get("extra", {})
        if extra.get("kind") != "serve_suspend":
            raise CheckpointError("not a serve suspend checkpoint",
                                  step=step)
        like, shardings = {}, {}
        for m in extra["active"]:
            key = f"r{m['rid']}"
            if self.sequential:
                like[key] = self.engine.art.abstract_cache
                shardings[key] = self.cache_sh
            else:
                like[key] = transformer.cache_specs(self.cfg, 1,
                                                    self.spec.cache_len)
                shardings[key] = self.rep_sh
        slabs = {}
        if like:
            _, slabs = restore_checkpoint(ckpt_dir, like, step=step,
                                          shardings=shardings)
        with self.tracer.span("serve/resume", active=len(extra["active"]),
                              queued=len(extra["queued"])):
            for m in extra["active"]:
                rid = m["rid"]
                req = Request(tokens=np.asarray(m["prompt"], np.int32),
                              max_new=m["max_new"],
                              arrival_s=m["arrival_s"],
                              home_pod=m["home_pod"], rid=rid)
                row = self.paged.reserve(rid, req.tokens.size, req.max_new,
                                         home_pod=req.home_pod)
                if row is None:
                    raise RuntimeError(
                        f"resume: no free row for suspended request {rid}")
                slab = slabs[f"r{rid}"]
                if self.sequential:
                    self._cache = slab
                else:
                    self._cache = self._insert_fn(
                        self._cache, slab, jnp.asarray(row, jnp.int32))
                st = _Active(req=req, row=row, started_s=m["started_s"],
                             migrated=m["migrated"],
                             tokens=list(m["generated"]),
                             times=list(m["times"]))
                self.active[rid] = st
                if not self.sequential:
                    self._tok[row, 0] = st.tokens[-1]
            for qm in extra["queued"]:
                req = Request(tokens=np.asarray(qm["prompt"], np.int32),
                              max_new=qm["max_new"],
                              arrival_s=qm["arrival_s"],
                              home_pod=qm["home_pod"], rid=qm["rid"])
                bisect.insort(self.queue, req,
                              key=lambda r: (r.arrival_s, r.rid))
        self._next_rid = max(self._next_rid, extra["next_rid"])
        self._steps = extra["steps"]
        if not isinstance(self.clock, WallClock):
            # StepClock replay: resumed stamps continue from the suspend
            # point; WallClock perf_counters don't compare across processes
            self.clock.idle_until(extra["now"])
        self.registry.count("serve/resumes")
        return len(extra["active"]) + len(extra["queued"])

    def result(self, rid: int) -> RequestResult | None:
        return self.results.get(rid)

    def stats(self) -> dict:
        out = {"steps": self._steps, "migrations": self._migrations,
               "active": len(self.active), "queued": len(self.queue),
               "finished": len(self.results)}
        if self.comm_telemetry:
            out["comm"] = {label: self.registry.reconcile(label)
                           for label in self._stamped_labels()}
        return out

    def _stamped_labels(self) -> list[str]:
        labels = [self._decode_label]
        labels += [entry[3] for entry in self._prefills.values()]
        if self._migrate_label is not None and self._migrations:
            labels.append(self._migrate_label)
        return [l for l in labels
                if self.registry.comm_report(l) is not None]

    # -- internals ------------------------------------------------------
    def _next_token(self, logits) -> jax.Array:
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        return jnp.minimum(tok, self.cfg.vocab_size - 1)

    def _admit(self) -> None:
        now = self.clock.now()
        while self.queue:
            req = self.queue[0]
            if req.arrival_s > now:
                break                      # not arrived yet
            if self.sequential and self.active:
                break                      # one request at a time
            row = self.paged.reserve(req.rid, req.tokens.size, req.max_new,
                                     home_pod=req.home_pod)
            if row is None:
                break                      # FCFS: the head waits, nobody
            self.queue.pop(0)              # overtakes (starvation-free)
            self._start(req, row)
            now = self.clock.now()

    def _start(self, req: Request, row: int) -> None:
        S = int(req.tokens.size)
        home = req.home_pod
        use_pod_prefill = (self.resolved.n_pods > 1
                           and self.resolved.batch_sharded
                           and not self.sequential)
        pod = (home if home is not None
               else self.paged.pod_of_row(row)) if use_pod_prefill else None
        fn, params, tok_sh, label, mesh_sub = self._prefill_for(pod, S)
        toks = jax.device_put(jnp.asarray(req.tokens)[None, :], tok_sh)
        with self.tracer.span("serve/prefill", rid=req.rid, prompt_len=S):
            # the prefill (non-AOT path: its trace) and the sampling of its
            # logits, which live on the pod's devices, run under the submesh
            with jax.set_mesh(mesh_sub):
                logits, req_cache = fn(params, toks)
                tok0 = np.asarray(self._next_token(logits))
        self.clock.advance("prefill")
        if self.comm_telemetry \
                and self.registry.comm_report(label) is not None:
            self.registry.record_comm(label)

        migrated = False
        if self.sequential:
            # B=1: the request cache IS the serving cache (the device_put
            # is the donor→serving reshard)
            self._cache = jax.device_put(req_cache, self.cache_sh)
        else:
            row_pod = self.paged.pod_of_row(row)
            if (self._migrate_fn is not None and pod is not None
                    and row_pod != pod):
                # home pod's slab must cross the DCN to the owning rows
                migrated = True
                self._migrations += 1
                req_cache = jax.device_put(req_cache, self.donor_sh)
                with self.tracer.span("serve/migrate", rid=req.rid,
                                      src_pod=pod, dst_pod=row_pod):
                    mfn = (self._migrate_compiled
                           if self.comm_telemetry
                           and self._migrate_compiled is not None
                           else self._migrate_fn)
                    self._cache = mfn(self._cache, req_cache,
                                      jnp.asarray(row, jnp.int32))
                if self.comm_telemetry and self.registry.comm_report(
                        self._migrate_label) is not None:
                    self.registry.record_comm(self._migrate_label)
            else:
                req_cache = jax.device_put(req_cache, self.rep_sh)
                self._cache = self._insert_fn(self._cache, req_cache,
                                              jnp.asarray(row, jnp.int32))
        t = self.clock.now()
        st = _Active(req=req, row=row, started_s=t, migrated=migrated)
        st.tokens.append(int(tok0[0, 0]))
        st.times.append(t)
        self._tok[row, 0] = st.tokens[-1]
        self.active[req.rid] = st
        if len(st.tokens) >= req.max_new:
            self._finish(req.rid, "length")

    def _harvest(self, nxt: np.ndarray) -> list[RequestResult]:
        t = self.clock.now()
        done = []
        for rid in list(self.active):
            st = self.active[rid]
            st.tokens.append(int(nxt[st.row, 0]))
            st.times.append(t)
            self._tok[st.row, 0] = st.tokens[-1]
            if len(st.tokens) >= st.req.max_new:
                done.append(self._finish(rid, "length"))
        return done

    def _step_sequential(self) -> list[RequestResult]:
        (rid, st), = self.active.items()
        tok = jnp.asarray([[st.tokens[-1]]], jnp.int32)
        if self.engine.comm_report is not None:
            tok = jax.device_put(tok, self.engine.art.tok_sharding)
        with self.tracer.span("serve/decode_step"):
            logits, self._cache = self._decode(self.engine.params,
                                               self._cache, tok)
            nxt = np.asarray(self._next_token(logits))
        self.last_logits = logits
        self.clock.advance("decode")
        self._steps += 1
        if self.engine.comm_report is not None:
            self.registry.record_comm(self._decode_label)
        t = self.clock.now()
        st.tokens.append(int(nxt[0, 0]))
        st.times.append(t)
        if len(st.tokens) >= st.req.max_new:
            return [self._finish(rid, "length")]
        return []

    def _finish(self, rid: int, reason: str) -> RequestResult:
        st = self.active.pop(rid)
        self.paged.release(rid)
        self.registry.count("serve/tokens", len(st.tokens))
        return self._finish_meta(rid, st.req, st, reason)

    def _finish_meta(self, rid: int, req: Request, st, reason: str
                     ) -> RequestResult:
        res = RequestResult(
            rid=rid,
            tokens=np.asarray(st.tokens if st else [], np.int32),
            finish_reason=reason,
            arrival_s=req.arrival_s or 0.0,
            started_s=st.started_s if st else self.clock.now(),
            finished_s=self.clock.now(),
            token_times_s=list(st.times) if st else [],
            home_pod=req.home_pod or 0,
            slot=st.row if st else -1,
            migrated=st.migrated if st else False)
        self.results[rid] = res
        return res
