"""Trainer: the fault-tolerant training loop.

Wires together data pipeline, jitted train step, async checkpointing,
straggler monitoring and (simulated) failure recovery:

* every ``ckpt_every`` steps the full TrainState is checkpointed
  asynchronously (atomic commit — see checkpoint/store.py);
* a ``SimulatedFault`` (stand-in for a lost chip/host) triggers recovery:
  restore the newest complete checkpoint and continue — optionally onto a
  *different* mesh (elastic restart; the data pipeline is stateless so the
  batch stream resumes exactly at the restored step);
* step wall-times feed the StepMonitor; events are structured
  ``TelemetryEvent``s in ``trainer.events`` (str subclasses — the legacy
  substring consumers keep working) and are logged the moment they occur,
  never gated behind ``log_every``;
* telemetry (DESIGN.md §8): every phase runs under a tracer span
  (``train/step``, ``train/data``, ``train/compile``, checkpoint spans from
  the store), step metrics publish into the metrics registry, and — when
  ``comm_telemetry`` is on — the step is AOT-compiled so its HLO can be
  scanned once by ``collective_stats``: the resulting ``CommReport``
  (expected inter-pod bytes/msgs per invocation) is stamped into the
  registry under ``"train/step:<mode>"`` and accounted per executed step,
  making
  predicted-vs-actual comm reconciliation exact by construction and any
  unstamped/stale step path a visible mismatch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM
from repro.optim import AdamW
from repro.runtime import (FaultInjector, PreemptionSignal, SimulatedFault,
                           StepMonitor)
from repro import telemetry
from repro.telemetry import TelemetryEvent
from .step import StepArtifacts, custom_batch_specs, init_state, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    ckpt_dir: str | None = None       # required: where this run checkpoints
    ckpt_every: int = 25
    keep_last: int = 3
    log_every: int = 10
    grad_sync: str = "locality"
    fsdp: bool = False
    seq_shard: bool = False
    prefetch_depth: int | str = 0     # FSDP gather lookahead (DESIGN.md §5)
    moe_dispatch: str = "none"        # locality expert parallelism (§12)
    lr: float = 3e-4
    seed: int = 0
    straggler_k: float = 3.0
    # AOT-compile the step and stamp its CommReport (HLO comm ground truth)
    # into the metrics registry; a compile failure raises.
    comm_telemetry: bool = True


class Trainer:
    def __init__(self, model_cfg, mesh, tcfg: TrainerConfig,
                 *, data: SyntheticLM | None = None,
                 fault_injector: FaultInjector | None = None,
                 preemption: PreemptionSignal | None = None,
                 log: Callable[[str], None] = print,
                 tracer: telemetry.Tracer | None = None,
                 registry: telemetry.MetricsRegistry | None = None,
                 step_hook: Callable[["Trainer"], None] | None = None):
        self.model_cfg = model_cfg
        self.mesh = mesh
        self.tcfg = tcfg
        self.data = data or SyntheticLM(
            vocab_size=model_cfg.vocab_size, seq_len=tcfg.seq_len,
            global_batch=tcfg.global_batch, seed=tcfg.seed)
        self.faults = fault_injector or FaultInjector()
        self.preemption = preemption
        self.status = "initialized"
        self._ckpt_failures_seen = 0
        self.monitor = StepMonitor(k=tcfg.straggler_k)
        if not tcfg.ckpt_dir:
            # a shared default directory would let an unrelated earlier run's
            # LATEST be resumed by resume="auto"
            raise ValueError("TrainerConfig.ckpt_dir is required")
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep_last=tcfg.keep_last)
        self.events: list[TelemetryEvent] = []
        self.log = log
        # called at the top of every loop iteration (before the preemption
        # poll) with the trainer itself: the fleet controller's tick — it
        # may flip ``self.preemption`` to request a graceful resize drain
        self.step_hook = step_hook
        self.tracer = tracer or telemetry.get_tracer()
        self.registry = registry or telemetry.get_registry()
        self.metrics_history: list[dict] = []
        self.comm_report: telemetry.CommReport | None = None
        self._build(mesh)
        self._init_or_restore()

    # ------------------------------------------------------------------
    def _event(self, message: str, *, kind: str = "info",
               attrs: dict | None = None, log: bool = True) -> TelemetryEvent:
        """Append one structured event; surface it through ``log``
        immediately (events must never be lost to ``log_every`` skipping a
        step's output)."""
        ev = TelemetryEvent(message, kind=kind, step=getattr(self, "step",
                                                             None),
                            attrs=attrs)
        self.events.append(ev)
        if log:
            self.log(f"[trainer] {ev}")
        return ev

    def _abstract_batch(self) -> dict:
        t = self.tcfg
        return custom_batch_specs(self.model_cfg, t.global_batch, t.seq_len)

    def _build(self, mesh) -> None:
        self.mesh = mesh
        t = self.tcfg
        with self.tracer.span("train/build", mesh=str(mesh.devices.shape)):
            self.artifacts = make_train_step(
                self.model_cfg, mesh,
                optimizer=AdamW(lr=t.lr),
                grad_sync=t.grad_sync, fsdp=t.fsdp, seq_shard=t.seq_shard,
                prefetch_depth=t.prefetch_depth,
                moe_dispatch=t.moe_dispatch,
                shape=self._abstract_batch())
        # degradation warnings raised while building (e.g. a requested
        # prefetch pipeline the config cannot run) surface immediately
        for ev in self.artifacts.events:
            self.events.append(ev)
            self.log(f"[trainer] {ev}")
        # the EWMA describes the topology the old step function ran on —
        # carrying it across an elastic rebuild falsely flags the first
        # steps on a slower mesh (see StepMonitor.reset)
        self.monitor.reset()
        if t.grad_sync == "auto":
            self.log(f"[trainer] grad_sync=auto -> "
                     f"{self.artifacts.grad_sync} "
                     f"({self.artifacts.grad_algorithm}, "
                     f"{self.artifacts.grad_sync_source})")
        if t.prefetch_depth == "auto":
            self.log(f"[trainer] prefetch_depth=auto -> "
                     f"{self.artifacts.prefetch_depth} "
                     f"({self.artifacts.prefetch_source})")
        if t.moe_dispatch != "none":
            self.log(f"[trainer] moe_dispatch={t.moe_dispatch} -> "
                     f"{self.artifacts.moe_dispatch} "
                     f"({self.artifacts.moe_transport or '-'}, "
                     f"{self.artifacts.moe_dispatch_source})")
        self._stamp_comm(t)
        self._stamp_moe_comm(t)

    def _stamp_comm(self, t: TrainerConfig) -> None:
        """AOT-compile the step ONCE ahead of time: the compiled executable
        both serves the train loop (no second jit compile on first step) and
        yields the HLO text the CommReport is derived from. Compile time
        lands in the registry as a tracked gauge."""
        self.comm_report = None
        self._step_callable = self.artifacts.step_fn
        # label qualified by the RESOLVED sync mode so A/B trainers in one
        # process (locality vs xla) keep separate reconciliation ledgers
        self.comm_label = f"train/step:{self.artifacts.grad_sync}"
        if not t.comm_telemetry:
            return
        with self.tracer.span("train/compile"):
            t0 = time.perf_counter()
            lowered = self.artifacts.step_fn.lower(
                self.artifacts.abstract_state, self._abstract_batch())
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
        report = telemetry.comm_report(hlo, self.mesh, label=self.comm_label)
        self._step_callable = compiled
        self.comm_report = report
        self.registry.gauge("train/compile_time_s").set(compile_s)
        self.registry.attach_comm_report(self.comm_label, report)
        self._event(
            f"comm report: {report.nonlocal_bytes:.0f} inter-pod B / "
            f"{report.nonlocal_msgs:.0f} msgs, {report.dp_bytes:.0f} "
            f"DP-crossing B per step "
            f"(locality_schedule={report.has_locality_schedule})",
            kind="comm", attrs=report.asdict(), log=False)

    def _stamp_moe_comm(self, t: TrainerConfig) -> None:
        """Per-layer attribution ledger for the locality MoE dispatch: lower
        ONE representative dispatch round-trip (collect -> expert shard ->
        return) on the step's abstract shapes, stamp its CommReport under
        ``train/moe_dispatch:<alg>`` and account ``n_moe_layers``
        invocations per executed step — reconcile() stays exact by
        construction while attributing the dispatch's share of the step's
        inter-pod traffic to the MoE exchange specifically."""
        self.moe_comm_label = None
        self._moe_layers = 0
        art = self.artifacts
        if not t.comm_telemetry or art.moe_dispatch == "none":
            return
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models import moe as moe_mod
        from repro.models.moe import MoeDispatch
        from repro.train.sharding import dp_axes

        cfg = self.model_cfg
        mesh = self.mesh
        dp = dp_axes(mesh)
        outer = ("pod",) if "pod" in mesh.axis_names else ()
        local = tuple(a for a in dp if a != "pod")
        names = list(mesh.axis_names)
        p = 1
        for ax in dp:
            p *= np.asarray(mesh.devices).shape[names.index(ax)]
        hook = MoeDispatch(outer=outer, local=local,
                           algorithm=art.moe_dispatch,
                           transport=art.moe_transport, p=p)
        E, d = cfg.n_experts, cfg.d_model
        dff = cfg.d_expert or cfg.d_ff
        S = t.seq_len
        C_cap = moe_mod.capacity(cfg, S)
        dt = jnp.dtype(cfg.dtype)
        pdt = jnp.dtype(cfg.param_dtype)

        def body(params, x_pad, tok_idx):
            return moe_mod._ep_apply(params, x_pad, tok_idx, cfg, hook,
                                     C_cap)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=({k: P(dp, None, None)
                       for k in ("gate", "up", "down")},
                      P(dp, None, None), P(dp, None)),
            out_specs=P(dp, None, None),
            axis_names=set(dp), check_vma=False)
        B = t.global_batch
        a_params = {
            "gate": jax.ShapeDtypeStruct((E, d, dff), pdt),
            "up": jax.ShapeDtypeStruct((E, d, dff), pdt),
            "down": jax.ShapeDtypeStruct((E, dff, d), pdt),
        }
        a_x = jax.ShapeDtypeStruct((B, S + 1, d), dt)
        a_idx = jax.ShapeDtypeStruct((B, E * C_cap), jnp.int32)
        hlo = jax.jit(fn).lower(a_params, a_x, a_idx).compile().as_text()
        label = f"train/moe_dispatch:{art.moe_dispatch}"
        report = telemetry.comm_report(hlo, mesh, label=label)
        self.registry.attach_comm_report(label, report)
        self.moe_comm_label = label
        self._moe_layers = sum(1 for s in cfg.layer_plan()
                               if s.mlp == "moe")
        self._event(
            f"moe dispatch comm ({art.moe_dispatch}/"
            f"{art.moe_transport}): {report.nonlocal_bytes:.0f} "
            f"inter-pod B / {report.nonlocal_msgs:.0f} msgs per layer "
            f"x {self._moe_layers} layers/step",
            kind="comm", attrs=report.asdict(), log=False)

    def _init_or_restore(self, step: int | None = None) -> None:
        with self.tracer.span("train/restore"):
            if step is None:
                restored = self.ckpt.restore(
                    self.artifacts.abstract_state,
                    shardings=self.artifacts.state_shardings)
            else:
                from repro.checkpoint import restore_checkpoint
                restored = restore_checkpoint(
                    self.tcfg.ckpt_dir, self.artifacts.abstract_state,
                    step=step, shardings=self.artifacts.state_shardings)
        if restored is not None:
            ckpt_step, self.state = restored
            self.step = ckpt_step
            self._event(f"restored checkpoint at step {ckpt_step}",
                        kind="restore", attrs={"ckpt_step": ckpt_step},
                        log=False)
            self.log(f"[trainer] restored step {ckpt_step}")
        else:
            self.state = init_state(self.model_cfg, self.mesh, self.artifacts,
                                    seed=self.tcfg.seed)
            self.step = 0

    def _device_batch(self, batch: dict[str, np.ndarray]):
        out = {}
        for k, v in batch.items():
            sh = self.artifacts.batch_shardings.get(k)
            out[k] = jax.device_put(v, sh)
        return out

    def _augment(self, batch: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Attach stub modality inputs (frames / patch embeddings)."""
        cfg = self.model_cfg
        B = self.tcfg.global_batch
        rng = np.random.Generator(np.random.Philox(key=self.step))
        if cfg.family == "audio":
            batch["frames"] = rng.standard_normal(
                (B, cfg.enc_seq, cfg.d_model), dtype=np.float32).astype("bfloat16")
        if cfg.family == "vlm":
            batch["img_embeds"] = rng.standard_normal(
                (B, cfg.n_img_tokens, cfg.d_model), dtype=np.float32
            ).astype("bfloat16")
        return batch

    # ------------------------------------------------------------------
    def recover(self, mesh=None) -> None:
        """Failure path: rebuild (possibly on a smaller mesh) and restore."""
        self.ckpt.wait()
        self._build(mesh or self.mesh)
        self._init_or_restore()

    def fit(self, *, resume: str | int = "auto") -> dict[str, Any]:
        """Elastic entry point: train to ``tcfg.steps``, resuming per
        ``resume`` — ``"auto"`` continues from the committed LATEST step
        (the constructor already restored it; this is the restart-loop
        default), ``"none"`` reinitializes from scratch, an int restores
        that exact step (rollback)."""
        if resume == "none":
            if self.step:
                self._event("resume=none: reinitializing from scratch",
                            kind="restore")
            self.state = init_state(self.model_cfg, self.mesh,
                                    self.artifacts, seed=self.tcfg.seed)
            self.step = 0
        elif isinstance(resume, int) and not isinstance(resume, bool):
            self._init_or_restore(step=resume)
            if self.step != resume:
                from repro.checkpoint import CheckpointError
                raise CheckpointError(f"no checkpoint at step {resume} "
                                      f"under {self.tcfg.ckpt_dir}")
        elif resume != "auto":
            raise ValueError(f"resume must be 'auto', 'none' or an int, "
                             f"got {resume!r}")
        return self.run()

    def _check_ckpt_health(self) -> None:
        """Surface writer failures as events the moment they happen — the
        old manager deferred them into the next save()/wait() call."""
        h = self.ckpt.health
        if h.failures > self._ckpt_failures_seen:
            self._ckpt_failures_seen = h.failures
            self._event(f"checkpoint writer unhealthy ({h.state}): "
                        f"{h.last_error}", kind="warning",
                        attrs={"state": h.state, "failures": h.failures,
                               "retries": h.retries})

    def _preempt(self) -> bool:
        if self.preemption is None or not self.preemption.should_stop(
                self.step):
            return False
        # final blocking save + clean drain: restart resumes exactly here
        self.ckpt.save(self.step, self.state, blocking=True)
        self.registry.count("train/preemptions")
        self._event(f"preempted: drained after blocking save at step "
                    f"{self.step}", kind="preemption",
                    attrs={"step": self.step})
        self.status = "preempted"
        return True

    def run(self) -> dict[str, Any]:
        t = self.tcfg
        reg = self.registry
        self.status = "running"
        while self.step < t.steps:
            if self.step_hook is not None:
                self.step_hook(self)
            if self._preempt():
                break
            try:
                with self.tracer.span("train/step", step=self.step):
                    with self.tracer.span("train/data"):
                        batch = self._augment(self.data.batch(self.step))
                        device_batch = self._device_batch(batch)
                    t0 = time.perf_counter()
                    with self.tracer.span("train/step_fn"):
                        self.state, metrics = self._step_callable(
                            self.state, device_batch)
                        jax.block_until_ready(metrics["loss"])
                    # injected straggler: the sleep lands INSIDE the timed
                    # region, scaled past k×ewma so the monitor must flag it
                    slept = self.faults.delay(
                        self.step, floor_s=2 * t.straggler_k *
                        self.monitor.ewma)
                    dt = time.perf_counter() - t0
                self.faults.check(self.step)
            except SimulatedFault as e:
                self._event(str(e), kind="fault", log=False)
                reg.count("train/faults")
                self.log(f"[trainer] {e} -> recovering")
                self.recover()
                continue
            if slept:
                self._event(f"injected straggler: slept {slept:.3f}s",
                            kind="fault", attrs={"slept": slept}, log=False)
            for ev in self.monitor.record(
                    dt, algorithm=self.artifacts.grad_algorithm):
                # surfaced immediately — a straggler between log_every
                # boundaries used to vanish into the event list silently
                self.events.append(ev)
                self.log(f"[trainer] {ev}")
                if ev.kind == "straggler":
                    reg.count("train/stragglers")
            self.step += 1
            reg.count("train/steps")
            reg.observe("train/step_time_s", dt)
            reg.gauge("train/tokens_per_s").set(
                t.global_batch * t.seq_len / dt if dt else 0.0)
            if self.comm_report is not None:
                reg.record_comm(self.comm_label)
            if self.moe_comm_label is not None and self._moe_layers:
                reg.record_comm(self.moe_comm_label, self._moe_layers)
            m = {k: float(v) for k, v in metrics.items()}
            m["step"], m["dt"] = self.step, dt
            m["grad_algorithm"] = self.artifacts.grad_algorithm
            self.metrics_history.append(m)
            reg.gauge("train/loss").set(m["loss"])
            if self.step % t.log_every == 0 or self.step == t.steps:
                self.log(f"[trainer] step {self.step:5d} "
                         f"loss {m['loss']:.4f} gnorm {m['grad_norm']:.3f} "
                         f"({dt*1e3:.0f} ms)")
            if self.step % t.ckpt_every == 0 or self.step == t.steps:
                self.ckpt.save(self.step, self.state)
            self._check_ckpt_health()
        self.ckpt.wait()
        if self.status != "preempted":
            self.status = "complete"
        return {"final_loss": (self.metrics_history[-1]["loss"]
                               if self.metrics_history else None),
                "steps": self.step, "status": self.status,
                "events": list(self.events)}
