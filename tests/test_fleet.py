"""Fleet controller unit suite (fast, in-process).

* decision policy: the bounded escalation ladder, hysteresis/cooldown
  anti-oscillation (hypothesis properties), capacity-forced shrinks;
* pod-aligned layout selection priced by the postal cost model;
* StepMonitor.reset() across elastic rebuilds + the runtime/stragglers
  counter mirror;
* PreemptionSignal SIGTERM chaining + uninstall();
* FaultInjector straggler delays;
* ChaosSchedule determinism and re-arming;
* a 1-device FleetController end-to-end smoke with counter
  reconciliation (the multi-pod soak lives in test_fleet_chaos.py).
"""
import signal

import pytest

from hypothesis import given, settings, strategies as st
from repro.fleet import (ACTION_COUNTERS, ChaosSchedule, ChaosSpec,
                         FleetPolicy, FleetSignals, Layout, PolicyConfig,
                         choose_layout, layout_price_s, pod_aligned_layouts)
from repro.runtime import FaultInjector, PreemptionSignal, StepMonitor
from repro.telemetry import MetricsRegistry, set_registry


# ---------------------------------------------------------------------------
# policy: the deterministic ladder
# ---------------------------------------------------------------------------
def _kill(step=10, commit=8, devices=12, capacity=12):
    return FleetSignals(kind="kill", step=step, committed_step=commit,
                        devices=devices, capacity=capacity)


def _tick(step=10, commit=8, devices=12, capacity=12, **kw):
    return FleetSignals(kind="tick", step=step, committed_step=commit,
                        devices=devices, capacity=capacity, **kw)


def test_escalation_ladder_retry_shrink_halt():
    p = FleetPolicy(PolicyConfig(max_retries=2, max_shrinks=1))
    actions = [p.decide(_kill()).action for _ in range(6)]
    # retry x2 -> shrink (ladder restarts) -> retry x2 -> halt
    assert actions == ["retry", "retry", "shrink", "retry", "retry", "halt"]
    # halt is absorbing, whatever arrives next
    assert p.decide(_tick(capacity=24)).action == "halt"
    assert p.decide(FleetSignals(kind="preemption")).action == "halt"
    assert p.halted


def test_committed_progress_resets_retry_budget():
    p = FleetPolicy(PolicyConfig(max_retries=1, max_shrinks=1))
    assert p.decide(_kill(step=10, commit=8)).action == "retry"
    # progress since the incident opened: new incident, fresh budget
    assert p.decide(_kill(step=20, commit=18)).action == "retry"
    assert p.decide(_kill(step=21, commit=18)).action == "shrink"


def test_preemption_is_benign_retry():
    p = FleetPolicy(PolicyConfig(max_retries=1))
    for _ in range(5):
        d = p.decide(FleetSignals(kind="preemption", step=3))
        assert d.action == "retry"
    assert not p.halted


def test_capacity_revocation_forces_shrink_without_budget():
    p = FleetPolicy(PolicyConfig(max_shrinks=0, cooldown_steps=100))
    d = p.decide(_tick(step=5, devices=12, capacity=8))
    assert d.action == "shrink" and d.target_devices == 8
    assert p.shrinks == 0          # mandatory, not an escalation shrink
    # and cooldown does NOT gate it: again right away
    d = p.decide(_tick(step=6, devices=8, capacity=4))
    assert d.action == "shrink" and d.target_devices == 4


def test_capacity_below_minimum_halts():
    p = FleetPolicy(PolicyConfig(min_devices=4))
    assert p.decide(_tick(devices=12, capacity=2)).action == "halt"
    assert p.halted


def test_straggler_hysteresis_and_cooldown():
    cfg = PolicyConfig(straggler_window=8, straggler_high=2,
                       straggler_low=0, cooldown_steps=4, max_shrinks=1)
    p = FleetPolicy(cfg)
    # first signal anchors the counter baseline: no pressure yet
    assert p.decide(_tick(step=0, stragglers=5)).action == "none"
    # 2 new flags inside the window -> shrink
    d = p.decide(_tick(step=2, stragglers=7))
    assert d.action == "shrink" and p.shrinks == 1
    # grow blocked inside the cooldown even with spare capacity + calm
    assert p.decide(_tick(step=4, stragglers=7, devices=8,
                          capacity=12)).action == "none"
    # cooldown passed but pressure still above the low watermark: no grow
    # (and the shrink budget is spent, so no further shrink either)
    assert p.decide(_tick(step=7, stragglers=9, devices=8,
                          capacity=12)).action == "none"
    # cooldown passed AND window drained back to the low watermark: grow
    d = p.decide(_tick(step=20, stragglers=9, devices=8, capacity=12))
    assert d.action == "grow" and d.target_devices == 12


def test_queue_depth_gates_grow():
    cfg = PolicyConfig(queue_grow_depth=4, cooldown_steps=0,
                       straggler_window=1)
    p = FleetPolicy(cfg)
    assert p.decide(_tick(step=1, devices=8, capacity=12,
                          queue_depth=1)).action == "none"
    assert p.decide(_tick(step=2, devices=8, capacity=12,
                          queue_depth=4)).action == "grow"


def test_degraded_ckpt_blocks_grow_failed_ckpt_is_incident():
    p = FleetPolicy(PolicyConfig(cooldown_steps=0, max_retries=1))
    assert p.decide(_tick(step=1, devices=8, capacity=12,
                          ckpt_state="degraded")).action == "none"
    assert p.decide(_tick(step=2, devices=8, capacity=12,
                          ckpt_state="failed")).action == "retry"


def test_hysteresis_gap_must_not_invert():
    with pytest.raises(ValueError):
        PolicyConfig(straggler_high=1, straggler_low=1)


# ---------------------------------------------------------------------------
# policy: hypothesis properties
# ---------------------------------------------------------------------------
_signals_st = st.lists(
    st.builds(FleetSignals,
              kind=st.sampled_from(["tick", "kill", "fault", "preemption"]),
              step=st.integers(0, 200),
              committed_step=st.integers(0, 200),
              stragglers=st.integers(0, 50),
              queue_depth=st.integers(0, 20),
              ckpt_state=st.sampled_from(["ok", "degraded", "failed"]),
              devices=st.integers(1, 64),
              capacity=st.integers(0, 64)),
    min_size=1, max_size=60)


@pytest.mark.hypothesis
@settings(max_examples=200, deadline=None)
@given(seq=_signals_st, cooldown=st.integers(1, 20))
def test_no_grow_within_cooldown_of_a_shrink(seq, cooldown):
    """Anti-oscillation: under ANY signal sequence, a grow never lands
    within ``cooldown_steps`` trainer steps of any earlier shrink."""
    p = FleetPolicy(PolicyConfig(cooldown_steps=cooldown))
    hist = [p.decide(s) for s in seq]
    for i, di in enumerate(hist):
        if di.action != "shrink":
            continue
        for dj in hist[i + 1:]:
            if dj.action == "grow":
                assert dj.step - di.step >= cooldown, (di, dj)


@pytest.mark.hypothesis
@settings(max_examples=200, deadline=None)
@given(seq=_signals_st,
       max_retries=st.integers(0, 4), max_shrinks=st.integers(0, 3))
def test_escalation_bounded_and_halt_absorbing(seq, max_retries,
                                               max_shrinks):
    p = FleetPolicy(PolicyConfig(max_retries=max_retries,
                                 max_shrinks=max_shrinks))
    hist = [p.decide(s) for s in seq]
    halted = False
    for s, d in zip(seq, hist):
        if halted:
            assert d.action == "halt", (s, d)
        if d.action == "halt":
            halted = True
    # escalation shrinks (policy-counted) never exceed the budget
    assert p.shrinks <= max_shrinks


@pytest.mark.hypothesis
@settings(max_examples=100, deadline=None)
@given(n=st.integers(1, 30), max_retries=st.integers(0, 3),
       max_shrinks=st.integers(0, 2))
def test_crash_loop_escalation_is_monotone(n, max_retries, max_shrinks):
    """A pure crash loop (no progress ever) walks the ladder EXACTLY:
    (retry^max_retries shrink)^max_shrinks retry^max_retries halt*."""
    p = FleetPolicy(PolicyConfig(max_retries=max_retries,
                                 max_shrinks=max_shrinks))
    got = [p.decide(_kill(step=10, commit=8)).action for _ in range(n)]
    expect = (["retry"] * max_retries + ["shrink"]) * max_shrinks \
        + ["retry"] * max_retries
    expect = expect + ["halt"] * (n - len(expect))
    assert got == expect[:n]
    # and per-incident escalation ranks never decrease
    from repro.fleet import ESCALATION
    rank = 0
    for a in got:
        r = ESCALATION[a]
        if a == "shrink":          # a resize closes the incident
            rank = 0
            continue
        assert r >= rank, got
        rank = r


# ---------------------------------------------------------------------------
# layout selection
# ---------------------------------------------------------------------------
def test_pod_aligned_layouts_nest_rows_in_pods():
    for lay in pod_aligned_layouts(12, 4):
        if lay.per_pod < 4:
            assert 4 % lay.per_pod == 0, lay
        assert lay.total <= 12


def test_choose_layout_prefers_fewest_regions_at_equal_total():
    # three 4-chip pods: (3,4), (6,2) and (12,1) all use 12 devices, but
    # splitting pods multiplies the DCN round count — Eq. 4 rejects it
    assert choose_layout(12, 4) == Layout(3, 4)
    assert layout_price_s(Layout(3, 4)) < layout_price_s(Layout(6, 2))
    assert layout_price_s(Layout(6, 2)) < layout_price_s(Layout(12, 1))


def test_choose_layout_utilization_dominates_price():
    # (2,4)=8 devices beats the cheaper (1,4)=4: never idle a whole pod
    assert choose_layout(8, 4) == Layout(2, 4)
    # a ragged capacity drops the partial pod (pod-aligned), keeps both
    # whole ones
    assert choose_layout(10, 4) == Layout(2, 4)
    # q=2 wide pods (the soak's second geometry)
    assert choose_layout(12, 6) == Layout(2, 6)


def test_choose_layout_subpod_fallback():
    # capacity below one pod: the flat remnant is the only aligned shape
    assert choose_layout(3, 4) == Layout(1, 3)
    with pytest.raises(Exception):
        choose_layout(0, 4)


def test_layout_price_finite_on_nonpower_region_counts():
    # Algorithm-2 territory: q in {3, 5, 6, 7} must price finitely
    for q in (3, 5, 6, 7):
        p = layout_price_s(Layout(q, 4))
        assert p > 0 and p == p, (q, p)


# ---------------------------------------------------------------------------
# StepMonitor: reset across rebuilds + the counter mirror
# ---------------------------------------------------------------------------
def test_monitor_reset_prevents_false_flags_and_counts():
    reg = MetricsRegistry()
    old = set_registry(reg)
    try:
        m = StepMonitor(warmup=0)
        m.record(0.1)                        # seeds the EWMA
        assert m.record(0.11) == []
        evs = m.record(1.0)                  # 1.0 > 3 x ewma: flagged
        assert [e.kind for e in evs] == ["straggler"]
        assert m.stragglers == 1
        assert reg.snapshot()["counters"]["runtime/stragglers"] == 1

        # WITHOUT reset, the first step on a 100x-slower topology would
        # flag; reset() forgets the stale EWMA so it seeds cleanly instead
        m.reset()
        assert m.ewma == 0.0
        assert m.record(10.0) == []          # reseeded, no false straggler
        assert m.record(10.5) == []
        # cumulative count and the counter survive the reset
        assert m.stragglers == 1
        assert reg.snapshot()["counters"]["runtime/stragglers"] == 1

        # warmup is honored again after reset
        m2 = StepMonitor(warmup=2)
        m2.record(0.1), m2.record(0.1), m2.record(0.1)
        m2.reset()
        assert m2.record(50.0) == []         # warmup step, not a straggler
    finally:
        set_registry(old)


# ---------------------------------------------------------------------------
# PreemptionSignal: SIGTERM chaining + uninstall
# ---------------------------------------------------------------------------
def test_sigterm_chains_previous_handler_and_uninstalls():
    hits = []
    outer = signal.signal(signal.SIGTERM, lambda s, f: hits.append("outer"))
    try:
        ps = PreemptionSignal(install_sigterm=True)
        signal.raise_signal(signal.SIGTERM)
        assert ps.triggered()
        assert hits == ["outer"]            # the old handler still ran
        ps.uninstall()
        assert signal.getsignal(signal.SIGTERM) is not None
        signal.raise_signal(signal.SIGTERM)
        assert hits == ["outer", "outer"]   # restored exactly
        ps.uninstall()                      # idempotent
    finally:
        signal.signal(signal.SIGTERM, outer)


def test_sigterm_uninstall_restores_default_handler():
    prev = signal.signal(signal.SIGTERM, signal.SIG_DFL)
    try:
        ps = PreemptionSignal(install_sigterm=True)
        assert callable(signal.getsignal(signal.SIGTERM))
        ps.uninstall()
        assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL
        # no-install signals never touch the handler
        PreemptionSignal().uninstall()
        assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL
    finally:
        signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# FaultInjector delays + ChaosSchedule
# ---------------------------------------------------------------------------
def test_fault_injector_delay_once_and_scaled():
    fi = FaultInjector(delay_at_steps=(3,), delay_s=0.01)
    assert fi.delay(2) == 0.0
    assert fi.delay(3, floor_s=0.02) == 0.02     # floor wins over delay_s
    assert fi.delay(3) == 0.0                    # one-shot


def test_chaos_schedule_deterministic_and_rearming():
    a = ChaosSchedule(ChaosSpec(steps=12, seed=7, kills=2, preempts=2,
                                straggles=2))
    b = ChaosSchedule(ChaosSpec(steps=12, seed=7, kills=2, preempts=2,
                                straggles=2))
    assert a.describe() == b.describe()
    steps = a.kills + a.preempts + a.straggles
    assert len(set(steps)) == 6 and min(steps) >= 3
    a.observe_kill(a.kills[0])
    a.observe_preempt(a.preempts[1])
    fi = a.fault_injector()
    assert set(fi.kill_at_steps) == set(a.kills) - {a.kills[0]}
    assert set(fi.delay_at_steps) == set(a.straggles)
    ps = a.preemption_signal()
    assert not ps.should_stop(a.preempts[1])     # fired: not re-armed
    cap = ChaosSchedule(ChaosSpec(steps=12, capacity=((4, 8), (9, 12))))
    assert cap.capacity_at(0, 12) == 12
    assert cap.capacity_at(5, 12) == 8
    assert cap.capacity_at(9, 12) == 12


def test_chaos_schedule_rejects_overfull_draw():
    with pytest.raises(ValueError):
        ChaosSchedule(ChaosSpec(steps=5, kills=2, preempts=2, straggles=2,
                                first_step=3))


# ---------------------------------------------------------------------------
# controller end-to-end smoke (1 device, real Trainer)
# ---------------------------------------------------------------------------
def test_controller_converges_and_counters_reconcile(tmp_path):
    import dataclasses

    import jax.numpy as jnp

    from repro import configs
    from repro.fleet import FleetController
    from repro.train import Trainer, TrainerConfig

    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=1,
                              d_model=32, d_ff=64, vocab_size=64,
                              n_heads=2, n_kv_heads=2, head_dim=16,
                              dtype=jnp.float32)
    steps = 6
    reg = MetricsRegistry()
    old = set_registry(reg)
    try:
        def make_trainer(mesh):
            tcfg = TrainerConfig(
                steps=steps, seq_len=8, global_batch=4, ckpt_every=2,
                keep_last=4, log_every=100, grad_sync="flat_psum",
                fsdp=False, lr=1e-3, comm_telemetry=False,
                ckpt_dir=str(tmp_path / "ck"))
            return Trainer(cfg, mesh, tcfg, log=lambda s: None,
                           registry=reg)

        chaos = ChaosSchedule(ChaosSpec(steps=steps, seed=3, kills=1,
                                        preempts=1, straggles=1,
                                        first_step=3, delay_s=0.05))
        fc = FleetController(make_trainer, pod_size=1, devices=1,
                             chaos=chaos, log=lambda s: None, registry=reg)
        report = fc.run()
    finally:
        set_registry(old)

    assert report.status == "complete"
    assert report.steps == steps
    # one episode per disturbance + the final complete one
    assert len(report.episodes) == 3, report.episodes
    assert report.episodes[-1]["outcome"] == "complete"
    # every restart resumed at the committed step (asserted in _build;
    # recorded here for the report's own story)
    for ep in report.episodes:
        assert ep["resumed_step"] <= ep["end_step"]
    # the loss trajectory covers every step exactly once after folding
    assert sorted(report.loss_by_step) == list(range(1, steps + 1))
    # fleet/* counter reconciliation — the same invariant
    # scripts/check_metrics_schema.py enforces in CI
    c = reg.snapshot()["counters"]
    actions = sum(c.get(f"fleet/{s}", 0) for s in ACTION_COUNTERS.values())
    assert c["fleet/decisions"] == actions > 0
    assert c["fleet/episodes"] == 3
    assert reg.snapshot()["gauges"]["fleet/healthy"] == 1.0
    assert c.get("fleet/halts", 0) == 0
