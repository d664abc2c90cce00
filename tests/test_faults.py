"""Crash-mid-write recovery: every fault point × mode must leave the store
restoring the last committed step with exact data — zero data loss.

"Committed" means the LATEST pointer replace finished. Faults at the
chunk/manifest/commit points leave no trace of the new step; faults at the
LATEST points leave a complete-but-unreferenced step dir, and restore
(which follows the committed pointer) still serves the previous commit —
consistent either way, and the next successful save heals the pointer.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.checkpoint import (FAULT_POINTS, committed_step,
                              restore_checkpoint, save_checkpoint)
from repro.faults import (FaultHarness, FaultSpec, ProcessKilled, guard,
                          write_bytes)


def make_tree(v: float):
    return {"a": jnp.full((3, 4), v), "b": [jnp.arange(5.0) + v],
            "c": {"d": jnp.asarray(int(v))}}


def assert_tree_equals(tree, v: float) -> None:
    ref = make_tree(v)
    import jax
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture()
def ckdir(tmp_path):
    return str(tmp_path / "ck")


# ---------------------------------------------------------------------------
# harness unit behaviour
# ---------------------------------------------------------------------------
def test_harness_fires_at_exact_hit():
    h = FaultHarness([FaultSpec(point="p", mode="io_error", at=2)])
    assert [h.check("p") for _ in range(4)] == [None, None, "io_error", None]
    assert h.hits("p") == 4


def test_harness_glob_and_times():
    h = FaultHarness([FaultSpec(point="checkpoint/*", mode="kill",
                                rate=1.0, times=2)])
    fired = [h.check("checkpoint/chunk_write") for _ in range(5)]
    assert fired == ["kill", "kill", None, None, None]
    assert h.check("other/point") is None


def test_harness_seeded_rate_is_deterministic():
    def run(seed):
        h = FaultHarness([FaultSpec(point="p", mode="torn", rate=0.3,
                                    times=100)], seed=seed)
        return [h.check("p") for _ in range(50)]

    assert run(7) == run(7)
    assert run(7) != run(8)          # astronomically unlikely to collide


def test_write_bytes_torn_leaves_half(tmp_path):
    h = FaultHarness([FaultSpec(point="p", mode="torn", at=0)])
    path = str(tmp_path / "f.bin")
    with pytest.raises(ProcessKilled):
        write_bytes(path, b"0123456789", faults=h, point="p")
    assert os.path.getsize(path) == 5        # half landed, then the kill
    write_bytes(path, b"0123456789", faults=h, point="p")
    assert os.path.getsize(path) == 10


def test_guard_modes():
    h = FaultHarness([FaultSpec(point="r", mode="io_error", at=0),
                      FaultSpec(point="r", mode="kill", at=1)])
    with pytest.raises(OSError):
        guard("r", h)
    with pytest.raises(ProcessKilled):
        guard("r", h)
    guard("r", h)                            # disarmed
    guard("r", None)                         # no harness: no-op


# ---------------------------------------------------------------------------
# the zero-data-loss matrix: every point × every mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["torn", "kill", "io_error"])
@pytest.mark.parametrize("point", FAULT_POINTS)
def test_crash_at_every_point_restores_last_commit(ckdir, point, mode):
    save_checkpoint(ckdir, 1, make_tree(1.0))
    faults = FaultHarness([FaultSpec(point=point, mode=mode, at=0)])
    with pytest.raises((ProcessKilled, OSError)):
        save_checkpoint(ckdir, 2, make_tree(2.0), faults=faults)
    assert faults.log, f"fault at {point} never fired"
    # the last committed step restores, bit-exact
    step, tree = restore_checkpoint(ckdir, make_tree(0.0))
    assert step == 1
    assert_tree_equals(tree, 1.0)
    # and the store heals: the next save commits and restores normally
    save_checkpoint(ckdir, 3, make_tree(3.0))
    step, tree = restore_checkpoint(ckdir, make_tree(0.0))
    assert step == 3
    assert_tree_equals(tree, 3.0)


@pytest.mark.parametrize("point", FAULT_POINTS)
def test_torn_write_mid_sequence(ckdir, point):
    """A torn write inside a save *sequence* never rolls back past the
    previous commit and never serves a torn step."""
    committed = None
    faults = FaultHarness([FaultSpec(point=point, mode="torn", at=3)])
    for s in range(1, 6):
        try:
            save_checkpoint(ckdir, s, make_tree(float(s)), faults=faults)
            committed = s
        except (ProcessKilled, OSError):
            pass
        step, tree = restore_checkpoint(ckdir, make_tree(0.0))
        assert step == committed
        assert_tree_equals(tree, float(committed))
    assert faults.log, f"fault at {point} never fired"


# ---------------------------------------------------------------------------
# property test: random kill points over a save sequence
# ---------------------------------------------------------------------------
@pytest.mark.hypothesis
@given(seed=st.integers(min_value=0, max_value=10**6))
@settings(max_examples=30, deadline=None)
def test_random_kill_points_never_lose_data(seed, tmp_path_factory):
    ckdir = str(tmp_path_factory.mktemp("faults") / f"ck_{seed}")
    rng = np.random.default_rng(seed)
    mode = ["torn", "kill", "io_error"][int(rng.integers(3))]
    faults = FaultHarness(
        [FaultSpec(point="checkpoint/*", mode=mode,
                   rate=float(rng.uniform(0.02, 0.25)), times=4)],
        seed=seed)
    save_checkpoint(ckdir, 0, make_tree(0.0))      # fault-free baseline
    committed = 0
    for s in range(1, 9):
        try:
            save_checkpoint(ckdir, s, make_tree(float(s)), faults=faults)
            committed = s
        except (ProcessKilled, OSError):
            pass
        step, tree = restore_checkpoint(ckdir, make_tree(0.0))
        assert step == committed, (
            f"seed={seed} mode={mode} log={faults.log}: restored {step}, "
            f"last commit {committed}")
        assert_tree_equals(tree, float(committed))


def test_committed_step_tracks_pointer_not_dirs(ckdir):
    """A kill between commit-rename and the pointer replace leaves a newer
    complete dir; the committed pointer — not the scan — wins."""
    save_checkpoint(ckdir, 1, make_tree(1.0))
    faults = FaultHarness([FaultSpec(point="checkpoint/latest_rename",
                                     mode="kill", at=0)])
    with pytest.raises(ProcessKilled):
        save_checkpoint(ckdir, 2, make_tree(2.0), faults=faults)
    assert os.path.isdir(os.path.join(ckdir, "step_00000002"))
    assert committed_step(ckdir) == 1
