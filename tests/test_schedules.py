"""Property tests for the paper's schedule generators (Algorithm 1 & 2)."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import schedules as S
from repro.core.topology import RegionMap, ceil_log

pytestmark = pytest.mark.hypothesis

ALGS = ["bruck", "ring", "hierarchical", "multilane", "locality_bruck"]


def region_cases():
    """(p, p_local) pairs incl. power and non-power region counts."""
    return st.tuples(st.sampled_from([2, 4, 8, 16]),
                     st.integers(1, 5)).map(lambda t: (t[0] * t[1], t[0]))


@settings(max_examples=30, deadline=None)
@given(region_cases(), st.sampled_from(ALGS))
def test_schedule_correct(case, alg):
    p, pl = case
    sched = S.ALGORITHMS[alg](p, pl)
    sched.validate()          # every rank ends with all p blocks, canonical


@settings(max_examples=30, deadline=None)
@given(region_cases())
def test_paper_eq3_bruck_counts(case):
    """Standard Bruck on a flat network: log2(p) msgs, p-1 blocks (Eq. 3)."""
    p, _ = case
    sched = S.ALGORITHMS["bruck"](p)          # no region: all msgs non-local
    assert sched.max_nonlocal_msgs() == ceil_log(2, p)
    assert sched.max_nonlocal_blocks() == p - 1


@settings(max_examples=30, deadline=None)
@given(st.sampled_from([2, 4, 8, 16]), st.sampled_from([1, 2, 3]))
def test_paper_eq4_locality_counts(pl, k):
    """Locality-aware Bruck with r = p_ℓ^k regions: ceil(log_pl(r)) non-local
    messages per rank; non-local blocks = sum_i pl^(i+1) (paper §4)."""
    from hypothesis import assume
    assume(pl ** (k + 1) <= 1024)        # generators are O(p²) host memory
    r = pl ** k
    p = r * pl
    sched = S.ALGORITHMS["locality_bruck"](p, pl)
    region = RegionMap(p, pl)
    assert sched.max_nonlocal_msgs(region) == k
    expect_blocks = sum(pl ** (i + 1) for i in range(k))
    assert sched.max_nonlocal_blocks(region) == expect_blocks


@settings(max_examples=20, deadline=None)
@given(region_cases())
def test_locality_beats_bruck_nonlocal(case):
    """The paper's core claim: fewer non-local messages AND blocks — for
    EVERY region count. The allgatherv adaptation (partial final-round
    payloads) removed the power-of-p_ℓ caveat: the wrapped final exchange
    no longer re-sends data the peer already holds."""
    p, pl = case
    if pl < 2 or p <= pl:
        return
    region = RegionMap(p, pl)
    loc = S.ALGORITHMS["locality_bruck"](p, pl)
    std = S.ALGORITHMS["bruck"](p, pl)
    assert loc.max_nonlocal_msgs(region) <= std.max_nonlocal_msgs(region)
    assert loc.max_nonlocal_blocks(region) <= std.max_nonlocal_blocks(region)


def test_allgatherv_partial_final_round():
    """Non-power region counts q ∈ {3, 5, 6}: round count is
    ceil(log_pl(q)) and the worst rank's non-local blocks follow the
    partial-payload recurrence Σ min(group, q−group)·p_ℓ — strictly below
    the full-buffer exchange wherever the final round wraps."""
    for q, pl in ((3, 2), (3, 4), (5, 2), (5, 3), (5, 4), (6, 2), (6, 4),
                  (10, 4), (7, 3)):
        p = q * pl
        region = RegionMap(p, pl)
        sched = S.ALGORITHMS["locality_bruck"](p, pl)
        sched.validate()
        assert sched.max_nonlocal_msgs(region) == ceil_log(pl, q), (q, pl)
        expect = full = 0
        group = 1
        while group < q:
            active = min(pl, -(-q // group))
            expect += min(group, q - group) * pl
            full += group * pl                  # the pre-adaptation payload
            group = min(group * active, q)
        assert sched.max_nonlocal_blocks(region) == expect, (q, pl)
        wraps = expect != full
        if wraps:
            assert sched.max_nonlocal_blocks(region) < full, (q, pl)


def test_example_2_1():
    """Paper Example 2.1: 16 ranks, 4 per region: 1 non-local message of 4
    values vs Bruck's 4 messages / 15 values."""
    region = RegionMap(16, 4)
    loc = S.ALGORITHMS["locality_bruck"](16, 4)
    std = S.ALGORITHMS["bruck"](16, 4)
    assert loc.max_nonlocal_msgs(region) == 1
    assert loc.max_nonlocal_blocks(region) == 4
    assert std.max_nonlocal_msgs(region) == 4
    assert std.max_nonlocal_blocks(region) == 15


def test_figure_6_64_ranks():
    """Paper Fig. 6: 64 ranks / 16 regions of 4 → 2 non-local rounds."""
    region = RegionMap(64, 4)
    loc = S.ALGORITHMS["locality_bruck"](64, 4)
    assert loc.max_nonlocal_msgs(region) == 2
