"""DMA schedule compilation: table executor oracle + paper properties."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import schedules as S
from repro.core.topology import RegionMap, ceil_log
from repro.kernels.dma_allgather.schedule_compile import (
    compile_schedule, execute_table, locality_bruck_raw)

pytestmark = pytest.mark.hypothesis


def _check(dma):
    out = execute_table(dma)
    assert (out == np.arange(dma.p)[None, :]).all()


@settings(max_examples=25, deadline=None)
@given(st.sampled_from([2, 4, 8]), st.integers(1, 5))
def test_tables_correct(pl, k):
    p = pl * pl * k        # mixes power and non-power region counts
    for alg in ("bruck", "ring", "multilane"):
        _check(compile_schedule(S.ALGORITHMS[alg](p, pl)))
    _check(compile_schedule(locality_bruck_raw(p, pl)))


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([2, 4, 8, 16]), st.sampled_from([1, 2, 3]))
def test_raw_locality_preserves_paper_traffic(pl, k):
    """The DMA-clean variant must not inflate non-local traffic vs Alg. 2."""
    from hypothesis import assume
    assume(pl ** (k + 1) <= 1024)        # tables are O(p²) host memory
    r = pl ** k
    p = r * pl
    region = RegionMap(p, pl)
    dma = compile_schedule(locality_bruck_raw(p, pl))
    nl_msgs, nl_blocks = dma.nonlocal_stats(region)
    assert nl_msgs == k                                # ceil(log_pl(r))
    assert nl_blocks == sum(pl ** (i + 1) for i in range(k))
    # capacity: no duplicate receives for power-of-pl region counts
    assert dma.capacity == p


def test_raw_locality_non_power_regions():
    """Non-power region counts still complete (wrapped exchanges allowed to
    duplicate; capacity grows accordingly)."""
    for (p, pl) in [(24, 4), (40, 4), (48, 8), (12, 2)]:
        dma = compile_schedule(locality_bruck_raw(p, pl))
        _check(dma)
        assert dma.capacity >= p


def test_hierarchical_rejected():
    from repro.kernels.dma_allgather.dma_ag import build_schedule
    with pytest.raises(NotImplementedError):
        build_schedule("hierarchical", 16, 4)
