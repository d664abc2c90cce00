import pytest
from hypothesis import given, strategies as st
from conftest import fake_mesh as _fake_mesh

pytestmark = pytest.mark.hypothesis

from repro.core.topology import (RegionMap, ceil_log, device_pod_map,
                                 is_power_of, mesh_region_map)


@given(st.integers(1, 64), st.integers(1, 8))
def test_region_roundtrip(n_regions, p_local):
    rm = RegionMap(p=n_regions * p_local, p_local=p_local)
    for rank in range(rm.p):
        r, l = rm.region_of(rank), rm.local_rank_of(rank)
        assert rm.rank_of(r, l) == rank
        assert 0 <= r < rm.n_regions and 0 <= l < p_local


@given(st.integers(2, 10), st.integers(1, 10 ** 6))
def test_ceil_log(base, x):
    k = ceil_log(base, x)
    assert base ** k >= x
    assert k == 0 or base ** (k - 1) < x


def test_is_power_of():
    assert is_power_of(2, 8) and is_power_of(4, 16) and not is_power_of(4, 8)
    assert is_power_of(3, 27) and not is_power_of(3, 28)


def test_rd_rounds():
    from repro.core.topology import rd_rounds
    # powers: log2(n); non-powers: fold + log2(m) core + unfold
    assert [rd_rounds(n) for n in range(1, 9)] == [0, 1, 3, 2, 4, 4, 4, 3]
    assert rd_rounds(16) == 4 and rd_rounds(17) == 6


def test_indivisible_raises():
    with pytest.raises(ValueError):
        RegionMap(p=10, p_local=4)


def test_device_pod_map_two_axis():
    mesh = _fake_mesh((2, 4), ("pod", "data"))
    pod = device_pod_map(mesh, ("pod",))
    # row-major enumeration: devices 0..3 in pod 0, 4..7 in pod 1
    assert pod == {i: i // 4 for i in range(8)}


def test_device_pod_map_three_axis_mesh():
    mesh = _fake_mesh((2, 4, 2), ("pod", "data", "model"))
    pod = device_pod_map(mesh, ("pod",))
    assert pod == {i: i // 8 for i in range(16)}
    # composite pod axes: ("pod", "data") as the region product
    both = device_pod_map(mesh, ("pod", "data"))
    assert both == {i: i // 2 for i in range(16)}
    # pod axis NOT leading: grouping follows the axis, not memory order
    mesh2 = _fake_mesh((4, 3, 2), ("data", "pod", "model"))
    pod2 = device_pod_map(mesh2, ("pod",))
    assert len(pod2) == 24 and set(pod2.values()) == {0, 1, 2}
    for i in range(24):
        assert pod2[i] == (i // 2) % 3       # row-major (data, pod, model)


def test_device_pod_map_non_power_of_two_pods():
    mesh = _fake_mesh((3, 4), ("pod", "data"))
    pod = device_pod_map(mesh, ("pod",))
    assert pod == {i: i // 4 for i in range(12)}
    rm = mesh_region_map(mesh, ("pod",), ("data",))
    assert rm.n_regions == 3 and rm.p_local == 4
    # the two maps agree on every rank's region
    for rank in range(12):
        assert rm.region_of(rank) == pod[rank]
